"""The repo's ``SDPipeline`` with SD3-Medium's MMDiT at its published
widths and depth (see sd3m_mmdit.json: the text towers are cut in depth).

The benchmark draws the weights from the seed (``bench.reference.mmdit``),
hands them to the pipeline as it is deployed (float32), and times
``SDPipeline.generate_jit(plan, stepper="flow")`` with the three towers'
prompt encoding in front of it. The plain reference
(``bench.reference.mmdit.sample``) recomputes sampled images in float32 at
``highest`` precision. The control (``control_fp8``): the reference with
the MMDiT's products computed from fp8 inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_mmdit as FM
from bench.reference import mmdit as RM

MMDIT_KEYS = ("sample_size", "patch_size", "in_channels", "out_channels",
              "num_layers", "num_attention_heads", "attention_head_dim",
              "joint_attention_dim", "caption_projection_dim",
              "pooled_projection_dim", "pos_embed_max_size", "clip_len", "t5_len")


def mmdit_config(cfg: dict):
    from repro.configs.base import MMDiTConfig
    return MMDiTConfig(name=cfg["name"], **{k: cfg[k] for k in MMDIT_KEYS})


def batch_inputs(cfg: dict, seed: int, index: int, batch: int):
    """Prompt token ids, CLIP's 77 then T5's 256 in one row (8 to 40
    tokens each, then padding), and initial noise for batch ``index`` of
    the run with ``seed``; the same sizes for every seed."""
    rng = np.random.default_rng([seed, index])
    V = cfg["text_encoders"]["vocab"]
    tokens = np.zeros((batch, cfg["clip_len"] + cfg["t5_len"]), np.int32)
    for b in range(batch):
        for start, length in ((0, cfg["clip_len"]), (cfg["clip_len"], cfg["t5_len"])):
            n = min(int(rng.integers(8, 41)), length)
            tokens[b, start:start + n] = rng.integers(4, V, n)
    s, c = cfg["sample_size"], cfg["in_channels"]
    x0 = rng.standard_normal((batch, s, s, c), dtype=np.float32)
    return tokens, x0


class System:
    """The timed path: the towers' prompt encoding, then the guided
    flow-matching loop."""

    def __init__(self, cfg: dict, seed: int, traffic: dict, *,
                 variant: str = "program"):
        from repro.core.pipeline import SDPipeline
        from repro.core.schedules import FlowSchedule
        from repro.core.selective import GuidancePlan
        from repro.models import frontends as F

        self.cfg, self.traffic = cfg, traffic
        self.variant = variant
        if variant == "control_fp8":
            self.params = RM.init_params(cfg, seed)
            self.params["mmdit"] = RM.rounded(self.params["mmdit"], RM.fp8)
            self._control = reference(cfg, seed, traffic, c=RM.fp8,
                                      params=self.params)
            return
        if variant != "program":
            raise ValueError(f"no variant {variant!r}")
        self.params = RM.init_params(cfg, seed)
        self.pipe = SDPipeline(mmdit_config(cfg), self.params,
                               FlowSchedule(cfg["shift"], cfg["num_train_timesteps"]))
        self.plan = GuidancePlan.suffix(cfg["steps"], traffic["fraction"],
                                        cfg["guidance_scale"])
        t = cfg["text_encoders"]
        tcfgs = F.sd3_text_configs(t["vocab"], t["layers"])
        n, width = cfg["clip_len"], cfg["joint_attention_dim"]
        self._encode = jax.jit(lambda p, tk: F.encode_sd3(p, tcfgs, tk[:, :n],
                                                          tk[:, n:], width))
        self._generate = self.pipe.generate_jit(self.plan, stepper="flow")
        self._key = jax.random.PRNGKey(0)

    def __call__(self, tokens, x0):
        if self.variant == "control_fp8":
            return self._control(tokens, x0)
        tk = jnp.asarray(tokens)
        text = self.params["text"]
        cond = self._encode(text, tk)
        uncond = self._encode(text, jnp.zeros_like(tk))
        return self._generate(cond, uncond, jnp.asarray(x0), self._key)

    def release(self):
        self.params = self.pipe = self._generate = self._encode = None
        self._control = None

    def image_flops(self) -> int:
        return FM.image_flops(self.cfg, self.cfg["steps"], self.traffic["fraction"])


def reference(cfg: dict, seed: int, traffic: dict, c=RM._same, params=None):
    """-> f(tokens, x0) -> final latents, in float32 at ``highest``."""
    params = RM.init_params(cfg, seed) if params is None else params
    fn = jax.jit(lambda p, tk, x: RM.sample(
        p, cfg, tk, x, steps=cfg["steps"], scale=cfg["guidance_scale"],
        fraction=traffic["fraction"], c=c))
    return lambda tokens, x0: fn(params, jnp.asarray(tokens), jnp.asarray(x0))


def rel_err(out, ref) -> float:
    """Worst image's ||out - ref|| / ||ref||."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    axes = tuple(range(1, out.ndim))
    num = np.sqrt(((out - ref) ** 2).sum(axes))
    den = np.sqrt((ref ** 2).sum(axes))
    return float((num / den).max())
