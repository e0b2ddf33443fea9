"""The repo's ``SDPipeline`` at SD-1.5's widths (see sd15_unet_noff.json:
its attention blocks carry no feed-forward, as the program's have none).

The benchmark draws the weights from the seed (``bench.reference.unet``),
hands them to the pipeline as it is deployed (float32), and times
``SDPipeline.generate_jit(plan)`` with the prompt encoding in front of it.
The plain reference (``bench.reference.unet.sample``) recomputes sampled
images in float32 at ``highest`` precision. The controls: the pipeline's
own bfloat16 path (weights, text embeddings and latents in bfloat16),
and the reference with the UNet's products computed from fp8 inputs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops as FL
from bench.reference import unet as RU

UNET_KEYS = ("in_channels", "out_channels", "base_channels", "num_res_blocks",
             "num_heads", "text_dim", "text_len", "latent_size", "time_dim",
             "norm_groups")


def unet_config(cfg: dict):
    from repro.configs.base import UNetConfig
    return UNetConfig(name=cfg["name"], channel_mults=tuple(cfg["channel_mults"]),
                      attn_resolutions=tuple(cfg["attn_resolutions"]),
                      **{k: cfg[k] for k in UNET_KEYS})


def batch_inputs(cfg: dict, seed: int, index: int, batch: int):
    """Prompt token ids (8 to 40 tokens, then padding) and initial noise
    for batch ``index`` of the run with ``seed``; the same sizes for every
    seed."""
    rng = np.random.default_rng([seed, index])
    L, V = cfg["text_len"], cfg["text_encoder"]["vocab"]
    tokens = np.zeros((batch, L), np.int32)
    for b in range(batch):
        n = min(int(rng.integers(8, 41)), L)
        tokens[b, :n] = rng.integers(4, V, n)
    s, c = cfg["latent_size"], cfg["in_channels"]
    x0 = rng.standard_normal((batch, s, s, c), dtype=np.float32)
    return tokens, x0


class System:
    """The timed path: prompt encoding, then the guided denoising loop."""

    def __init__(self, cfg: dict, seed: int, traffic: dict, *,
                 variant: str = "program"):
        from repro.core.pipeline import SDPipeline
        from repro.core.schedules import NoiseSchedule
        from repro.core.selective import GuidancePlan
        from repro.models import frontends as F

        self.cfg, self.traffic = cfg, traffic
        self.variant = variant
        dtype = {"program": jnp.float32, "control": jnp.bfloat16,
                 "control_fp8": jnp.float32}[variant]
        self.params = RU.init_params(cfg, seed, dtype)
        self.dtype = dtype
        if variant == "control_fp8":
            self._control = reference(cfg, seed, traffic, c=RU.fp8,
                                      params=self.params)
            return
        self.pipe = SDPipeline(unet_config(cfg), self.params,
                               NoiseSchedule.sd_default())
        self.plan = GuidancePlan.suffix(cfg["steps"], traffic["fraction"],
                                        cfg["guidance_scale"])
        t = cfg["text_encoder"]
        tcfg = dataclasses.replace(
            F.text_encoder_config(t["vocab"], cfg["text_dim"], cfg["text_len"]),
            num_layers=t["layers"])
        assert (tcfg.num_heads, tcfg.d_ff) == (t["heads"], t["ff"]), tcfg
        self._encode = jax.jit(lambda p, tk: F.encode_text(p, tcfg, tk))
        self._generate = self.pipe.generate_jit(self.plan)
        self._key = jax.random.PRNGKey(0)

    def __call__(self, tokens, x0):
        if self.variant == "control_fp8":
            return self._control(tokens, x0)
        tk = jnp.asarray(tokens)
        text = self.params["text"]
        cond = self._encode(text, tk).astype(self.dtype)
        uncond = self._encode(text, jnp.zeros_like(tk)).astype(self.dtype)
        return self._generate(cond, uncond, jnp.asarray(x0, self.dtype), self._key)

    def release(self):
        self.params = self.pipe = self._generate = self._encode = None
        self._control = None

    def image_flops(self) -> int:
        return FL.diffusion_image_flops(self.cfg, self.cfg["steps"],
                                        self.traffic["fraction"])


def reference(cfg: dict, seed: int, traffic: dict, c=RU._same, params=None):
    """-> f(tokens, x0) -> final latents, in float32 at ``highest``."""
    params = RU.init_params(cfg, seed) if params is None else params
    fn = jax.jit(lambda p, tk, x: RU.sample(
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
