"""The guided Stable-Diffusion-style pipeline (the paper's §1 target system).

Bundles: hash tokenizer -> small text encoder -> latent UNet denoiser ->
DDIM sampler with a :class:`GuidancePlan`. Mirrors the HuggingFace pipeline
the paper instruments, with the selective-guidance optimization as a
first-class argument.

The denoiser follows the config's family: a ``UNetConfig`` runs
``models/unet.py``, an ``MMDiTConfig`` SD3's ``models/mmdit.py`` (a
velocity, stepped by ``stepper="flow"`` over a ``FlowSchedule``), its
weights under ``params["unet"]`` / ``params["mmdit"]``. The prompt
helpers (``init``'s text encoder, ``encode_prompts``, ``generate``) are
the UNet's; SD3's conditioning, a (context, pooled) pair, comes from
``frontends.encode_sd3`` and goes to ``generate_jit``'s function.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MMDiTConfig, UNetConfig
from repro.core.sampler import sample
from repro.core.schedules import FlowSchedule, NoiseSchedule
from repro.core.selective import GuidancePlan
from repro.data.tokenizer import encode_batch
from repro.models import frontends as F
from repro.models import layers as L
from repro.models import mmdit as M
from repro.models import unet as U
from repro.profiling import span

TEXT_VOCAB = 4096
#: config family -> forward(params, cfg, latents, t, cond)
DENOISERS = {"unet": U.unet_forward, "mmdit": M.mmdit_forward}


@dataclass
class SDPipeline:
    cfg: UNetConfig | MMDiTConfig
    params: dict
    sched: NoiseSchedule | FlowSchedule

    @classmethod
    def init(cls, cfg: UNetConfig, rng, *, dtype=jnp.float32,
             sched: NoiseSchedule | None = None):
        mk = L.ArrayMaker(rng, dtype)
        tcfg = F.text_encoder_config(TEXT_VOCAB, cfg.text_dim, cfg.text_len)
        params = {
            "unet": U.init_unet(cfg, mk),
            "text": F.init_text_encoder(tcfg, mk),
        }
        return cls(cfg, params, sched or NoiseSchedule.sd_default())

    # -- pieces -------------------------------------------------------------

    def text_cfg(self):
        if self.cfg.family != "unet":
            raise ValueError("SD3's conditioning comes from frontends.encode_sd3")
        return F.text_encoder_config(TEXT_VOCAB, self.cfg.text_dim, self.cfg.text_len)

    def encode_prompts(self, prompts: list[str]):
        toks = jnp.asarray(encode_batch(prompts, TEXT_VOCAB, self.cfg.text_len))
        return F.encode_text(self.params["text"], self.text_cfg(), toks)

    def null_embedding(self, batch: int):
        toks = F.null_tokens(batch, self.cfg.text_len)
        return F.encode_text(self.params["text"], self.text_cfg(), toks)

    def eps_fn(self, denoiser_params=None):
        """The denoiser ``(latents, t, cond) -> eps`` (a velocity for the
        MMDiT) over ``denoiser_params`` (default: this pipeline's
        weights)."""
        cfg = self.cfg
        forward = DENOISERS[cfg.family]
        if denoiser_params is None:
            denoiser_params = self.params[cfg.family]

        def fn(latents, t, cond):
            return forward(denoiser_params, cfg, latents, t, cond)

        return fn

    # -- generation ---------------------------------------------------------

    def generate(self, prompts: list[str], plan: GuidancePlan, *, seed: int = 0,
                 stepper: str = "ddim", eta: float = 0.0, **combine_kw):
        """-> latents (B, latent_size, latent_size, C) in [-1, 1]-ish.

        ``combine_kw`` passes through to :func:`repro.core.sampler.sample`
        (``combine=``, ``apg_eta=``, ``apg_threshold=``, ``apg_momentum=``
        — the DESIGN.md §15 combine stage); the plan gives each step its
        mode and scale."""
        B = len(prompts)
        rng = jax.random.PRNGKey(seed)
        cond = self.encode_prompts(prompts)
        uncond = self.null_embedding(B)
        x0 = jax.random.normal(jax.random.fold_in(rng, 1),
                               (B, self.cfg.latent_size, self.cfg.latent_size,
                                self.cfg.in_channels), jnp.float32)
        return sample(self.eps_fn(), plan, self.sched, x0, cond, uncond,
                      stepper=stepper, eta=eta, rng=jax.random.fold_in(rng, 2),
                      **combine_kw)

    def generate_jit(self, plan: GuidancePlan, *, stepper="ddim", eta=0.0,
                     **combine_kw):
        """Returns a jitted (cond_emb, uncond_emb, x0, rng) -> latents fn —
        the measured object for the Table-1 latency benchmark. The
        denoiser's weights enter the compiled program as an argument:
        closed over, jit would fold them into it as constants. Each call's
        dispatch is the host span ``sd.generate``; ``fn.lower(cond,
        uncond, x0, rng)`` lowers the program."""
        sched = self.sched
        weights = self.params[self.cfg.family]

        @jax.jit
        def run(weights, cond, uncond, x0, rng):
            return sample(self.eps_fn(weights), plan, sched, x0, cond,
                          uncond, stepper=stepper, eta=eta, rng=rng,
                          **combine_kw)

        def generate(cond, uncond, x0, rng):
            with span("sd.generate"):
                return run(weights, cond, uncond, x0, rng)

        generate.lower = functools.partial(run.lower, weights)
        return generate

    def timed_generate(self, prompts, plan: GuidancePlan, *, seed=0,
                       warmup: int = 2, iters: int = 5):
        """Paper §3.3 protocol: warm up, then average wall time."""
        B = len(prompts)
        cond = self.encode_prompts(prompts)
        uncond = self.null_embedding(B)
        run = self.generate_jit(plan)
        shape = (B, self.cfg.latent_size, self.cfg.latent_size, self.cfg.in_channels)
        times = []
        out = None
        for i in range(warmup + iters):
            rng = jax.random.PRNGKey(seed + i)
            x0 = jax.random.normal(jax.random.fold_in(rng, 1), shape, jnp.float32)
            t0 = time.perf_counter()
            out = jax.block_until_ready(run(cond, uncond, x0, jax.random.fold_in(rng, 2)))
            dt = time.perf_counter() - t0
            if i >= warmup:
                times.append(dt)
        return out, float(np.mean(times)), float(np.std(times))
