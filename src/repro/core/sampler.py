"""Guided diffusion sampling with phase-split selective guidance.

``sample`` executes a :class:`GuidancePlan` as one ``lax.scan`` per plan
segment. FULL segments run the denoiser at 2x batch (cond first, uncond
second — the SD/diffusers batching trick) and combine with Eq. 1; COND
segments run 1x batch and use the conditional eps directly. Because the
partition is static, cond-only segments carry exactly half the denoiser
FLOPs in the lowered HLO.

Alternate combine modes (DESIGN.md §15): ``combine="apg"`` replaces Eq. 1
on FULL steps with APG normalized/projected guidance (arxiv 2410.02416),
optionally momentum-averaging the cond/uncond difference across steps
(the EMA rides in the scan carry); ``combine="interval"`` weakens the
guidance scale to 1.0 for steps outside ``interval`` (fractions of the
plan, arxiv 2404.07724) while the pass schedule stays the plan's.

Steppers: DDIM (eta=0, the paper's 50-step setting), Euler
(probability-flow ODE) and ancestral DDPM over a ``NoiseSchedule``, and
``flow``, the rectified-flow Euler step over a ``FlowSchedule`` (SD3: the
denoiser predicts a velocity). The conditioning may be a pytree (SD3's
context and pooled vector); it is batched leaf by leaf.

Each segment's scan body runs under the named scope ``sd.step.full`` or
``sd.step.cond``, with the combine under ``sd.combine`` and the stepper
update under ``sd.update``; the profiler's trace keeps these names on the
device ops (DESIGN.md §13).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.guidance import (apg_combine, cfg_combine, merge_cond_uncond,
                                 split_cond_uncond)
from repro.core.schedules import FlowSchedule, NoiseSchedule
from repro.core.selective import GuidancePlan, Mode, round_half_up

COMBINE_MODES = ("cfg", "apg", "interval")
STEPPERS = ("ddim", "euler", "ddpm", "flow")
STEP_SCOPES = {Mode.FULL: "sd.step.full", Mode.COND: "sd.step.cond"}


def _scoped(mode: Mode, body):
    """``body`` run under its segment's named scope."""
    def run(carry, i):
        with jax.named_scope(STEP_SCOPES[mode]):
            return body(carry, i)
    return run


def _segment_scale(plan: GuidancePlan, combine: str,
                   interval: tuple[float, float] | None):
    """Per-step combine scale: the plan's flat scale, except under
    interval guidance where steps outside [start, stop) run at 1.0."""
    s = plan.guidance_scale
    if combine != "interval":
        return lambda i: s
    iv = (0.0, 1.0) if interval is None else interval
    a = round_half_up(plan.total_steps * iv[0])
    b = round_half_up(plan.total_steps * iv[1])
    return lambda i: jnp.where((i >= a) & (i < b), s, 1.0)


def _step_coeffs(sched, num_steps: int):
    """Per-step (timestep, a, b): (t, alpha_bar_t, alpha_bar_prev) over a
    ``NoiseSchedule``, (1000 sigma, sigma, sigma_next) over a
    ``FlowSchedule``."""
    if isinstance(sched, FlowSchedule):
        sig = sched.sigmas(num_steps)
        return (jnp.asarray(sched.T * sig[:-1], jnp.float32),
                jnp.asarray(sig[:-1], jnp.float32),
                jnp.asarray(sig[1:], jnp.float32))
    ts = sched.spaced_timesteps(num_steps)                     # descending
    ab = sched.alphas_bar
    ab_t = ab[ts]
    ab_prev = np.concatenate([ab[ts[1:]], [1.0]])
    return (jnp.asarray(ts, jnp.int32), jnp.asarray(ab_t, jnp.float32),
            jnp.asarray(ab_prev, jnp.float32))


def _stepper(sched, num_steps: int, stepper: str, eta: float, rng):
    """-> (timesteps, update(x, eps, i)): the one stepper dispatch of
    ``sample`` and ``sample_trajectory``. The update runs under the named
    scope ``sd.update``; a stochastic one draws its noise from
    ``fold_in(rng, i)``."""
    if stepper not in STEPPERS:
        raise ValueError(f"stepper {stepper!r} not in {STEPPERS}")
    if (stepper == "flow") != isinstance(sched, FlowSchedule):
        raise ValueError(f"stepper {stepper!r} does not step a "
                         f"{type(sched).__name__}")
    ts, a, b = _step_coeffs(sched, num_steps)
    stochastic = stepper == "ddpm" or (stepper == "ddim" and eta > 0.0)
    if stochastic and rng is None:
        raise ValueError("ddpm / eta>0 needs rng")

    def update(x, eps, i):
        with jax.named_scope("sd.update"):
            noise = (jax.random.normal(jax.random.fold_in(rng, i), x.shape,
                                       jnp.float32) if stochastic else None)
            if stepper == "ddim":
                return ddim_update(x, eps, a[i], b[i], eta=eta, noise=noise)
            if stepper == "euler":
                return euler_update(x, eps, a[i], b[i])
            if stepper == "ddpm":
                return ddpm_update(x, eps, a[i], b[i], noise)
            return flow_update(x, eps, a[i], b[i])

    return ts, update


def ddim_update(x, eps, ab_t, ab_prev, *, eta: float = 0.0, noise=None):
    xf = x.astype(jnp.float32)
    ef = eps.astype(jnp.float32)
    x0 = (xf - jnp.sqrt(1.0 - ab_t) * ef) / jnp.sqrt(ab_t)
    sigma = eta * jnp.sqrt((1 - ab_prev) / (1 - ab_t)) * jnp.sqrt(1 - ab_t / ab_prev)
    dir_xt = jnp.sqrt(jnp.maximum(1.0 - ab_prev - sigma ** 2, 0.0)) * ef
    out = jnp.sqrt(ab_prev) * x0 + dir_xt
    if noise is not None:
        out = out + sigma * noise.astype(jnp.float32)
    return out.astype(x.dtype)


def euler_update(x, eps, ab_t, ab_prev):
    """Euler step on the sigma-space probability-flow ODE (k-diffusion
    style): x' = x + (sigma_prev - sigma_t) * d, d = (x - sqrt(ab)x0)/sigma
    expressed via the eps-parameterisation."""
    xf = x.astype(jnp.float32)
    ef = eps.astype(jnp.float32)
    sigma_t = jnp.sqrt((1.0 - ab_t) / ab_t)
    sigma_prev = jnp.sqrt(jnp.maximum((1.0 - ab_prev) / ab_prev, 0.0))
    x_sig = xf / jnp.sqrt(ab_t)               # to sigma-space
    x_sig = x_sig + (sigma_prev - sigma_t) * ef
    return (x_sig * jnp.sqrt(ab_prev)).astype(x.dtype)


def ddpm_update(x, eps, ab_t, ab_prev, noise):
    xf = x.astype(jnp.float32)
    ef = eps.astype(jnp.float32)
    alpha_t = ab_t / ab_prev
    beta_t = 1.0 - alpha_t
    mean = (xf - beta_t / jnp.sqrt(1.0 - ab_t) * ef) / jnp.sqrt(alpha_t)
    sigma = jnp.sqrt(beta_t * (1.0 - ab_prev) / (1.0 - ab_t))
    return (mean + sigma * noise.astype(jnp.float32)).astype(x.dtype)


def flow_update(x, v, sigma, sigma_next):
    """Euler step of the rectified-flow ODE dx/dsigma = v (diffusers'
    ``FlowMatchEulerDiscreteScheduler.step``), in float32."""
    xf = x.astype(jnp.float32)
    return (xf + (sigma_next - sigma) * v.astype(jnp.float32)).astype(x.dtype)


def sample(
    eps_fn: Callable,            # (latents (N,...), t (N,), cond (N,...)) -> eps
    plan: GuidancePlan,
    sched: NoiseSchedule | FlowSchedule,
    x_init,                      # (B, h, w, c) initial noise
    cond_emb,                    # (B, L, D), or a pytree of (B, ...) leaves
    uncond_emb,                  # as cond_emb
    *,
    stepper: str = "ddim",
    eta: float = 0.0,
    rng=None,
    combine: str = "cfg",
    apg_eta: float = 0.0,
    apg_threshold: float = 0.0,
    apg_momentum: float = 0.0,
    interval: tuple[float, float] | None = None,
):
    """Run the guided denoising loop under ``plan``. Returns final latents."""
    if combine not in COMBINE_MODES:
        raise ValueError(f"combine {combine!r} not in {COMBINE_MODES}")
    ts, update = _stepper(sched, plan.total_steps, stepper, eta, rng)
    B = x_init.shape[0]
    text2 = merge_cond_uncond(cond_emb, uncond_emb)
    step_scale = _segment_scale(plan, combine, interval)

    def combine_eps(e_u, e_c, i, diff=None):
        with jax.named_scope("sd.combine"):
            if combine == "apg":
                return apg_combine(e_u, e_c, step_scale(i), eta=apg_eta,
                                   threshold=apg_threshold, diff=diff)
            return cfg_combine(e_u, e_c, step_scale(i))

    def full_step(x, i):
        t2 = jnp.broadcast_to(ts[i], (2 * B,))
        eps2 = eps_fn(merge_cond_uncond(x, x), t2, text2)
        e_c, e_u = split_cond_uncond(eps2)
        eps = combine_eps(e_u, e_c, i)
        return update(x, eps, i), None

    def cond_step(x, i):
        t1 = jnp.broadcast_to(ts[i], (B,))
        eps = eps_fn(x, t1, cond_emb)
        return update(x, eps, i), None

    if combine == "apg" and apg_momentum != 0.0:
        # the MomentumBuffer EMA rides in the scan carry (one running
        # average per latent element) and flows untouched through COND
        # segments — the stream is dead there, not the memory of it
        def full_step_m(carry, i):
            x, avg = carry
            t2 = jnp.broadcast_to(ts[i], (2 * B,))
            eps2 = eps_fn(merge_cond_uncond(x, x), t2, text2)
            e_c, e_u = split_cond_uncond(eps2)
            diff = (e_c.astype(jnp.float32) - e_u.astype(jnp.float32))
            avg = diff + apg_momentum * avg
            eps = combine_eps(e_u, e_c, i, diff=avg)
            return (update(x, eps, i), avg), None

        def cond_step_m(carry, i):
            x, avg = carry
            x, _ = cond_step(x, i)
            return (x, avg), None

        carry = (x_init, jnp.zeros(x_init.shape, jnp.float32))
        for seg in plan.segments:
            body = full_step_m if seg.mode is Mode.FULL else cond_step_m
            carry, _ = jax.lax.scan(_scoped(seg.mode, body), carry,
                                    jnp.arange(seg.start, seg.stop))
        return carry[0]

    x = x_init
    for seg in plan.segments:
        body = full_step if seg.mode is Mode.FULL else cond_step
        x, _ = jax.lax.scan(_scoped(seg.mode, body), x,
                            jnp.arange(seg.start, seg.stop))
    return x


def sample_trajectory(eps_fn, plan, sched, x_init, cond_emb, uncond_emb, **kw):
    """As ``sample`` but also returns per-segment-boundary latents (for the
    window-placement analyses)."""
    xs = [x_init]
    x = x_init
    for seg in plan.segments:
        x = _run_segment(eps_fn, plan, sched, x, cond_emb, uncond_emb, seg, **kw)
        xs.append(x)
    return x, xs


def _run_segment(eps_fn, plan, sched, x, cond_emb, uncond_emb, seg, *,
                 stepper="ddim", eta=0.0, rng=None):
    ts, update = _stepper(sched, plan.total_steps, stepper, eta, rng)
    B = x.shape[0]
    text2 = merge_cond_uncond(cond_emb, uncond_emb)
    s = plan.guidance_scale

    def full_step(x, i):
        t2 = jnp.broadcast_to(ts[i], (2 * B,))
        eps2 = eps_fn(merge_cond_uncond(x, x), t2, text2)
        e_c, e_u = split_cond_uncond(eps2)
        return update(x, cfg_combine(e_u, e_c, s), i), None

    def cond_step(x, i):
        t1 = jnp.broadcast_to(ts[i], (B,))
        return update(x, eps_fn(x, t1, cond_emb), i), None

    body = full_step if seg.mode is Mode.FULL else cond_step
    x, _ = jax.lax.scan(body, x, jnp.arange(seg.start, seg.stop))
    return x
