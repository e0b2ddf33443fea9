"""Noise schedules + phase-split sampler semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import UNetConfig
from repro.core.guidance import merge_cond_uncond, split_cond_uncond
from repro.core.pipeline import SDPipeline
from repro.core.sampler import ddim_update, sample
from repro.core.schedules import NoiseSchedule, cosine_beta_schedule
from repro.core.selective import GuidancePlan


def test_alphas_bar_monotone():
    s = NoiseSchedule.sd_default()
    assert (np.diff(s.alphas_bar) < 0).all()
    assert 0 < s.alphas_bar[-1] < s.alphas_bar[0] < 1


def test_cosine_schedule_valid():
    b = cosine_beta_schedule(100)
    assert ((b > 0) & (b < 1)).all()


def test_spaced_timesteps():
    s = NoiseSchedule.sd_default(1000)
    ts = s.spaced_timesteps(50)
    assert len(ts) == 50
    assert (np.diff(ts) < 0).all()         # descending
    assert ts.max() < 1000 and ts.min() >= 0


def test_ddim_noiseless_roundtrip():
    """With eps == the true noise, one DDIM step recovers x0 scaling."""
    rng = jax.random.PRNGKey(0)
    x0 = jax.random.normal(rng, (2, 4, 4, 1))
    eps = jax.random.normal(jax.random.fold_in(rng, 1), x0.shape)
    ab_t, ab_prev = 0.5, 1.0
    x_t = jnp.sqrt(ab_t) * x0 + jnp.sqrt(1 - ab_t) * eps
    out = ddim_update(x_t, eps, jnp.float32(ab_t), jnp.float32(ab_prev))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x0),
                               rtol=1e-4, atol=1e-5)


def _toy_eps_fn(coef=0.1):
    """Deterministic fake denoiser: eps = coef * latents + f(text mean)."""
    def fn(lat, t, text):
        bias = jnp.mean(text, axis=(1, 2))[:, None, None, None]
        return coef * lat + bias * 0.01 + t[:, None, None, None] * 0.0
    return fn


@pytest.fixture
def setup():
    sched = NoiseSchedule.sd_default(100)
    B, H = 2, 8
    rng = jax.random.PRNGKey(0)
    x0 = jax.random.normal(rng, (B, H, H, 4))
    cond = jax.random.normal(jax.random.fold_in(rng, 1), (B, 6, 16))
    uncond = jnp.zeros((B, 6, 16))
    return sched, x0, cond, uncond


def test_f0_equals_baseline(setup):
    sched, x0, cond, uncond = setup
    eps = _toy_eps_fn()
    base = sample(eps, GuidancePlan.full(10, 4.0), sched, x0, cond, uncond)
    f0 = sample(eps, GuidancePlan.suffix(10, 0.0, 4.0), sched, x0, cond, uncond)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(f0))


def test_scale1_selective_exact(setup):
    """At s=1 the optimized sampler output is bit-identical to baseline."""
    sched, x0, cond, uncond = setup
    eps = _toy_eps_fn()
    base = sample(eps, GuidancePlan.full(10, 1.0), sched, x0, cond, uncond)
    sel = sample(eps, GuidancePlan.suffix(10, 0.5, 1.0), sched, x0, cond, uncond)
    np.testing.assert_allclose(np.asarray(base), np.asarray(sel),
                               rtol=1e-5, atol=1e-6)


def test_selective_divergence_grows_with_fraction(setup):
    """Fig. 2 structure: larger optimized fraction => larger deviation from
    the unoptimized baseline (monotone in expectation for a linear toy)."""
    sched, x0, cond, uncond = setup
    eps = _toy_eps_fn()
    base = sample(eps, GuidancePlan.full(20, 6.0), sched, x0, cond, uncond)
    dists = []
    for f in [0.2, 0.5, 0.8]:
        out = sample(eps, GuidancePlan.suffix(20, f, 6.0), sched, x0, cond, uncond)
        dists.append(float(jnp.mean((out - base) ** 2)))
    assert dists[0] <= dists[1] <= dists[2]
    assert dists[0] > 0


def test_later_window_less_damage(setup):
    """Fig. 1: same optimization budget hurts less when placed later."""
    sched, x0, cond, uncond = setup
    eps = _toy_eps_fn()
    base = sample(eps, GuidancePlan.full(20, 6.0), sched, x0, cond, uncond)
    d_early = float(jnp.mean((sample(
        eps, GuidancePlan.window(20, 0.0, 0.25, 6.0), sched, x0, cond, uncond)
        - base) ** 2))
    d_late = float(jnp.mean((sample(
        eps, GuidancePlan.window(20, 0.75, 1.0, 6.0), sched, x0, cond, uncond)
        - base) ** 2))
    assert d_late < d_early


def test_ddpm_stepper_runs(setup):
    sched, x0, cond, uncond = setup
    out = sample(_toy_eps_fn(), GuidancePlan.suffix(10, 0.3, 4.0), sched,
                 x0, cond, uncond, stepper="ddpm", rng=jax.random.PRNGKey(7))
    assert out.shape == x0.shape
    assert not bool(jnp.isnan(out).any())


def test_euler_stepper_runs_and_matches_ddim_direction(setup):
    sched, x0, cond, uncond = setup
    plan = GuidancePlan.suffix(10, 0.3, 4.0)
    out_e = sample(_toy_eps_fn(), plan, sched, x0, cond, uncond, stepper="euler")
    out_d = sample(_toy_eps_fn(), plan, sched, x0, cond, uncond, stepper="ddim")
    assert out_e.shape == x0.shape
    assert not bool(jnp.isnan(out_e).any())
    # different discretisations of the same ODE: outputs correlate strongly
    a = np.asarray(out_e, np.float64).ravel()
    b = np.asarray(out_d, np.float64).ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert corr > 0.9


def test_sample_follows_segment_scales():
    """``sample`` over ``GuidancePlan.interval`` against a hand-written
    loop over the same tiny UNet: the 2x batch combined at 1.0 before
    ``a`` and at ``s`` in ``[a, b)``, then the cond pass alone from ``b``."""
    T, s = 10, 5.0
    a, b = 2, 7                                  # the plan's boundaries
    cfg = UNetConfig().reduced()
    pipe = SDPipeline.init(cfg, jax.random.PRNGKey(0),
                           sched=NoiseSchedule.sd_default(100))
    cond = pipe.encode_prompts(["a red disc", "a blue square"])
    uncond = pipe.null_embedding(2)
    x0 = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.latent_size,
                                                   cfg.latent_size,
                                                   cfg.in_channels))
    eps_fn = jax.jit(pipe.eps_fn())
    plan = GuidancePlan.interval(T, 0.2, 0.7, s)
    out = np.asarray(jax.jit(lambda x: sample(
        eps_fn, plan, pipe.sched, x, cond, uncond))(x0))

    ts = pipe.sched.spaced_timesteps(T)
    ab = pipe.sched.alphas_bar
    ab_prev = np.concatenate([ab[ts[1:]], [1.0]])
    x = x0
    for i in range(T):
        t = jnp.full((2,), ts[i], jnp.int32)
        if i < b:
            e_c, e_u = split_cond_uncond(eps_fn(
                merge_cond_uncond(x, x), jnp.concatenate([t, t]),
                merge_cond_uncond(cond, uncond)))
            eps = e_u + (1.0 if i < a else s) * (e_c - e_u)
        else:
            eps = eps_fn(x, t, cond)
        x = ddim_update(x, eps, jnp.float32(ab[ts[i]]),
                        jnp.float32(ab_prev[i]))
    np.testing.assert_allclose(out, np.asarray(x), rtol=1e-5, atol=1e-5)
    # the unit-scale prefix matters: without it the result differs
    flat = np.asarray(sample(eps_fn, GuidancePlan.suffix(T, 0.3, s),
                             pipe.sched, x0, cond, uncond))
    assert np.abs(flat - out).max() > 1e-3
