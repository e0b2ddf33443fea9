"""Named scopes on the guided denoising loop (DESIGN.md §13).

The benchmark attributes device time to these names through the
profiler's trace, so they are a contract: each must reach the lowered
program's op metadata, and naming must not change what the program
computes or how the weights enter it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import UNetConfig
from repro.core.pipeline import SDPipeline
from repro.core.schedules import NoiseSchedule
from repro.core.selective import GuidancePlan
from repro.models import frontends as F

STEP_SCOPES = ["sd.step.full", "sd.step.cond", "sd.combine", "sd.update"]
UNET_SCOPES = ["unet", "unet.time", "unet.io", "unet.down.0", "unet.down.1",
               "unet.mid", "unet.up.0", "unet.up.1", "unet.res",
               "unet.resample", "unet.attn.norm", "unet.attn.self",
               "unet.attn.cross"]

# final latents of the call below, as the program computed them before it
# was given scopes: every 64th element, then the sums of |x| and x**2
GOLDEN_EVERY_64TH = [-0.12977249920368195, 3.499136209487915,
                     -2.0106935501098633, -0.9246156811714172,
                     -0.9347946643829346, 0.008105185814201832,
                     0.2658000588417053, 1.5194498300552368]
GOLDEN_ABS_SUM, GOLDEN_SQ_SUM = 625.6311951109674, 1210.0445274315603
# the same for APG with momentum 0.5, eta 0.3 and threshold 1.0, as the
# program computed them with a separate momentum step body
APG_M_KW = dict(combine="apg", apg_momentum=0.5, apg_eta=0.3,
                apg_threshold=1.0)
APG_M_EVERY_64TH = [-0.03701071813702583, 3.0569632053375244,
                    -1.925444483757019, -1.1172035932540894,
                    -1.0686893463134766, 0.23295806348323822,
                    0.09617837518453598, 1.3915159702301025]
APG_M_ABS_SUM, APG_M_SQ_SUM = 588.5171500622528, 1056.5005585583513


@pytest.fixture(scope="module")
def tiny():
    cfg = UNetConfig().reduced()
    pipe = SDPipeline.init(cfg, jax.random.PRNGKey(0),
                           sched=NoiseSchedule.sd_default(100))
    cond = pipe.encode_prompts(["a red disc", "a blue square"])
    uncond = pipe.null_embedding(2)
    x0 = jax.random.normal(jax.random.PRNGKey(5), (2, cfg.latent_size,
                                                   cfg.latent_size, cfg.in_channels))
    return pipe, (cond, uncond, x0, jax.random.PRNGKey(7))


def _lowered_text(pipe, args, **combine_kw):
    run = pipe.generate_jit(GuidancePlan.suffix(4, 0.5, 4.0), **combine_kw)
    return run.lower(*args).as_text(debug_info=True)


@pytest.fixture(scope="module")
def generate_text(tiny):
    return _lowered_text(*tiny)


def _has_scope(text, scope):
    """Whether ``scope`` is a component of an op's name stack."""
    return re.search(rf'["/]{re.escape(scope)}/', text) is not None


@pytest.mark.parametrize("scope", STEP_SCOPES + UNET_SCOPES)
def test_generate_program_carries_scope(generate_text, scope):
    assert _has_scope(generate_text, scope)


def test_step_scopes_hold_the_unet(generate_text):
    for step in ("sd.step.full", "sd.step.cond"):
        assert re.search(rf'["/]{re.escape(step)}/unet/unet\.down\.0/unet\.res/',
                         generate_text), step


def test_apg_momentum_bodies_carry_scopes(tiny):
    text = _lowered_text(*tiny, combine="apg", apg_momentum=0.5)
    for scope in ("sd.step.full", "sd.step.cond", "sd.combine", "sd.update"):
        assert _has_scope(text, scope), scope


def test_encode_program_carries_scope(tiny):
    pipe, _ = tiny
    tokens = jnp.zeros((2, pipe.cfg.text_len), jnp.int32)
    text = jax.jit(lambda p, tk: F.encode_text(p, pipe.text_cfg(), tk)).lower(
        pipe.params["text"], tokens).as_text(debug_info=True)
    assert _has_scope(text, "sd.encode")


def test_latents_unchanged_by_scopes(tiny):
    pipe, args = tiny
    out = np.asarray(pipe.generate_jit(GuidancePlan.suffix(4, 0.5, 4.0))(*args),
                     np.float64)
    np.testing.assert_allclose(out.reshape(-1)[::64], GOLDEN_EVERY_64TH,
                               rtol=1e-6, atol=1e-6)
    assert np.abs(out).sum() == pytest.approx(GOLDEN_ABS_SUM, rel=1e-6)
    assert (out ** 2).sum() == pytest.approx(GOLDEN_SQ_SUM, rel=1e-6)


def test_apg_momentum_latents_unchanged(tiny):
    pipe, args = tiny
    out = np.asarray(pipe.generate_jit(GuidancePlan.suffix(4, 0.5, 4.0),
                                       **APG_M_KW)(*args), np.float64)
    np.testing.assert_allclose(out.reshape(-1)[::64], APG_M_EVERY_64TH,
                               rtol=1e-6, atol=1e-6)
    assert np.abs(out).sum() == pytest.approx(APG_M_ABS_SUM, rel=1e-6)
    assert (out ** 2).sum() == pytest.approx(APG_M_SQ_SUM, rel=1e-6)


def test_weights_enter_as_arguments(tiny):
    pipe, args = tiny
    lowered = pipe.generate_jit(GuidancePlan.suffix(4, 0.5, 4.0)).lower(*args)
    n_weights = len(jax.tree.leaves(pipe.params["unet"]))
    assert len(jax.tree.leaves(lowered.args_info)) == n_weights + len(args)
    # no weight folded in as a constant: none of 1 KiB or more
    assert not re.search(r'dense<"0x[0-9A-F]{2048,}"', lowered.as_text())
