"""SD pipeline: UNet shapes, diffusion loss, end-to-end guided generation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import UNetConfig
from repro.core.pipeline import SDPipeline
from repro.core.schedules import NoiseSchedule
from repro.core.selective import GuidancePlan
from repro.models import layers as L
from repro.models import unet as U
from repro.train.losses import diffusion_loss


@pytest.fixture(scope="module")
def pipe():
    cfg = UNetConfig().reduced()
    return SDPipeline.init(cfg, jax.random.PRNGKey(0),
                           sched=NoiseSchedule.sd_default(100))


def test_unet_shapes(pipe):
    cfg = pipe.cfg
    B = 2
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (B, cfg.latent_size, cfg.latent_size, cfg.in_channels))
    t = jnp.array([3, 77])
    text = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.text_len, cfg.text_dim))
    out = U.unet_forward(pipe.params["unet"], cfg, x, t, text)
    assert out.shape == x.shape
    assert not bool(jnp.isnan(out).any())


def test_text_encoder_cond_differs_from_null(pipe):
    cond = pipe.encode_prompts(["a red disc", "a blue square"])
    null = pipe.null_embedding(2)
    assert cond.shape == null.shape
    assert float(jnp.abs(cond - null).max()) > 0


def test_generate_shapes_and_determinism(pipe):
    plan = GuidancePlan.suffix(6, 0.5, 4.0)
    a = pipe.generate(["a red disc"], plan, seed=3)
    b = pipe.generate(["a red disc"], plan, seed=3)
    assert a.shape == (1, pipe.cfg.latent_size, pipe.cfg.latent_size,
                       pipe.cfg.in_channels)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generate_scale1_selective_exact(pipe):
    """End-to-end exactness at s=1 through the real UNet."""
    base = pipe.generate(["a green ring"], GuidancePlan.full(6, 1.0), seed=1)
    sel = pipe.generate(["a green ring"], GuidancePlan.suffix(6, 0.5, 1.0), seed=1)
    np.testing.assert_allclose(np.asarray(base), np.asarray(sel),
                               rtol=1e-4, atol=1e-5)


def test_selective_divergence_ordering(pipe):
    """Fig. 1 through the real UNet: late windows hurt less than early."""
    plan_full = GuidancePlan.full(8, 5.0)
    base = pipe.generate(["a red cross"], plan_full, seed=5)
    d = {}
    for name, plan in {
        "early": GuidancePlan.window(8, 0.0, 0.5, 5.0),
        "late": GuidancePlan.suffix(8, 0.5, 5.0),
    }.items():
        out = pipe.generate(["a red cross"], plan, seed=5)
        d[name] = float(jnp.mean((out - base) ** 2))
    assert d["late"] < d["early"]


def test_diffusion_loss_finite_and_learns_direction(pipe):
    cfg = pipe.cfg
    rng = jax.random.PRNGKey(0)
    lat = jax.random.normal(rng, (4, cfg.latent_size, cfg.latent_size,
                                  cfg.in_channels))
    text = jax.random.normal(jax.random.fold_in(rng, 1),
                             (4, cfg.text_len, cfg.text_dim))
    null = jnp.zeros_like(text)
    loss, m = diffusion_loss(pipe.eps_fn(), pipe.sched,
                             jax.random.PRNGKey(2), lat, text, null)
    assert np.isfinite(float(loss))
    # untrained eps-prediction MSE should be near Var(eps) ~ 1
    assert 0.2 < float(loss) < 5.0


def test_timed_generate_protocol(pipe):
    plan = GuidancePlan.suffix(4, 0.5, 3.0)
    out, mean_s, std_s = pipe.timed_generate(["x"], plan, warmup=1, iters=2)
    assert out.shape[0] == 1
    assert mean_s > 0


def _attn_inputs(hw, c, heads=8, text_dim=64, text_len=16):
    key = jax.random.PRNGKey(hw)
    p = U.init_attnblock(L.ArrayMaker(key), c, heads, text_dim)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, hw, hw, c))
    text = jax.random.normal(jax.random.fold_in(key, 2), (2, text_len, text_dim))
    return p, x, text


def test_self_attention_kernel_matches_einsum():
    """The flash-kernel form of the self-attention (interpreted here, at
    the UNet's head dim 40) gives the einsum form's output within bf16
    rounding: it takes bf16 q/k/v and p where the einsums here are f32."""
    p, x, _ = _attn_inputs(16, 320)
    h = x.reshape(2, 256, 320)
    out = U._mha(p["self"], h, h, 8, blocks=(128, 256))
    expect = U._mha(p["self"], h, h, 8)
    assert out.dtype == expect.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-2, atol=1e-2)


def test_attnblock_dispatch(monkeypatch):
    """Off a TPU every attention takes the einsum path. On one, only a
    self-attention over at least FLASH_MIN_LEN positions takes the
    kernel; cross-attention (text keys) never does."""
    assert U._flash_blocks(64 * 64) is None          # CPU: einsum path
    p, x, text = _attn_inputs(16, 64)
    expect = U.attnblock(p, x, text, 8, 8)
    calls = []
    kernel = U.flash_attention_pallas

    def spy(q, k, v, **kw):
        calls.append((q.shape[1], k.shape[1]))
        return kernel(q, k, v, **{**kw, "interpret": True})

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(U, "flash_attention_pallas", spy)
    monkeypatch.setattr(U, "FLASH_MIN_LEN", 256)
    monkeypatch.setattr(U, "FLASH_BLOCKS", (128, 128))
    assert U._flash_blocks(256) == (128, 128)
    assert U._flash_blocks(192) is None               # below the threshold
    assert U._flash_blocks(320) is None               # not a block multiple
    out = U.attnblock(p, x, text, 8, 8)               # 16x16: 256 positions
    assert calls == [(256, 256)]                      # self only, not cross
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-2, atol=1e-2)
    small = _attn_inputs(8, 64)
    U.attnblock(*small, 8, 8)                         # 8x8: 64 positions
    assert calls == [(256, 256)]
