"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cfg_combine import cfg_combine_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(7,), (3, 33), (2, 5, 129), (1, 8, 8, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("scale", [0.0, 1.0, 7.5, -2.0])
def test_cfg_combine_sweep(shape, dtype, scale):
    rng = jax.random.PRNGKey(hash((shape, scale)) % 2**31)
    u = jax.random.normal(rng, shape, jnp.float32).astype(dtype)
    c = jax.random.normal(jax.random.fold_in(rng, 1), shape, jnp.float32).astype(dtype)
    out = cfg_combine_pallas(u, c, scale)
    expect = ref.ref_cfg_combine(u, c, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("rows,dim", [(1, 64), (5, 128), (16, 256), (33, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, dim, dtype):
    rng = jax.random.PRNGKey(rows * dim)
    x = jax.random.normal(rng, (rows, dim), jnp.float32).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(rng, 1), (dim,), jnp.float32)
    out = rmsnorm_pallas(x, s)
    expect = ref.ref_rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


_FLASH_SHAPES = [(128, 4, 4, 64), (256, 8, 2, 64), (128, 8, 1, 128)]
_FLASH_MASKS = [(True, None), (True, 64), (False, None)]
# the UNet's self-attention class: 8 heads of 40, non-causal, bf16 q/k/v
# with f32 output, blocks with bq != bk and with bk = S
_FLASH_UNET = [(256, 64, 128), (256, 128, 256), (512, 256, 128),
               (512, 128, 512)]
_FLASH_CASES = (
    [pytest.param(S, H, K, hd, causal, window, jnp.float32, 64, 64,
                  id=f"{causal}-{window}-{S}-{H}-{K}-{hd}")
     for causal, window in _FLASH_MASKS for S, H, K, hd in _FLASH_SHAPES]
    + [pytest.param(S, 8, 8, 40, False, None, jnp.bfloat16, bq, bk,
                    id=f"unet-{S}-bq{bq}-bk{bk}")
       for S, bq, bk in _FLASH_UNET])


@pytest.mark.parametrize("S,H,K,hd,causal,window,dtype,bq,bk", _FLASH_CASES)
def test_flash_attention_sweep(S, H, K, hd, causal, window, dtype, bq, bk):
    B = 2
    rng = jax.random.PRNGKey(S + H * K)
    q = jax.random.normal(rng, (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, K, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, K, hd),
                          jnp.float32).astype(dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 bq=bq, bk=bk, out_dtype=jnp.float32)
    assert out.dtype == jnp.float32
    # the oracle sees the same (possibly bf16) values in f32 arithmetic;
    # a bf16 kernel differs by its p rounded to bf16 for the PV product
    f32 = lambda a: a.astype(jnp.float32)
    expect = ref.ref_flash_attention(f32(q), f32(k), f32(v), causal=causal,
                                     window=window)
    tol = 3e-5 if dtype == jnp.float32 else 4e-3
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, S, H, K, hd = 1, 128, 4, 2, 64
    rng = jax.random.PRNGKey(9)
    q = jax.random.normal(rng, (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, K, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, K, hd),
                          jnp.float32).astype(dtype)
    out = flash_attention_pallas(q, k, v, bq=64, bk=64)
    expect = ref.ref_flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("S,H,K,hd,pos", [
    (256, 4, 4, 64, 100), (512, 8, 2, 64, 511), (256, 8, 1, 128, 0),
])
@pytest.mark.parametrize("window", [None, 64])
def test_decode_attention_sweep(S, H, K, hd, pos, window):
    B = 2
    rng = jax.random.PRNGKey(S + pos)
    q = jax.random.normal(rng, (B, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, K, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, K, hd), jnp.float32)
    out = decode_attention_pallas(q, k, v, pos, window=window, bk=128)
    expect = ref.ref_decode_attention(q, k, v, pos, window=window)
    np.testing.assert_allclose(out, expect, rtol=3e-5, atol=3e-5)


def test_kernels_match_model_attention():
    """The flash kernel agrees with the model's production attention path
    (same semantics end to end)."""
    from repro.configs import get_smoke_config
    from repro.models import attention as A
    from repro.models import layers as L

    cfg = get_smoke_config("yi-9b")
    mk = L.ArrayMaker(jax.random.PRNGKey(0))
    p = A.init_attention(cfg, mk)
    B, S = 2, 128
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out_model, _ = A.attn_forward(p, cfg, x, pos)
    q, k, v = A._qkv(p, cfg, x, pos)
    ctx = flash_attention_pallas(q, k, v, bq=64, bk=64)
    rep = cfg.num_heads // cfg.num_kv_heads
    ctx = ctx.reshape(B, S, cfg.num_kv_heads, rep, -1)
    out_kernel = A._out_proj(p, ctx, x.dtype)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=2e-4, atol=2e-4)


def test_ops_wrappers_jit():
    u = jnp.ones((4, 130))
    c = jnp.zeros((4, 130))
    out = ops.cfg_combine(u, c, 0.5)
    np.testing.assert_allclose(out, 0.5 * jnp.ones((4, 130)))
