"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as FA
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
# the MMDiT's joint attention class: 64-wide heads, non-causal, bf16 q/k/v
# with f32 output, the sequence padded to a block multiple and only the
# first kv_len keys real: masked in the last real tile (a few real keys in
# it, or one), with whole tiles of padding after it at (64, 64)
_FLASH_KV_LEN = [(256, 200, 128, 128), (256, 100, 64, 64), (512, 333, 128, 256),
                 (384, 257, 128, 128)]
# one kv block over the sequence, so each row's softmax in one pass, bqc
# rows of the q tile at a time (set through the kernel's score-tile
# budget; the MMDiT's body): kv_len inside the block; one real key in the
# last 128-lane group; SD3's share of real keys (333 of 384) under three
# groups of rows; several q tiles of several groups without padding;
# groups of 32
_FLASH_ONE_PASS = [(512, 450, 256, 128), (384, 257, 128, 64),
                   (384, 333, 384, 128), (512, None, 256, 64),
                   (256, 200, 128, 32)]
_FLASH_CASES = (
    [pytest.param(S, H, K, hd, causal, window, jnp.float32, 64, 64, None, None,
                  id=f"{causal}-{window}-{S}-{H}-{K}-{hd}")
     for causal, window in _FLASH_MASKS for S, H, K, hd in _FLASH_SHAPES]
    + [pytest.param(S, 8, 8, 40, False, None, jnp.bfloat16, bq, bk, None, None,
                    id=f"unet-{S}-bq{bq}-bk{bk}")
       for S, bq, bk in _FLASH_UNET]
    + [pytest.param(S, 4, 4, 64, False, None, jnp.bfloat16, bq, bk, n, None,
                    id=f"kvlen-{n}of{S}-bq{bq}-bk{bk}")
       for S, n, bq, bk in _FLASH_KV_LEN]
    + [pytest.param(S, 4, 4, 64, False, None, dtype, bq, S, n, bqc,
                    id=f"onepass-{n}of{S}-bq{bq}-bqc{bqc}-{jnp.dtype(dtype).name}")
       for S, n, bq, bqc in _FLASH_ONE_PASS
       for dtype in (jnp.float32, jnp.bfloat16)])


@pytest.mark.parametrize("S,H,K,hd,causal,window,dtype,bq,bk,kv_len,bqc",
                         _FLASH_CASES)
def test_flash_attention_sweep(S, H, K, hd, causal, window, dtype, bq, bk,
                               kv_len, bqc, monkeypatch):
    if bqc is not None:
        monkeypatch.setattr(FA, "SCORE_TILE_BYTES", 4 * (H // K) * bk * bqc)
        assert FA._row_group(bq, H // K, bk) == bqc
    B = 2
    rng = jax.random.PRNGKey(S + H * K)
    q = jax.random.normal(rng, (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, K, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, K, hd),
                          jnp.float32).astype(dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 bq=bq, bk=bk, out_dtype=jnp.float32,
                                 kv_len=kv_len)
    assert out.dtype == jnp.float32
    # the oracle sees the same (possibly bf16) values in f32 arithmetic;
    # a bf16 kernel differs by its p rounded to bf16 for the PV product.
    # With kv_len it sees the real tokens alone: the kernel's rows of real
    # queries must not see the padded keys
    n = S if kv_len is None else kv_len
    f32 = lambda a: a[:, :n].astype(jnp.float32)
    expect = ref.ref_flash_attention(f32(q), f32(k), f32(v), causal=causal,
                                     window=window)
    tol = 3e-5 if dtype == jnp.float32 else 4e-3
    np.testing.assert_allclose(out[:, :n], expect, rtol=tol, atol=tol)
    assert bool(jnp.isfinite(out).all())


def test_flash_attention_kv_len_at_full_length_is_unmasked():
    """``kv_len`` equal to the sequence length masks nothing: the output is
    the unmasked call's, bit for bit."""
    B, S, H, hd = 2, 256, 4, 64
    rng = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (B, S, H, hd),
                                 jnp.float32).astype(jnp.bfloat16) for i in range(3))
    kw = dict(causal=False, bq=128, bk=64, out_dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(flash_attention_pallas(q, k, v, kv_len=S, **kw)),
        np.asarray(flash_attention_pallas(q, k, v, **kw)))


def test_flash_attention_one_pass_is_online_over_one_block(monkeypatch):
    """Over one kv block the one-pass body is the online body's arithmetic
    (its rescale multiplies by exact zeros): the same output bit for bit,
    with and without padded keys, whatever the group of rows."""
    B, S, H, hd = 2, 256, 4, 64
    rng = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (B, S, H, hd),
                                 jnp.float32).astype(jnp.bfloat16) for i in range(3))
    for kv_len in (None, 200):
        kw = dict(causal=False, bq=128, bk=S, out_dtype=jnp.float32,
                  kv_len=kv_len)
        with monkeypatch.context() as m:
            m.setattr(FA, "_one_pass", lambda *a: False)
            online = np.asarray(flash_attention_pallas(q, k, v, **kw))
        for rows in (128, 64, 32):
            monkeypatch.setattr(FA, "SCORE_TILE_BYTES", 4 * S * rows)
            assert FA._row_group(128, 1, S) == rows
            np.testing.assert_array_equal(
                np.asarray(flash_attention_pallas(q, k, v, **kw)), online)


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
