"""Flash attention — Pallas TPU kernel.

Grid (B, K, nq, nk); the last grid axis is the sequential KV sweep with the
online-softmax running state (m, l, acc) held in VMEM scratch, so no
(S, S) score tensor ever reaches HBM. GQA is free: the K/V BlockSpec
index_map sends query-head-group g to kv head g — no head-replicated KV
ever materialises. Causal + sliding-window masks are applied in-kernel;
fully-masked tiles still execute (masked) — the TPU grid is sequential so
correctness is unaffected. Without either mask the kernel builds no
positions and no mask at all.

Two callers: causal (and windowed) prefill for the LM attention, and the
non-causal self-attention of the SD UNet's large levels
(``models/unet.py``, head dim 40 at 64x64 latents), which passes bf16
q/k/v with the softmax scale folded into q (``scale=1.0``) and asks for an
f32 output. Scores, running max, sum and accumulator are f32 throughout;
p is cast to v's dtype for the PV product, which accumulates in f32.

Block sizes default to (128 q x 128 kv) tiles at hd lanes — MXU-aligned for
hd in {64, 128, 256}; the UNet picks larger ones for its sequence lengths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window, bq: int, bk: int, nk: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(2)
    q = q_ref[...]                                  # (rep, bq, hd)
    k = k_ref[...]                                  # (bk, hd)
    v = v_ref[...]
    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if causal or window is not None:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq, 1), 1)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
        mask = jnp.bool_(True)
        if causal:
            mask = kpos <= qpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)             # (rep, bq, bk)

    # m and l are (rep, bq, 1) columns: the row reductions produce them in
    # that layout and the broadcasts back over the tile read them from it
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jax.lax.dot_general(p.astype(v.dtype), v,
                                          (((2,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None, bq: int = 128,
                           bk: int = 128, scale: float | None = None,
                           out_dtype=None, interpret: bool = True):
    """q (B,S,H,hd); k,v (B,S,K,hd). Returns (B,S,H,hd) in ``out_dtype``
    (default q's). ``scale`` multiplies the scores (default 1/sqrt(hd));
    at 1.0 the kernel skips the multiply."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    rep = H // K
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    # layout: q (B,K,rep,S,hd); kv (B,K,S,hd)
    qr = q.reshape(B, S, K, rep, hd).transpose(0, 2, 3, 1, 4)
    kr = k.transpose(0, 2, 1, 3)
    vr = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, nk=nk),
        grid=(B, K, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, rep, bq, hd), lambda b, g, i, j: (b, g, 0, i, 0)),
            pl.BlockSpec((None, None, bk, hd), lambda b, g, i, j: (b, g, j, 0)),
            pl.BlockSpec((None, None, bk, hd), lambda b, g, i, j: (b, g, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, bq, hd),
                               lambda b, g, i, j: (b, g, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, rep, S, hd), out_dtype or q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, bq, 1), jnp.float32),
            pltpu.VMEM((rep, bq, 1), jnp.float32),
            pltpu.VMEM((rep, bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
