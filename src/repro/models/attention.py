"""GQA attention: RoPE, qk-norm, sliding windows, blocked prefill, ring caches.

Execution paths
---------------
* ``attn_forward``          — direct O(S^2)-scores path for short sequences
                              (tests, smoke configs).
* ``attn_forward_blocked``  — flash-style nested-scan online-softmax path for
                              long sequences: scores never materialise beyond
                              one (Bq x Bk) tile; sliding-window blocks slide
                              a *dynamic* KV range so SWA FLOPs are honest.
* ``attn_decode``           — one token vs a linear (B,S,K,hd) cache.
* ``attn_decode_ring``      — one token vs a ring buffer of size ``window``
                              (Mistral-style); the memory-honest path for
                              SWA / long_500k decode.

Grouped-head einsums never materialise H-replicated KV.

The Pallas kernels in ``repro.kernels`` implement the same math with explicit
VMEM BlockSpecs for TPU; ``repro.kernels.ref`` mirrors this module.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


def _constrain(x, logical):
    """Mesh-aware sharding hint (no-op without a mesh context). Pins the
    batch/kv-head layout of q,k,v inside the blocked scans — without it
    GSPMD's propagation through dynamic-slice + nested scans can replicate
    the batch dim (observed: 16x activation blowup on the train step)."""
    from repro.dist.sharding import RULES_SERVE, constrain
    return constrain(x, logical, RULES_SERVE)


def init_attention(cfg, mk):
    D, H, K = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    p = {
        "wq": mk((D, H, hd), ("embed", "heads", "head_dim"), scale=1 / math.sqrt(D)),
        "wk": mk((D, K, hd), ("embed", "kv_heads", "head_dim"), scale=1 / math.sqrt(D)),
        "wv": mk((D, K, hd), ("embed", "kv_heads", "head_dim"), scale=1 / math.sqrt(D)),
        "wo": mk((H, hd, D), ("heads", "head_dim", "embed"), scale=1 / math.sqrt(H * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = mk((hd,), ("head_dim",), init="ones")
        p["k_norm"] = mk((hd,), ("head_dim",), init="ones")
    return p


def _qkv(params, cfg, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = L.head_rmsnorm(params["q_norm"], q)
        k = L.head_rmsnorm(params["k_norm"], k)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q, num_kv: int):
    """(B,S,H,hd) -> (B,S,K,rep,hd)."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, num_kv, H // num_kv, hd)


def _out_proj(params, ctx, dtype):
    # ctx: (B,Q,K,rep,hd) -> (B,Q,D)
    B, Q, K, rep, hd = ctx.shape
    ctx = ctx.reshape(B, Q, K * rep, hd)
    return jnp.einsum("bqhk,hkd->bqd", ctx, params["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# Full-sequence paths
# ---------------------------------------------------------------------------


def attn_forward(params, cfg, x, positions, *, causal=True, window=None):
    """Direct path; x (B,S,D). Returns (out, cache {k,v} (B,S,K,hd))."""
    q, k, v = _qkv(params, cfg, x, positions)
    hd = q.shape[-1]
    qg = _group(q, cfg.num_kv_heads)
    scores = jnp.einsum("bqkrh,bskh->bkrqs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    qpos = positions[:, None, None, :, None]
    kpos = positions[:, None, None, None, :]
    mask = (kpos <= qpos) if causal else jnp.bool_(True)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkrqs,bskh->bqkrh", w, v)
    return _out_proj(params, ctx, x.dtype), {"k": k, "v": v}


def attn_forward_blocked(params, cfg, x, positions, *, causal=True, window=None,
                         q_chunk=512, kv_chunk=1024):
    """Flash-style nested scan; never materialises more than one score tile.

    For ``window`` (SWA) the inner scan covers only ceil((window+q_chunk)/
    kv_chunk)+1 KV chunks, positioned dynamically per q-chunk, so sliding-
    window FLOPs scale with the window, not the sequence.
    """
    B, S, D = x.shape
    assert S % q_chunk == 0, (S, q_chunk)
    q, k, v = _qkv(params, cfg, x, positions)
    K = cfg.num_kv_heads
    hd = q.shape[-1]
    rep = cfg.num_heads // K
    k = _constrain(k, ("batch", None, "kv_heads", None))
    v = _constrain(v, ("batch", None, "kv_heads", None))
    qg = _group(q, K)                                    # (B,S,K,rep,hd)
    qg = _constrain(qg, ("batch", None, "kv_heads", None, None))
    scale = 1.0 / math.sqrt(hd)

    if window is not None:
        n_kv = min(S // kv_chunk + (S % kv_chunk > 0),
                   (window + q_chunk) // kv_chunk + 2)
    else:
        n_kv = S // kv_chunk + (S % kv_chunk > 0)

    kv_pos_base = positions[:, 0]                        # (B,) absolute base

    def q_step(_, qi):
        qs = qi * q_chunk
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qs, q_chunk, axis=1)
        qpos = jax.lax.dynamic_slice_in_dim(positions, qs, q_chunk, axis=1)

        if window is not None:
            # earliest kv index any row in this q-chunk can see
            lo = jnp.maximum(qs + q_chunk - 1 - (window - 1) - (kv_chunk - 1), 0)
            lo = (lo // kv_chunk) * kv_chunk
            lo = jnp.minimum(lo, S - n_kv * kv_chunk) if S >= n_kv * kv_chunk else 0
            lo = jnp.maximum(lo, 0)
        else:
            lo = 0

        def kv_step(carry, kj):
            m, l, acc = carry
            ks = lo + kj * kv_chunk
            k_blk = jax.lax.dynamic_slice_in_dim(k, ks, kv_chunk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ks, kv_chunk, axis=1)
            kpos = kv_pos_base[:, None] + ks + jnp.arange(kv_chunk)[None, :]
            s = jnp.einsum("bqkrh,bskh->bkrqs", q_blk, k_blk).astype(jnp.float32) * scale
            msk = jnp.bool_(True)
            if causal:
                msk = kpos[:, None, None, None, :] <= qpos[:, None, None, :, None]
            if window is not None:
                msk = msk & (kpos[:, None, None, None, :]
                             > qpos[:, None, None, :, None] - window)
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkrqs,bskh->bkrqh", p.astype(x.dtype), v_blk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, K, rep, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_kv),
                                      unroll=n_kv if _cost_mode() else 1)
        out = acc / jnp.maximum(l, 1e-20)[..., None]     # (B,K,rep,Q,hd)
        return None, out.transpose(0, 3, 1, 2, 4).astype(x.dtype)

    # flash-bwd pattern: recompute each q-chunk's inner sweep in backward
    # instead of saving per-kv-step residuals (nested-scan residuals are what
    # blow temp memory in train steps otherwise)
    q_step_ck = jax.checkpoint(q_step, prevent_cse=False)
    _, chunks = jax.lax.scan(q_step_ck, None, jnp.arange(S // q_chunk),
                             unroll=S // q_chunk if _cost_mode() else 1)
    # chunks: (nq, B, q_chunk, K, rep, hd) -> (B, S, K, rep, hd)
    ctx = chunks.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, rep, hd)
    return _out_proj(params, ctx, x.dtype), {"k": k, "v": v}


def _cost_mode() -> bool:
    return os.environ.get("REPRO_COST_MODE") == "1"


def _kv_quant() -> bool:
    """REPRO_KV_QUANT=int8: symmetric per-(position, kv-head) int8 KV cache.
    Halves cache residency and per-step HBM traffic (the decode roofline's
    dominant term); dequantisation fuses into the attention matmul on TPU.
    §Perf H3 iteration."""
    return os.environ.get("REPRO_KV_QUANT") == "int8"


def _quantize_kv(x):
    """Slot-arena env-var path: bf16 scales and the historical 1e-6 amax
    floor (the pinned REPRO_KV_QUANT cache behavior). The paged
    ``kv_dtype="int8"`` arena uses the fp32-scale forms in
    ``repro.kernels.quant`` directly."""
    from repro.kernels.quant import quantize_kv
    return quantize_kv(x, scale_dtype=jnp.bfloat16, eps=1e-6)


def _dequantize_kv(q, scale, dtype):
    from repro.kernels.quant import dequantize_kv
    return dequantize_kv(q, scale, dtype)


def attn_forward_auto(params, cfg, x, positions, *, causal=True, window=None,
                      blocked_threshold=2048):
    S = x.shape[1]
    if S > blocked_threshold and S % 512 == 0:
        if _cost_mode():
            # bigger tiles -> short, fully-unrolled scans so cost_analysis
            # counts the whole quadratic term (never executed)
            return attn_forward_blocked(params, cfg, x, positions,
                                        causal=causal, window=window,
                                        q_chunk=max(512, S // 8),
                                        kv_chunk=max(1024, S // 4))
        return attn_forward_blocked(params, cfg, x, positions,
                                    causal=causal, window=window)
    return attn_forward(params, cfg, x, positions, causal=causal, window=window)


# ---------------------------------------------------------------------------
# Decode paths
# ---------------------------------------------------------------------------


def attn_decode(params, cfg, x, cache, pos, *, window=None):
    """One token vs linear cache. x (B,1,D); cache k/v (B,S,K,hd) — or int8
    values + scales when REPRO_KV_QUANT=int8; pos scalar."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(params, cfg, x, positions)
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
            buf, val.astype(buf.dtype), pos, axis=1)
        new_cache = {"k": upd(cache["k"], kq), "v": upd(cache["v"], vq),
                     "k_scale": upd(cache["k_scale"], ks),
                     "v_scale": upd(cache["v_scale"], vs)}
        k = _dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        v = _dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
        new_cache = {"k": k, "v": v}
    qg = _group(q, cfg.num_kv_heads)
    hd = q.shape[-1]
    scores = jnp.einsum("bqkrh,bskh->bkrqs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    kpos = jnp.arange(k.shape[1])
    valid = kpos <= pos
    if window is not None:
        valid = valid & (kpos > pos - window)
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkrqs,bskh->bqkrh", w, v)
    return _out_proj(params, ctx, x.dtype), new_cache


def attn_decode_ring(params, cfg, x, cache, pos, *, window: int):
    """One token vs a ring buffer of ``window`` slots (memory-honest SWA).

    cache: {k,v: (B,W,K,hd), slot_pos: (W,) int32 absolute positions, -1 = empty}.
    """
    B = x.shape[0]
    W = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _qkv(params, cfg, x, positions)
    slot = jnp.mod(pos, W)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    slot_pos = jax.lax.dynamic_update_slice_in_dim(
        cache["slot_pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0)
    qg = _group(q, cfg.num_kv_heads)
    hd = q.shape[-1]
    scores = jnp.einsum("bqkrh,bskh->bkrqs", qg, k).astype(jnp.float32) / math.sqrt(hd)
    valid = (slot_pos >= 0) & (slot_pos <= pos) & (slot_pos > pos - window)
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkrqs,bskh->bqkrh", w, v)
    return _out_proj(params, ctx, x.dtype), {"k": k, "v": v, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# Paged decode path
# ---------------------------------------------------------------------------


def _paged_kernel() -> bool:
    """Paged decode attention runs the compiled block-table Pallas kernels
    on a TPU and the jnp gather form everywhere else (the CPU has no
    Mosaic backend, and interpreting the kernels there is a test tool)."""
    return jax.default_backend() == "tpu"


def paged_cache_spec(cfg, mk, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, *, kv_dtype: str = "bf16"):
    """One layer's share of the paged KV pool.

    Pages are whole-pool resources (``pages`` leading axis), not
    per-request rows; the ``pages``/``page`` logical names are wired into
    the §3 rule tables so ``dist`` shards the pool like any other cache.

    ``kv_dtype="int8"`` (DESIGN.md §11) stores int8 values plus paired
    per-(position, kv-head) fp32 scale leaves (``k_scale``/``v_scale``,
    shape ``(pages, page, kv_heads, 1)``). The scale leaves reuse the
    same ``pages``/``page`` logical names, so the §3 rule tables shard
    them alongside the values with no extra rules, and every pool-wide
    op (CoW ``copy_page``, defrag-free page moves, partition specs)
    treats the pair as one physical page.
    """
    if kv_dtype not in ("bf16", "int8"):
        raise ValueError(f"kv_dtype {kv_dtype!r} not in ('bf16', 'int8')")
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    val_dtype = jnp.int8 if kv_dtype == "int8" else dtype
    p = {
        "k": mk((num_pages, page_size, K, hd),
                ("pages", "page", "kv_heads", "head_dim"), init="zeros",
                dtype=val_dtype),
        "v": mk((num_pages, page_size, K, hd),
                ("pages", "page", "kv_heads", "head_dim"), init="zeros",
                dtype=val_dtype),
    }
    if kv_dtype == "int8":
        for name in ("k_scale", "v_scale"):
            p[name] = mk((num_pages, page_size, K, 1),
                         ("pages", "page", "kv_heads", None), init="zeros",
                         dtype=jnp.float32)
    return p


def attn_decode_paged(params, cfg, x, pool, block_table, pos, *,
                      window=None, phase=None, kernel=None):
    """One token per row vs the shared paged KV pool.

    x (B,1,D); pool {k,v: (P, page_size, K, hd)} — shared across every
    resident request; block_table (B, nb) int32 maps each row's logical
    page index to a physical page (entries >= P are padding: writes drop,
    reads clamp and are masked); pos (B,) int32 per-row positions — rows
    at *different* sequence positions step together, which is what lets
    mixed-length requests share one pool.

    ``phase`` (B,) int32, when given, marks the batch as a **ragged pass
    list** (DESIGN.md §12): rows with ``phase == 0`` are padding whose
    attention output is exactly zero (their block tables are all
    out-of-range, so their writes drop too), live rows are unchanged.
    On the kernel path the ragged kernels additionally skip the dead
    rows' page DMA and FLOPs inside the launch.

    ``kernel`` picks the attention form: ``None`` follows the platform
    (:func:`_paged_kernel`), ``False`` forces the gather form — the
    choice for a step partitioned over several devices, since Mosaic
    kernels cannot be partitioned automatically.

    Returns (out (B,1,D), updated pool). The new K/V is scattered into
    the row's current page before attention, so the semantics match
    ``attn_decode`` exactly on the covered positions. An int8 pool
    (``k_scale`` leaves present, DESIGN.md §11) quantizes on write —
    the one-row append quantizes just the new position, never touching
    already-written rows — and dequantizes on read, fused in-kernel on
    the kernel path.
    """
    B = x.shape[0]
    P, ps = pool["k"].shape[:2]
    nb = block_table.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _qkv(params, cfg, x, pos[:, None])
    wpage = jnp.take_along_axis(block_table, (pos // ps)[:, None], axis=1)[:, 0]
    woff = pos % ps
    quant = "k_scale" in pool
    put = lambda leaf, val: leaf.at[wpage, woff].set(
        val.astype(leaf.dtype), mode="drop")
    if quant:
        from repro.kernels.quant import quantize_kv
        kq, ks = quantize_kv(k_new[:, 0])            # (B,K,hd) -> + (B,K,1)
        vq, vs = quantize_kv(v_new[:, 0])
        new_pool = {"k": put(pool["k"], kq), "v": put(pool["v"], vq),
                    "k_scale": put(pool["k_scale"], ks),
                    "v_scale": put(pool["v_scale"], vs)}
    else:
        new_pool = {"k": put(pool["k"], k_new[:, 0]),
                    "v": put(pool["v"], v_new[:, 0])}
    qg = _group(q, cfg.num_kv_heads)                 # (B,1,K,rep,hd)
    hd = q.shape[-1]
    if _paged_kernel() if kernel is None else kernel:
        from repro.kernels import paged_decode_attention as PDA
        interpret = jax.default_backend() != "tpu"
        if phase is not None and quant:
            ctx = PDA.ragged_paged_decode_attention_int8_pallas(
                q[:, 0], new_pool["k"], new_pool["k_scale"],
                new_pool["v"], new_pool["v_scale"], block_table, pos,
                phase, window=window, interpret=interpret)
        elif phase is not None:
            ctx = PDA.ragged_paged_decode_attention_pallas(
                q[:, 0], new_pool["k"], new_pool["v"], block_table, pos,
                phase, window=window, interpret=interpret)
        elif quant:
            ctx = PDA.paged_decode_attention_int8_pallas(
                q[:, 0], new_pool["k"], new_pool["k_scale"],
                new_pool["v"], new_pool["v_scale"], block_table, pos,
                window=window, interpret=interpret)
        else:
            ctx = PDA.paged_decode_attention_pallas(
                q[:, 0], new_pool["k"], new_pool["v"], block_table, pos,
                window=window, interpret=interpret)
        ctx = ctx.reshape(B, 1, cfg.num_kv_heads, qg.shape[3], hd)
        return _out_proj(params, ctx, x.dtype), new_pool
    bt = jnp.clip(block_table, 0, P - 1)
    if quant:
        from repro.kernels.quant import dequantize_kv
        k = dequantize_kv(new_pool["k"][bt], new_pool["k_scale"][bt],
                          x.dtype).reshape(B, nb * ps, cfg.num_kv_heads, hd)
        v = dequantize_kv(new_pool["v"][bt], new_pool["v_scale"][bt],
                          x.dtype).reshape(B, nb * ps, cfg.num_kv_heads, hd)
    else:
        k = new_pool["k"][bt].reshape(B, nb * ps, cfg.num_kv_heads, hd)
        v = new_pool["v"][bt].reshape(B, nb * ps, cfg.num_kv_heads, hd)
    scores = jnp.einsum("bqkrh,bskh->bkrqs", qg, k).astype(jnp.float32) \
        / math.sqrt(hd)
    kpos = jnp.arange(nb * ps)
    valid = kpos[None, :] <= pos[:, None]
    if window is not None:
        valid = valid & (kpos[None, :] > pos[:, None] - window)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkrqs,bskh->bqkrh", w, v)
    if phase is not None:
        # ragged padding rows attend over clamped garbage pages; pin their
        # context to the kernels' exact-zero contract so both paths agree
        live = (jnp.asarray(phase, jnp.int32) > 0)[:, None, None, None, None]
        ctx = jnp.where(live, ctx, jnp.zeros_like(ctx))
    return _out_proj(params, ctx, x.dtype), new_pool


def paged_scatter_prefill(pool_layer, cache_layer, pages, offs):
    """Scatter one layer's batched-prefill KV into its paged pool,
    quantizing on write when the pool is int8.

    ``cache_layer`` {k, v} with leaves (kb, Sb, K, hd) — or (n, kb, Sb,
    K, hd) for stacked scan segments; ``pool_layer`` the matching paged
    pool (values, plus scale leaves when quantized); ``pages``/``offs``
    (kb*Sb,) flattened per-position destinations (out-of-range pages —
    padding rows, masked uncond shares, positions past a short prompt —
    drop). Quantize-on-write keeps prefill one-pass: the scatter is the
    only traversal of the prefill KV, so the int8 conversion rides it for
    free instead of re-reading the pool afterwards (DESIGN.md §11).
    """
    from repro.kernels.quant import quantize_kv

    quant = "k_scale" in pool_layer

    def put(pool_leaf, vals):
        if pool_leaf.ndim == 5:                      # stacked scan segment
            return pool_leaf.at[:, pages, offs].set(
                vals.astype(pool_leaf.dtype), mode="drop")
        return pool_leaf.at[pages, offs].set(
            vals.astype(pool_leaf.dtype), mode="drop")

    out = {}
    for name in ("k", "v"):
        c = cache_layer[name]
        if c.ndim == 5:                              # (n, kb, Sb, K, hd)
            flat = c.reshape(c.shape[0], -1, *c.shape[3:])
        else:                                        # (kb, Sb, K, hd)
            flat = c.reshape(-1, *c.shape[2:])
        if quant:
            vals, scales = quantize_kv(flat)
            out[name] = put(pool_layer[name], vals)
            out[name + "_scale"] = put(pool_layer[name + "_scale"], scales)
        else:
            out[name] = put(pool_layer[name], flat)
    return out


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def cache_spec(cfg, mk, batch: int, capacity: int, *, ring: bool,
               dtype=jnp.bfloat16):
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    quant = _kv_quant() and not ring
    val_dtype = jnp.int8 if quant else dtype
    p = {
        "k": mk((batch, capacity, K, hd),
                ("batch", "kv_seq", "kv_heads", "head_dim"), init="zeros",
                dtype=val_dtype),
        "v": mk((batch, capacity, K, hd),
                ("batch", "kv_seq", "kv_heads", "head_dim"), init="zeros",
                dtype=val_dtype),
    }
    if quant:
        p["k_scale"] = mk((batch, capacity, K, 1),
                          ("batch", "kv_seq", "kv_heads", None), init="zeros",
                          dtype=jnp.bfloat16)
        p["v_scale"] = mk((batch, capacity, K, 1),
                          ("batch", "kv_seq", "kv_heads", None), init="zeros",
                          dtype=jnp.bfloat16)
    if ring:
        p["slot_pos"] = mk((capacity,), ("kv_seq",), init="zeros", dtype=jnp.int32)
    return p


def cache_from_prefill(kv, *, window: int | None, seq_len: int):
    """Convert prefill {k,v} (B,S,K,hd) into the decode cache.

    window=None: linear cache, padded to capacity by the caller.
    window=W: ring cache holding the last W positions.
    """
    if window is None or window >= seq_len:
        return kv
    k, v = kv["k"], kv["v"]
    W = window
    tail_k = k[:, seq_len - W:seq_len]
    tail_v = v[:, seq_len - W:seq_len]
    abs_pos = jnp.arange(seq_len - W, seq_len, dtype=jnp.int32)
    # place each absolute position at slot pos % W
    slots = jnp.mod(abs_pos, W)
    order = jnp.argsort(slots)
    return {"k": tail_k[:, order], "v": tail_v[:, order],
            "slot_pos": abs_pos[order]}
