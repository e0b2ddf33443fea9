"""Composable transformer stacks for all assigned families.

A model is a list of **segments**; each segment is either

* ``("scan", pattern, n_groups)`` — ``lax.scan`` over ``n_groups`` stacked
  copies of the repeating block ``pattern`` (HLO size O(1) in depth — load-
  bearing for 512-way GSPMD compiles), or
* ``("plain", kind)``            — one unrolled block (pattern remainders,
  DeepSeek's leading dense layer).

Block kinds: ``attn`` | ``swa`` (GQA or MLA + SwiGLU/MoE), ``rglru``
(Griffin recurrent), ``mlstm`` / ``slstm`` (xLSTM). Encoder stacks
(``cfg.is_encoder``) use bidirectional attention + LayerNorm + GELU-MLP.

Every forward path exists in three flavours sharing the block code:
``forward`` (train / scoring), ``prefill`` (returns per-layer caches) and
``decode_step`` (one token, caches threaded through the scans).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.dist.sharding import AxisRules, constrain as _dist_constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import xlstm as XL


def cost_mode() -> bool:
    """REPRO_COST_MODE=1: unroll scans so ``compiled.cost_analysis()`` counts
    every layer (XLA reports while-loop bodies once — verified empirically).
    The cost-mode lowering is never executed; only its cost_analysis is read.
    """
    return os.environ.get("REPRO_COST_MODE") == "1"


def _unroll(n: int) -> int:
    return n if cost_mode() else 1


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------


def segments(cfg):
    """-> list of ('scan', pattern, n) | ('plain', kind) covering all layers."""
    blocks = cfg.blocks
    segs = []
    start = 0
    if cfg.moe is not None and cfg.moe.first_k_dense:
        for i in range(cfg.moe.first_k_dense):
            segs.append(("plain", blocks[i]))
        start = cfg.moe.first_k_dense
    rest = blocks[start:]
    period = len(cfg.block_pattern)
    n_full = len(rest) // period
    if n_full > 0:
        segs.append(("scan", tuple(rest[:period]), n_full))
    for kind in rest[n_full * period:]:
        segs.append(("plain", kind))
    return segs


def _is_moe_layer(cfg, seg_idx_is_leading_dense: bool) -> bool:
    return cfg.moe is not None and not seg_idx_is_leading_dense


def _window(cfg, kind, long_ctx: bool):
    if kind == "swa":
        return cfg.sliding_window
    if kind == "attn" and long_ctx and cfg.mla is None:
        return cfg.long_context_window    # SWA substitute for long_500k
    return None


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------


def init_block(cfg, mk, kind: str, *, moe_layer: bool):
    norm = L.init_layernorm if cfg.is_encoder else L.init_rmsnorm
    p = {"norm1": norm(mk, cfg.d_model)}
    if kind in ("attn", "swa"):
        p["attn"] = MLA.init_mla(cfg, mk) if cfg.mla else A.init_attention(cfg, mk)
    elif kind == "rglru":
        p["mix"] = RG.init_rglru(cfg, mk)
    elif kind == "mlstm":
        p["mix"] = XL.init_mlstm(cfg, mk)
    elif kind == "slstm":
        p["mix"] = XL.init_slstm(cfg, mk)
    else:
        raise ValueError(kind)
    if kind in ("attn", "swa", "rglru") and cfg.d_ff > 0:
        p["norm2"] = norm(mk, cfg.d_model)
        if moe_layer:
            p["mlp"] = MOE.init_moe(cfg, mk)
        elif cfg.is_encoder:
            p["mlp"] = L.init_gelu_mlp(mk, cfg.d_model, cfg.d_ff)
        else:
            p["mlp"] = L.init_swiglu(mk, cfg.d_model, cfg.d_ff)
    return p


def _norm(cfg, params, x):
    return L.layernorm(params, x, cfg.norm_eps) if cfg.is_encoder \
        else L.rmsnorm(params, x, cfg.norm_eps)


def block_forward(params, cfg, kind, x, positions, *, moe_layer: bool,
                  long_ctx: bool = False, want_cache: bool = False):
    """-> (y, cache, aux)."""
    h = _norm(cfg, params["norm1"], x)
    window = _window(cfg, kind, long_ctx)
    causal = not cfg.is_encoder
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "swa"):
        if cfg.mla:
            S = x.shape[1]
            fwd = MLA.mla_forward_blocked if (S > 2048 and S % 512 == 0) else MLA.mla_forward
            mix, cache = fwd(params["attn"], cfg, h, positions, causal=causal)
        else:
            mix, cache = A.attn_forward_auto(params["attn"], cfg, h, positions,
                                             causal=causal, window=window)
    elif kind == "rglru":
        mix, cache = RG.rglru_forward(params["mix"], cfg, h)
    elif kind == "mlstm":
        mix, cache = XL.mlstm_forward(params["mix"], cfg, h)
    elif kind == "slstm":
        mix, cache = XL.slstm_forward(params["mix"], cfg, h)
    x = x + mix
    if "mlp" in params:
        h2 = _norm(cfg, params["norm2"], x)
        if moe_layer:
            y, aux = MOE.moe_forward(params["mlp"], cfg, h2)
        elif cfg.is_encoder:
            y = L.gelu_mlp(params["mlp"], h2)
        else:
            y = L.swiglu(params["mlp"], h2)
        x = x + y
    if not want_cache:
        cache = None
    return x, cache, aux


def block_decode(params, cfg, kind, x, cache, pos, *, moe_layer: bool,
                 long_ctx: bool = False):
    """One-token step. -> (y, new_cache)."""
    h = _norm(cfg, params["norm1"], x)
    window = _window(cfg, kind, long_ctx)
    if kind in ("attn", "swa"):
        if cfg.mla:
            mix, cache = MLA.mla_decode(params["attn"], cfg, h, cache, pos)
        elif "slot_pos" in cache:
            mix, cache = A.attn_decode_ring(params["attn"], cfg, h, cache, pos,
                                            window=window)
        else:
            mix, cache = A.attn_decode(params["attn"], cfg, h, cache, pos,
                                       window=window)
    elif kind == "rglru":
        mix, cache = RG.rglru_decode(params["mix"], cfg, h, cache)
    elif kind == "mlstm":
        mix, cache = XL.mlstm_decode(params["mix"], cfg, h, cache)
    elif kind == "slstm":
        mix, cache = XL.slstm_decode(params["mix"], cfg, h, cache)
    x = x + mix
    if "mlp" in params:
        h2 = _norm(cfg, params["norm2"], x)
        if moe_layer:
            y, _ = MOE.moe_forward(params["mlp"], cfg, h2)
        elif cfg.is_encoder:
            y = L.gelu_mlp(params["mlp"], h2)
        else:
            y = L.swiglu(params["mlp"], h2)
        x = x + y
    return x, cache


def block_decode_paged(params, cfg, kind, x, pool, block_table, pos, *,
                       moe_layer: bool, long_ctx: bool = False, phase=None,
                       kernel=None):
    """One-token step per row against the shared paged KV pool.

    Only attention caches page (KV grows with the sequence); recurrent /
    xLSTM state is O(1) per request and MLA latents keep their own layout,
    so paged serving is restricted to plain GQA attention stacks —
    enforced structurally by :func:`paged_cache_specs`.
    ``phase`` marks a ragged pass list (DESIGN.md §12) and ``kernel``
    picks the attention form (see
    :func:`repro.models.attention.attn_decode_paged`).
    """
    h = _norm(cfg, params["norm1"], x)
    window = _window(cfg, kind, long_ctx)
    mix, pool = A.attn_decode_paged(params["attn"], cfg, h, pool,
                                    block_table, pos, window=window,
                                    phase=phase, kernel=kernel)
    x = x + mix
    if "mlp" in params:
        h2 = _norm(cfg, params["norm2"], x)
        if moe_layer:
            y, _ = MOE.moe_forward(params["mlp"], cfg, h2)
        elif cfg.is_encoder:
            y = L.gelu_mlp(params["mlp"], h2)
        else:
            y = L.swiglu(params["mlp"], h2)
        x = x + y
    return x, pool


def block_cache_spec(cfg, mk, kind, batch: int, capacity: int, *,
                     long_ctx: bool = False, dtype=jnp.bfloat16):
    window = _window(cfg, kind, long_ctx)
    if kind in ("attn", "swa"):
        if cfg.mla:
            return MLA.mla_cache_spec(cfg, mk, batch, capacity, dtype)
        ring = window is not None and window < capacity
        cap = min(capacity, window) if ring else capacity
        return A.cache_spec(cfg, mk, batch, cap, ring=ring, dtype=dtype)
    if kind == "rglru":
        return RG.rglru_state_spec(cfg, mk, batch, dtype)
    if kind == "mlstm":
        return XL.mlstm_state_spec(cfg, mk, batch)
    if kind == "slstm":
        return XL.slstm_state_spec(cfg, mk, batch)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------


def init_model(cfg, mk):
    segs = segments(cfg)
    p = {"segments": []}
    if not cfg.embedding_inputs:
        p["embed"] = L.init_embedding(mk, cfg.vocab_size, cfg.d_model)
    leading_dense = cfg.moe.first_k_dense if cfg.moe else 0
    seen = 0
    for seg in segs:
        if seg[0] == "plain":
            moe_layer = _is_moe_layer(cfg, seen < leading_dense)
            p["segments"].append(init_block(cfg, mk, seg[1], moe_layer=moe_layer))
            seen += 1
        else:
            _, pattern, n = seg
            smk = L.StackedMaker(mk, n)
            moe_layer = _is_moe_layer(cfg, False)
            p["segments"].append(
                [init_block(cfg, smk, kind, moe_layer=moe_layer) for kind in pattern])
            seen += n * len(pattern)
    norm = L.init_layernorm if cfg.is_encoder else L.init_rmsnorm
    p["final_norm"] = norm(mk, cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"] = mk((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                          scale=cfg.d_model ** -0.5)
    return p


def cache_specs(cfg, mk, batch: int, capacity: int, *, long_ctx=False,
                dtype=jnp.bfloat16):
    """Same segment structure as params; scan segments get stacked caches."""
    segs = segments(cfg)
    out = []
    leading_dense = cfg.moe.first_k_dense if cfg.moe else 0
    seen = 0
    for seg in segs:
        if seg[0] == "plain":
            out.append(block_cache_spec(cfg, mk, seg[1], batch, capacity,
                                        long_ctx=long_ctx, dtype=dtype))
            seen += 1
        else:
            _, pattern, n = seg
            smk = L.StackedMaker(mk, n)
            out.append([block_cache_spec(cfg, smk, kind, batch, capacity,
                                         long_ctx=long_ctx, dtype=dtype)
                        for kind in pattern])
            seen += n * len(pattern)
    return out


def paged_cache_specs(cfg, mk, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16, *, kv_dtype: str = "bf16"):
    """Per-layer paged KV pools, same segment structure as ``cache_specs``.

    Every block must be a plain GQA attention block (``attn``/``swa``
    without MLA): pages hold KV rows, and non-KV state (recurrent, xLSTM,
    MLA latents) has no page structure to share. Raises ``ValueError``
    for unpageable stacks so the serving engine can fail admission early.
    ``kv_dtype="int8"`` pools carry paired scale leaves per layer
    (DESIGN.md §11).
    """
    if cfg.mla is not None:
        raise ValueError("paged KV arena requires plain GQA attention "
                         "(MLA latent caches are not paged)")
    segs = segments(cfg)
    out = []
    for seg in segs:
        kinds = [seg[1]] if seg[0] == "plain" else list(seg[1])
        for kind in kinds:
            if kind not in ("attn", "swa"):
                raise ValueError(f"paged KV arena requires attention "
                                 f"blocks, got {kind!r}")
        if seg[0] == "plain":
            out.append(A.paged_cache_spec(cfg, mk, num_pages, page_size,
                                          dtype=dtype, kv_dtype=kv_dtype))
        else:
            _, pattern, n = seg
            smk = L.StackedMaker(mk, n)
            out.append([A.paged_cache_spec(cfg, smk, num_pages, page_size,
                                           dtype=dtype, kv_dtype=kv_dtype)
                        for _ in pattern])
    return out


def decode_step_paged(params, cfg, token_embeds, pools, block_table, pos, *,
                      rules=None, long_ctx=False, phase=None, kernel=None):
    """One-token step for the whole stack against paged KV pools.

    token_embeds (B,1,D); ``pools`` from :func:`paged_cache_specs`;
    block_table (B, nb) int32 shared by every layer (one table per
    request-stream, the pool is per-layer); pos (B,) int32 per-row.
    ``phase`` (B,) int32, when given, marks the batch as a ragged pass
    list: rows with ``phase == 0`` are padding (zero attention output,
    dropped writes) — the fixed-shape contract the serving engine's
    single-compile step relies on (DESIGN.md §12). ``kernel`` picks the
    attention form (:func:`repro.models.attention.attn_decode_paged`).
    Returns (hidden (B,1,D), new pools).
    """
    x = token_embeds
    segs = segments(cfg)
    leading_dense = cfg.moe.first_k_dense if cfg.moe else 0
    new_pools = []
    seen = 0
    for seg, seg_params, seg_pool in zip(segs, params["segments"], pools):
        x = constrain(x, ("batch", None, None), rules)
        if seg[0] == "plain":
            moe_layer = _is_moe_layer(cfg, seen < leading_dense)
            x, p = block_decode_paged(seg_params, cfg, seg[1], x, seg_pool,
                                      block_table, pos, moe_layer=moe_layer,
                                      long_ctx=long_ctx, phase=phase,
                                      kernel=kernel)
            new_pools.append(p)
            seen += 1
        else:
            _, pattern, n = seg
            moe_layer = _is_moe_layer(cfg, False)

            def body(x, xs):
                grp_params, grp_pool = xs
                new_ps = []
                for kind, bp, p in zip(pattern, grp_params, grp_pool):
                    x, p2 = block_decode_paged(bp, cfg, kind, x, p,
                                               block_table, pos,
                                               moe_layer=moe_layer,
                                               long_ctx=long_ctx,
                                               phase=phase, kernel=kernel)
                    new_ps.append(p2)
                return x, new_ps

            x, ps = jax.lax.scan(body, x, (seg_params, seg_pool),
                                 unroll=_unroll(n))
            new_pools.append(ps)
            seen += n * len(pattern)
    return x, new_pools


def prepare_decode_caches(cfg, caches, *, seq_len: int, capacity: int,
                          long_ctx: bool = False):
    """Convert prefill caches into decode-ready caches.

    Windowed attention blocks become ring buffers (``A.cache_from_prefill``);
    full-attention / MLA caches are padded from ``seq_len`` to ``capacity``;
    recurrent states pass through unchanged.
    """
    segs = segments(cfg)
    pad = capacity - seq_len

    def convert(kind, cache, stacked: bool):
        if kind not in ("attn", "swa"):
            return cache
        if cfg.mla:
            def padlat(x):
                if pad <= 0:
                    return x
                cfgpad = [(0, 0)] * x.ndim
                cfgpad[2 if stacked else 1] = (0, pad)
                return jnp.pad(x, cfgpad)
            return {"c": padlat(cache["c"]), "k_rope": padlat(cache["k_rope"])}
        window = _window(cfg, kind, long_ctx)
        if window is not None and window < capacity:
            fn = lambda kv: A.cache_from_prefill(kv, window=window, seq_len=seq_len)
            return jax.vmap(fn)(cache) if stacked else fn(cache)
        axis = 2 if stacked else 1
        out = cache
        if pad > 0:
            cfgpad = [(0, 0)] * cache["k"].ndim
            cfgpad[axis] = (0, pad)
            out = {"k": jnp.pad(cache["k"], cfgpad),
                   "v": jnp.pad(cache["v"], cfgpad)}
        if A._kv_quant():
            # quantize the prefill cache for the int8 decode path (H3)
            def q(kv):
                vals, scale = A._quantize_kv(kv)
                return vals, scale
            kq, ks = q(out["k"])
            vq, vs = q(out["v"])
            out = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        return out

    out = []
    for seg, seg_cache in zip(segs, caches):
        if seg[0] == "plain":
            out.append(convert(seg[1], seg_cache, stacked=False))
        else:
            _, pattern, _ = seg
            out.append([convert(kind, c, stacked=True)
                        for kind, c in zip(pattern, seg_cache)])
    return out


# ---------------------------------------------------------------------------
# Constraint helper
# ---------------------------------------------------------------------------


def constrain(x, logical, rules: AxisRules | None):
    return _dist_constrain(x, logical, rules)


# ---------------------------------------------------------------------------
# Forward paths
# ---------------------------------------------------------------------------


def _embed_in(params, cfg, inputs):
    if cfg.embedding_inputs:
        return inputs          # (B,S,D) precomputed frontend embeddings
    return L.embed(params["embed"], inputs, dtype=jnp.bfloat16)


def unembed(params, cfg, x):
    h = _norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        w = params["embed"]["table"].astype(x.dtype)
        return jnp.einsum("bsd,vd->bsv", h, w)
    return h @ params["lm_head"].astype(x.dtype)


def forward(params, cfg, inputs, *, positions=None, rules=None,
            want_caches=False, long_ctx=False, remat=False):
    """Full-sequence forward. -> (hidden, caches, aux_loss)."""
    x = _embed_in(params, cfg, inputs)
    B, S = x.shape[:2]
    if positions is None:
        # (1, S), broadcast: a (B, S) positions tensor rides the layer-scan
        # carry unsharded and its masks force GSPMD to replicate the batch
        # dim of every score tensor downstream (observed 16x temp blowup).
        positions = jnp.arange(S, dtype=jnp.int32)[None]
    segs = segments(cfg)
    leading_dense = cfg.moe.first_k_dense if cfg.moe else 0
    caches = []
    aux = jnp.zeros((), jnp.float32)
    seen = 0
    for seg, seg_params in zip(segs, params["segments"]):
        x = constrain(x, ("batch", "seq", None), rules)
        if seg[0] == "plain":
            kind = seg[1]
            moe_layer = _is_moe_layer(cfg, seen < leading_dense)
            x, cache, a = block_forward(seg_params, cfg, kind, x, positions,
                                        moe_layer=moe_layer, long_ctx=long_ctx,
                                        want_cache=want_caches)
            caches.append(cache)
            aux = aux + a
            seen += 1
        else:
            _, pattern, n = seg
            moe_layer = _is_moe_layer(cfg, False)

            def group(x, grp_params):
                cs = []
                a_tot = jnp.zeros((), jnp.float32)
                for kind, bp in zip(pattern, grp_params):
                    # constraint INSIDE the scan body: under remat this is the
                    # saved per-layer activation — sharding it (batch over
                    # data, seq over model in train rules) is what keeps
                    # 34B-scale train steps inside HBM.
                    x = constrain(x, ("batch", "seq", None), rules)
                    x, c, a = block_forward(bp, cfg, kind, x, positions,
                                            moe_layer=moe_layer, long_ctx=long_ctx,
                                            want_cache=want_caches)
                    cs.append(c)
                    a_tot = a_tot + a
                return x, cs, a_tot

            if remat:
                group = jax.checkpoint(group)

            def body(carry, grp_params):
                x, aux = carry
                x, cs, a = group(x, grp_params)
                return (x, aux + a), cs

            (x, aux), cs = jax.lax.scan(body, (x, aux), seg_params,
                                        unroll=_unroll(n))
            caches.append(cs)
            seen += n * len(pattern)
    x = constrain(x, ("batch", "seq", None), rules)
    return x, (caches if want_caches else None), aux


def decode_step(params, cfg, token_embeds, caches, pos, *, rules=None,
                long_ctx=False):
    """One-token step for the whole stack. -> (hidden (B,1,D), new caches)."""
    x = token_embeds
    segs = segments(cfg)
    leading_dense = cfg.moe.first_k_dense if cfg.moe else 0
    new_caches = []
    seen = 0
    for seg, seg_params, seg_cache in zip(segs, params["segments"], caches):
        x = constrain(x, ("batch", None, None), rules)
        if seg[0] == "plain":
            moe_layer = _is_moe_layer(cfg, seen < leading_dense)
            x, c = block_decode(seg_params, cfg, seg[1], x, seg_cache, pos,
                                moe_layer=moe_layer, long_ctx=long_ctx)
            new_caches.append(c)
            seen += 1
        else:
            _, pattern, n = seg
            moe_layer = _is_moe_layer(cfg, False)

            def body(x, xs):
                grp_params, grp_cache = xs
                new_cs = []
                for kind, bp, c in zip(pattern, grp_params, grp_cache):
                    x, c2 = block_decode(bp, cfg, kind, x, c, pos,
                                         moe_layer=moe_layer, long_ctx=long_ctx)
                    new_cs.append(c2)
                return x, new_cs

            x, cs = jax.lax.scan(body, x, (seg_params, seg_cache),
                                 unroll=_unroll(n))
            new_caches.append(cs)
            seen += n * len(pattern)
    return x, new_caches


def embed_tokens(params, cfg, tokens):
    return L.embed(params["embed"], tokens, dtype=jnp.bfloat16)
