"""SD-style latent-diffusion UNet in pure JAX (NHWC).

Structurally faithful to the SD denoiser: ResBlocks with time-embedding
injection, GroupNorm+SiLU, self-attention + cross-attention (to text
embeddings) at configured resolutions, down/up sampling with skip
connections. Scaled by ``UNetConfig`` so the full guided pipeline runs on
CPU for the paper-claim validation (Table 1 / Figs 1-4).

``unet_forward`` runs under the named scope ``unet``: ``unet.time`` (time
MLP), ``unet.io`` (``conv_in``, ``gn_out``, ``conv_out``), one scope per
level (``unet.down.<lvl>``, ``unet.mid``, ``unet.up.<lvl>``) and inside
them ``unet.res``, ``unet.attn.norm``, ``unet.attn.self``,
``unet.attn.cross`` and ``unet.resample`` (DESIGN.md §13).

Self-attention over ``FLASH_MIN_LEN`` positions or more runs the Pallas
flash kernel on a TPU (DESIGN.md §6); everything else, cross-attention
and every CPU run included, takes the einsum path.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import layers as L

# Self-attention over at least this many positions (32x32 latents and
# up) takes the flash kernel on a TPU, in (bq, bk) blocks; both from a
# sweep on a v5e, where 16x16 (256 positions) was faster on einsums
# (PERF.md §6).
FLASH_MIN_LEN = 1024
FLASH_BLOCKS = (1024, 1024)


def _conv_init(mk, kh, kw, cin, cout, name_axes=("time", "time", "embed", "mlp")):
    s = 1.0 / math.sqrt(kh * kw * cin)
    return {"w": mk((kh, kw, cin, cout), name_axes, scale=s),
            "b": mk((cout,), ("mlp",), init="zeros")}


def conv2d(p, x, *, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(x.dtype)


def groupnorm(p, x, groups: int, eps=1e-5):
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(B, H, W, g, C // g)
    mu = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = xf.var(axis=(1, 2, 4), keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(B, H, W, C)
    return (xf * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _gn_init(mk, c):
    return {"scale": mk((c,), ("mlp",), init="ones"),
            "bias": mk((c,), ("mlp",), init="zeros")}


def init_resblock(mk, cin, cout, time_dim):
    p = {
        "gn1": _gn_init(mk, cin),
        "conv1": _conv_init(mk, 3, 3, cin, cout),
        "time_proj": {"w": mk((time_dim, cout), ("embed", "mlp"), scale=1 / math.sqrt(time_dim)),
                      "b": mk((cout,), ("mlp",), init="zeros")},
        "gn2": _gn_init(mk, cout),
        "conv2": _conv_init(mk, 3, 3, cout, cout),
    }
    if cin != cout:
        p["skip"] = _conv_init(mk, 1, 1, cin, cout)
    return p


def resblock(p, x, t_emb, groups):
    with jax.named_scope("unet.res"):
        h = jax.nn.silu(groupnorm(p["gn1"], x, groups).astype(jnp.float32)).astype(x.dtype)
        h = conv2d(p["conv1"], h)
        t = jax.nn.silu(t_emb.astype(jnp.float32)).astype(x.dtype)
        t = t @ p["time_proj"]["w"].astype(x.dtype) + p["time_proj"]["b"].astype(x.dtype)
        h = h + t[:, None, None, :]
        h = jax.nn.silu(groupnorm(p["gn2"], h, groups).astype(jnp.float32)).astype(x.dtype)
        h = conv2d(p["conv2"], h)
        skip = conv2d(p["skip"], x) if "skip" in p else x
        return skip + h


def init_attnblock(mk, c, heads, text_dim):
    s = 1 / math.sqrt(c)
    return {
        "gn": _gn_init(mk, c),
        "self": {"wq": mk((c, c), ("embed", "heads"), scale=s),
                 "wk": mk((c, c), ("embed", "heads"), scale=s),
                 "wv": mk((c, c), ("embed", "heads"), scale=s),
                 "wo": mk((c, c), ("heads", "embed"), scale=s)},
        "cross": {"wq": mk((c, c), ("embed", "heads"), scale=s),
                  "wk": mk((text_dim, c), ("embed", "heads"), scale=1 / math.sqrt(text_dim)),
                  "wv": mk((text_dim, c), ("embed", "heads"), scale=1 / math.sqrt(text_dim)),
                  "wo": mk((c, c), ("heads", "embed"), scale=s)},
    }


def _flash_blocks(n: int):
    """The flash kernel's (bq, bk) for self-attention over ``n``
    positions, or None for the einsum path: the kernel runs on a TPU, for
    ``n`` of at least ``FLASH_MIN_LEN`` and a multiple of both blocks."""
    bq, bk = FLASH_BLOCKS
    if (jax.default_backend() != "tpu" or n < FLASH_MIN_LEN
            or n % bq or n % bk):
        return None
    return FLASH_BLOCKS


def _mha(p, q_in, kv_in, heads, blocks=None):
    """Multi-head attention of ``q_in`` over ``kv_in``; ``blocks`` (bq, bk)
    runs softmax(QK^T)V through the flash kernel, None through einsums."""
    B, Nq, C = q_in.shape
    hd = C // heads
    q = (q_in @ p["wq"].astype(q_in.dtype)).reshape(B, Nq, heads, hd)
    k = (kv_in @ p["wk"].astype(q_in.dtype)).reshape(B, -1, heads, hd)
    v = (kv_in @ p["wv"].astype(q_in.dtype)).reshape(B, -1, heads, hd)
    if blocks is None:
        s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) / math.sqrt(hd)
        w = jax.nn.softmax(s, axis=-1).astype(q_in.dtype)
        o = jnp.einsum("bhqs,bshk->bqhk", w, v)
    else:
        # the default TPU precision feeds the einsums' products bf16 q, k,
        # v and p; the kernel takes the same, with f32 softmax and output
        bf = jnp.bfloat16
        o = flash_attention_pallas(
            (q * (1.0 / math.sqrt(hd))).astype(bf), k.astype(bf),
            v.astype(bf), causal=False, bq=blocks[0], bk=blocks[1],
            scale=1.0, out_dtype=q_in.dtype,
            interpret=jax.default_backend() != "tpu")
    return o.reshape(B, Nq, C) @ p["wo"].astype(q_in.dtype)


def attnblock(p, x, text, heads, groups):
    B, H, W, C = x.shape
    with jax.named_scope("unet.attn.norm"):
        h = groupnorm(p["gn"], x, groups).reshape(B, H * W, C)
    with jax.named_scope("unet.attn.self"):
        h = h + _mha(p["self"], h, h, heads, _flash_blocks(H * W))
    with jax.named_scope("unet.attn.cross"):
        h = h + _mha(p["cross"], h, text, heads)
    return x + h.reshape(B, H, W, C)


def init_unet(cfg, mk):
    ch = [cfg.base_channels * m for m in cfg.channel_mults]
    td = cfg.time_dim
    p = {
        "time_mlp": {
            "w1": mk((cfg.base_channels, td), ("embed", "mlp"), scale=1 / math.sqrt(cfg.base_channels)),
            "b1": mk((td,), ("mlp",), init="zeros"),
            "w2": mk((td, td), ("mlp", "mlp"), scale=1 / math.sqrt(td)),
            "b2": mk((td,), ("mlp",), init="zeros"),
        },
        "conv_in": _conv_init(mk, 3, 3, cfg.in_channels, ch[0]),
        "down": [], "up": [],
    }
    skips = [ch[0]]
    cin = ch[0]
    for lvl, c in enumerate(ch):
        lp = {"res": [], "attn": []}
        for _ in range(cfg.num_res_blocks):
            lp["res"].append(init_resblock(mk, cin, c, td))
            lp["attn"].append(init_attnblock(mk, c, cfg.num_heads, cfg.text_dim)
                              if 2 ** lvl in cfg.attn_resolutions else None)
            cin = c
            skips.append(c)
        if lvl < len(ch) - 1:
            lp["downsample"] = _conv_init(mk, 3, 3, c, c)
            skips.append(c)
        p["down"].append(lp)
    p["mid1"] = init_resblock(mk, cin, cin, td)
    p["mid_attn"] = init_attnblock(mk, cin, cfg.num_heads, cfg.text_dim)
    p["mid2"] = init_resblock(mk, cin, cin, td)
    for lvl, c in reversed(list(enumerate(ch))):
        lp = {"res": [], "attn": []}
        for _ in range(cfg.num_res_blocks + 1):
            sk = skips.pop()
            lp["res"].append(init_resblock(mk, cin + sk, c, td))
            lp["attn"].append(init_attnblock(mk, c, cfg.num_heads, cfg.text_dim)
                              if 2 ** lvl in cfg.attn_resolutions else None)
            cin = c
        if lvl > 0:
            lp["upsample"] = _conv_init(mk, 3, 3, c, c)
        p["up"].append(lp)
    p["gn_out"] = _gn_init(mk, cin)
    p["conv_out"] = _conv_init(mk, 3, 3, cin, cfg.out_channels)
    return p


def unet_forward(params, cfg, x, t, text):
    """x (B,h,w,Cin) latents, t (B,) timesteps, text (B,L,text_dim)."""
    with jax.named_scope("unet"):
        return _unet_forward(params, cfg, x, t, text)


def _unet_forward(params, cfg, x, t, text):
    g = cfg.norm_groups
    with jax.named_scope("unet.time"):
        te = L.sinusoidal_embedding(t, cfg.base_channels)
        tm = params["time_mlp"]
        te = jax.nn.silu(te @ tm["w1"].astype(te.dtype) + tm["b1"].astype(te.dtype))
        te = te @ tm["w2"].astype(te.dtype) + tm["b2"].astype(te.dtype)

    with jax.named_scope("unet.io"):
        h = conv2d(params["conv_in"], x)
    skips = [h]
    n_lvls = len(cfg.channel_mults)
    for lvl, lp in enumerate(params["down"]):
        with jax.named_scope(f"unet.down.{lvl}"):
            for rp, ap in zip(lp["res"], lp["attn"]):
                h = resblock(rp, h, te, g)
                if ap is not None:
                    h = attnblock(ap, h, text, cfg.num_heads, g)
                skips.append(h)
            if lvl < n_lvls - 1:
                with jax.named_scope("unet.resample"):
                    h = conv2d(lp["downsample"], h, stride=2)
                skips.append(h)
    with jax.named_scope("unet.mid"):
        h = resblock(params["mid1"], h, te, g)
        h = attnblock(params["mid_attn"], h, text, cfg.num_heads, g)
        h = resblock(params["mid2"], h, te, g)
    for i, lp in enumerate(params["up"]):
        lvl = n_lvls - 1 - i
        with jax.named_scope(f"unet.up.{lvl}"):
            for rp, ap in zip(lp["res"], lp["attn"]):
                h = jnp.concatenate([h, skips.pop()], axis=-1)
                h = resblock(rp, h, te, g)
                if ap is not None:
                    h = attnblock(ap, h, text, cfg.num_heads, g)
            if lvl > 0:
                with jax.named_scope("unet.resample"):
                    B, hh, ww, c = h.shape
                    h = jax.image.resize(h, (B, hh * 2, ww * 2, c), "nearest")
                    h = conv2d(lp["upsample"], h)
    with jax.named_scope("unet.io"):
        h = jax.nn.silu(groupnorm(params["gn_out"], h, g).astype(jnp.float32)).astype(h.dtype)
        return conv2d(params["conv_out"], h)
