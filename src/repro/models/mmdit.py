"""SD3's multimodal diffusion transformer (MMDiT; arXiv:2403.03206) in
pure JAX, as diffusers' ``SD3Transformer2DModel`` computes it.

The latents (B, H, W, C) become 2x2 patches, a linear embedding and a 2-D
sin-cos position embedding centre-cropped from a ``pos_embed_max_size``
grid: the image stream x. The text context (B, M, joint_attention_dim)
becomes the context stream y by one linear. The conditioning vector is
c = MLP(sinusoid_256(t)) + MLP(pooled). Each joint block gives each stream
its own adaLN-Zero modulation (shift, scale, gate for the attention and
for the MLP, from Linear(SiLU(c))), its own q/k/v, output projection and
MLP (GELU, tanh form, 4x); the attention is one softmax over [x; y]. The
last block is context-pre-only: y takes (scale, shift) only and supplies
k and v and queries, and has no output projection or MLP. The output is
an adaLN-continuous (scale, shift) LayerNorm of x, a linear to patches and
the inverse of the patching: a velocity. LayerNorms have no affine part
and eps 1e-6.

The 24 blocks are unrolled in Python, as ``unet.py`` unrolls its levels:
every op of a denoising step then runs once a step. ``mmdit_forward``
runs under the named scope ``mmdit``, with ``mmdit.embed``,
``mmdit.adaln``, ``mmdit.attn.qkv`` (projections, concatenation and
padding), ``mmdit.attn.core`` (the attention kernel call),
``mmdit.attn.out`` (slicing, output projections and gated residuals),
``mmdit.mlp`` and ``mmdit.final`` inside (DESIGN.md §13).

On a TPU the joint attention runs the Pallas flash kernel: the 4096 + 333
tokens divide by no block size, so q/k/v are padded to a block multiple
and the kernel masks the padded keys (``kv_len``; DESIGN.md §6). Off a TPU
it takes the einsum path.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.models import layers as L

TIME_DIM = 256
LN_EPS = 1e-6


# -- parameters ----------------------------------------------------------------


def _lin(mk, din, dout):
    return {"w": mk((din, dout), ("embed", "mlp"), scale=1 / math.sqrt(din)),
            "b": mk((dout,), ("mlp",), scale=0.02)}


def _mlp2(mk, din, d):
    return {"fc1": _lin(mk, din, d), "fc2": _lin(mk, d, d)}


def _stream(mk, d, pre_only: bool):
    if pre_only:
        return {"ada": _lin(mk, d, 2 * d), "qkv": _lin(mk, d, 3 * d)}
    return {"ada": _lin(mk, d, 6 * d), "qkv": _lin(mk, d, 3 * d),
            "out": _lin(mk, d, d),
            "mlp": {"fc1": _lin(mk, d, 4 * d), "fc2": _lin(mk, 4 * d, d)}}


def init_mmdit(cfg, mk):
    """Parameters: ``patch`` (w (p, p, C, D)), ``time`` and ``pooled``
    (two-layer MLPs), ``context`` (linear), ``blocks`` (one dict per block:
    streams ``x`` and ``y``, each ``ada``, ``qkv`` (D, 3D) and, except the
    last block's ``y``, ``out`` and ``mlp``), ``final`` (``ada`` (D, 2D)
    and ``proj``)."""
    d, p = cfg.hidden, cfg.patch_size
    n = cfg.num_layers
    return {
        "patch": {"w": mk((p, p, cfg.in_channels, d), ("time", "time", "embed", "mlp"),
                          scale=1 / math.sqrt(p * p * cfg.in_channels)),
                  "b": mk((d,), ("mlp",), scale=0.02)},
        "time": _mlp2(mk, TIME_DIM, d),
        "pooled": _mlp2(mk, cfg.pooled_projection_dim, d),
        "context": _lin(mk, cfg.joint_attention_dim, cfg.caption_projection_dim),
        "blocks": [{"x": _stream(mk, d, False), "y": _stream(mk, d, i == n - 1)}
                   for i in range(n)],
        "final": {"ada": _lin(mk, d, 2 * d),
                  "proj": _lin(mk, d, p * p * cfg.out_channels)},
    }


# -- pieces --------------------------------------------------------------------


def _linear(p, x):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _mlp2_forward(p, x):
    return _linear(p["fc2"], jax.nn.silu(_linear(p["fc1"], x)))


def _layer_norm(x):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + LN_EPS)).astype(x.dtype)


def _modulate(x, shift, scale):
    return _layer_norm(x) * (1 + scale[:, None]) + shift[:, None]


def pos_embed(cfg, dtype=jnp.float32):
    """The 2-D sin-cos position embedding of the latent's patch grid,
    centre-cropped from the ``pos_embed_max_size`` grid: (h*w, D), half
    the width for the column coordinate, then half for the row
    (diffusers' ``get_2d_sincos_pos_embed`` order)."""
    g, d = cfg.pos_embed_max_size, cfg.hidden
    h = cfg.sample_size // cfg.patch_size
    top = (g - h) // 2
    # the grid's coordinates run over base_size = h in g steps
    coord = (top + jnp.arange(h, dtype=jnp.float32)) / (g / h)
    omega = 1.0 / 10000 ** (jnp.arange(d // 4, dtype=jnp.float32) / (d // 4))

    def sincos(pos):                                   # (h,) -> (h, D/2)
        a = pos[:, None] * omega
        return jnp.concatenate([jnp.sin(a), jnp.cos(a)], -1)

    col, row = sincos(coord), sincos(coord)
    emb = jnp.concatenate([jnp.broadcast_to(col[None], (h, h, d // 2)),
                           jnp.broadcast_to(row[:, None], (h, h, d // 2))], -1)
    return emb.reshape(h * h, d).astype(dtype)


def patchify(x, p):
    B, H, W, C = x.shape
    x = x.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(x, p, hw, c):
    B = x.shape[0]
    h = hw // p
    x = x.reshape(B, h, h, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, hw, hw, c)


def _joint_blocks(n: int):
    """The flash kernel's (bq, bk) for the joint attention over ``n``
    tokens, or None for the einsum path (off a TPU): one kv tile over
    ``n`` padded to a multiple of 128 lanes, and q tiles of the most rows,
    up to 512 and a multiple of 8, that divide it: (448, 4480) at SD3's
    4429 tokens, where a sweep on a v5e found one kv tile 40% faster than
    square 512 tiles (PERF.md §6)."""
    if jax.default_backend() != "tpu":
        return None
    p = -(-n // 128) * 128
    return max(d for d in range(8, 513, 8) if p % d == 0), p


def _padded(n: int, blocks) -> int:
    m = math.lcm(*blocks)
    return -(-n // m) * m


def joint_attention(qkv_x, qkv_y, heads: int):
    """One softmax over the image and text tokens of both streams' (B, N,
    3D) / (B, M, 3D) projections -> (B, N, D), (B, M, D) attention
    outputs (f32 where the inputs are)."""
    B, N, D3 = qkv_x.shape
    M = qkv_y.shape[1]
    D = D3 // 3
    hd = D // heads
    S = N + M
    blocks = _joint_blocks(S)
    with jax.named_scope("mmdit.attn.qkv"):
        qkv = jnp.concatenate([qkv_x, qkv_y], axis=1)   # (B, S, 3D), [x; y]
        q, k, v = (a.reshape(B, S, heads, hd) for a in jnp.split(qkv, 3, -1))
        if blocks is not None:
            # the default TPU precision feeds the einsums' products bf16
            # q, k, v and p; the kernel takes the same, with the scale
            # folded into q and f32 softmax and output
            P = _padded(S, blocks)
            pad = ((0, 0), (0, P - S), (0, 0), (0, 0))
            bf = jnp.bfloat16
            q = jnp.pad((q * (1.0 / math.sqrt(hd))).astype(bf), pad)
            k, v = jnp.pad(k.astype(bf), pad), jnp.pad(v.astype(bf), pad)
    with jax.named_scope("mmdit.attn.core"):
        if blocks is None:
            s = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) / math.sqrt(hd)
            w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            o = jnp.einsum("bhqs,bshk->bqhk", w, v)
        else:
            o = flash_attention_pallas(
                q, k, v, causal=False, bq=blocks[0], bk=blocks[1], scale=1.0,
                out_dtype=qkv_x.dtype, kv_len=S,
                interpret=jax.default_backend() != "tpu")
    with jax.named_scope("mmdit.attn.out"):
        o = o.reshape(B, -1, D)
        return o[:, :N], o[:, N:S]


def joint_block(p, x, y, c, heads: int):
    """One joint block over the image stream x (B, N, D) and the context
    stream y (B, M, D) under conditioning c (B, D). -> (x, y); y is None
    after a context-pre-only block (its ``y`` has no ``out``)."""
    pre_only = "out" not in p["y"]
    with jax.named_scope("mmdit.adaln"):
        sc = jax.nn.silu(c)
        mx = jnp.split(_linear(p["x"]["ada"], sc), 6, -1)
        hx = _modulate(x, mx[0], mx[1])
        my = jnp.split(_linear(p["y"]["ada"], sc), 2 if pre_only else 6, -1)
        # AdaLayerNormContinuous gives (scale, shift); AdaLayerNormZero
        # (shift, scale, gate, ...)
        hy = _modulate(y, my[1], my[0]) if pre_only else _modulate(y, my[0], my[1])
    with jax.named_scope("mmdit.attn.qkv"):
        qx, qy = _linear(p["x"]["qkv"], hx), _linear(p["y"]["qkv"], hy)
    ax, ay = joint_attention(qx, qy, heads)
    with jax.named_scope("mmdit.attn.out"):
        x = x + mx[2][:, None] * _linear(p["x"]["out"], ax)
        if not pre_only:
            y = y + my[2][:, None] * _linear(p["y"]["out"], ay)
    with jax.named_scope("mmdit.mlp"):
        x = x + mx[5][:, None] * _ff(p["x"]["mlp"], _modulate(x, mx[3], mx[4]))
        if pre_only:
            return x, None
        y = y + my[5][:, None] * _ff(p["y"]["mlp"], _modulate(y, my[3], my[4]))
    return x, y


def _ff(p, h):
    return _linear(p["fc2"], jax.nn.gelu(_linear(p["fc1"], h), approximate=True))


def mmdit_forward(params, cfg, x, t, cond):
    """x (B, H, W, C) latents, t (B,) timesteps (1000 sigma), cond =
    (context (B, M, joint_attention_dim), pooled (B, pooled_projection_dim))
    -> the velocity (B, H, W, C)."""
    with jax.named_scope("mmdit"):
        return _mmdit_forward(params, cfg, x, t, cond)


def _mmdit_forward(params, cfg, x, t, cond):
    context, pooled = cond
    dt = x.dtype
    with jax.named_scope("mmdit.embed"):
        h = _linear({"w": params["patch"]["w"].reshape(-1, cfg.hidden),
                     "b": params["patch"]["b"]}, patchify(x, cfg.patch_size))
        h = h + pos_embed(cfg, dt)[None]
        temb = L.sinusoidal_embedding(t, TIME_DIM).astype(dt)
        c = _mlp2_forward(params["time"], temb) \
            + _mlp2_forward(params["pooled"], pooled.astype(dt))
        y = _linear(params["context"], context.astype(dt))
    for bp in params["blocks"]:
        h, y = joint_block(bp, h, y, c, cfg.num_attention_heads)
    with jax.named_scope("mmdit.final"):
        scale, shift = jnp.split(_linear(params["final"]["ada"], jax.nn.silu(c)), 2, -1)
        h = _linear(params["final"]["proj"], _modulate(h, shift, scale))
        return unpatchify(h, cfg.patch_size, cfg.sample_size, cfg.out_channels)
