"""Plain float32 text encoder of the SD pipeline, written from its
published equations.

Parameters use the layout the system under test serves (one stacked group
of identical blocks under ``segments[0][0]``, leading axis = layer), so the
same arrays can be handed to both. Everything here is ``jax.numpy`` in
float32 at ``highest`` matmul precision: no kernels or batching tricks.
Nothing is imported from the system under test.

Encoder block (pre-LN): x += MHA(LN(x)); x += W2 gelu(W1 LN(x) + b1) + b2,
bidirectional attention, RoPE on q and k.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


def f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * f32(scale) + f32(bias)


def rope(x, positions, theta):
    """x (S, H, hd), rotate-half convention; positions (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(q, k, v):
    """Bidirectional attention; q, k, v (S, H, hd)."""
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


class Draw:
    """Deterministic normal draws from one key, one fold per leaf."""

    def __init__(self, key, dtype):
        self.key, self.n, self.dtype = key, 0, dtype

    def normal(self, shape, scale):
        self.n += 1
        k = jax.random.fold_in(self.key, self.n)
        return jax.random.normal(k, shape, self.dtype) * jnp.asarray(scale, self.dtype)

    def zeros(self, shape):
        return jnp.zeros(shape, self.dtype)

    def ones(self, shape):
        return jnp.ones(shape, self.dtype)


def layer_params(params, i):
    """The i-th layer of the stacked group, as float32."""
    blk = params["segments"][0][0]
    return jax.tree.map(lambda a: f32(a[i]), blk)


def n_layers(params):
    return params["segments"][0][0]["norm1"]["scale"].shape[0]


def encoder_layer(p, x, positions, *, heads, eps, theta):
    h = layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
    a = p["attn"]
    q = rope(jnp.einsum("sd,dhk->shk", h, a["wq"]), positions, theta)
    k = rope(jnp.einsum("sd,dhk->shk", h, a["wk"]), positions, theta)
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    x = x + jnp.einsum("shk,hkd->sd", attention(q, k, v), a["wo"])
    h = layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
    m = p["mlp"]
    h = jax.nn.gelu(h @ m["w_in"] + m["b_in"])
    return x + h @ m["w_out"] + m["b_out"]


def encode(params, tokens, *, eps=1e-5, theta=10000.0):
    """Text encoder: tokens (S,) -> residual stream (S, D) after the last
    block (the pipeline feeds the UNet the un-normed residual stream)."""
    with jax.default_matmul_precision(HIGHEST):
        x = f32(params["embed"]["table"])[tokens]
        pos = jnp.arange(tokens.shape[0])
        heads = params["segments"][0][0]["attn"]["wq"].shape[2]
        for i in range(n_layers(params)):
            x = encoder_layer(layer_params(params, i), x, pos, heads=heads,
                              eps=eps, theta=theta)
        return x

