"""Plain float32 SD-style UNet, DDIM sampler and classifier-free guidance.

Written from the published equations (LDM / SD-1.5 UNet, DDIM with eta=0,
Ho & Salimans' CFG), over the parameter layout the system under test
serves, so one set of arrays drives both. NHWC, ``highest`` matmul
precision, no kernels. Nothing is imported from the system under test.

Also makes the weights (``init_params``): the benchmark, not the program,
draws them from the seed, in one jitted call on the device.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import transformer as RT

HIGHEST = "highest"


# -- weights ------------------------------------------------------------------


def _conv(d, kh, cin, cout):
    return {"w": d.normal((kh, kh, cin, cout), 1 / math.sqrt(kh * kh * cin)),
            "b": d.zeros((cout,))}


def _gn(d, c):
    return {"scale": d.ones((c,)), "bias": d.zeros((c,))}


def _res(d, cin, cout, td):
    p = {"gn1": _gn(d, cin), "conv1": _conv(d, 3, cin, cout),
         "time_proj": {"w": d.normal((td, cout), 1 / math.sqrt(td)),
                       "b": d.zeros((cout,))},
         "gn2": _gn(d, cout), "conv2": _conv(d, 3, cout, cout)}
    if cin != cout:
        p["skip"] = _conv(d, 1, cin, cout)
    return p


def _attn(d, c, text_dim):
    s, st = 1 / math.sqrt(c), 1 / math.sqrt(text_dim)
    return {"gn": _gn(d, c),
            "self": {n: d.normal((c, c), s) for n in ("wq", "wk", "wv", "wo")},
            "cross": {"wq": d.normal((c, c), s),
                      "wk": d.normal((text_dim, c), st),
                      "wv": d.normal((text_dim, c), st),
                      "wo": d.normal((c, c), s)}}


def unet_params(d, cfg):
    ch = [cfg["base_channels"] * m for m in cfg["channel_mults"]]
    td, base = cfg["time_dim"], cfg["base_channels"]
    attn_at = set(cfg["attn_resolutions"])
    p = {"time_mlp": {"w1": d.normal((base, td), 1 / math.sqrt(base)),
                      "b1": d.zeros((td,)),
                      "w2": d.normal((td, td), 1 / math.sqrt(td)),
                      "b2": d.zeros((td,))},
         "conv_in": _conv(d, 3, cfg["in_channels"], ch[0]),
         "down": [], "up": []}
    skips, cin = [ch[0]], ch[0]
    for lvl, c in enumerate(ch):
        lp = {"res": [], "attn": []}
        for _ in range(cfg["num_res_blocks"]):
            lp["res"].append(_res(d, cin, c, td))
            lp["attn"].append(_attn(d, c, cfg["text_dim"])
                              if 2 ** lvl in attn_at else None)
            cin = c
            skips.append(c)
        if lvl < len(ch) - 1:
            lp["downsample"] = _conv(d, 3, c, c)
            skips.append(c)
        p["down"].append(lp)
    p["mid1"] = _res(d, cin, cin, td)
    p["mid_attn"] = _attn(d, cin, cfg["text_dim"])
    p["mid2"] = _res(d, cin, cin, td)
    for lvl, c in reversed(list(enumerate(ch))):
        lp = {"res": [], "attn": []}
        for _ in range(cfg["num_res_blocks"] + 1):
            lp["res"].append(_res(d, cin + skips.pop(), c, td))
            lp["attn"].append(_attn(d, c, cfg["text_dim"])
                              if 2 ** lvl in attn_at else None)
            cin = c
        if lvl > 0:
            lp["upsample"] = _conv(d, 3, c, c)
        p["up"].append(lp)
    p["gn_out"] = _gn(d, cin)
    p["conv_out"] = _conv(d, 3, cin, cfg["out_channels"])
    return p


def text_params(d, vocab, dim, layers, heads, ff):
    hd = dim // heads
    L = layers

    def ln():
        return {"scale": d.ones((L, dim)), "bias": d.zeros((L, dim))}

    blk = {"norm1": ln(),
           "attn": {"wq": d.normal((L, dim, heads, hd), 1 / math.sqrt(dim)),
                    "wk": d.normal((L, dim, heads, hd), 1 / math.sqrt(dim)),
                    "wv": d.normal((L, dim, heads, hd), 1 / math.sqrt(dim)),
                    "wo": d.normal((L, heads, hd, dim), 1 / math.sqrt(dim))},
           "norm2": ln(),
           "mlp": {"w_in": d.normal((L, dim, ff), 1 / math.sqrt(dim)),
                   "b_in": d.zeros((L, ff)),
                   "w_out": d.normal((L, ff, dim), 1 / math.sqrt(ff)),
                   "b_out": d.zeros((L, dim))}}
    return {"embed": {"table": d.normal((vocab, dim), 1 / math.sqrt(dim))},
            "segments": [[blk]],
            "final_norm": {"scale": d.ones((dim,)), "bias": d.zeros((dim,))},
            "lm_head": d.normal((dim, vocab), 1 / math.sqrt(dim))}


def init_params(cfg: dict, seed: int, dtype=jnp.float32):
    """{"unet": ..., "text": ...} from the seed, made on the device in one
    jitted call (drawn in float32, then held in ``dtype``)."""
    t = cfg["text_encoder"]

    def make(key):
        d = RT.Draw(key, jnp.float32)
        return {"unet": unet_params(d, cfg),
                "text": text_params(d, t["vocab"], cfg["text_dim"], t["layers"],
                                    t["heads"], t["ff"])}

    def made(key):
        return jax.tree.map(lambda a: a.astype(dtype), make(key))

    return jax.jit(made)(jax.random.PRNGKey(seed % 2**31))


# -- forward ------------------------------------------------------------------


def _same(a):
    return a


def conv(p, x, stride=1, c=_same):
    y = jax.lax.conv_general_dilated(c(x), c(RT.f32(p["w"])), (stride, stride), "SAME",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + RT.f32(p["b"])


def group_norm(p, x, groups, eps=1e-5):
    B, H, W, C = x.shape
    g = groups
    while C % g:
        g -= 1
    xg = x.reshape(B, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(B, H, W, C) * RT.f32(p["scale"]) + RT.f32(p["bias"])


def res_block(p, x, temb, groups, c=_same):
    h = conv(p["conv1"], jax.nn.silu(group_norm(p["gn1"], x, groups)), c=c)
    h = h + (c(jax.nn.silu(temb)) @ c(RT.f32(p["time_proj"]["w"]))
             + RT.f32(p["time_proj"]["b"]))[:, None, None, :]
    h = conv(p["conv2"], jax.nn.silu(group_norm(p["gn2"], h, groups)), c=c)
    return (conv(p["skip"], x, c=c) if "skip" in p else x) + h


def mha(p, xq, xkv, heads, c=_same):
    B, N, C = xq.shape
    hd = C // heads
    q = (c(xq) @ c(RT.f32(p["wq"]))).reshape(B, N, heads, hd)
    k = (c(xkv) @ c(RT.f32(p["wk"]))).reshape(B, -1, heads, hd)
    v = (c(xkv) @ c(RT.f32(p["wv"]))).reshape(B, -1, heads, hd)
    w = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", c(q), c(k)) / math.sqrt(hd), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", c(w), c(v)).reshape(B, N, C)
    return c(o) @ c(RT.f32(p["wo"]))


def attn_block(p, x, text, heads, groups, c=_same):
    B, H, W, C = x.shape
    h = group_norm(p["gn"], x, groups).reshape(B, H * W, C)
    h = h + mha(p["self"], h, h, heads, c)
    h = h + mha(p["cross"], h, text, heads, c)
    return x + h.reshape(B, H, W, C)


def timestep_embedding(t, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    a = t.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.cos(a), jnp.sin(a)], -1)


def unet(p, cfg, x, t, text, c=_same):
    """``c`` rounds both inputs of every matrix product and convolution
    (the identity in float32); the control passes an fp8 rounding."""
    g, heads = cfg["norm_groups"], cfg["num_heads"]
    n = len(cfg["channel_mults"])
    tm = p["time_mlp"]
    te = jax.nn.silu(timestep_embedding(t, cfg["base_channels"]) @ RT.f32(tm["w1"])
                     + RT.f32(tm["b1"]))
    te = te @ RT.f32(tm["w2"]) + RT.f32(tm["b2"])
    h = conv(p["conv_in"], x, c=c)
    skips = [h]
    for lvl, lp in enumerate(p["down"]):
        for rp, ap in zip(lp["res"], lp["attn"]):
            h = res_block(rp, h, te, g, c)
            if ap is not None:
                h = attn_block(ap, h, text, heads, g, c)
            skips.append(h)
        if lvl < n - 1:
            h = conv(lp["downsample"], h, stride=2, c=c)
            skips.append(h)
    h = res_block(p["mid1"], h, te, g, c)
    h = attn_block(p["mid_attn"], h, text, heads, g, c)
    h = res_block(p["mid2"], h, te, g, c)
    for i, lp in enumerate(p["up"]):
        for rp, ap in zip(lp["res"], lp["attn"]):
            h = res_block(rp, jnp.concatenate([h, skips.pop()], -1), te, g, c)
            if ap is not None:
                h = attn_block(ap, h, text, heads, g, c)
        if n - 1 - i > 0:
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = conv(lp["upsample"], h, c=c)
    h = jax.nn.silu(group_norm(p["gn_out"], h, g))
    return conv(p["conv_out"], h, c=c)


# -- sampler ------------------------------------------------------------------


def ddim_coeffs(steps, T=1000, beta_start=8.5e-4, beta_end=1.2e-2):
    """SD's scaled-linear betas, DDIM's evenly spaced descending timesteps."""
    betas = np.linspace(beta_start ** 0.5, beta_end ** 0.5, T, dtype=np.float64) ** 2
    ab = np.cumprod(1.0 - betas)
    stride = T // steps
    ts = (np.arange(steps) * stride + stride - 1)[::-1]
    ab_prev = np.concatenate([ab[ts[1:]], [1.0]])
    return ts.astype(np.int32), ab[ts].astype(np.float32), ab_prev.astype(np.float32)


def full_steps(steps, fraction):
    """Selective guidance: the first ``steps - floor(steps*f + 1/2)`` steps
    run both passes, the rest the conditional pass only."""
    return steps - math.floor(steps * fraction + 0.5)


def fp8(a):
    """Round to float8 e4m3 with one scale per tensor (its largest
    magnitude maps to e4m3's largest finite value, 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def sample(params, cfg, tokens, x0, *, steps, scale, fraction, c=_same):
    """Guided DDIM (eta = 0) from noise ``x0`` (B, h, w, c) for prompt
    tokens (B, L); the null prompt is all-zero tokens. The first
    ``full_steps`` steps combine both passes, the rest use the
    conditional pass alone. ``c=fp8`` gives the control: the UNet's
    products computed from fp8 inputs."""
    enc = jax.vmap(lambda tk: RT.encode(params["text"], tk, eps=1e-5))
    with jax.default_matmul_precision(HIGHEST):
        cond, unc = enc(tokens), enc(jnp.zeros_like(tokens))
        ts, ab_t, ab_prev = (jnp.asarray(a) for a in ddim_coeffs(steps))
        B = x0.shape[0]

        def ddim(i, x, eps):
            x0_hat = (x - jnp.sqrt(1 - ab_t[i]) * eps) / jnp.sqrt(ab_t[i])
            return jnp.sqrt(ab_prev[i]) * x0_hat + jnp.sqrt(1 - ab_prev[i]) * eps

        def full(i, x):
            eps2 = unet(params["unet"], cfg, jnp.concatenate([x, x]),
                        jnp.full((2 * B,), ts[i]), jnp.concatenate([cond, unc]), c)
            e_c, e_u = eps2[:B], eps2[B:]
            return ddim(i, x, e_u + scale * (e_c - e_u))

        def cond_only(i, x):
            return ddim(i, x, unet(params["unet"], cfg, x, jnp.full((B,), ts[i]), cond, c))

        n_full = full_steps(steps, fraction)
        x = jax.lax.fori_loop(0, n_full, full, x0.astype(jnp.float32))
        return jax.lax.fori_loop(n_full, steps, cond_only, x)
