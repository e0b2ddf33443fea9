"""Plain float32 SD3 MMDiT, its text conditioning, the flow-matching Euler
sampler and classifier-free guidance under the selective plan.

Written from the published description (Esser et al., arXiv:2403.03206,
and the diffusers ``SD3Transformer2DModel`` / ``JointTransformerBlock`` /
``FlowMatchEulerDiscreteScheduler`` / ``StableDiffusion3Pipeline`` they
document), over the parameter layout the system under test serves, so one
set of arrays drives both. ``highest`` matmul precision, no kernels, no
padding; the joint attention is computed in query blocks so that 4429
tokens fit on one chip. Nothing is imported from the system under test.

Departures from diffusers, each noted at its line: the three text towers
are the benchmark's encoder block (``bench.reference.transformer``) at
the towers' widths, cut in depth; a tower's states are its last block's
output; the pooled vector is both CLIPs' states at the prompt's last
token, with no final norm or text projection; latents are NHWC; no VAE.

Also makes the weights (``init_params``): the benchmark, not the program,
draws them from the seed, in one jitted call on the device.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import transformer as RT
from bench.reference import unet as RU

HIGHEST = "highest"
LN_EPS = 1e-6
TIME_DIM = 256
#: queries per block of the reference attention
QUERY_BLOCK = 512
#: (width, heads, feed-forward) of CLIP-L, OpenCLIP bigG, T5-XXL
TOWERS = {"clip_l": (768, 12, 3072), "clip_g": (1280, 20, 5120),
          "t5": (4096, 64, 10240)}

_same = RU._same
fp8 = RU.fp8
full_steps = RU.full_steps


def hidden(cfg) -> int:
    return cfg["num_attention_heads"] * cfg["attention_head_dim"]


# -- weights ------------------------------------------------------------------


def _lin(d, din, dout):
    return {"w": d.normal((din, dout), 1 / math.sqrt(din)),
            "b": d.normal((dout,), 0.02)}


def _stream(d, D, pre_only):
    if pre_only:  # the last block's context stream only feeds the attention
        return {"ada": _lin(d, D, 2 * D), "qkv": _lin(d, D, 3 * D)}
    return {"ada": _lin(d, D, 6 * D), "qkv": _lin(d, D, 3 * D),
            "out": _lin(d, D, D),
            "mlp": {"fc1": _lin(d, D, 4 * D), "fc2": _lin(d, 4 * D, D)}}


def mmdit_params(d, cfg):
    D, p, n = hidden(cfg), cfg["patch_size"], cfg["num_layers"]
    c_in = cfg["in_channels"]
    return {
        # the 2x2 stride-2 patch convolution, HWIO
        "patch": {"w": d.normal((p, p, c_in, D), 1 / math.sqrt(p * p * c_in)),
                  "b": d.normal((D,), 0.02)},
        "time": {"fc1": _lin(d, TIME_DIM, D), "fc2": _lin(d, D, D)},
        "pooled": {"fc1": _lin(d, cfg["pooled_projection_dim"], D),
                   "fc2": _lin(d, D, D)},
        "context": _lin(d, cfg["joint_attention_dim"], cfg["caption_projection_dim"]),
        "blocks": [{"x": _stream(d, D, False), "y": _stream(d, D, i == n - 1)}
                   for i in range(n)],
        "final": {"ada": _lin(d, D, 2 * D),
                  "proj": _lin(d, D, p * p * cfg["out_channels"])},
    }


def init_params(cfg: dict, seed: int, dtype=jnp.float32):
    """{"mmdit": ..., "text": {"clip_l", "clip_g", "t5"}} from the seed,
    made on the device in one jitted call (drawn in float32, then held in
    ``dtype``)."""
    t = cfg["text_encoders"]

    def make(key):
        d = RT.Draw(key, jnp.float32)
        text = {name: RU.text_params(d, t["vocab"], w, t["layers"][name], h, ff)
                for name, (w, h, ff) in TOWERS.items()}
        return {"mmdit": mmdit_params(d, cfg), "text": text}

    def made(key):
        return jax.tree.map(lambda a: a.astype(dtype), make(key))

    return jax.jit(made)(jax.random.PRNGKey(seed % 2**31))


def rounded(net, c):
    """The MMDiT's weights (every matrix) rounded by ``c``, once: the
    control's products take fp8 weights without a second copy of them in
    the sampling loop. The input buffers are donated."""
    return jax.jit(lambda n: jax.tree.map(lambda a: c(a) if a.ndim >= 2 else a, n),
                   donate_argnums=0)(net)


# -- text conditioning --------------------------------------------------------


def conditioning(text, cfg, clip_tokens, t5_tokens):
    """(context (B, 77 + 256, 4096), pooled (B, 2048)): CLIP-L's and bigG's
    states joined on width, zero-padded to ``joint_attention_dim`` and
    followed by T5's; pooled from both CLIPs at the prompt's last token."""
    enc = {name: jax.vmap(lambda tk, name=name: RT.encode(text[name], tk, eps=1e-5))
           for name in TOWERS}
    # a tower's states are its last block's output (diffusers: CLIP's
    # penultimate layer, T5's final-normed last layer)
    hl, hg = enc["clip_l"](clip_tokens), enc["clip_g"](clip_tokens)
    ht = enc["t5"](t5_tokens)
    clip = jnp.concatenate([hl, hg], -1)
    pad = cfg["joint_attention_dim"] - clip.shape[-1]
    context = jnp.concatenate([jnp.pad(clip, ((0, 0), (0, 0), (0, pad))), ht], 1)
    # diffusers: final LayerNorm and text projection of the EOS token
    last = jnp.maximum((clip_tokens != 0).sum(-1) - 1, 0)
    pooled = clip[jnp.arange(clip.shape[0]), last]
    return context, pooled


# -- forward ------------------------------------------------------------------


def linear(p, x, c=_same):
    return c(x) @ RT.f32(p["w"]) + RT.f32(p["b"])


def layer_norm(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def modulate(x, shift, scale):
    return layer_norm(x) * (1 + scale[:, None]) + shift[:, None]


def timestep_embedding(t):
    """Sinusoid of ``TIME_DIM``: cos then sin (flip_sin_to_cos), frequencies
    exp(-ln(10^4) i / 128)."""
    return RU.timestep_embedding(t, TIME_DIM)


def sincos_1d(dim, pos):
    omega = 1.0 / 10000 ** (np.arange(dim // 2, dtype=np.float64) / (dim // 2))
    a = pos.reshape(-1)[:, None] * omega[None]
    return np.concatenate([np.sin(a), np.cos(a)], 1)


def pos_embed(cfg):
    """The 2-D sin-cos table over the ``pos_embed_max_size`` grid (its
    coordinates spaced by grid / base_size, base_size = the latent's patch
    grid), column half then row half, centre-cropped to the patch grid."""
    g, D = cfg["pos_embed_max_size"], hidden(cfg)
    h = cfg["sample_size"] // cfg["patch_size"]
    coord = np.arange(g, dtype=np.float64) / (g / h)
    cols, rows = np.meshgrid(coord, coord)          # [i, j] = coord[j], coord[i]
    table = np.concatenate([sincos_1d(D // 2, cols), sincos_1d(D // 2, rows)], 1)
    top = (g - h) // 2
    crop = table.reshape(g, g, D)[top:top + h, top:top + h]
    return jnp.asarray(crop.reshape(h * h, D), jnp.float32)


def attention(q, k, v, heads, n, c=_same):
    """softmax(q k^T / sqrt(hd)) v over all keys; q, k, v (B, S, D), the
    first ``n`` tokens the image's. The image's queries go in blocks of up
    to ``QUERY_BLOCK`` (one loop body), the text's in one block."""
    B, S, D = q.shape
    hd = D // heads
    q, k, v = (a.reshape(B, S, heads, hd) for a in (q, k, v))

    def attend(qb):
        w = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", c(qb), c(k)) / math.sqrt(hd), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", c(w), c(v))

    blk = math.gcd(n, QUERY_BLOCK)
    qi = q[:, :n].reshape(B, n // blk, blk, heads, hd).swapaxes(0, 1)
    oi = jax.lax.map(attend, qi).swapaxes(0, 1).reshape(B, n, heads, hd)
    return jnp.concatenate([oi, attend(q[:, n:])], 1).reshape(B, S, D)


def joint_block(p, x, y, cvec, heads, c=_same):
    pre_only = "out" not in p["y"]
    sc = jax.nn.silu(cvec)
    # AdaLayerNormZero: shift, scale, gate (attention), shift, scale, gate (MLP)
    mx = jnp.split(linear(p["x"]["ada"], sc, c), 6, -1)
    my = jnp.split(linear(p["y"]["ada"], sc, c), 2 if pre_only else 6, -1)
    hx = modulate(x, mx[0], mx[1])
    # AdaLayerNormContinuous of the context-pre-only block: scale, shift
    hy = modulate(y, my[1], my[0]) if pre_only else modulate(y, my[0], my[1])
    qx, kx, vx = jnp.split(linear(p["x"]["qkv"], hx, c), 3, -1)
    qy, ky, vy = jnp.split(linear(p["y"]["qkv"], hy, c), 3, -1)
    n = x.shape[1]
    o = attention(jnp.concatenate([qx, qy], 1), jnp.concatenate([kx, ky], 1),
                  jnp.concatenate([vx, vy], 1), heads, n, c)
    ax, ay = o[:, :n], o[:, n:]
    x = x + mx[2][:, None] * linear(p["x"]["out"], ax, c)
    x = x + mx[5][:, None] * feed_forward(p["x"]["mlp"], modulate(x, mx[3], mx[4]), c)
    if pre_only:
        return x, None
    y = y + my[2][:, None] * linear(p["y"]["out"], ay, c)
    y = y + my[5][:, None] * feed_forward(p["y"]["mlp"], modulate(y, my[3], my[4]), c)
    return x, y


def feed_forward(p, h, c=_same):
    return linear(p["fc2"], jax.nn.gelu(linear(p["fc1"], h, c), approximate=True), c)


def mlp2(p, x, c=_same):
    return linear(p["fc2"], jax.nn.silu(linear(p["fc1"], x, c)), c)


def mmdit(p, cfg, x, t, context, pooled, c=_same):
    """Velocity for latents x (B, H, W, C) at timesteps t (B,) = 1000
    sigma. ``c`` rounds the activation inputs of every matrix product (the
    identity in float32); the control passes an fp8 rounding, and its
    weights come rounded by ``rounded``."""
    B, H, W, C = x.shape
    ps, D = cfg["patch_size"], hidden(cfg)
    h = jax.lax.conv_general_dilated(c(x), RT.f32(p["patch"]["w"]), (ps, ps),
                                     "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    h = (h + RT.f32(p["patch"]["b"])).reshape(B, -1, D) + pos_embed(cfg)[None]
    cvec = mlp2(p["time"], timestep_embedding(t), c) + mlp2(p["pooled"], pooled, c)
    y = linear(p["context"], context, c)
    for bp in p["blocks"]:
        h, y = joint_block(bp, h, y, cvec, cfg["num_attention_heads"], c)
    scale, shift = jnp.split(linear(p["final"]["ada"], jax.nn.silu(cvec), c), 2, -1)
    h = linear(p["final"]["proj"], modulate(h, shift, scale), c)
    hp, co = H // ps, cfg["out_channels"]
    # diffusers unpatchifies "nhwpqc -> nchpwq"; here to NHWC
    h = h.reshape(B, hp, hp, ps, ps, co).transpose(0, 1, 3, 2, 4, 5)
    return h.reshape(B, H, W, co)


# -- sampler ------------------------------------------------------------------


def flow_sigmas(steps, shift, T=1000):
    """FlowMatchEulerDiscreteScheduler(shift) at ``steps``: the training
    sigmas t/T (t = T..1) shifted by s -> shift s / (1 + (shift - 1) s);
    ``steps`` points evenly from the first to the last, shifted again;
    then 0."""
    def shifted(s):
        return shift * s / (1 + (shift - 1) * s)

    train = shifted(np.arange(T, 0, -1, dtype=np.float64) / T)
    return np.concatenate([shifted(np.linspace(train[0], train[-1], steps)), [0.0]])


def split_tokens(cfg, tokens):
    """The benchmark's (B, 77 + 256) token rows -> CLIP and T5 tokens."""
    n = cfg["clip_len"]
    return tokens[:, :n], tokens[:, n:]


def sample(params, cfg, tokens, x0, *, steps, scale, fraction, c=_same):
    """Guided flow-matching Euler from noise ``x0`` (B, h, w, c) for prompt
    tokens (B, 77 + 256); the null prompt is all-zero tokens. ``c=fp8``
    with ``params["mmdit"]`` made by ``rounded(.., fp8)`` gives the
    control: the MMDiT's products computed from fp8 inputs."""
    clip_tok, t5_tok = split_tokens(cfg, tokens)
    with jax.default_matmul_precision(HIGHEST):
        cond = conditioning(params["text"], cfg, clip_tok, t5_tok)
        uncond = conditioning(params["text"], cfg, jnp.zeros_like(clip_tok),
                              jnp.zeros_like(t5_tok))
        return guided_flow(params["mmdit"], cfg, cond, uncond, x0, steps=steps,
                           scale=scale, fraction=fraction, c=c)


def guided_flow(net, cfg, cond, uncond, x0, *, steps, scale, fraction, c=_same):
    """The loop of ``sample`` from (context, pooled) conditionings: step i
    moves x by (sigma_{i+1} - sigma_i) v. The first ``full_steps`` steps
    guide, v = v_u + s (v_c - v_u); the rest take v = v_c. Every step
    computes both passes (one loop body to compile), and the COND steps
    leave v_u unused."""
    (ctx, pooled), (ctx_u, pooled_u) = cond, uncond
    with jax.default_matmul_precision(HIGHEST):
        sig = jnp.asarray(flow_sigmas(steps, cfg["shift"]), jnp.float32)
        B = x0.shape[0]
        n_full = full_steps(steps, fraction)
        ctx2, pooled2 = jnp.concatenate([ctx, ctx_u]), jnp.concatenate([pooled, pooled_u])

        def step(i, x):
            v2 = mmdit(net, cfg, jnp.concatenate([x, x]), jnp.full((2 * B,), 1000 * sig[i]),
                       ctx2, pooled2, c)
            v_c, v_u = v2[:B], v2[B:]
            v = jnp.where(i < n_full, v_u + scale * (v_c - v_u), v_c)
            return x + (sig[i + 1] - sig[i]) * v

        return jax.lax.fori_loop(0, steps, step, x0.astype(jnp.float32))
