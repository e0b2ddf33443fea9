"""bench/flops.py against hand counts and against a count of every matrix
product and convolution in the plain references' jaxprs, at tiny sizes."""

import math

import jax
import jax.numpy as jnp

from bench import flops as FL
from bench.reference import transformer as RT
from bench.reference import unet as RU

TINY_UNET = dict(base_channels=8, channel_mults=[1, 2], num_res_blocks=1,
                 attn_resolutions=[1], num_heads=2, text_dim=4, text_len=3,
                 latent_size=4, time_dim=8, in_channels=4, out_channels=4,
                 norm_groups=4, text_encoder={"layers": 1, "vocab": 16,
                                              "heads": 2, "ff": 16})


def matmul_flops(jaxpr) -> int:
    """2 x multiply-adds of every dot_general and convolution, recursively."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _rc), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(lhs[d] for d in lc)
        elif eqn.primitive.name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape          # HWIO
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * rhs[0] * rhs[1] * rhs[2]
        for sub in _subjaxprs(eqn.params.values()):
            total += matmul_flops(sub)
    return total


def _subjaxprs(values):
    for v in values:
        if isinstance(v, (list, tuple)):
            yield from _subjaxprs(v)
        elif hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr


def test_hand_counts():
    # 4x4 output, 3x3 kernel, 2 -> 3 channels: 16 * 9 * 2 * 3 multiply-adds
    assert FL.conv_flops(4, 4, 3, 2, 3) == 2 * 16 * 9 * 2 * 3
    # self: 4 projections of n x c x c, QK^T and PV of n x n x c;
    # cross: q and o (n x c x c), k and v (L x Dt x c), QK^T and PV (n x L x c)
    n, c, L, Dt = 16, 8, 3, 4
    assert FL.attn_block_flops(n, c, L, Dt) == 2 * (
        4 * n * c * c + 2 * n * n * c + 2 * n * c * c + 2 * L * Dt * c + 2 * n * L * c)


def test_unet_row_matches_reference_jaxpr():
    cfg = TINY_UNET
    params = jax.eval_shape(lambda k: RU.init_params(cfg, 0)["unet"], 0)
    x = jax.ShapeDtypeStruct((1, 4, 4, 4), jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.int32)
    text = jax.ShapeDtypeStruct((1, 3, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x, t, s: RU.unet(p, cfg, x, t, s))(params, x, t, text)
    assert matmul_flops(jaxpr.jaxpr) == FL.unet_row_flops(cfg)


def test_encoder_matches_reference_jaxpr():
    t = TINY_UNET["text_encoder"]
    params = jax.eval_shape(lambda k: RU.init_params(TINY_UNET, 0)["text"], 0)
    L = 5
    tokens = jax.ShapeDtypeStruct((L,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, tk: RT.encode(p, tk))(params, tokens)
    want = FL.encoder_flops(L, TINY_UNET["text_dim"], t["layers"], t["ff"])
    assert matmul_flops(jaxpr.jaxpr) == want


def test_diffusion_image_flops_counts_plan_rows():
    cfg = TINY_UNET
    row = FL.unet_row_flops(cfg)
    enc = FL.encoder_flops(3, 4, 1, 16)
    assert FL.diffusion_image_flops(cfg, 50, 0.0) == 100 * row + enc
    assert FL.diffusion_image_flops(cfg, 50, 0.5) == 75 * row + enc
    assert FL.diffusion_image_flops(cfg, 50, 0.2) == 90 * row + enc
