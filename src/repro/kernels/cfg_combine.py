"""Fused guidance combiners — Pallas TPU kernels.

* ``cfg_combine_pallas`` — Eq. 1, ``eps_hat = u + s * (c - u)``, fp32, tiled
  over lanes-aligned VMEM blocks.  Purely memory-bound (3 streams, 1 FMA per
  element): the win over the unfused XLA form is eliminating the
  intermediate ``(c - u)`` round-trip.  Its oracle is the jnp form in
  ``repro.core.guidance.cfg_combine``.
* ``apg_combine_pallas`` — APG normalized/projected guidance (arxiv
  2410.02416): the cond/uncond difference is norm-clamped, split into
  components parallel/orthogonal to the conditional prediction, and only
  the orthogonal part guides at full strength.  Two passes over feature
  blocks: the first accumulates the per-row norms and dot, the second
  writes.  Its oracle is ``apg_combine_ref`` below, which
  ``repro.core.guidance`` also takes as the XLA path.

Both take the scale as a Python float.  Like the paged-decode kernels
(``repro.kernels.ops``), ``interpret`` defaults to platform detection:
interpreted off-TPU (CPU CI), compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EPS = 1e-12   # guards 0-norm rows (ragged padding); 0-diff rows stay exact
_BLOCK_FEAT = 2048   # feature lanes per block of the row-wise combines


def _interpret_default(interpret: bool | None) -> bool:
    """Resolve ``interpret=None`` the same way the paged-decode kernels do:
    interpreted everywhere except a real TPU backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _kernel(u_ref, c_ref, o_ref, *, scale: float):
    u = u_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    o_ref[...] = (u + scale * (c - u)).astype(o_ref.dtype)


def cfg_combine_pallas(eps_uncond, eps_cond, scale: float, *,
                       block_rows: int = 256, interpret: bool | None = None):
    assert eps_uncond.shape == eps_cond.shape
    if float(scale) == 1.0:
        # static short-circuit mirroring the jnp oracle: u + 1*(c - u) lands
        # a last-ulp away from c in fp32, but the paper's skip at s=1 is only
        # lossless if eps_hat == eps_cond bit-exactly — and there is no point
        # streaming both tensors through VMEM to return one of them.
        return eps_cond
    orig_shape = eps_cond.shape
    n = eps_cond.size
    lanes = 128
    rows = pl.cdiv(n, lanes)
    pad = rows * lanes - n
    u2 = jnp.pad(eps_uncond.reshape(-1), (0, pad)).reshape(rows, lanes)
    c2 = jnp.pad(eps_cond.reshape(-1), (0, pad)).reshape(rows, lanes)
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale)),
        grid=grid,
        in_specs=[pl.BlockSpec((br, lanes), lambda i: (i, 0)),
                  pl.BlockSpec((br, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), eps_cond.dtype),
        interpret=_interpret_default(interpret),
    )(u2, c2)
    return out.reshape(-1)[:n].reshape(orig_shape)


def _as_rows(x):
    """View as (rows, features): leading axis is the batch, everything else
    flattens — matching APG's per-sample reductions (dims [-1,-2,-3] in the
    reference, i.e. all non-batch axes)."""
    if x.ndim <= 1:
        return x.reshape(1, -1)
    return x.reshape(x.shape[0], -1)


def apg_combine_ref(eps_uncond, eps_cond, scale, *, eta: float = 0.0,
                    threshold: float = 0.0, diff=None):
    """jnp oracle for APG normalized guidance (arxiv 2410.02416), fp32.

    ``scale`` may be a python float or a traced per-row ``(B, 1)`` array.
    ``diff`` optionally supplies an externally momentum-averaged
    ``(cond - uncond)`` (the sampler's ``MomentumBuffer`` path); by default
    the raw difference is used (the stateless serve-engine form).

    Per row: ``d`` is norm-clamped to ``threshold`` (0 disables), split into
    components parallel/orthogonal to the conditional prediction, and
    ``out = c + (scale - 1) * (d_orth + eta * d_par)``.  Rows with ``u == c``
    (ragged self-pairing) return ``c`` exactly; all-zero rows (padding) are
    safe via the norm epsilon.
    """
    u = eps_uncond.astype(jnp.float32)
    c = eps_cond.astype(jnp.float32)
    d = (c - u) if diff is None else diff.astype(jnp.float32)
    axes = tuple(range(1, c.ndim)) if c.ndim > 1 else (0,)
    keep = dict(axis=axes, keepdims=True)
    if threshold > 0.0:
        d_norm = jnp.sqrt(jnp.sum(d * d, **keep))
        d = d * jnp.minimum(1.0, threshold / jnp.maximum(d_norm, _EPS))
    c_norm = jnp.sqrt(jnp.sum(c * c, **keep))
    v1 = c / jnp.maximum(c_norm, _EPS)
    d_par = jnp.sum(d * v1, **keep) * v1
    d_orth = d - d_par
    return (c + (scale - 1.0) * (d_orth + eta * d_par)).astype(eps_cond.dtype)


def _row_blocks(rows: int, feat: int):
    """Tile a (rows, feat) view for Mosaic: the row block is the full row
    extent when it is at most 8 (a block dim equal to the array dim is
    legal) and 8 otherwise; the feature block is a multiple of 128 lanes.
    Returns (row block, padded rows, feature block, padded features)."""
    br = rows if rows <= 8 else 8
    bf = min(pl.cdiv(feat, 128) * 128, _BLOCK_FEAT)
    return br, pl.cdiv(rows, br) * br, bf, pl.cdiv(feat, bf) * bf


def _pad2(x, rows: int, feat: int):
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, feat - x.shape[1])))


def _apg_kernel(u_ref, c_ref, o_ref, dd_ref, cc_ref, dc_ref, *, scale: float,
                eta: float, threshold: float):
    """Grid (row block, pass, feature block). Pass 0 accumulates the three
    per-row sums (|d|^2, |c|^2, d.c) over the feature blocks; pass 1
    applies the clamp and projection from them. The output block index
    stays put through pass 0, so only pass 1's values are written back."""
    p, j = pl.program_id(1), pl.program_id(2)
    u = u_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    d = c - u

    @pl.when((p == 0) & (j == 0))
    def _():
        dd_ref[...] = jnp.zeros_like(dd_ref)
        cc_ref[...] = jnp.zeros_like(cc_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    @pl.when(p == 0)
    def _():
        dd_ref[...] += jnp.sum(d * d, axis=1, keepdims=True)
        cc_ref[...] += jnp.sum(c * c, axis=1, keepdims=True)
        dc_ref[...] += jnp.sum(d * c, axis=1, keepdims=True)

    @pl.when(p == 1)
    def _():
        k = 1.0
        if threshold > 0.0:
            k = jnp.minimum(1.0, threshold
                            / jnp.maximum(jnp.sqrt(dd_ref[...]), _EPS))
        c_norm = jnp.maximum(jnp.sqrt(cc_ref[...]), _EPS)
        dk = d * k
        d_par = (k * dc_ref[...] / c_norm) * (c / c_norm)
        o_ref[...] = (c + (scale - 1.0) * ((dk - d_par) + eta * d_par)
                      ).astype(o_ref.dtype)


def apg_combine_pallas(eps_uncond, eps_cond, scale: float, *,
                       eta: float = 0.0, threshold: float = 0.0,
                       interpret: bool | None = None):
    """Fused APG combine over a (rows, features) view, tiled in both axes
    (``_row_blocks``) so any row count and feature width fits VMEM. The
    per-row norm and dot reductions accumulate in scratch across feature
    blocks before the second pass writes; zero padding perturbs neither
    sums nor dots."""
    assert eps_uncond.shape == eps_cond.shape
    orig_shape = eps_cond.shape
    u2, c2 = _as_rows(eps_uncond), _as_rows(eps_cond)
    rows, feat = c2.shape
    br, rp, bf, fp = _row_blocks(rows, feat)
    u2, c2 = _pad2(u2, rp, fp), _pad2(c2, rp, fp)
    blk = pl.BlockSpec((br, bf), lambda i, p, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_apg_kernel, scale=float(scale), eta=float(eta),
                          threshold=float(threshold)),
        grid=(rp // br, 2, fp // bf),
        in_specs=[blk, blk],
        out_specs=pl.BlockSpec((br, bf), lambda i, p, j: (i, j * p)),
        out_shape=jax.ShapeDtypeStruct((rp, fp), eps_cond.dtype),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32)] * 3,
        interpret=_interpret_default(interpret),
    )(u2, c2)
    return out[:rows, :feat].reshape(orig_shape)
