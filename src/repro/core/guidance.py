"""Classifier-free guidance (Ho & Salimans) — Eq. 1 of the paper.

``cfg_combine`` is the exact formula the paper optimizes:

    eps_hat = eps_uncond + s * (eps_cond - eps_uncond)

Properties the tests rely on:
* s = 1  ->  eps_hat == eps_cond exactly (skipping uncond is *lossless*);
* s = 0  ->  eps_hat == eps_uncond.

``repro.kernels.cfg_combine`` is the fused Pallas TPU version of this exact
op; this jnp form is its oracle and the XLA fallback.

``combine_guidance`` is the one combine stage every executor calls on a
FULL step: Eq. 1 (``"cfg"``) or APG (``"apg"``), at the scale the plan
gives the step (DESIGN.md §15).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

COMBINE_MODES = ("cfg", "apg")


def cfg_combine(eps_uncond, eps_cond, scale):
    """Eq. 1. ``scale`` may be a python float or a traced scalar.

    ``scale == 1`` (statically) short-circuits to the conditional term —
    algebraically equal and bit-exact, which is what makes the paper's
    skip *lossless* at guidance scale 1."""
    if isinstance(scale, (int, float)) and float(scale) == 1.0:
        return eps_cond
    if isinstance(scale, (int, float)) and _use_pallas():
        # fused TPU kernel (repro.kernels.cfg_combine); jnp path is its oracle
        from repro.kernels.cfg_combine import cfg_combine_pallas
        return cfg_combine_pallas(eps_uncond, eps_cond, float(scale),
                                  interpret=False)
    u = eps_uncond.astype(jnp.float32)
    c = eps_cond.astype(jnp.float32)
    return (u + scale * (c - u)).astype(eps_cond.dtype)


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def apg_combine(eps_uncond, eps_cond, scale, *, eta: float = 0.0,
                threshold: float = 0.0, diff=None):
    """APG normalized/projected guidance (arxiv 2410.02416) — the ``apg``
    combine mode (DESIGN.md §15).

    The cond/uncond difference (or ``diff``, an externally momentum-averaged
    one) is norm-clamped to ``threshold`` and split against the conditional
    prediction; only the orthogonal component guides at full strength,
    ``eta`` attenuating the parallel (over-saturating) one.  Dispatches to
    the fused Pallas kernel on TPU when every knob is static; the jnp
    reference is the oracle and the XLA fallback.
    """
    if isinstance(scale, (int, float)) and diff is None and _use_pallas():
        from repro.kernels.cfg_combine import apg_combine_pallas
        return apg_combine_pallas(eps_uncond, eps_cond, float(scale),
                                  eta=eta, threshold=threshold)
    from repro.kernels.cfg_combine import apg_combine_ref
    return apg_combine_ref(eps_uncond, eps_cond, scale, eta=eta,
                           threshold=threshold, diff=diff)


def combine_guidance(eps_uncond, eps_cond, scale, mode: str = "cfg", *,
                     apg_eta: float = 0.0, apg_threshold: float = 0.0,
                     diff=None):
    """The combine stage: APG for ``mode == "apg"`` (``diff``, a
    momentum-averaged difference, applies to it only), else Eq. 1. The
    callers check ``mode`` against ``COMBINE_MODES``."""
    if mode == "apg":
        return apg_combine(eps_uncond, eps_cond, scale, eta=apg_eta,
                           threshold=apg_threshold, diff=diff)
    return cfg_combine(eps_uncond, eps_cond, scale)


def split_cond_uncond(batched):
    """Inverse of the 2x-batch trick: (2B, ...) -> ((B,...) cond, (B,...) uncond).

    Convention everywhere in this framework: conditional first half,
    unconditional second half. ``batched`` may be a pytree of arrays (a
    conditioning of several tensors): each leaf is split.
    """
    def b(a):
        assert a.shape[0] % 2 == 0, a.shape[0]
        return a.shape[0] // 2

    return (jax.tree.map(lambda a: a[:b(a)], batched),
            jax.tree.map(lambda a: a[b(a):], batched))


def merge_cond_uncond(cond, uncond):
    """(B, ...) cond and uncond -> (2B, ...), leaf by leaf for pytrees."""
    return jax.tree.map(lambda c, u: jnp.concatenate([c, u], axis=0), cond, uncond)
