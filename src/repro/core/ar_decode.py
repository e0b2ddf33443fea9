"""Guided autoregressive decoding with selective guidance.

CFG for AR decoding (the paper's mechanism lifted to token generation, cf.
Sanchez et al. 2023; standard for Chameleon-style image-token generation):
two streams — conditional (the real prompt) and unconditional (the null
prompt) — each with its own cache; per step

    logits_hat = logits_uncond + s * (logits_cond - logits_uncond)

Selective guidance skips the unconditional forward for the last ``f`` of the
generated tokens, halving those steps' decode FLOPs. Suffix-only plans are
enforced: after the switch the uncond cache is stale and is never touched
again (DESIGN.md §2).

The two streams are separate trees + separate forward calls (not one
2x-batch call): this makes the COND phase a structural drop of one call and
keeps cache pytrees mode-independent.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.guidance import COMBINE_MODES, combine_guidance
from repro.core.selective import GuidancePlan, Mode
from repro.models import transformer as T


def _sample_token(logits, key, temperature: float):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


def prefill(params, cfg, tokens, *, rules=None, long_ctx=False):
    """One stream's prefill. tokens (B,S) -> (last_logits (B,V), caches)."""
    h, caches, _ = T.forward(params, cfg, tokens, want_caches=True,
                             rules=rules, long_ctx=long_ctx)
    logits = T.unembed(params, cfg, h[:, -1:, :])[:, 0, :]
    return logits.astype(jnp.float32), caches


def null_prompt(tokens):
    """CFG null stream: zero (pad/BOS) tokens, same shape."""
    return jnp.zeros_like(tokens)


def decode_step_full(params, cfg, token, caches_c, caches_u, pos, scale,
                     *, rules=None, long_ctx=False, combine: str = "cfg",
                     apg_eta: float = 0.0, apg_threshold: float = 0.0):
    """Baseline CFG decode step: two forwards + the combine stage (Eq. 1,
    or APG under ``combine="apg"``, DESIGN.md §15) at ``scale``.

    token (B,) -> (logits_hat (B,V) fp32, caches_c', caches_u').
    """
    emb = T.embed_tokens(params, cfg, token[:, None])
    h_c, caches_c = T.decode_step(params, cfg, emb, caches_c, pos,
                                  rules=rules, long_ctx=long_ctx)
    h_u, caches_u = T.decode_step(params, cfg, emb, caches_u, pos,
                                  rules=rules, long_ctx=long_ctx)
    l_c = T.unembed(params, cfg, h_c)[:, 0, :].astype(jnp.float32)
    l_u = T.unembed(params, cfg, h_u)[:, 0, :].astype(jnp.float32)
    logits = combine_guidance(l_u, l_c, scale, combine, apg_eta=apg_eta,
                              apg_threshold=apg_threshold)
    return logits, caches_c, caches_u


def decode_step_cond(params, cfg, token, caches_c, pos, *, rules=None,
                     long_ctx=False):
    """The paper's optimized step: conditional stream only (half the FLOPs)."""
    emb = T.embed_tokens(params, cfg, token[:, None])
    h_c, caches_c = T.decode_step(params, cfg, emb, caches_c, pos,
                                  rules=rules, long_ctx=long_ctx)
    logits = T.unembed(params, cfg, h_c)[:, 0, :].astype(jnp.float32)
    return logits, caches_c


def guided_decode(params, cfg, prompt_tokens, plan: GuidancePlan, *,
                  rng=None, temperature: float = 0.0, rules=None,
                  long_ctx=False, capacity: int | None = None,
                  combine: str = "cfg", apg_eta: float = 0.0,
                  apg_threshold: float = 0.0):
    """End-to-end guided generation: prefill both streams, then execute the
    plan's segments as separate scans (phase-split).

    prompt_tokens (B,S); ``plan.total_steps`` = number of new tokens.
    Returns (generated (B, n_new) int32, final position).

    ``combine`` selects the FULL-step combine stage (DESIGN.md §15): Eq. 1
    (``"cfg"``) or APG normalized guidance (``"apg"``, arxiv 2410.02416).
    A FULL segment combines at its own ``scale`` if the plan sets one (as
    ``GuidancePlan.interval`` does), else at ``plan.guidance_scale``; the
    first token, from the prefill, at step 0's.
    """
    if combine not in COMBINE_MODES:
        raise ValueError(f"combine {combine!r} not in {COMBINE_MODES}")
    plan.validate_for_ar()
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    B, S = prompt_tokens.shape
    n_new = plan.total_steps
    cap = capacity or (S + n_new)

    # --- prefill both streams into decode-ready caches -------------------
    logits_c, caches_c = prefill(params, cfg, prompt_tokens, rules=rules,
                                 long_ctx=long_ctx)
    logits_u, caches_u = prefill(params, cfg, null_prompt(prompt_tokens),
                                 rules=rules, long_ctx=long_ctx)
    caches_c = T.prepare_decode_caches(cfg, caches_c, seq_len=S, capacity=cap,
                                       long_ctx=long_ctx)
    caches_u = T.prepare_decode_caches(cfg, caches_u, seq_len=S, capacity=cap,
                                       long_ctx=long_ctx)

    def scale_of(seg):
        return plan.guidance_scale if seg.scale is None else seg.scale

    apg = dict(apg_eta=apg_eta, apg_threshold=apg_threshold)
    logits0 = combine_guidance(logits_u, logits_c, scale_of(plan.segments[0]),
                               combine, **apg)
    tok = _sample_token(logits0, jax.random.fold_in(rng, 0), temperature)

    outs = []

    def full_body(scale):
        def body(carry, i):
            tok, cc, cu = carry
            logits, cc, cu = decode_step_full(
                params, cfg, tok, cc, cu, S + i, scale, rules=rules,
                long_ctx=long_ctx, combine=combine, **apg)
            nxt = _sample_token(logits, jax.random.fold_in(rng, 1 + i),
                                temperature)
            return (nxt, cc, cu), tok
        return body

    def cond_body(carry, i):
        tok, cc = carry
        logits, cc = decode_step_cond(params, cfg, tok, cc, S + i,
                                      rules=rules, long_ctx=long_ctx)
        nxt = _sample_token(logits, jax.random.fold_in(rng, 1 + i), temperature)
        return (nxt, cc), tok

    for seg in plan.segments:
        idx = jnp.arange(seg.start, seg.stop)
        if seg.mode is Mode.FULL:
            (tok, caches_c, caches_u), toks = jax.lax.scan(
                full_body(scale_of(seg)), (tok, caches_c, caches_u), idx)
        else:
            (tok, caches_c), toks = jax.lax.scan(cond_body, (tok, caches_c), idx)
        outs.append(toks)

    gen = jnp.concatenate(outs, axis=0).swapaxes(0, 1)   # (B, n_new)
    return gen, S + n_new
