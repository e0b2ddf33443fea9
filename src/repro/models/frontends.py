"""Modality frontends.

* ``TextEncoder`` — a small in-framework transformer encoder standing in for
  CLIP's text tower in the SD pipeline (no pretrained weights offline). The
  *unconditional* embedding (classifier-free guidance's null prompt) is the
  encoding of the empty token sequence, exactly like SD's "" prompt.
* Audio (HuBERT conv codec) and vision (VQ / ViT) frontends are stubs per the
  assignment carve-out: ``input_specs`` supplies precomputed frame/patch
  embeddings; these helpers only generate synthetic stand-ins for tests.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T


def text_encoder_config(vocab: int, dim: int, length: int) -> ModelConfig:
    return ModelConfig(
        name="text-encoder", family="encoder", num_layers=4, d_model=dim,
        num_heads=max(2, dim // 64), num_kv_heads=max(2, dim // 64),
        d_ff=4 * dim, vocab_size=vocab, is_encoder=True)


def init_text_encoder(cfg: ModelConfig, mk):
    return T.init_model(cfg, mk)


def encode_text(params, cfg: ModelConfig, tokens):
    """tokens (B,L) int32 -> (B,L,d_model), under the named scope
    ``sd.encode``."""
    with jax.named_scope("sd.encode"):
        h, _, _ = T.forward(params, cfg, tokens)
    return h


def null_tokens(batch: int, length: int):
    """The CFG null prompt: all-zero (BOS/pad) token sequence."""
    return jnp.zeros((batch, length), jnp.int32)


def synthetic_audio_frames(rng, batch: int, frames: int, dim: int,
                           dtype=jnp.bfloat16):
    """Stand-in for the HuBERT conv feature extractor output."""
    return jax.random.normal(rng, (batch, frames, dim), jnp.float32).astype(dtype)


def synthetic_image_tokens(rng, batch: int, n_patches: int, vocab: int,
                           image_token_base: int = 0):
    """Stand-in for a VQ image tokenizer (Chameleon early fusion)."""
    return jax.random.randint(rng, (batch, n_patches), image_token_base,
                              vocab, jnp.int32)
