"""Modality frontends.

* ``TextEncoder`` — a small in-framework transformer encoder standing in for
  CLIP's text tower in the SD pipeline (no pretrained weights offline). The
  *unconditional* embedding (classifier-free guidance's null prompt) is the
  encoding of the empty token sequence, exactly like SD's "" prompt.
* SD3's three text towers (CLIP-L, OpenCLIP bigG, T5-XXL) at their
  published widths, in the same encoder block, and the (context, pooled)
  conditioning SD3 builds from them (``encode_sd3``).
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


#: SD3's text towers: (width, heads, feed-forward) as published
#: (CLIP ViT-L/14, OpenCLIP ViT-bigG/14, T5 v1.1 XXL encoder).
SD3_TOWERS = {"clip_l": (768, 12, 3072), "clip_g": (1280, 20, 5120),
              "t5": (4096, 64, 10240)}


def sd3_text_configs(vocab: int, layers: dict) -> dict:
    """The three towers' encoder configs at their published widths,
    ``layers[name]`` blocks deep, over a ``vocab``-token vocabulary."""
    out = {}
    for name, (dim, heads, ff) in SD3_TOWERS.items():
        out[name] = replace(text_encoder_config(vocab, dim, 0),
                            name=f"text-encoder-{name}", num_layers=layers[name],
                            num_heads=heads, num_kv_heads=heads, d_ff=ff)
    return out


def encode_sd3(params, cfgs: dict, clip_tokens, t5_tokens, context_dim: int):
    """SD3's conditioning from CLIP tokens (B, Lc) and T5 tokens (B, Lt),
    prompts left-aligned and zero-padded -> (context (B, Lc + Lt,
    context_dim), pooled (B, 768 + 1280)).

    The context is CLIP-L's and bigG's states concatenated on width and
    zero-padded to ``context_dim``, then T5's states; the pooled vector
    joins both CLIPs' states at the prompt's last token (position 0 for
    the empty prompt). A tower's states are its last block's output."""
    hl = encode_text(params["clip_l"], cfgs["clip_l"], clip_tokens)
    hg = encode_text(params["clip_g"], cfgs["clip_g"], clip_tokens)
    ht = encode_text(params["t5"], cfgs["t5"], t5_tokens)
    with jax.named_scope("sd.encode"):
        clip = jnp.concatenate([hl, hg], -1)
        clip = jnp.pad(clip, ((0, 0), (0, 0), (0, context_dim - clip.shape[-1])))
        context = jnp.concatenate([clip, ht.astype(clip.dtype)], 1)
        last = jnp.maximum((clip_tokens != 0).sum(-1) - 1, 0)
        pooled = jnp.take_along_axis(jnp.concatenate([hl, hg], -1),
                                     last[:, None, None], 1)[:, 0]
    return context, pooled


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
