"""Device time of the MMDiT's joint attention kernel, summed over blocks
and steps, per image finished: time under ``mmdit.attn.core`` over images
(``bench/scopes_mmdit.py``)."""

from bench import scopes_mmdit


def read(ctx):
    return scopes_mmdit.per_image_ms(ctx, "mmdit.attn.core")
