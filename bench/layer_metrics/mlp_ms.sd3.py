"""Device time of the MMDiT's feed-forwards (both streams, with their
norms, modulations and gated residuals), summed over blocks and steps,
per image finished: time under ``mmdit.mlp`` over images
(``bench/scopes_mmdit.py``)."""

from bench import scopes_mmdit


def read(ctx):
    return scopes_mmdit.per_image_ms(ctx, "mmdit.mlp")
