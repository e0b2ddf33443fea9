"""Device time of the UNet's self-attention, summed over levels and
steps, per image finished: time under ``unet.attn.self`` over images
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.per_image_ms(ctx, "unet.attn.self")
