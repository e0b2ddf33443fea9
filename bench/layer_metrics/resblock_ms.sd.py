"""Device time of the UNet's residual blocks, summed over levels and
steps, per image finished: time under ``unet.res`` over images
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.per_image_ms(ctx, "unet.res")
