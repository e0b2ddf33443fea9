"""UNet passes per image, counted on the device: (2 x FULL steps + COND
steps) x batch over images, the step bodies' runs read from the trace
(``bench/scopes.py``). The plan fixes it; a dynamic guidance policy would
move it."""

from bench import scopes


def read(ctx):
    return scopes.passes_per_image(ctx)
