"""MMDiT passes per image, counted on the device: (2 x FULL steps + COND
steps) x batch over images, the step bodies' runs read from the trace
(``bench/scopes.py``). At f = 0.5 of 28 steps the plan fixes it at 42."""

from bench import scopes


def read(ctx):
    return scopes.passes_per_image(ctx)
