"""Device time of one FULL step (the batch's cond and uncond UNet rows,
the combine and the update): time under ``sd.step.full`` over the FULL
steps run in the window (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "sd.step.full")
