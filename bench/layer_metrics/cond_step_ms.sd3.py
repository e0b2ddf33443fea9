"""Device time of one COND step of the MMDiT loop (the batch's cond rows
and the update): time under ``sd.step.cond`` over the COND steps run in
the window (``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, "sd.step.cond")
