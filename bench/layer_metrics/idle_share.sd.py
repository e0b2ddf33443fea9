"""Share of the traced window in which no op ran on the device."""


def read(ctx):
    share = ctx["trace"]["idle_share"]
    return None if share is None else 100.0 * share
