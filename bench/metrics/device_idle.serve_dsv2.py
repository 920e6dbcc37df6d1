"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    lo, hi = ctx["window"]
    return 100.0 * (1.0 - ctx["trace"].busy_s(lo, hi) / ctx["window_s"])
