"""Device time of the engine's whole step program per tick: the runs of
``jit_serve_step`` in the traced window over the window's ticks.  Nothing to
read where the step program has another name."""


def read(ctx):
    lo, hi = ctx["window"]
    s = sum(v for name, v in ctx["trace"].module_seconds(lo, hi).items()
            if name.startswith("jit_serve_step("))
    ticks = ctx["stats"].get("ticks")
    if s <= 0 or not ticks:
        return None
    return 1e3 * s / ticks
