"""Mean engine tick in the window: the harness's span around each
``ServingEngine.step`` call, over the ticks."""


def read(ctx):
    s = ctx["stats"]
    return 1e3 * s["tick_s"] / s["ticks"] if s.get("ticks") else None
