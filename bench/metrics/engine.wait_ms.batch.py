"""Time an engine tick waits for the device: the program's ``engine.wait``
span (the tick's first device read), summed over the window and divided by
its ticks."""
from bench import program


def read(ctx):
    ticks = ctx.get("counters", {}).get("engine.ticks")
    if not ticks:
        return None
    return 1e3 * program.span_seconds(ctx["program_spans"],
                                      ["engine.wait"]) / ticks
