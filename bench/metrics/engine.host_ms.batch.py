"""Host time of an engine tick: the program's engine phase spans other
than ``engine.wait`` (admit, inputs, dispatch, harvest, retire), summed over
the window and divided by its ticks."""
from bench import program

HOST = ("engine.admit", "engine.inputs", "engine.dispatch", "engine.harvest",
        "engine.retire")


def read(ctx):
    ticks = ctx.get("counters", {}).get("engine.ticks")
    if not ticks:
        return None
    return 1e3 * program.span_seconds(ctx["program_spans"], HOST) / ticks
