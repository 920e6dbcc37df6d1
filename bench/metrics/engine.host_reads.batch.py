"""Device-to-host reads an engine tick issues: the program's counter
``engine.host_reads`` (one per decoding slot's token) over ``engine.ticks``
in the window."""


def read(ctx):
    c = ctx.get("counters", {})
    if not c.get("engine.ticks"):
        return None
    return c.get("engine.host_reads", 0) / c["engine.ticks"]
