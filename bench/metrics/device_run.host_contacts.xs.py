"""Host contacts a simulation makes: queue flushes plus immediate RPC
calls, from the transport's counters that the program's recorder
publishes, per simulation in the window."""


def read(ctx):
    c = ctx.get("counters", {})
    sims = ctx["stats"].get("simulations")
    if "rpc.flushes" not in c or not sims:
        return None
    return (c["rpc.flushes"] + c.get("rpc.immediate_calls", 0)) / sims
