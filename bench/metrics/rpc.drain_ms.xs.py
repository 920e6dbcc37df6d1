"""Host time the transport spends replaying drained records: the program's
``rpc.drain`` spans in the window, per simulation."""
from bench import program


def read(ctx):
    spans = ctx.get("program_spans")
    sims = ctx["stats"].get("simulations")
    if spans is None or not sims:
        return None
    if not any(s[0] == "rpc.drain" for s in spans):
        return None
    return 1e3 * program.span_seconds(spans, ["rpc.drain"]) / sims
