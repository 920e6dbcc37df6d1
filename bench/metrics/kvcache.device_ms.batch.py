"""Device time of the page heap per tick: the self time of the ops under
the ``kvcache`` named scope (page allocation, the K/V writes, the length
advance, the release of retired slots), over the window's ticks."""
from bench import program


def read(ctx):
    scopes = ctx.get("scopes")
    if not scopes or not ctx["stats"].get("ticks"):
        return None
    s = sum(v for op, v in scopes.items() if program.in_scope(op, "kvcache"))
    if s <= 0:
        return None
    return 1e3 * s / ctx["stats"]["ticks"]
