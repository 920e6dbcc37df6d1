"""Device time of the expert layer per tick: the self time of the ops under
the ``moe`` named scope (routing, the held experts, the shared experts and
their combination), over the window's ticks."""
from bench import program


def read(ctx):
    scopes = ctx.get("scopes")
    if not scopes or not ctx["stats"].get("ticks"):
        return None
    s = sum(v for op, v in scopes.items() if program.in_scope(op, "moe"))
    if s <= 0:
        return None
    return 1e3 * s / ctx["stats"]["ticks"]
