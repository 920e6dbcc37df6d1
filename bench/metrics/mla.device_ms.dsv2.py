"""Device time of the latent attention per tick: the self time of the ops
under the ``mla`` named scope (projections, the absorbed query, the latent
row's write and the kernel), over the window's ticks."""
from bench import program


def read(ctx):
    scopes = ctx.get("scopes")
    if not scopes or not ctx["stats"].get("ticks"):
        return None
    s = sum(v for op, v in scopes.items() if program.in_scope(op, "mla"))
    if s <= 0:
        return None
    return 1e3 * s / ctx["stats"]["ticks"]
