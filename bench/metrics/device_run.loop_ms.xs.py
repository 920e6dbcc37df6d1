"""Device time a simulation spends in its main loop outside the lookup
region: the self time of the simulation program's ops that are not under
the ``parallel_for`` named scope, per simulation in the window."""
from bench import program

ROOT = "jit(run_event_simulation)"


def read(ctx):
    scopes = ctx.get("scopes")
    sims = ctx["stats"].get("simulations")
    if not scopes or not sims:
        return None
    if program.scope_seconds(scopes, ROOT, "parallel_for") <= 0:
        return None
    return 1e3 * program.scope_seconds(scopes, ROOT, "parallel_for",
                                       inside=False) / sims
