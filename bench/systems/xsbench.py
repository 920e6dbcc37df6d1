"""XSBench through the GPU First path: the frozen port in ``bench/apps``
run as one ``device_run`` program per simulation.

Traffic keys: ``mode`` (``event``), ``block_lanes`` (lookups per step of
the main loop), ``index_rows_per_block`` (rows of the index grid built per
step of set-up; must divide the unionized grid) and ``check_sample``
(lookups compared with the reference after the window)."""
from __future__ import annotations

import functools
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench.apps import xsbench as app
from bench.reference import xsbench as ref

#: relative gap of a sampled lookup's cross sections from the float64
#: reference: float32 sound runs read at most 1.33e-6 over 17 seeds, the
#: bfloat16 control at least 0.0896 over 3 (see PERF.md)
XS_REL_LIMIT = 1e-4

_LINE = re.compile(r"^Verification checksum: (-?\d+)$")


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        if traffic["mode"] != "event":
            raise ValueError(f"unsupported XSBench mode {traffic['mode']!r}")
        self.c, self.t, self.seed = config, traffic, seed
        self.n = int(config["lookups"])
        self.stats: dict = {}
        self.fault = None             # tests break the timed path here

    def setup(self) -> None:
        c, t = self.c, self.t
        mat = c["materials"]
        t0 = time.perf_counter()
        self.num_nucs, self.mats, self.concs, self.width = \
            gen.xs_materials(gen.device_key(self.seed, 2),
                             mat["nuclides"])
        self.grids = jax.jit(functools.partial(
            gen.xs_nuclide_grids, n_isotopes=c["n_isotopes"],
            n_gridpoints=c["n_gridpoints"]))(gen.device_key(self.seed, 0))
        self.energies, self.materials = jax.jit(functools.partial(
            gen.xs_lookups, n=self.n, dist=tuple(mat["dist"])))(
                gen.device_key(self.seed, 1))
        jax.block_until_ready((self.grids, self.energies))
        t1 = time.perf_counter()
        order = app.unionized_order(self.grids[0::app.POINT])
        self.egrid, self.index_data = jax.jit(functools.partial(
            app.unionized_grid, n_isotopes=c["n_isotopes"],
            n_gridpoints=c["n_gridpoints"],
            rows_per_block=int(t["index_rows_per_block"])))(self.grids, order)
        jax.block_until_ready(self.index_data)
        t2 = time.perf_counter()
        sim = jax.jit(app.run_event_simulation, static_argnames=(
            "n_isotopes", "n_gridpoints", "max_num_nucs", "block"))
        self.args = (self.energies, self.materials, self.egrid,
                     self.index_data, self.grids, self.num_nucs,
                     self.concs, self.mats)
        sizes = dict(n_isotopes=c["n_isotopes"],
                     n_gridpoints=c["n_gridpoints"], max_num_nucs=self.width,
                     block=int(t["block_lanes"]))
        self.simulate = lambda: sim(*self.args, **sizes)
        # warm-up: the one simulation program the window runs
        self._one()
        app_lines()
        self.setup_phases = {"data": t1 - t0, "index_grid": t2 - t1,
                             "first_simulation": time.perf_counter() - t2}

    def _one(self):
        verification, out = self.simulate()
        if self.fault is not None:
            verification, out = self.fault(verification, out)
        jax.block_until_ready(out)
        jax.effects_barrier()         # the checksum line has reached the host
        return verification, out

    def window(self, seconds: float, spans) -> dict:
        sims = 0
        t0 = time.perf_counter()
        while True:
            with spans.span("bench.simulation"):
                self.last = self._one()
            sims += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.lines = app_lines()
        self.sims = sims
        print("set-up phases: " + ", ".join(
            f"{k} {v:.2f} s" for k, v in self.setup_phases.items())
            + f"; one simulation in the window {elapsed / sims:.2f} s",
            file=sys.stderr)
        self.stats = {"xs_lookups_per_s": sims * self.n / elapsed,
                      "attempted": sims * self.n, "failed": 0,
                      "lookups": sims * self.n, "simulations": sims,
                      "elapsed_s": elapsed}
        return self.stats

    def check(self) -> dict:
        """Cross sections of a seeded sample of the last simulation's
        lookups against the float64 reference, and every simulation's
        printed checksum against the checksum of the answers it produced."""
        _, out = self.last
        n, c = self.n, self.c
        comps = [out[k:n * app.N_XS:app.N_XS] for k in range(app.N_XS)]
        expected = int(jax.jit(_checksum)(comps))
        printed = [int(m.group(1)) for m in map(_LINE.match, self.lines) if m]
        printed += [None] * (self.sims - len(printed))
        bad = sum(1 for p in printed if p != expected)
        self.stats["failed"] = bad * n

        rng = gen.np_rng(self.seed, 3)
        idx = np.sort(rng.choice(n, size=min(int(self.t["check_sample"]), n),
                                 replace=False))
        got = np.stack([np.asarray(x[idx]) for x in comps], axis=1)
        want = ref.macro_xs(
            np.asarray(self.energies[idx]), np.asarray(self.materials[idx]),
            np.asarray(self.grids), c["n_isotopes"], c["n_gridpoints"],
            np.asarray(self.num_nucs), np.asarray(self.mats),
            np.asarray(self.concs))
        return {"xs_rel_err": (ref.relative_error(got, want), XS_REL_LIMIT),
                "checksum_mismatch": (float(bad), 0.0)}

    def close(self) -> None:
        for name in ("args", "last", "grids", "energies", "materials",
                     "egrid", "index_data"):
            self.__dict__.pop(name, None)


def _checksum(comps):
    """XSBench's verification sum over every lookup's answers."""
    best = jnp.full_like(comps[0], -1.0)
    idx = jnp.zeros(comps[0].shape, jnp.int32)
    for j, x in enumerate(comps):
        bigger = x > best
        best = jnp.where(bigger, x, best)
        idx = jnp.where(bigger, j, idx)
    return jnp.sum(idx + 1)


def app_lines():
    from repro.core.libc import drain_printf
    return [ln.strip() for ln in drain_printf()]
