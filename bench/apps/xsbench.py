"""XSBench v20 (ANL-CESAR), event-based unionized-grid lookups, ported as
GPU First runs a legacy application.  This is the frozen application
source: later changes go to the runtime under it, never here.

The functions below are one-to-one ports of XSBench's ``grid_search``,
``calculate_micro_xs`` and ``calculate_macro_xs``, written as the CPU loop
over flat arrays (pointer arithmetic as in the C source), with doubles
replaced by float32 because the chip has no native float64.  The main loop
runs on the device under ``device_run``; its lookup loop is a region
expanded by ``parallel_for``; the verification checksum is printed through
the device libc's ``fprintf`` on the RPC queue, once per simulation.

``parallel_for`` vectorises a region's whole iteration space at once, so the
main loop steps over blocks of ``block`` lookups (a power of two); the
lookups past the end of the last block are computed and discarded.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import device_run, fprintf, parallel_for

N_XS = 5                 # total, elastic, absorption, fission, nu-fission
POINT = 1 + N_XS         # NuclideGridPoint: energy then the five xs


def grid_search(n, quarry, A):
    """Binary search of the unionized energy grid ``A`` of length ``n``."""
    def cond(c):
        return c[2] > 1

    def body(c):
        lower, upper, _ = c
        examination = lower + (upper - lower) // 2
        above = A[examination] > quarry
        upper = jnp.where(above, examination, upper)
        lower = jnp.where(above, lower, examination)
        return lower, upper, upper - lower

    lower, _, _ = lax.while_loop(
        cond, body, (jnp.int32(0), jnp.int32(n - 1), jnp.int32(n - 1)))
    return lower


def calculate_micro_xs(p_energy, nuc, n_isotopes, n_gridpoints,
                       index_data, nuclide_grids, idx):
    """Five microscopic cross sections of nuclide ``nuc`` at ``p_energy``,
    interpolated between the grid points the index grid names."""
    i = index_data[idx * n_isotopes + nuc]
    low = nuc * n_gridpoints + jnp.where(i == n_gridpoints - 1, i - 1, i)
    high = low + 1
    low_e = nuclide_grids[low * POINT]
    high_e = nuclide_grids[high * POINT]
    f = (high_e - p_energy) / (high_e - low_e)
    xs = []
    for k in range(1, POINT):
        hi = nuclide_grids[high * POINT + k]
        lo = nuclide_grids[low * POINT + k]
        xs.append(hi - f * (hi - lo))
    return jnp.stack(xs)


def calculate_macro_xs(p_energy, mat, n_isotopes, n_gridpoints, num_nucs,
                       concs, egrid, index_data, nuclide_grids, mats,
                       max_num_nucs):
    """Macroscopic cross sections of material ``mat`` at ``p_energy``:
    one unionized-grid search, then the concentration-weighted sum over
    the material's nuclides."""
    idx = grid_search(n_isotopes * n_gridpoints, p_energy, egrid)

    def body(j, macro_xs_vector):
        p_nuc = mats[mat * max_num_nucs + j]
        conc = concs[mat * max_num_nucs + j]
        xs_vector = calculate_micro_xs(p_energy, p_nuc, n_isotopes,
                                       n_gridpoints, index_data,
                                       nuclide_grids, idx)
        return macro_xs_vector + xs_vector * conc

    return lax.fori_loop(0, num_nucs[mat], body,
                         jnp.zeros((N_XS,), jnp.float32))


def verification_index(macro_xs_vector):
    """XSBench's checksum term: 1 + index of the largest cross section."""
    best = jnp.float32(-1.0)
    max_idx = jnp.int32(0)
    for j in range(N_XS):
        bigger = macro_xs_vector[j] > best
        best = jnp.where(bigger, macro_xs_vector[j], best)
        max_idx = jnp.where(bigger, j, max_idx)
    return max_idx + 1


def run_event_simulation(energies, materials, egrid, index_data,
                         nuclide_grids, num_nucs, concs, mats, *,
                         n_isotopes: int, n_gridpoints: int,
                         max_num_nucs: int, block: int):
    """The event-based main loop: every lookup ``i`` reads its energy and
    material, computes its macroscopic cross sections into ``out[i]`` and
    adds its checksum term.  Returns ``(verification, out)`` with ``out``
    flat, ``N_XS`` floats per lookup (padded to whole blocks)."""
    n_lookups = energies.shape[0]
    n_steps = -(-n_lookups // block)

    def step(s, state, q):
        verification, out = state
        base = s * block

        def lookup(i):
            gi = base + i
            valid = gi < n_lookups
            gi = jnp.minimum(gi, n_lookups - 1)
            macro = calculate_macro_xs(
                energies[gi], materials[gi], n_isotopes, n_gridpoints,
                num_nucs, concs, egrid, index_data, nuclide_grids, mats,
                max_num_nucs)
            return macro, jnp.where(valid, verification_index(macro), 0)

        macro, term = parallel_for(lookup, block)
        verification = verification + jnp.sum(term)
        out = lax.dynamic_update_slice(out, macro.reshape(-1),
                                       (base * N_XS,))
        q = fprintf(q, "Verification checksum: %d", verification,
                    where=s == n_steps - 1)
        return (verification, out), q

    state = (jnp.int32(0), jnp.zeros((n_steps * block * N_XS,), jnp.float32))
    return device_run(step, state, n_steps, thread_queue=True, donate=False,
                      queue_capacity=4, queue_width=2, queue_payload=4)


def unionized_order(energy):
    """XSBench's unionized energy grid is every nuclide energy, sorted;
    returns the sort permutation.  Sorted on the host, as XSBench's
    initialisation does (``qsort``): one sort of four million floats
    costs the device compiler far longer than the host takes to run it."""
    return np.argsort(np.asarray(energy), kind="stable").astype(np.int32)


def unionized_grid(nuclide_grids, order, n_isotopes: int, n_gridpoints: int,
                   rows_per_block: int):
    """The unionized energy grid and its index grid: for each unionized
    energy, the index of each nuclide's grid point at or below it (clamped
    to leave a point above).  Built a block of rows at a time into the flat
    row-major index grid."""
    egrid = nuclide_grids[0::POINT][order]
    owner = order // n_gridpoints
    n = n_isotopes * n_gridpoints
    if n % rows_per_block:
        raise ValueError(f"{rows_per_block} rows per block do not tile {n}")
    nuclides = jnp.arange(n_isotopes, dtype=jnp.int32)

    def fill(b, carry):
        counts, index_data = carry
        rows = lax.dynamic_slice(owner, (b * rows_per_block,),
                                 (rows_per_block,))
        seen = jnp.cumsum((rows[:, None] == nuclides[None, :])
                          .astype(jnp.int32), axis=0) + counts[None, :]
        block = jnp.clip(seen - 1, 0, n_gridpoints - 2)
        index_data = lax.dynamic_update_slice(
            index_data, block.reshape(-1), (b * rows_per_block * n_isotopes,))
        return seen[-1], index_data

    _, index_data = lax.fori_loop(
        0, n // rows_per_block, fill,
        (jnp.zeros((n_isotopes,), jnp.int32),
         jnp.zeros((n * n_isotopes,), jnp.int32)))
    return egrid, index_data
