"""The least bytes one XSBench unionized-grid lookup has to read.

One binary search of the unionized energy grid (one float32 read per
step), then for each nuclide of the lookup's material one index-grid entry
(int32) and the two grid points around the energy (six float32 each).
This is the work the algorithm needs, whatever a program pads or repeats."""
from __future__ import annotations

import math

F32 = 4
I32 = 4
POINT_FLOATS = 6


def search_steps(n_union: int) -> int:
    """Steps of XSBench's ``grid_search`` over ``n_union`` energies."""
    return math.ceil(math.log2(n_union - 1))


def mean_nuclides(dist, num_nucs) -> float:
    """Mean nuclides per lookup under ``pick_mat``: material ``i >= 1``
    with probability ``dist[i]``, fuel (0) with what is left."""
    p = [1.0 - sum(dist[1:])] + list(dist[1:])
    return sum(pi * n for pi, n in zip(p, num_nucs))


def bytes_per_lookup(config: dict) -> float:
    n_union = config["n_isotopes"] * config["n_gridpoints"]
    mat = config["materials"]
    nucs = mean_nuclides(mat["dist"], [len(m) for m in mat["nuclides"]])
    per_nuclide = I32 + 2 * POINT_FLOATS * F32
    return search_steps(n_union) * F32 + nucs * per_nuclide
