"""Plain XSBench lookup in NumPy float64, independent of the port: no
unionized grid and no index grid, one ``searchsorted`` per nuclide.

``dtype`` selects the precision of the cross sections, the concentrations
and the interpolation and sum over them; energies and the search stay in
float64.  The benchmark's control runs it in bfloat16, one step below the
port's float32: the step a port would take to halve its table bytes."""
from __future__ import annotations

import numpy as np


def macro_xs(energies, materials, nuclide_grids, n_isotopes, n_gridpoints,
             num_nucs, mats, concs, dtype=np.float64):
    """Macroscopic cross sections, shape (len(energies), 5).

    ``nuclide_grids`` is flat (energy and five cross sections per point,
    points in rising energy, nuclides one after another); ``mats`` and
    ``concs`` are flat with ``len(mats) // len(num_nucs)`` per material."""
    grids = np.asarray(nuclide_grids).reshape(n_isotopes, n_gridpoints, 6)
    energy = grids[:, :, 0].astype(np.float64)
    xs_table = grids[:, :, 1:].astype(dtype)
    e = np.asarray(energies).astype(np.float64)
    m = np.asarray(materials)
    width = len(mats) // len(num_nucs)
    mats = np.asarray(mats).reshape(-1, width)
    concs = np.asarray(concs).astype(dtype).reshape(-1, width)
    out = np.zeros((len(e), 5), dtype)
    for mat in range(len(num_nucs)):
        sel = np.nonzero(m == mat)[0]
        if not len(sel):
            continue
        em = e[sel]
        acc = np.zeros((len(sel), 5), dtype)
        for j in range(int(num_nucs[mat])):
            ge, gx = energy[mats[mat, j]], xs_table[mats[mat, j]]
            low = np.searchsorted(ge, em, side="right") - 1
            low = np.clip(low, 0, n_gridpoints - 2)
            f = ((ge[low + 1] - em) / (ge[low + 1] - ge[low])).astype(dtype)
            lo, hi = gx[low], gx[low + 1]
            xs = hi - f[:, None] * (hi - lo)
            acc = acc + xs * concs[mat, j]
        out[sel] = acc
    return out


def relative_error(got, want) -> float:
    """Largest gap of any cross section, relative to the lookup's largest
    reference cross section."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.max(np.abs(want), axis=1)
    return float(np.max(np.max(np.abs(got - want), axis=1) / scale))
