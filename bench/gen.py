"""Seeded generators for every traffic mix and data set.

The same seed gives the same inputs.  Where a mix has lengths, they
are the distribution's quantiles at fixed points,
in an order fixed by the mix alone: every seed gets the same schedule and
draws only the token ids, so seeds change what is computed and not how
much."""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def np_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent host stream ``stream`` of ``seed`` (any int, any size)."""
    return np.random.default_rng([int(seed) & (2**64 - 1), int(stream)])


def device_key(seed: int, stream: int) -> jax.Array:
    """Device PRNG key for ``seed``'s stream ``stream``; ``seed`` may be
    wider than 32 bits."""
    s = int(seed) & (2**64 - 1)
    key = jax.random.key(s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, stream)


# ---------------------------------------------------------------------------
# serving lengths
# ---------------------------------------------------------------------------

def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9)."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / \
        ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0]*r+a[1])*r+a[2])*r+a[3])*r+a[4])*r+a[5])*q / \
        (((((b[0]*r+b[1])*r+b[2])*r+b[3])*r+b[4])*r+1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0]*q+c[1])*q+c[2])*q+c[3])*q+c[4])*q+c[5]) / \
        ((((d[0]*q+d[1])*q+d[2])*q+d[3])*q+1)
    return out


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a log-normal with
    ``median`` and ``sigma``, rounded and clipped to ``[min, max]``."""
    p = (np.arange(n) + 0.5) / n
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(p))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _grouped(values: np.ndarray, group: int, rng) -> np.ndarray:
    """``values`` dealt into consecutive groups of ``group`` that each hold
    an even spread of the sorted values, then shuffled inside each group:
    every window of ``group`` requests carries about the same work."""
    n = len(values)
    groups = n // group
    order = np.argsort(values, kind="stable")
    dealt = order.reshape(group, groups).T.copy()    # group g: g, g+G, ...
    for row in dealt:
        rng.shuffle(row)
    return values[dealt.reshape(-1)]


def mix_lengths(traffic: dict, n: int) -> np.ndarray:
    """The mix's fixed multiset of ``n`` (prompt, output) length pairs:
    the k-th shortest prompt gets the k-th output of a fixed interleaving
    of the output lengths, whatever the seed."""
    p = lognormal_lengths(n, traffic["prompt"])
    o = lognormal_lengths(n, traffic["output"])
    o = np.concatenate([o[0::2], o[1::2][::-1]])
    return np.stack([p, o], axis=1)


def in_flight_lengths(pairs: np.ndarray, slots: int) -> np.ndarray:
    """What is left of the ``slots`` requests that a grid of slots holds at
    a random tick once it has served the mix ``pairs`` for long, as
    (prompt, output) lengths to submit.

    A request of prompt ``P`` and output ``O`` holds its slot ``P + O - 1``
    ticks (the last prompt token yields the first output).  At a random
    tick a slot is at each tick of each request alike, so the requests in
    flight are drawn over all (request, ticks done) pairs: ``a`` ticks into
    the request, what is left is a prompt of ``max(P - a, 1)`` tokens and
    ``O - max(a - P + 1, 0)`` outputs, which holds the slot the ``P + O - 1
    - a`` ticks that remain.  Slots that start on these and then take the
    backlog serve the mix at its long-run rate from the first tick (a
    renewal process whose first interval is drawn from its equilibrium).
    The ``slots`` pairs sit at fixed quantiles of the ticks left, so every
    seed gets the same ones."""
    P, O = pairs[:, 0], pairs[:, 1]
    hold = P + O - 1
    req = np.repeat(np.arange(len(pairs)), hold)
    done = np.arange(hold.sum()) - np.repeat(np.cumsum(hold) - hold, hold)
    prompt = np.maximum(P[req] - done, 1)
    output = O[req] - np.maximum(done - P[req] + 1, 0)
    order = np.lexsort((prompt, hold[req] - done))
    pick = order[((np.arange(slots) + 0.5) / slots * len(order)).astype(int)]
    return np.stack([prompt[pick], output[pick]], axis=1)


def requests(traffic: dict, seed: int, vocab: int, n: int) -> List[dict]:
    """The backlog of a serving mix: ``n`` requests, each its prompt token
    ids and output length.  Lengths are the mix's fixed multiset, dealt
    into groups of ``traffic["group"]`` consecutive requests that each hold
    an even spread of it, shuffled inside each group in an order that is
    the same for every seed; the seed draws the token ids.  With
    ``in_flight`` set the backlog starts with that many requests of
    :func:`in_flight_lengths`."""
    if traffic["arrival"] != "backlog":
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")
    group = int(traffic["group"])
    if n % group:
        raise ValueError(f"{n} requests do not fill groups of {group}")
    rng = np_rng(0, 1)              # the schedule is the mix's, not the seed's
    mix = mix_lengths(traffic, n)
    pairs = mix[_grouped(np.arange(n), group, rng)]
    slots = int(traffic.get("in_flight", 0))
    if slots:
        first = in_flight_lengths(mix, slots)
        pairs = np.concatenate([first[rng.permutation(slots)], pairs])
    toks = np_rng(seed, 2)
    return [{"prompt": toks.integers(0, vocab, int(a)).tolist(),
             "max_new": int(b)} for a, b in pairs]


# ---------------------------------------------------------------------------
# XSBench data
# ---------------------------------------------------------------------------

def pick_mat_table(dist: Sequence[float]) -> np.ndarray:
    """The thresholds of XSBench's ``pick_mat``: material ``i >= 1`` is
    picked when the roll is below ``sum(dist[1..i])`` and no smaller ``i``
    was; a roll above them all picks material 0 (fuel)."""
    return np.cumsum(np.asarray(dist[1:], np.float64)).astype(np.float32)


def xs_lookups(key, n: int, dist: Sequence[float]):
    """Energies (uniform on [0, 1)) and materials (by ``pick_mat``) of
    ``n`` independent lookups, as float32 and int32 device arrays."""
    ke, km = jax.random.split(key)
    energies = jax.random.uniform(ke, (n,), jnp.float32)
    roll = jax.random.uniform(km, (n,), jnp.float32)
    thr = jnp.asarray(pick_mat_table(dist))
    below = roll[:, None] < thr[None, :]
    first = jnp.argmax(below, axis=1).astype(jnp.int32) + 1
    materials = jnp.where(jnp.any(below, axis=1), first, 0).astype(jnp.int32)
    return energies, materials


def xs_nuclide_grids(key, n_isotopes: int, n_gridpoints: int):
    """Each nuclide's grid of ``n_gridpoints`` points, flat and row-major:
    (energy, total, elastic, absorption, fission, nu-fission) per point.
    Energies rise strictly, one uniform draw per ``1/n_gridpoints`` stratum
    of [0, 1); cross sections are uniform on [0, 1)."""
    ke, kx = jax.random.split(key)
    u = jax.random.uniform(ke, (n_isotopes, n_gridpoints), jnp.float32)
    energy = (jnp.arange(n_gridpoints, dtype=jnp.float32) + u) / n_gridpoints
    xs = jax.random.uniform(kx, (n_isotopes, n_gridpoints, 5), jnp.float32)
    return jnp.concatenate([energy[..., None], xs], axis=-1).reshape(-1)


def xs_materials(key, nuclides: Sequence[Sequence[int]]):
    """XSBench's material tables: nuclide counts, the nuclide ids and their
    concentrations (uniform on [0, 1)), flat with ``max_num_nucs`` per
    material."""
    width = max(len(m) for m in nuclides)
    mats = np.zeros((len(nuclides), width), np.int32)
    for i, m in enumerate(nuclides):
        mats[i, :len(m)] = m
    num = np.asarray([len(m) for m in nuclides], np.int32)
    concs = jax.random.uniform(key, (len(nuclides) * width,), jnp.float32)
    return jnp.asarray(num), jnp.asarray(mats.reshape(-1)), concs, width
