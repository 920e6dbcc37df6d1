"""Seeded traffic generators: the same seed gives the same inputs; another
seed the same schedule and lengths with other token ids."""
import json
import os

import jax
import numpy as np
import pytest

from bench import gen

from tiny import ROOT

SEED = 2**33 + 12345          # seeds may be wider than 32 bits


def _mix():
    return {"arrival": "backlog", "group": 16,
            "prompt": {"median": 128, "sigma": 0.6, "min": 16, "max": 1024},
            "output": {"median": 96, "sigma": 0.7, "min": 16, "max": 512}}


def test_same_seed_same_schedule():
    a = gen.requests(_mix(), SEED, 1000, 64)
    b = gen.requests(_mix(), SEED, 1000, 64)
    assert a == b
    c = gen.requests(_mix(), SEED + 1, 1000, 64)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    # every seed gets the same work: the same lengths in the same order
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in c]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in c]
    # ... each group of 16 consecutive requests an even share of the mix
    whole = np.sort([len(r["prompt"]) for r in a])
    for g in range(0, 64, 16):
        assert sorted(len(r["prompt"]) for r in a[g:g + 16]) == \
            sorted(whole[g // 16::4])


def test_in_flight_requests_are_the_same_work_for_every_seed():
    mix = dict(_mix(), in_flight=8)
    a = gen.requests(mix, SEED, 1000, 64)
    c = gen.requests(mix, SEED + 1, 1000, 64)
    assert len(a) == 72
    first = gen.in_flight_lengths(gen.mix_lengths(mix, 64), 8)
    for reqs in (a, c):
        got = sorted((len(r["prompt"]), r["max_new"]) for r in reqs[:8])
        assert got == sorted(map(tuple, first.tolist()))
    # the backlog behind them is the mix without the in-flight requests
    plain = gen.requests(_mix(), SEED, 1000, 64)
    assert sorted(len(r["prompt"]) for r in a[8:]) == \
        sorted(len(r["prompt"]) for r in plain)


def test_in_flight_lengths_are_what_is_left_of_a_request():
    # one request, prompt 3 and output 2, holds its slot 4 ticks: after
    # 0, 1, 2, 3 of them it has left prompts 3, 2, 1, 1 and outputs 2, 2,
    # 2, 1
    got = gen.in_flight_lengths(np.asarray([[3, 2]]), 4)
    assert got.tolist() == [[1, 1], [1, 2], [2, 2], [3, 2]]


def _outputs_per_slot_tick(reqs, slots, ticks):
    """The engine's slot grid without the model: each tick refills free
    slots from the queue, feeds one prompt token or yields one output."""
    queue = [(len(r["prompt"]), r["max_new"]) for r in reqs]
    grid = [None] * slots
    outs = []
    for _ in range(ticks):
        n = 0
        for j in range(slots):
            if grid[j] is None and queue:
                grid[j] = list(queue.pop(0)) + [0, 0]
            s = grid[j]
            if s is None:
                continue
            if s[2] < s[0]:
                s[2] += 1
                if s[2] < s[0]:
                    continue
            s[3] += 1
            n += 1
            if s[3] >= s[1]:
                grid[j] = None
        outs.append(n / slots)
    return np.asarray(outs)


def test_backlog_with_in_flight_requests_is_steady_from_the_first_tick():
    with open(os.path.join(ROOT, "bench", "traffic", "serve-batch.json")) as f:
        mix = json.load(f)
    pairs = gen.mix_lengths(mix, mix["requests"])
    steady = pairs[:, 1].sum() / (pairs.sum() - len(pairs))
    reqs = gen.requests(mix, SEED, 100, mix["requests"])
    thirds = [x.mean() for x in np.array_split(
        _outputs_per_slot_tick(reqs, mix["in_flight"], 540), 3)]
    assert np.allclose(thirds, steady, rtol=0.08), thirds
    # without them the slots start together, all taking prompt tokens
    cold = gen.requests(dict(mix, in_flight=0), SEED, 100, mix["requests"])
    first = _outputs_per_slot_tick(cold, mix["in_flight"], 180).mean()
    assert first < 0.5 * steady


def test_lognormal_lengths_median_and_clip():
    x = gen.lognormal_lengths(1001, {"median": 128, "sigma": 0.6,
                                     "min": 32, "max": 512})
    assert np.median(x) == 128
    assert x.min() >= 32 and x.max() <= 512


def test_xsbench_lookups_follow_pick_mat():
    dist = [0.140, 0.052, 0.275, 0.134, 0.154, 0.064, 0.066, 0.055, 0.008,
            0.015, 0.025, 0.013]
    e1, m1 = gen.xs_lookups(gen.device_key(SEED, 1), 200_000, dist)
    e2, m2 = gen.xs_lookups(gen.device_key(SEED, 1), 200_000, dist)
    assert np.array_equal(e1, e2) and np.array_equal(m1, m2)
    share = np.bincount(np.asarray(m1), minlength=12) / 200_000
    want = np.asarray([1 - sum(dist[1:])] + dist[1:])
    assert np.abs(share - want).max() < 0.004
    assert 0.0 <= float(e1.min()) and float(e1.max()) < 1.0


def test_xsbench_nuclide_grids_rise_strictly():
    g = np.asarray(gen.xs_nuclide_grids(gen.device_key(SEED, 0), 3, 500))
    g = g.reshape(3, 500, 6)
    assert np.all(np.diff(g[:, :, 0], axis=1) > 0)
    assert np.all((g[:, :, 1:] >= 0) & (g[:, :, 1:] < 1))


def test_device_key_differs_in_high_bits():
    a = jax.random.key_data(gen.device_key(5, 0))
    b = jax.random.key_data(gen.device_key(5 + 2**32, 0))
    assert not np.array_equal(a, b)
