"""Sharded device runtime (ISSUE 3): per-device heaps + per-device RPC
queues under ``expand``.

In-process tests drive the sharded state as a *logical* device axis (vmap on
one physical device — the sharded heap/queue are data layouts, not
placements); subprocess tests force a real multi-device host platform and
run the same machinery under ``shard_map`` (the pattern of
``test_multidevice.py``).

Property tests (satellite): per-device non-overlap, team-local watermark
monotonicity, sharded ``find_obj`` agreeing with the per-shard linear
reference; determinism: sharded-queue flush replay order is stable across
runs.
"""
import os
import random
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.allocator import (
    FAIL, BalancedAllocator as BA, GenericAllocator as GA, ShardedAllocator
    as SA, ShardedHeap, find_obj, find_obj_linear, shard_heap)
from repro.core.rpc import (
    READ, REGISTRY, ArenaRef, RpcQueue, ShardedRpcQueue, flush_stats,
    reset_rpc_stats, rpc_call)
from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

I32S = jax.ShapeDtypeStruct((), jnp.int32)


def run_child(code: str, devices: int = 4, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # pin the cpu platform: forced host devices ARE cpu devices, and letting
    # the child probe for accelerators stalls for minutes on hosts that
    # carry a (here unusable) TPU runtime
    env["JAX_PLATFORMS"] = "cpu"
    # children build meshes with the repository's Auto-axes helper
    code = "from repro.launch.mesh import make_mesh\n" + code
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# Sharded heap: property tests (per-device invariants)
# ---------------------------------------------------------------------------

D, SPAN, CAP = 4, 128, 16


def _drive_sharded(seed: int):
    """Random per-device op rounds against a ShardedHeap(Generic inner);
    mirrors each device's live set in python.  Returns (heap, live[d])."""
    rng = random.Random(seed)
    sh = shard_heap(GA.init(SPAN, cap=CAP), D)
    live = [dict() for _ in range(D)]      # global ptr -> size, per device
    for _ in range(12):
        if rng.random() < 0.6:
            sizes = [rng.randint(1, 24) for _ in range(D)]
            sh, ptrs = SA.malloc(sh, jnp.asarray(sizes, jnp.int32))
            for d, (p, s) in enumerate(zip(np.asarray(ptrs), sizes)):
                if p >= 0:
                    assert int(p) not in live[d]
                    live[d][int(p)] = s
        else:
            victims = []
            for d in range(D):
                if live[d] and rng.random() < 0.8:
                    v = rng.choice(sorted(live[d]))
                    del live[d][v]
                    victims.append(v)
                else:
                    victims.append(int(FAIL))
            sh = SA.free(sh, jnp.asarray(victims, jnp.int32)[:, None])
    return sh, live


@pytest.mark.parametrize("seed", range(5))
def test_sharded_heap_per_device_nonoverlap(seed):
    """No two live blocks overlap; every block stays inside its device's
    span (global pointer spaces are disjoint by construction)."""
    sh, live = _drive_sharded(seed)
    for d in range(D):
        blocks = sorted((p, s) for p, s in live[d].items())
        for p, s in blocks:
            assert d * SPAN <= p and p + s <= (d + 1) * SPAN
        for (p1, s1), (p2, _) in zip(blocks, blocks[1:]):
            assert p1 + s1 <= p2, f"dev {d}: overlap at {p1}+{s1} > {p2}"


@pytest.mark.parametrize("seed", range(5))
def test_sharded_heap_watermark_monotone(seed):
    """Each shard's watermark never lies below the end of any of its live
    blocks (team-local monotonicity)."""
    sh, live = _drive_sharded(seed)
    wm = np.asarray(sh.shards.watermark)
    for d in range(D):
        top = max((p - d * SPAN + s for p, s in live[d].items()), default=0)
        assert int(wm[d]) >= top


@pytest.mark.parametrize("seed", range(5))
def test_sharded_find_obj_matches_linear(seed):
    """Sharded find_obj == per-shard linear reference on live interiors,
    boundaries, freed, FAIL, and out-of-mesh probes."""
    sh, live = _drive_sharded(seed)
    probes = [int(FAIL), -7, D * SPAN, D * SPAN + 3]
    for d in range(D):
        probes += [d * SPAN, (d + 1) * SPAN - 1]
        for p, s in live[d].items():
            probes += [p, p + s - 1, p + s]
    for ptr in probes:
        f2, b2, s2 = (int(x) for x in find_obj(sh, jnp.int32(ptr)))
        fl, bl, sl = (int(x) for x in find_obj_linear(sh, jnp.int32(ptr)))
        assert f2 == fl, (ptr, f2, fl)
        if f2:
            assert (b2, s2) == (bl, sl), (ptr, b2, s2, bl, sl)
            d = ptr // SPAN
            assert b2 in live[d] and live[d][b2] == s2
            assert b2 <= ptr < b2 + s2


def test_sharded_heap_one_device_bit_identical():
    """A 1-device sharded heap is the single heap: identical pointer
    streams from the same request sequence (the acceptance contrast)."""
    single = GA.init(SPAN, cap=CAP)
    sh = shard_heap(GA.init(SPAN, cap=CAP), 1)
    for sizes in ([5, 9, 3], [2, 7], [1]):
        for s in sizes:
            single, p1 = GA.malloc(single, s)
            sh, p2 = SA.malloc(sh, jnp.asarray([s], jnp.int32))
            assert int(p1) == int(np.asarray(p2)[0])
    # balanced grid path
    bsing = BA.init(256, 2, 2, cap=16)
    bsh = shard_heap(BA.init(256, 2, 2, cap=16), 1)
    sizes = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    bsing, g1 = BA.malloc_grid(bsing, 2, 4, sizes)
    bsh, g2 = SA.malloc_grid(bsh, 2, 4, sizes[None])
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2)[0])


def test_arena_ref_marshals_sharded_global_ptr():
    """ArenaRef(ptr into shard d) ships the GLOBAL (base, size) — the RPC
    layer's _FindObj path works unchanged on sharded heaps."""
    sh = shard_heap(GA.init(SPAN, cap=CAP), 2)
    sh, ptrs = SA.malloc(sh, jnp.asarray([8, 12], jnp.int32))
    gp = int(np.asarray(ptrs)[1])          # device 1's block
    seen = {}
    REGISTRY.register(
        "shard.probe",
        lambda ptr, base, size, found, arena: seen.update(
            ptr=int(ptr), base=int(base), size=int(size), found=int(found))
        or np.int32(0))

    @jax.jit
    def prog(state, arena, ptr):
        r, _ = rpc_call("shard.probe", ArenaRef(arena, ptr, state,
                                                access=READ),
                        result_shape=I32S)
        return r

    prog(sh, jnp.zeros(2 * SPAN, jnp.float32), jnp.int32(gp + 5))
    jax.effects_barrier()
    assert seen == {"ptr": gp + 5, "base": gp, "size": 12, "found": 1}


# ---------------------------------------------------------------------------
# Sharded queue: (device, slot) replay order, determinism, drop accounting
# ---------------------------------------------------------------------------

def _fill_sharded_queue(n_dev=3, per_dev=3, cap=8):
    REGISTRY.register("shq.rec", _REC.append)
    q = ShardedRpcQueue.create(n_dev, cap, width=2)

    def fill(lq, dev):
        def body(i, lq):
            return lq.enqueue("shq.rec", dev * 100 + i)
        return lax.fori_loop(0, per_dev, body, lq)

    return ShardedRpcQueue(jax.vmap(fill)(q.q, jnp.arange(n_dev)))


_REC = []


def test_sharded_flush_replays_device_slot_order():
    _REC.clear()
    q = _fill_sharded_queue()
    q = q.flush()                          # concrete shards -> direct drain
    expect = [d * 100 + i for d in range(3) for i in range(3)]
    assert _REC == expect
    assert np.asarray(q.q.head).tolist() == [0, 0, 0]


def test_sharded_flush_deterministic_across_runs():
    """Replay order is a deterministic total order: two identical runs
    produce identical record sequences (satellite determinism test)."""
    runs = []
    for _ in range(2):
        _REC.clear()
        _fill_sharded_queue(n_dev=4, per_dev=5).flush()
        runs.append(list(_REC))
    assert runs[0] == runs[1]
    assert len(runs[0]) == 20


def test_sharded_flush_traced_path_inside_jit():
    """Flush of a TRACED sharded queue (logical shards, one device) rides
    one ordered io_callback and preserves (device, slot) order."""
    _REC.clear()
    REGISTRY.register("shq.rec", _REC.append)

    @jax.jit
    def prog():
        q = ShardedRpcQueue.create(2, 4, width=2)

        def fill(lq, dev):
            def body(i, lq):
                return lq.enqueue("shq.rec", dev * 10 + i)
            return lax.fori_loop(0, 2, body, lq)

        q = ShardedRpcQueue(jax.vmap(fill)(q.q, jnp.arange(2)))
        q = q.flush()
        return q.q.head

    head = prog()
    jax.effects_barrier()
    assert np.asarray(head).tolist() == [0, 0]
    assert _REC == [0, 1, 10, 11]


def test_sharded_flush_reports_per_shard_drops():
    """capacity + k enqueues on a shard drop exactly k records (summed over
    shards) — reported via flush_stats, with the surviving records replayed
    in order."""
    reset_rpc_stats()
    _REC.clear()
    q = _fill_sharded_queue(n_dev=2, per_dev=6, cap=4)   # 2 over per shard
    q.flush()
    assert _REC == [100 * d + i for d in range(2) for i in range(2, 6)]
    st = flush_stats()
    assert st["flushes"] == 1 and st["last_drops"] == 4 and st["drops"] == 4


def test_sharded_payload_replay_and_determinism():
    """Payload-carrying records on a sharded queue: every shard's arrays
    resolve against ITS arena slice, replay is (device, slot) order, and
    two identical runs produce identical sequences."""
    REGISTRY.register("shq.pay", lambda i, a: _REC.append((i, a.tolist())))

    def one_run():
        _REC.clear()
        q = ShardedRpcQueue.create(3, 4, width=2, payload_capacity=32)

        def fill(lq, dev):
            def body(i, lq):
                return lq.enqueue(
                    "shq.pay", dev * 100 + i,
                    (dev * 10 + i) + jnp.arange(3, dtype=jnp.int32))
            return lax.fori_loop(0, 2, body, lq)

        q = ShardedRpcQueue(jax.vmap(fill)(q.q, jnp.arange(3)))
        q = q.flush()
        assert np.asarray(q.q.head).tolist() == [0, 0, 0]
        assert np.asarray(q.q.phead).tolist() == [0, 0, 0]
        return list(_REC)

    runs = [one_run(), one_run()]
    expect = [(d * 100 + i, [d * 10 + i, d * 10 + i + 1, d * 10 + i + 2])
              for d in range(3) for i in range(2)]
    assert runs[0] == expect
    assert runs[0] == runs[1]


def test_sharded_payload_traced_flush_inside_jit():
    """The traced (in-jit) sharded flush ships the stacked arenas through
    one ordered io_callback; payloads still reattach per shard."""
    _REC.clear()
    REGISTRY.register("shq.pay2", lambda i, a: _REC.append((i, a.tolist())))

    @jax.jit
    def prog():
        q = ShardedRpcQueue.create(2, 4, width=2, payload_capacity=8)

        def fill(lq, dev):
            return lq.enqueue("shq.pay2", dev,
                              jnp.full((2,), dev, jnp.float32) + 0.5)

        q = ShardedRpcQueue(jax.vmap(fill)(q.q, jnp.arange(2)))
        q = q.flush()
        return q.q.head

    prog()
    jax.effects_barrier()
    assert _REC == [(0, [0.5, 0.5]), (1, [1.5, 1.5])]


def test_sharded_payload_per_shard_arena_drops():
    """Arena overflow is per shard and atomic: a shard whose arena fills
    drops the overflowing record entirely; other shards are untouched;
    drops sum across shards in flush_stats."""
    reset_rpc_stats()
    _REC.clear()
    REGISTRY.register("shq.pay3", lambda i, a: _REC.append((i, a.tolist())))

    q = ShardedRpcQueue.create(2, 8, width=2, payload_capacity=4)

    def fill(lq, dev):
        # 3-word payloads against a 4-word arena: per shard the first fits,
        # the second would need 6 > 4 and is dropped atomically
        def body(i, lq):
            return lq.enqueue("shq.pay3", dev * 10 + i,
                              jnp.full((3,), dev * 10 + i, jnp.int32))
        return lax.fori_loop(0, 2, body, lq)

    q = ShardedRpcQueue(jax.vmap(fill)(q.q, jnp.arange(2)))
    with pytest.warns(RuntimeWarning, match="payload"):
        q = q.flush()
    assert _REC == [(0, [0, 0, 0]), (10, [10, 10, 10])]
    st = flush_stats()
    assert st["arena_drops"] == 2 and st["last_arena_drops"] == 2
    assert st["drops"] == 0


def test_sharded_grid_flat_dispatch_matches_per_device():
    """The flattened D*NC-chunk malloc_grid/free_grid (the ISSUE-4 perf
    fix) is bit-identical to running each device's balanced grid op
    separately."""
    D, T, G = 4, 8, 4
    sizes = (jnp.arange(D * T * G, dtype=jnp.int32) % 7 + 1
             ).reshape(D, T, G)
    sh = shard_heap(BA.init(4096, 4, 2, cap=64), D)
    sh2, gptrs = SA.malloc_grid(sh, T, G, sizes)
    # reference: each device's shard through the plain balanced allocator
    for d in range(D):
        st = BA.init(4096, 4, 2, cap=64)
        st, ref = BA.malloc_grid(st, T, G, sizes[d])
        ref = np.asarray(ref)
        got = np.asarray(gptrs[d])
        expect = np.where(ref < 0, ref, d * sh.span + ref)
        np.testing.assert_array_equal(got, expect)
    # free half the grid, then the rest: per-shard watermarks return to 0
    half = jnp.where(jnp.arange(T)[None, :, None] % 2 == 0, gptrs,
                     jnp.int32(FAIL))
    rest = jnp.where(jnp.arange(T)[None, :, None] % 2 == 0, jnp.int32(FAIL),
                     gptrs)
    sh2 = SA.free_grid(sh2, T, G, half)
    sh2 = SA.free_grid(sh2, T, G, rest)
    assert (np.asarray(sh2.shards.watermark) == 0).all()


def test_place_sharded_state_single_device():
    """distributed.sharding helpers: the device-axis spec covers every mesh
    axis, and placement keeps values intact (1-device mesh in-process; the
    real-mesh path is exercised implicitly by expand's P(axes) in_specs)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import (device_axis_spec,
                                            place_sharded_state)
    mesh = make_mesh((1,), ("dev",))
    assert device_axis_spec(mesh) == P(("dev",))
    q = ShardedRpcQueue.create(1, 8, width=2)
    q2 = place_sharded_state(q, mesh)
    assert isinstance(q2, ShardedRpcQueue)
    np.testing.assert_array_equal(np.asarray(q2.q.callee),
                                  np.asarray(q.q.callee))


# ---------------------------------------------------------------------------
# Transport v4: sharded reply arena
# ---------------------------------------------------------------------------

def test_sharded_remote_malloc_reply_roundtrip():
    """ISSUE 5 acceptance (2-device sharded queue): each device's
    remote-malloc ticket reads back global (device, offset) pointers
    through ITS reply arena in deterministic (flush-order, device, slot)
    order; the pointers pass find_obj and marshal as ArenaRefs."""
    from repro.core.libc import (remote_heap_register, remote_malloc_enqueue,
                                 remote_malloc_results)
    remote_heap_register("heap.sh_rt", shard_heap(GA.init(SPAN, cap=CAP), 2))

    def one_run():
        sq = ShardedRpcQueue.create(2, 8, width=3, payload_capacity=16,
                                    reply_capacity=8)

        def fill(lq, dev):
            # each device asks the host heap's shard `dev` for two blocks
            lq, t = remote_malloc_enqueue(
                lq, "heap.sh_rt", (dev + 1) * jnp.asarray([8, 4], jnp.int32),
                device=dev)
            return lq, t

        qq, tks = jax.vmap(fill)(sq.q, jnp.arange(2))
        sq = ShardedRpcQueue(qq).flush()    # concrete: host-side drain
        return [np.asarray(sq.result(d, tks[d], (2,), jnp.int32)).tolist()
                for d in range(2)]

    run1 = one_run()
    # device d's pointers live in device d's span of the global encoding
    assert run1[0] == [0, 8]                        # dev 0: sizes 8, 4
    assert run1[1] == [SPAN, SPAN + 16]             # dev 1: sizes 16, 8
    state, _ = remote_malloc_results("heap.sh_rt")
    for d, ptrs in enumerate(run1):
        for p, size in zip(ptrs, [(d + 1) * 8, (d + 1) * 4]):
            fo, b, s = find_obj(state, jnp.int32(p))
            assert (int(fo), int(b), int(s)) == (1, p, size)

    # ...and the reply pointer marshals as an ArenaRef in a subsequent RPC
    seen = {}
    REGISTRY.register(
        "sh_rt.probe",
        lambda ptr, base, size, found, arena: seen.update(
            ptr=int(ptr), base=int(base), size=int(size), found=int(found))
        or np.int32(0))

    @jax.jit
    def probe(state, arena, ptr):
        r, _ = rpc_call("sh_rt.probe",
                        ArenaRef(arena, ptr, state, access=READ),
                        result_shape=I32S)
        return r

    probe(state, jnp.zeros(2 * SPAN, jnp.float32), jnp.int32(run1[1][0] + 3))
    jax.effects_barrier()
    assert seen == {"ptr": SPAN + 3, "base": SPAN, "size": 16, "found": 1}

    # deterministic replay: a second identical run on a fresh heap yields
    # the identical pointer streams
    remote_heap_register("heap.sh_rt", shard_heap(GA.init(SPAN, cap=CAP), 2))
    assert one_run() == run1


def test_sharded_reply_traced_flush_inside_jit():
    """The traced (in-jit) sharded two-phase flush ships stacked reply
    buffers back through the one ordered io_callback; each shard's tickets
    resolve against its own reply slice."""
    REGISTRY.register("shq.rep", lambda x: np.arange(int(x), int(x) + 2,
                                                     dtype=np.int32))

    @jax.jit
    def prog():
        q = ShardedRpcQueue.create(2, 4, width=2, reply_capacity=8)

        def fill(lq, dev):
            return lq.enqueue_ticketed(
                "shq.rep", dev * 10,
                returns=jax.ShapeDtypeStruct((2,), jnp.int32))

        qq, tks = jax.vmap(fill)(q.q, jnp.arange(2))
        q = ShardedRpcQueue(qq).flush()
        return q.result(0, tks[0], (2,), jnp.int32), \
            q.result(1, tks[1], (2,), jnp.int32)

    r0, r1 = prog()
    jax.effects_barrier()
    assert np.asarray(r0).tolist() == [0, 1]
    assert np.asarray(r1).tolist() == [10, 11]


def test_device_run_mesh_thread_queue_replies():
    """device_run(mesh=, thread_queue=, return_queue=): each device's step
    enqueues a ticketed RPC into its shard; the boundary flush returns the
    sharded queue with per-device reply tables the host can read."""
    out = run_child(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.device_main import device_run
from repro.core.expand import team_id
from repro.core.rpc import REGISTRY

mesh = make_mesh((2,), ("dev",))
REGISTRY.register("mesh.sq", lambda x: np.int32(x) * np.int32(x))

def step(i, s, lq):
    lq, t = lq.enqueue_ticketed("mesh.sq",
                                (s[0] + team_id()).astype(jnp.int32),
                                returns=jax.ShapeDtypeStruct((), jnp.int32))
    return s + 1.0, lq

final, q = device_run(step, jnp.zeros((1,), jnp.float32), 3, mesh=mesh,
                      thread_queue=True, return_queue=True, queue_reply=16)
assert float(final[0]) == 3.0
# step i on device d enqueued (i + d)^2; tickets are the epoch order 0..2
got = [[int(q.result(d, t)) for t in range(3)] for d in range(2)]
assert got == [[0, 1, 4], [1, 4, 9]], got
print("MESH_REPLY_OK")
""", devices=2)
    assert "MESH_REPLY_OK" in out


# ---------------------------------------------------------------------------
# Sharded paged KV cache (serving conversion)
# ---------------------------------------------------------------------------

def _kv_cfg():
    from repro.configs import CONFIGS
    import dataclasses as dc
    cfg = CONFIGS["llama3.2-3b"].reduced()
    return dc.replace(cfg, num_layers=1)


def test_kvcache_sharded_one_device_bit_identical():
    """mesh=1 sharded page heap == single heap: identical page tables and
    lengths through alloc/advance/release cycles."""
    from repro.serving import kvcache
    cfg = _kv_cfg()
    kv1 = kvcache.paged_cache_init(cfg, 4, 64, page_size=16)
    kv2 = kvcache.paged_cache_init(cfg, 4, 64, page_size=16, mesh=1)
    active = jnp.asarray([True, True, False, True])
    for _ in range(20):
        kv1 = kvcache.advance(kvcache.ensure_pages(kv1, active), active)
        kv2 = kvcache.advance(kvcache.ensure_pages(kv2, active), active)
    np.testing.assert_array_equal(np.asarray(kv1.page_table),
                                  np.asarray(kv2.page_table))
    np.testing.assert_array_equal(np.asarray(kv1.lengths),
                                  np.asarray(kv2.lengths))
    mask = jnp.asarray([True, False, False, True])
    kv1 = kvcache.release_slots(kv1, mask)
    kv2 = kvcache.release_slots(kv2, mask)
    np.testing.assert_array_equal(np.asarray(kv1.page_table),
                                  np.asarray(kv2.page_table))


def test_kvcache_sharded_two_devices():
    """Under 2 heap shards, each slot block draws page ids from its own
    device's span; release + realloc recycles within the span."""
    from repro.serving import kvcache
    cfg = _kv_cfg()
    B, D = 4, 2
    kv = kvcache.paged_cache_init(cfg, B, 64, page_size=16, mesh=D)
    span = kv.alloc.span
    active = jnp.ones((B,), bool)
    for _ in range(32):
        kv = kvcache.advance(kvcache.ensure_pages(kv, active), active)
    table = np.asarray(kv.page_table)
    used = np.asarray(kv.lengths) // 16      # pages allocated per slot
    for b in range(B):
        dev = b // (B // D)
        pages = table[b, :used[b]]
        assert ((pages >= dev * span) & (pages < (dev + 1) * span)).all(), \
            (b, dev, pages)
    # all in-use pages globally distinct
    live = [int(p) for b in range(B) for p in table[b, :used[b]]]
    assert len(live) == len(set(live))
    kv = kvcache.release_slots(kv, jnp.asarray([True, False, True, False]))
    assert int(kv.lengths[0]) == 0 and int(kv.lengths[1]) == 32


# ---------------------------------------------------------------------------
# Real-mesh subprocess tests: expand threading, device_run(mesh=), ragged
# ---------------------------------------------------------------------------

def test_expand_team_heap_and_queue_over_mesh():
    """Per-team malloc inside an expanded region; team_ptr globals resolve
    through find_obj after the region; sharded ring flush replays (device,
    slot) — and the replay is identical across two runs."""
    out = run_child(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.allocator import GenericAllocator as GA, shard_heap, find_obj
from repro.core.expand import (expand, set_team_heap, set_team_queue,
                               team_heap, team_id, team_ptr, team_queue)
from repro.core.libc import LogRing, drain_log_lines

mesh = make_mesh((2, 2), ("data", "model"))

def region():
    st = team_heap()
    st, p = GA.malloc(st, 8 + team_id())
    set_team_heap(st)
    set_team_queue(team_queue().log(team_id(), p.astype(jnp.float32)))
    return team_ptr(p)[None]

f = expand(region, mesh, in_specs=(), out_specs=P(("data", "model")),
           heap=True, queue=True)

def once():
    heap = shard_heap(GA.init(64, cap=8), 4)
    ring = LogRing.create_sharded(4, 16)
    heap2, ring2, gptrs = jax.jit(f)(heap, ring)
    drain_log_lines()
    ring2.flush()
    return jax.device_get(heap2), np.asarray(gptrs), drain_log_lines()

heap2, gptrs, recs1 = once()
assert sorted(gptrs.tolist()) == [0, 64, 128, 192], gptrs
for d, gp in enumerate(gptrs):
    fo, b, s = find_obj(heap2, int(gp))
    assert int(fo) == 1 and int(b) == int(gp) and int(s) == 8 + d
_, _, recs2 = once()
assert recs1 == recs2 == [(d, 0.0) for d in range(4)], (recs1, recs2)
print("TEAM_HEAP_OK")
""")
    assert "TEAM_HEAP_OK" in out


def test_device_run_mesh_sharded_hook_queue():
    """device_run(mesh=): hooks ride per-device queue shards; every device
    reports its firings; records replay in (device, slot) order; zero host
    contact during the loop (all stats arrive via the ONE flush)."""
    out = run_child(r"""
import jax, jax.numpy as jnp
from repro.core.device_main import HostHook, device_run
from repro.core.expand import team_id
from repro.core.rpc import rpc_stats, reset_rpc_stats

mesh = make_mesh((4,), ("dev",))
recs = []
hook = HostHook(every=3,
                extract=lambda i, s: s[0] + team_id().astype(jnp.float32),
                host_fn=lambda i, v: recs.append((i, v)),
                name="hook.mesh")
reset_rpc_stats()
final = device_run(lambda i, s: s + 1.0, jnp.zeros((2,), jnp.float32), 10,
                   hooks=[hook], mesh=mesh)
assert float(final[0]) == 10.0
expect = [(i, float(i + d)) for d in range(4) for i in (3, 6, 9)]
assert recs == expect, recs
assert rpc_stats("hook.mesh")["calls"] == 12
print("MESH_RUN_OK")
""")
    assert "MESH_RUN_OK" in out


def test_device_run_mesh_hook_array_payload():
    """device_run(mesh=): a hook whose extract returns an ARRAY leaf ships
    it through the per-device payload arenas — zero host contact in the
    loop, one gathered flush, (device, slot)-ordered vectors on the host."""
    out = run_child(r"""
import jax, jax.numpy as jnp
from repro.core.device_main import HostHook, device_run
from repro.core.expand import team_id

mesh = make_mesh((2,), ("dev",))
recs = []
hook = HostHook(every=2,
                extract=lambda i, s: s + team_id().astype(jnp.float32),
                host_fn=lambda i, v: recs.append((i, v.tolist())),
                name="hook.mesh_payload")
final = device_run(lambda i, s: s + 1.0, jnp.zeros((3,), jnp.float32), 4,
                   hooks=[hook], mesh=mesh)
assert float(final[0]) == 4.0
expect = [(i, [float(i + d)] * 3) for d in range(2) for i in (2, 4)]
assert recs == expect, recs
print("MESH_PAYLOAD_OK")
""", devices=2)
    assert "MESH_PAYLOAD_OK" in out


def test_parallel_for_ragged_over_mesh():
    """n not divisible by mesh.size: padded + masked tail, equals serial."""
    out = run_child(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core.expand import parallel_for, serial_for

mesh = make_mesh((2, 2), ("data", "model"))
arr = jnp.arange(64.0)
body = lambda i, a: a[i] * 3.0 + i
for n in (30, 7, 64, 61):
    pf = parallel_for(body, n, arr, mesh=mesh)
    sf = serial_for(body, n, arr)
    assert pf.shape == sf.shape, (n, pf.shape, sf.shape)
    np.testing.assert_allclose(np.asarray(pf), np.asarray(sf))
print("RAGGED_OK")
""")
    assert "RAGGED_OK" in out
