"""RPC transport v2 (paper §3.2): order-preserving marshalling, cached
landing pads, dispatch-time callee resolution, the batched RpcQueue, and the
pure_callback fast path.

``test_arg_order_value_after_ref`` is the regression test for the v1
marshalling bug: value args were grouped before ref args, so any call site
with a value argument AFTER a ``Ref`` handed the host function its arguments
in the wrong positions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.allocator import GenericAllocator as GA
from repro.core.device_main import HostHook, device_run
from repro.core.rpc import (
    READ, READWRITE, REGISTRY, ArenaRef, Ref, RpcQueue, ShardedRpcQueue,
    flush_stats, host_rpc, pad_stats, pad_table, queue_drops,
    reset_rpc_stats, rpc_call, rpc_stats)

I32 = jax.ShapeDtypeStruct((), jnp.int32)
F32 = jax.ShapeDtypeStruct((), jnp.float32)


# ---------------------------------------------------------------------------
# Order-preserving marshalling
# ---------------------------------------------------------------------------

def test_arg_order_value_after_ref():
    """Regression: fn(Ref, value) must reach the host as (array, scalar).

    Under the v1 marshalling the host saw (scalar, array) — the scale landed
    in the buffer slot and vice versa."""
    seen = {}

    @host_rpc(result_shape=F32)
    def scale_buf(buf, scale):
        seen["buf_is_array"] = isinstance(buf, np.ndarray) and buf.ndim == 1
        seen["scale"] = float(scale)
        buf[:] = buf * np.float32(scale)
        return np.float32(scale)

    @jax.jit
    def prog(x):
        r, (buf,) = scale_buf.rpc(Ref(x, access=READWRITE), jnp.float32(3.0))
        return r, buf

    r, buf = prog(jnp.ones(4, jnp.float32))
    assert float(r) == 3.0
    assert seen["buf_is_array"] and seen["scale"] == 3.0
    np.testing.assert_allclose(buf, 3.0)


def test_arg_order_interleaved():
    """val, Ref, val, Ref arrives exactly as written at the call site."""
    seen = {}

    @host_rpc(result_shape=I32)
    def interleaved(a, buf1, b, buf2):
        seen["order"] = (float(a), buf1.shape, float(b), buf2.shape)
        buf1[:] = float(a)
        buf2[:] = float(b)
        return np.int32(0)

    @jax.jit
    def prog(x, y):
        _, (b1, b2) = interleaved.rpc(
            jnp.float32(1.0), Ref(x), jnp.float32(2.0), Ref(y))
        return b1, b2

    b1, b2 = prog(jnp.zeros(3, jnp.float32), jnp.zeros(5, jnp.float32))
    assert seen["order"] == (1.0, (3,), 2.0, (5,))
    np.testing.assert_allclose(b1, 1.0)
    np.testing.assert_allclose(b2, 2.0)


# ---------------------------------------------------------------------------
# ArenaRef: runtime object lookup, in-place expansion
# ---------------------------------------------------------------------------

def test_arena_ref_host_view():
    """malloc -> ArenaRef RPC: host sees correct (ptr, base, size, found)."""
    st = GA.init(64, cap=8)
    st, p1 = GA.malloc(st, 16)
    st, p2 = GA.malloc(st, 8)
    seen = {}

    @host_rpc(result_shape=I32)
    def inspect(ptr, base, size, found, arena):
        seen.update(ptr=int(ptr), base=int(base), size=int(size),
                    found=int(found))
        arena[int(base):int(base) + int(size)] = 9.0
        return np.int32(0)

    @jax.jit
    def prog(state, arena, ptr):
        _, (arena,) = rpc_call(
            "inspect", ArenaRef(arena, ptr, state, access=READWRITE),
            result_shape=I32)
        return arena

    # ptr into the middle of the second object: base/size of the OBJECT ship
    arena = prog(st, jnp.zeros(64, jnp.float32), p2 + 3)
    assert seen == {"ptr": int(p2) + 3, "base": int(p2), "size": 8, "found": 1}
    np.testing.assert_allclose(arena[int(p2):int(p2) + 8], 9.0)
    np.testing.assert_allclose(arena[:int(p2)], 0.0)


def test_arena_ref_not_found_ships_zero():
    """A pointer outside any live object ships found == 0."""
    st = GA.init(64, cap=8)
    st, p = GA.malloc(st, 8)
    st = GA.free(st, p)
    seen = {}

    @host_rpc(result_shape=I32)
    def probe(ptr, base, size, found, arena):
        seen["found"] = int(found)
        return np.int32(0)

    @jax.jit
    def prog(state, arena, ptr):
        r, _ = rpc_call("probe", ArenaRef(arena, ptr, state), result_shape=I32)
        return r

    prog(st, jnp.zeros(64, jnp.float32), jnp.int32(40))
    jax.effects_barrier()
    assert seen["found"] == 0


def test_arena_ref_between_values_keeps_order():
    """value, ArenaRef, value: the ArenaRef expands IN PLACE to
    (ptr, base, size, found, arena) at its call-site position."""
    st = GA.init(32, cap=4)
    st, p = GA.malloc(st, 4)
    seen = {}

    @host_rpc(result_shape=I32)
    def mixed(a, ptr, base, size, found, arena, b):
        seen.update(a=float(a), found=int(found), size=int(size), b=float(b))
        return np.int32(0)

    @jax.jit
    def prog(state, arena, ptr):
        r, _ = rpc_call("mixed", jnp.float32(1.5),
                        ArenaRef(arena, ptr, state, access=READ),
                        jnp.float32(2.5), result_shape=I32)
        return r

    prog(st, jnp.zeros(32, jnp.float32), p)
    jax.effects_barrier()
    assert seen == {"a": 1.5, "found": 1, "size": 4, "b": 2.5}


# ---------------------------------------------------------------------------
# Landing pads: cached wrappers, dispatch-time resolution, per-pad stats
# ---------------------------------------------------------------------------

def test_reregister_host_fn_rebinds_compiled_stub():
    """Re-registering a host function under the same name takes effect for
    already-traced AND already-compiled stubs (v1 captured the callee at
    wrapper-creation time, making re-registration a silent no-op)."""
    REGISTRY.register("rereg.target", lambda x: np.int32(1))

    @jax.jit
    def prog(x):
        r, _ = rpc_call("rereg.target", x, result_shape=I32)
        return r

    assert int(prog(jnp.int32(0))) == 1
    REGISTRY.register("rereg.target", lambda x: np.int32(2))
    assert int(prog(jnp.int32(0))) == 2        # same executable, new callee


def test_pad_cached_wrapper_and_stats():
    reset_rpc_stats()

    @host_rpc(result_shape=I32)
    def padded(a, buf):
        return np.int32(int(a))

    def prog(x):
        r, _ = padded.rpc(jnp.int32(7), Ref(x, access=READ))
        return r

    # two separate traces of the same signature -> ONE pad, one wrapper
    assert int(jax.jit(prog)(jnp.zeros(4, jnp.float32))) == 7
    assert int(jax.jit(prog)(jnp.zeros(4, jnp.float32))) == 7
    assert rpc_stats("padded")["pads"] == 1
    assert rpc_stats("padded")["calls"] == 2

    pads = {pid: key for pid, key in pad_table().items()
            if key[0] == "padded"}
    assert len(pads) == 1
    (pid, key), = pads.items()
    assert key[1][0] == "val" and key[2][0] == "ref"
    assert pad_stats(pid)["calls"] == 2
    assert pad_stats(pid)["bytes_in"] > 0

    # a second signature monomorphizes a second pad
    @jax.jit
    def prog2(x):
        r, _ = padded.rpc(jnp.int32(1), Ref(x, access=READ))
        return r

    prog2(jnp.zeros(8, jnp.float32))
    assert rpc_stats("padded")["pads"] == 2


# ---------------------------------------------------------------------------
# pure_callback fast path
# ---------------------------------------------------------------------------

def test_pure_fast_path():
    @host_rpc(result_shape=I32, pure=True)
    def double(x):
        return np.int32(int(x) * 2)

    @jax.jit
    def prog(v):
        r, _ = double.rpc(v)
        return r + 1

    assert int(prog(jnp.int32(21))) == 43


def test_pure_rejects_writeback_refs():
    @host_rpc(result_shape=I32, pure=True)
    def impure(buf):
        return np.int32(0)

    with pytest.raises(ValueError, match="write/readwrite"):
        jax.jit(lambda x: impure.rpc(Ref(x, access=READWRITE))[0])(
            jnp.zeros(2, jnp.float32))

    # READ refs are fine on the pure path
    r, _ = jax.jit(lambda x: impure.rpc(Ref(x, access=READ)))(
        jnp.zeros(2, jnp.float32))
    assert int(r) == 0


# ---------------------------------------------------------------------------
# Batched transport: RpcQueue
# ---------------------------------------------------------------------------

def test_queue_flush_preserves_order_and_types():
    reset_rpc_stats()
    seen = []
    REGISTRY.register("q.alpha", lambda i, x: seen.append(("a", i, x)))
    REGISTRY.register("q.beta", lambda flag, y: seen.append(("b", flag, y)))

    @jax.jit
    def prog():
        q = RpcQueue.create(capacity=8, width=2)
        q = q.enqueue("q.alpha", jnp.int32(1), jnp.float32(0.5))
        q = q.enqueue("q.beta", jnp.bool_(True), jnp.float32(-2.0))
        q = q.enqueue("q.alpha", jnp.int32(2), jnp.float32(1.5))
        q = q.flush()
        return q.head

    head = prog()
    jax.effects_barrier()
    assert int(head) == 0
    # enqueue order replayed exactly; int lanes come back as python ints,
    # float lanes as floats
    assert seen == [("a", 1, 0.5), ("b", 1, -2.0), ("a", 2, 1.5)]
    assert all(isinstance(rec[1], int) and isinstance(rec[2], float)
               for rec in seen)
    assert rpc_stats("q.alpha")["calls"] == 2
    assert rpc_stats("q.beta")["calls"] == 1


def test_queue_overflow_drops_oldest():
    reset_rpc_stats()
    seen = []
    REGISTRY.register("q.over", lambda i: seen.append(i))

    @jax.jit
    def prog():
        q = RpcQueue.create(capacity=4, width=1)
        for i in range(6):
            q = q.enqueue("q.over", jnp.int32(i))
        q.flush()
        return jnp.int32(0)

    prog()
    jax.effects_barrier()
    assert seen == [2, 3, 4, 5]          # oldest two overwritten
    assert queue_drops() == 2


def test_queue_overflow_surfaced_at_flush():
    """Satellite (ISSUE 3): capacity + k enqueues must REPORT k drops at
    flush — warn + counts in flush_stats — while the surviving records
    replay in exact enqueue order (no corruption); a non-overflowing flush
    then reports last_drops == 0."""
    jax.effects_barrier()
    reset_rpc_stats()
    seen = []
    REGISTRY.register("q.wrap", lambda i: seen.append(i))
    k, cap = 3, 4

    @jax.jit
    def overflowing():
        q = RpcQueue.create(capacity=cap, width=1)
        for i in range(cap + k):
            q = q.enqueue("q.wrap", jnp.int32(i))
        q.flush()
        return jnp.int32(0)

    overflowing()
    jax.effects_barrier()
    assert seen == list(range(k, cap + k))      # order preserved, k lost
    st = flush_stats()
    assert st == {"flushes": 1, "drops": k, "last_drops": k,
                  "arena_drops": 0, "last_arena_drops": 0,
                  "reply_drops": 0, "last_reply_drops": 0,
                  "callee_errors": 0, "last_callee_errors": 0,
                  "retries": 0}

    @jax.jit
    def clean():
        q = RpcQueue.create(capacity=cap, width=1)
        q = q.enqueue("q.wrap", jnp.int32(99))
        q.flush()
        return jnp.int32(0)

    clean()
    jax.effects_barrier()
    st = flush_stats()
    assert st == {"flushes": 2, "drops": k, "last_drops": 0,
                  "arena_drops": 0, "last_arena_drops": 0,
                  "reply_drops": 0, "last_reply_drops": 0,
                  "callee_errors": 0, "last_callee_errors": 0,
                  "retries": 0}


def test_queue_rejects_overwidth_unregistered_and_armless_arrays():
    REGISTRY.register("q.bad", lambda *a: None)
    q = RpcQueue.create(capacity=2, width=1)
    with pytest.raises(ValueError, match="width"):
        q.enqueue("q.bad", jnp.int32(0), jnp.int32(1))
    with pytest.raises(KeyError):
        q.enqueue("q.unregistered", jnp.int32(0))
    # v3: arrays are payloads — but only on a queue WITH an arena
    q0 = RpcQueue.create(capacity=2, width=1, payload_capacity=0)
    with pytest.raises(ValueError, match="payload"):
        q0.enqueue("q.bad", jnp.zeros(3, jnp.float32))
    # a single record that can NEVER fit the arena is a trace-time error
    q1 = RpcQueue.create(capacity=2, width=1, payload_capacity=4)
    with pytest.raises(ValueError, match="arena only holds"):
        q1.enqueue("q.bad", jnp.zeros(5, jnp.float32))


# ---------------------------------------------------------------------------
# Batched HostHooks through device_run
# ---------------------------------------------------------------------------

def test_batched_hook_fires_on_schedule():
    seen = []
    hook = HostHook(every=3, extract=lambda i, s: {"v": s},
                    host_fn=lambda i, v: seen.append((i, v)),
                    name="hook.batched_test", batched=True)
    final = device_run(lambda i, s: s + 1.0, jnp.float32(0.0), 10,
                       hooks=[hook], donate=False)
    jax.effects_barrier()
    assert float(final) == 10.0
    # identical schedule and payloads to the immediate hook, but delivered by
    # ONE flush after the loop, in firing order
    assert seen == [(3, 3.0), (6, 6.0), (9, 9.0)]


def test_queue_conditional_enqueue():
    """enqueue(where=...) commits the record iff the mask is true, without
    touching the rest of the queue."""
    seen = []
    REGISTRY.register("q.cond", lambda i: seen.append(i))

    @jax.jit
    def prog():
        q = RpcQueue.create(4, width=1)
        for i in range(4):
            q = q.enqueue("q.cond", jnp.int32(i), where=jnp.bool_(i % 2 == 1))
        q.flush()
        return q.head

    head = prog()
    jax.effects_barrier()
    assert int(head) == 2
    assert seen == [1, 3]


def test_flush_handlers_captured_per_program():
    """A sink passed to flush is baked into THAT compiled program: two
    programs flushing same-named rings keep their own sinks across
    alternating re-executions (the v1 closure semantics)."""
    from repro.core.libc import LogRing
    a, b = [], []

    @jax.jit
    def fa(r):
        return r.log(1, 1.0).flush(sink=lambda t, v: a.append((t, v)))

    @jax.jit
    def fb(r):
        return r.log(2, 2.0).flush(sink=lambda t, v: b.append((t, v)))

    r = LogRing.create(4)
    fa(r)
    fb(r)
    fa(r)            # re-execution of the cached program: must still use sink a
    jax.effects_barrier()
    assert a == [(1, 1.0), (1, 1.0)]
    assert b == [(2, 2.0)]


def test_named_log_rings_isolate_sinks():
    """Rings created with distinct names deliver to distinct sinks even
    when flushed with different sinks in the same process."""
    from repro.core.libc import LogRing
    a_lines, b_lines = [], []
    ra = LogRing.create(4, name="sink.a").log(1, 1.0)
    rb = LogRing.create(4, name="sink.b").log(2, 2.0)
    ra.flush(sink=lambda t, v: a_lines.append((t, v)))
    rb.flush(sink=lambda t, v: b_lines.append((t, v)))
    jax.effects_barrier()
    assert a_lines == [(1, 1.0)]
    assert b_lines == [(2, 2.0)]


def test_mixed_immediate_and_batched_hooks():
    now, later = [], []
    hooks = [
        HostHook(every=2, extract=lambda i, s: s,
                 host_fn=lambda i, v: now.append(i), name="hook.now"),
        HostHook(every=5, extract=lambda i, s: s,
                 host_fn=lambda i, v: later.append(i), name="hook.later",
                 batched=True),
    ]
    device_run(lambda i, s: s + 1.0, jnp.float32(0.0), 10, hooks=hooks,
               donate=False)
    jax.effects_barrier()
    assert now == [2, 4, 6, 8, 10]
    assert later == [5, 10]


# ---------------------------------------------------------------------------
# Transport v3: payload arena (variable-width records)
# ---------------------------------------------------------------------------

def test_payload_roundtrip_dtypes_and_order():
    """A record mixing scalar lanes and int/float array payloads reaches
    the host with every argument in call-site position, arrays as 1-D
    numpy of the right dtype and exact values."""
    seen = []
    REGISTRY.register(
        "p.mix", lambda i, ints, f, floats: seen.append(
            (i, ints.copy(), f, floats.copy())))

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=4, payload_capacity=64)
        q = q.enqueue("p.mix", jnp.int32(7),
                      jnp.asarray([3, -1, 12], jnp.int32), jnp.float32(2.5),
                      jnp.asarray([0.5, -1.25], jnp.float32))
        q = q.enqueue("p.mix", jnp.int32(8),
                      jnp.asarray([[9, 9]], jnp.int32),   # flattened
                      jnp.float32(0.5), jnp.zeros((3,), jnp.float32))
        q = q.flush()
        return q.head, q.phead

    head, phead = prog()
    jax.effects_barrier()
    assert int(head) == 0 and int(phead) == 0      # flush resets both
    assert len(seen) == 2
    i0, ints0, f0, floats0 = seen[0]
    assert (i0, f0) == (7, 2.5)
    assert ints0.dtype == np.int32 and ints0.tolist() == [3, -1, 12]
    assert floats0.dtype == np.float32 and floats0.tolist() == [0.5, -1.25]
    assert seen[1][1].tolist() == [9, 9]           # 2-D flattens to 1-D
    assert seen[1][3].tolist() == [0.0, 0.0, 0.0]


def test_payload_order_across_mixed_records():
    """Scalar-only and payload-carrying records interleave; replay is exact
    enqueue order (seeded property-style sweep)."""
    import random
    rng = random.Random(7)
    seen = []
    REGISTRY.register("p.scalar", lambda i: seen.append(("s", i)))
    REGISTRY.register("p.arr", lambda i, a: seen.append(("a", i, a.tolist())))

    plan = []
    for i in range(20):
        if rng.random() < 0.5:
            plan.append(("s", i, None))
        else:
            plan.append(("a", i, [rng.randint(-99, 99)
                                  for _ in range(rng.randint(0, 5))]))

    @jax.jit
    def prog():
        q = RpcQueue.create(32, width=2, payload_capacity=128)
        for kind, i, data in plan:
            if kind == "s":
                q = q.enqueue("p.scalar", jnp.int32(i))
            else:
                q = q.enqueue("p.arr", jnp.int32(i),
                              jnp.asarray(data, jnp.int32).reshape(-1))
        q = q.flush()
        return q.head

    prog()
    jax.effects_barrier()
    expect = [("s", i) if kind == "s" else ("a", i, data)
              for kind, i, data in plan]
    assert seen == expect


def test_payload_arena_overflow_drops_atomically():
    """Ring has room, arena does not: the record disappears entirely — not
    replayed, no orphaned words (the NEXT record's payload lands at the
    un-advanced watermark), and the drop is accounted separately."""
    jax.effects_barrier()
    reset_rpc_stats()
    seen = []
    REGISTRY.register("p.over", lambda i, a: seen.append((i, a.tolist())))

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=2, payload_capacity=10)
        q = q.enqueue("p.over", jnp.int32(0),
                      jnp.arange(6, dtype=jnp.int32))          # fits: 6/10
        q = q.enqueue("p.over", jnp.int32(1),
                      jnp.arange(6, dtype=jnp.int32) + 100)    # 12 > 10: DROP
        q = q.enqueue("p.over", jnp.int32(2),
                      jnp.arange(4, dtype=jnp.int32) + 50)     # fits: 10/10
        q = q.flush()
        return q.head

    with pytest.warns(RuntimeWarning, match="payload"):
        prog()
        jax.effects_barrier()
    assert seen == [(0, [0, 1, 2, 3, 4, 5]), (2, [50, 51, 52, 53])]
    st = flush_stats()
    assert st["arena_drops"] == 1 and st["last_arena_drops"] == 1
    assert st["drops"] == 0                      # ring never overflowed


def test_payload_conditional_enqueue_reserves_nothing():
    """where=False with a payload must not advance the arena watermark or
    write words — the next record's payload starts where the skipped one
    would have."""
    seen = []
    REGISTRY.register("p.cond", lambda i, a: seen.append((i, a.tolist())))

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=2, payload_capacity=4)
        q = q.enqueue("p.cond", jnp.int32(0),
                      jnp.asarray([1, 2], jnp.int32), where=jnp.bool_(False))
        # only fits if the skipped record reserved nothing (4-word arena)
        q = q.enqueue("p.cond", jnp.int32(1),
                      jnp.asarray([7, 8, 9, 10], jnp.int32))
        q = q.flush()
        return q.head

    head = prog()
    jax.effects_barrier()
    assert int(head) == 0
    assert seen == [(1, [7, 8, 9, 10])]
    assert flush_stats()["last_arena_drops"] == 0


def test_rpc_call_batched_path():
    """rpc_call(batched=True, queue=...) is the fire-and-forget array-arg
    fast path: enqueue returns the updated queue; Refs are rejected; the
    host sees the call at flush."""
    seen = []
    REGISTRY.register("p.batched", lambda i, a: seen.append((i, a.tolist())))

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=2, payload_capacity=32)
        q = rpc_call("p.batched", jnp.int32(3),
                     jnp.asarray([4.0, 5.0], jnp.float32),
                     batched=True, queue=q)
        q = q.flush()
        return q.head

    prog()
    jax.effects_barrier()
    assert seen == [(3, [4.0, 5.0])]

    q = RpcQueue.create(8, width=2, payload_capacity=32)
    with pytest.raises(ValueError, match="value args"):
        rpc_call("p.batched", jnp.int32(0),
                 Ref(jnp.zeros(2, jnp.float32)), batched=True, queue=q)
    with pytest.raises(ValueError, match="queue"):
        rpc_call("p.batched", jnp.int32(0), batched=True)
    with pytest.raises(TypeError, match="result_shape"):
        rpc_call("p.batched", jnp.int32(0))


def test_remote_malloc_rides_arena():
    """Bulk remote mallocs: the size vector travels as ONE payload record;
    at flush the host runs the prefix-sum bulk allocation against the
    registered host-side heap, in record order."""
    from repro.core.allocator import GenericAllocator as GAlloc
    from repro.core.libc import (remote_heap_register, remote_malloc_enqueue,
                                 remote_malloc_results)
    remote_heap_register("heap.t", GAlloc.init(256, cap=32))

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=3, payload_capacity=32,
                            reply_capacity=16)
        q, t0 = remote_malloc_enqueue(q, "heap.t",
                                      jnp.asarray([8, 16, 8], jnp.int32))
        q, t1 = remote_malloc_enqueue(q, "heap.t",
                                      jnp.asarray([4], jnp.int32))
        q = q.flush()
        return q.head, q.result(t0, (3,), jnp.int32), \
            q.result(t1, (1,), jnp.int32)

    _, r0, r1 = prog()
    jax.effects_barrier()
    state, ptr_batches = remote_malloc_results("heap.t")
    assert [p.tolist() for p in ptr_batches] == [[0, 8, 24], [32]]
    assert int(state.watermark) == 36
    # v4: the same pointers came back through the reply arena
    assert np.asarray(r0).tolist() == [0, 8, 24]
    assert np.asarray(r1).tolist() == [32]

    q = RpcQueue.create(8, width=3, payload_capacity=32)
    with pytest.raises(KeyError, match="remote heap"):
        remote_malloc_enqueue(q, "heap.unknown", jnp.asarray([1], jnp.int32))


def test_fprintf_fwrite_buffered():
    """libc.fprintf/fwrite buffer REAL formatted strings and binary data
    through the queue: zero host contact until ONE flush."""
    from repro.core.libc import drain_fwrite, drain_printf, fprintf, fwrite
    reset_rpc_stats()

    @jax.jit
    def prog():
        q = RpcQueue.create(16, width=4, payload_capacity=64)
        q = fprintf(q, "step %d loss %.2f", jnp.int32(3), jnp.float32(0.125))
        q = fwrite(q, jnp.asarray([10, 20, 30], jnp.int32))
        q = fprintf(q, "hist %s", jnp.asarray([1, 2, 3], jnp.int32))
        q = fwrite(q, jnp.asarray([40], jnp.int32))
        q = fwrite(q, jnp.asarray([0.5, 1.5], jnp.float32), stream=7)
        q = q.flush()
        return q.head

    prog()
    jax.effects_barrier()
    assert flush_stats()["flushes"] == 1          # ONE host contact
    assert drain_printf() == ["step 3 loss 0.12", "hist [1 2 3]"]
    assert drain_fwrite().tolist() == [10, 20, 30, 40]   # stream 0, in order
    assert drain_fwrite(7).tolist() == [0.5, 1.5]
    assert drain_fwrite(99).tolist() == []        # untouched stream is empty


def test_logring_payload_records():
    """LogRing.log(tag, value, payload=...) attaches an array that reaches
    the sink as a third argument; scalar records keep the 2-arg shape."""
    from repro.core.libc import LogRing, drain_log_lines
    drain_log_lines()

    @jax.jit
    def prog():
        r = LogRing.create(8, payload_capacity=16)
        r = r.log(1, 0.5)
        r = r.log(2, 1.5, payload=jnp.asarray([9.0, 8.0], jnp.float32))
        r = r.flush()
        return r.head

    prog()
    jax.effects_barrier()
    lines = drain_log_lines()
    assert lines[0] == (1, 0.5)
    tag, val, arr = lines[1]
    assert (tag, val) == (2, 1.5) and arr.tolist() == [9.0, 8.0]


# ---------------------------------------------------------------------------
# Transport v4: reply arena (device-visible results)
# ---------------------------------------------------------------------------

def test_reply_roundtrip_dtypes_and_validity():
    """Ticketed records read back int and float replies bit-exactly; a
    dropped (where=False) ticket and a no-reply slot read zeros with
    ok=False; stale tickets die at the next flush."""
    REGISTRY.register("r.int", lambda k: np.arange(int(k), dtype=np.int32))
    REGISTRY.register("r.flt", lambda x: np.float32(x) * 0.5)

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=2, reply_capacity=16)
        q, t0 = q.enqueue_ticketed(
            "r.int", jnp.int32(3),
            returns=jax.ShapeDtypeStruct((3,), jnp.int32))
        q, t1 = q.enqueue_ticketed(
            "r.flt", jnp.float32(7.0),
            returns=jax.ShapeDtypeStruct((), jnp.float32))
        q, t2 = q.enqueue_ticketed(
            "r.flt", jnp.float32(1.0),
            returns=jax.ShapeDtypeStruct((), jnp.float32),
            where=jnp.bool_(False))
        q = q.flush()
        v0, ok0 = q.result_ok(t0, (3,), jnp.int32)
        v1, ok1 = q.result_ok(t1, (), jnp.float32)
        v2, ok2 = q.result_ok(t2, (), jnp.float32)
        # a second flush starts a new epoch: t0 goes stale
        q = q.flush()
        v0b, ok0b = q.result_ok(t0, (3,), jnp.int32)
        return v0, ok0, v1, ok1, v2, ok2, v0b, ok0b

    v0, ok0, v1, ok1, v2, ok2, v0b, ok0b = prog()
    jax.effects_barrier()
    assert np.asarray(v0).tolist() == [0, 1, 2] and bool(ok0)
    assert float(v1) == 3.5 and bool(ok1)
    assert float(v2) == 0.0 and not bool(ok2)      # conditional: no record
    assert np.asarray(v0b).tolist() == [0, 0, 0] and not bool(ok0b)


def test_reply_arena_overflow_drops_whole_reply():
    """Replies pack in replay order; a record whose reply does not fit is
    dropped ATOMICALLY at drain — its callee never runs (effectful callees
    must not consume input for a result that cannot be delivered), the
    reader sees zeros + ok False — later smaller replies still land, and
    the drop is surfaced via flush_stats."""
    jax.effects_barrier()
    reset_rpc_stats()
    ran = []
    REGISTRY.register(
        "r.fill",
        lambda k: (ran.append(int(k)), np.full(int(k), int(k), np.int32))[1])

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=2, reply_capacity=6)
        q, t0 = q.enqueue_ticketed(
            "r.fill", jnp.int32(4),
            returns=jax.ShapeDtypeStruct((4,), jnp.int32))    # 4/6
        q, t1 = q.enqueue_ticketed(
            "r.fill", jnp.int32(3),
            returns=jax.ShapeDtypeStruct((3,), jnp.int32))    # 7 > 6: drop
        q, t2 = q.enqueue_ticketed(
            "r.fill", jnp.int32(2),
            returns=jax.ShapeDtypeStruct((2,), jnp.int32))    # 6/6: lands
        q = q.flush()
        return (q.result(t0, (4,), jnp.int32),
                q.result_ok(t1, (3,), jnp.int32)[1],
                q.result(t2, (2,), jnp.int32))

    with pytest.warns(RuntimeWarning, match="reply"):
        r0, ok1, r2 = prog()
        jax.effects_barrier()
    assert np.asarray(r0).tolist() == [4, 4, 4, 4]
    assert not bool(ok1)
    assert np.asarray(r2).tolist() == [2, 2]
    assert ran == [4, 2]                 # the dropped record NEVER ran
    st = flush_stats()
    assert st["reply_drops"] == 1 and st["last_reply_drops"] == 1
    assert st["drops"] == 0 and st["arena_drops"] == 0


def test_reply_rejected_without_reply_arena():
    REGISTRY.register("r.none", lambda: np.int32(0))
    q = RpcQueue.create(4, width=1)                # reply_capacity=0
    with pytest.raises(ValueError, match="reply arena"):
        q.enqueue_ticketed("r.none",
                           returns=jax.ShapeDtypeStruct((), jnp.int32))
    with pytest.raises(ValueError, match="result"):
        q.result(jnp.int32(0))
    q1 = RpcQueue.create(4, width=1, reply_capacity=2)
    with pytest.raises(ValueError, match="reply words"):
        q1.enqueue_ticketed("r.none",
                            returns=jax.ShapeDtypeStruct((3,), jnp.int32))
    with pytest.raises(ValueError, match="returns"):
        rpc_call("r.none", result_shape=I32,
                 returns=jax.ShapeDtypeStruct((), jnp.int32))


def test_remote_malloc_roundtrip_find_obj_arena_ref():
    """ISSUE 5 acceptance (single device): a pointer produced by
    remote_malloc_enqueue, read back ON DEVICE through the reply arena, is
    accepted by find_obj and usable as an ArenaRef in a subsequent RPC."""
    from repro.core.allocator import GenericAllocator as GAlloc, find_obj
    from repro.core.libc import remote_heap_register, remote_malloc_results
    from repro.core.libc import remote_malloc_enqueue
    remote_heap_register("heap.rt", GAlloc.init(128, cap=16))

    @jax.jit
    def acquire():
        q = RpcQueue.create(8, width=3, payload_capacity=16, reply_capacity=8)
        q, t = remote_malloc_enqueue(q, "heap.rt",
                                     jnp.asarray([24, 8], jnp.int32))
        q = q.flush()
        return q.result(t, (2,), jnp.int32)

    ptrs = acquire()
    jax.effects_barrier()
    assert np.asarray(ptrs).tolist() == [0, 24]
    state, _ = remote_malloc_results("heap.rt")

    # the reply pointer resolves through the tracking table on device
    f, b, s = jax.jit(lambda st, p: find_obj(st, p))(state, ptrs[0] + 5)
    assert (int(f), int(b), int(s)) == (1, 0, 24)

    # ...and marshals as an ArenaRef in a subsequent RPC
    seen = {}
    REGISTRY.register(
        "rt.probe",
        lambda ptr, base, size, found, arena: seen.update(
            ptr=int(ptr), base=int(base), size=int(size), found=int(found))
        or np.int32(0))

    @jax.jit
    def probe(state, arena, ptr):
        r, _ = rpc_call("rt.probe", ArenaRef(arena, ptr, state, access=READ),
                        result_shape=I32)
        return r

    probe(state, jnp.zeros(128, jnp.float32), ptrs[1] + 3)
    jax.effects_barrier()
    assert seen == {"ptr": 27, "base": 24, "size": 8, "found": 1}


def test_fread_fgets_input_through_reply_arena():
    """libc input path: fgets stops AFTER the first newline (zero-pad
    doubles as the NUL), fread pops exact element counts with zero-padded
    short reads, float streams round-trip bitcast, and the parsed codes
    feed atoi directly."""
    from repro.core.libc import atoi, fgets, fread, fread_feed
    fread_feed(61, "42 x\nrest", reset=True)
    fread_feed(62, np.asarray([1.5, -2.5, 3.0], np.float32), reset=True)

    @jax.jit
    def prog():
        q = RpcQueue.create(16, width=2, reply_capacity=64)
        q, t_line = fgets(q, 8, stream=61)          # "42 x\n" + 0-pad
        q, t_rest = fgets(q, 8, stream=61)          # "rest" (no newline)
        q, t_f = fread(q, 2, stream=62, dtype=jnp.float32)
        q, t_short = fread(q, 4, stream=62, dtype=jnp.float32)  # 1 left
        q, t_empty = fgets(q, 4, stream=61)         # exhausted: zeros
        q = q.flush()
        return (q.result(t_line, (8,), jnp.int32),
                q.result(t_rest, (8,), jnp.int32),
                q.result(t_f, (2,), jnp.float32),
                q.result(t_short, (4,), jnp.float32),
                q.result(t_empty, (4,), jnp.int32),
                atoi(q.result(t_line, (8,), jnp.int32).astype(jnp.uint8)))

    line, rest, fl, short, empty, parsed = prog()
    jax.effects_barrier()
    assert bytes(np.asarray(line, np.uint8)) == b"42 x\n\0\0\0"
    assert bytes(np.asarray(rest, np.uint8)) == b"rest\0\0\0\0"
    assert np.asarray(fl).tolist() == [1.5, -2.5]
    assert np.asarray(short).tolist() == [3.0, 0.0, 0.0, 0.0]  # short read
    assert np.asarray(empty).tolist() == [0, 0, 0, 0]
    assert int(parsed) == 42

    # per-stream dtype rule mirrors fwrite's
    with pytest.raises(ValueError, match="one stream per dtype"):
        fread_feed(62, np.asarray([1, 2], np.int32))


def test_device_run_thread_queue_midloop_flush():
    """Non-mesh thread_queue contract: the step flushes MID-LOOP and
    consumes the reply on the SAME step, threading the queue through the
    while_loop carry; return_queue hands back the last flushed queue."""
    REGISTRY.register("dr.twice", lambda x: np.int32(x) * 2)

    def step(i, s, q):
        q, t = q.enqueue_ticketed(
            "dr.twice", s.astype(jnp.int32),
            returns=jax.ShapeDtypeStruct((), jnp.int32))
        q = q.flush()
        return q.result(t).astype(jnp.float32) + 1.0, q

    final, q = device_run(step, jnp.float32(1.0), 4, thread_queue=True,
                          return_queue=True, queue_reply=8, donate=False)
    jax.effects_barrier()
    assert float(final) == 31.0            # 1 -> 3 -> 7 -> 15 -> 31
    assert q.reply_capacity == 8 and int(q.head) == 0


# ---------------------------------------------------------------------------
# Cross-transport conformance: immediate == batched == sharded
# ---------------------------------------------------------------------------

def _issue_fprintf(transport):
    from repro.core import libc
    fmt = "conf %d %.1f"
    fid = libc._intern_fmt(fmt)
    calls = [(3, 1.5), (4, -0.5)]
    if transport == "immediate":
        @jax.jit
        def prog():
            for a, b in calls:
                rpc_call("libc.fprintf", jnp.int32(fid), jnp.int32(a),
                         jnp.float32(b), result_shape=())
            return jnp.int32(0)
        prog()
    elif transport == "batched":
        @jax.jit
        def prog():
            q = RpcQueue.create(8, width=4, payload_capacity=16)
            for a, b in calls:
                q = libc.fprintf(q, fmt, jnp.int32(a), jnp.float32(b))
            return q.flush().head
        prog()
    else:
        q = ShardedRpcQueue.create(2, 8, width=4, payload_capacity=16)
        locals_ = [q.local(d) for d in range(2)]
        for d, (a, b) in enumerate(calls):          # one call per device
            locals_[d] = libc.fprintf(locals_[d], fmt, jnp.int32(a),
                                      jnp.float32(b))
        ShardedRpcQueue(jax.tree.map(
            lambda *xs: jnp.stack(xs), *locals_)).flush()
    jax.effects_barrier()
    return libc.drain_printf(), None


def _issue_fwrite(transport):
    from repro.core import libc
    stream = {"immediate": 31, "batched": 32, "sharded": 33}[transport]
    chunks = [[10, 20, 30], [40]]
    if transport == "immediate":
        @jax.jit
        def prog():
            for c in chunks:
                rpc_call("libc.fwrite", jnp.int32(stream),
                         jnp.asarray(c, jnp.int32), result_shape=())
            return jnp.int32(0)
        prog()
    elif transport == "batched":
        @jax.jit
        def prog():
            q = RpcQueue.create(8, width=2, payload_capacity=16)
            for c in chunks:
                q = libc.fwrite(q, jnp.asarray(c, jnp.int32), stream=stream)
            return q.flush().head
        prog()
    else:
        q = ShardedRpcQueue.create(2, 8, width=2, payload_capacity=16)
        locals_ = [q.local(d) for d in range(2)]
        for d, c in enumerate(chunks):
            locals_[d] = libc.fwrite(locals_[d], jnp.asarray(c, jnp.int32),
                                     stream=stream)
        ShardedRpcQueue(jax.tree.map(
            lambda *xs: jnp.stack(xs), *locals_)).flush()
    jax.effects_barrier()
    return libc.drain_fwrite(stream).tolist(), None


def _issue_remote_malloc(transport):
    from repro.core.allocator import GenericAllocator as GAlloc
    from repro.core import libc
    name = f"heap.conf.{transport}"
    libc.remote_heap_register(name, GAlloc.init(256, cap=16))
    batches = [[8, 16], [4]]
    nid = libc._intern_fmt(name)
    if transport == "immediate":
        @jax.jit
        def prog():
            outs = []
            for sizes in batches:
                r, _ = rpc_call(
                    "libc.remote_malloc", jnp.int32(nid), jnp.int32(0),
                    jnp.asarray(sizes, jnp.int32),
                    result_shape=jax.ShapeDtypeStruct((len(sizes),),
                                                      jnp.int32))
                outs.append(r)
            return outs
        device_ptrs = [np.asarray(o).tolist() for o in prog()]
    elif transport == "batched":
        @jax.jit
        def prog():
            q = RpcQueue.create(8, width=3, payload_capacity=16,
                                reply_capacity=8)
            tks = []
            for sizes in batches:
                q, t = libc.remote_malloc_enqueue(
                    q, name, jnp.asarray(sizes, jnp.int32))
                tks.append((t, len(sizes)))
            q = q.flush()
            return [q.result(t, (k,), jnp.int32) for t, k in tks]
        device_ptrs = [np.asarray(o).tolist() for o in prog()]
    else:
        q = ShardedRpcQueue.create(2, 8, width=3, payload_capacity=16,
                                   reply_capacity=8)
        locals_ = [q.local(d) for d in range(2)]
        tks = []
        for d, sizes in enumerate(batches):
            locals_[d], t = libc.remote_malloc_enqueue(
                locals_[d], name, jnp.asarray(sizes, jnp.int32))
            tks.append((d, t, len(sizes)))
        sq = ShardedRpcQueue(jax.tree.map(
            lambda *xs: jnp.stack(xs), *locals_)).flush()
        device_ptrs = [np.asarray(sq.result(d, t, (k,), jnp.int32)).tolist()
                       for d, t, k in tks]
    jax.effects_barrier()
    state, host_ptrs = libc.remote_malloc_results(name)
    effect = ([p.tolist() for p in host_ptrs], int(state.watermark))
    return effect, device_ptrs


_ISSUERS = {"fprintf": _issue_fprintf, "fwrite": _issue_fwrite,
            "remote_malloc": _issue_remote_malloc}


@pytest.mark.parametrize("call", sorted(_ISSUERS))
def test_cross_transport_conformance(call):
    """ISSUE 5 satellite: the same libc call issued via immediate ordered
    RPC, batched queue, and sharded queue produces identical host-visible
    effects AND identical device-visible results — one sweep, not three
    test copies.  (Replay order makes this meaningful: batched replays in
    enqueue order, sharded in (device, slot) order; the call sequences are
    laid out so all three coincide.)"""
    effects, results = {}, {}
    for transport in ("immediate", "batched", "sharded"):
        effects[transport], results[transport] = _ISSUERS[call](transport)
    assert effects["batched"] == effects["immediate"], call
    assert effects["sharded"] == effects["immediate"], call
    assert results["batched"] == results["immediate"], call
    assert results["sharded"] == results["immediate"], call


def test_batched_hook_array_payload():
    """device_run batched hooks ship array extract leaves host-free: the
    whole run is ONE flush, each firing delivering its vector."""
    seen = []
    hook = HostHook(every=2,
                    extract=lambda i, s: {"v": s, "hist": s + jnp.arange(
                        3, dtype=jnp.float32)},
                    host_fn=lambda i, hist, v: seen.append(
                        (i, hist.tolist(), v)),
                    name="hook.payload_test", batched=True)
    final = device_run(lambda i, s: s + 1.0, jnp.float32(0.0), 6,
                       hooks=[hook], donate=False)
    jax.effects_barrier()
    assert float(final) == 6.0
    assert seen == [(2, [2.0, 3.0, 4.0], 2.0),
                    (4, [4.0, 5.0, 6.0], 4.0),
                    (6, [6.0, 7.0, 8.0], 6.0)]


# ---------------------------------------------------------------------------
# Durable identity: content-hashed ids, manifest round trip, cold start
# ---------------------------------------------------------------------------

_XP_PROGRAM = r"""
import json, os, sys
import jax, jax.numpy as jnp
import numpy as np
from repro.core import libc
from repro.core import rpc as rpc_mod
from repro.core.allocator import GenericAllocator as GAlloc
from repro.core.rpc import REGISTRY, RpcQueue, RpcManifest

outdir, mode = sys.argv[1], sys.argv[2]
HEAP, FMT = "heap.xproc", "xp %d %.1f"
libc.remote_heap_register(HEAP, GAlloc.init(256, cap=16))

if mode == "adopt":
    # fresh process: bind ids from the manifest BEFORE issuing anything
    rpc_mod.adopt_manifest(
        RpcManifest.load(os.path.join(outdir, "manifest.json")))

fid = libc._intern_fmt(FMT)       # content-hashed: same id either way
nid = libc._intern_fmt(HEAP)

@jax.jit
def prog():
    q = RpcQueue.create(8, width=4, payload_capacity=32, reply_capacity=8)
    q = libc.fprintf(q, FMT, jnp.int32(3), jnp.float32(1.5))
    q, t = libc.remote_malloc_enqueue(q, HEAP,
                                      jnp.asarray([8, 16], jnp.int32))
    q = libc.fprintf(q, FMT, jnp.int32(4), jnp.float32(-0.5))
    q = q.flush()
    return q.result(t, (2,), jnp.int32)

ptrs = np.asarray(prog()).tolist()
jax.effects_barrier()
state, host_ptrs = libc.remote_malloc_results(HEAP)
out = {"printf": libc.drain_printf(),
       "host_ptrs": [p.tolist() for p in host_ptrs],
       "watermark": int(state.watermark),
       "device_ptrs": ptrs}
with open(os.path.join(outdir, f"{mode}.json"), "w") as f:
    json.dump(out, f)
if mode == "export":
    rpc_mod.export_manifest().save(os.path.join(outdir, "manifest.json"))
print("OK", mode)
"""


def _run_xproc(tmp_path, mode: str) -> dict:
    import json as _json
    import os as _os
    import subprocess
    import sys as _sys
    env = dict(_os.environ)
    env["PYTHONPATH"] = _os.path.join(_os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [_sys.executable, "-c", _XP_PROGRAM, str(tmp_path), mode],
        capture_output=True, text=True, timeout=560, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    with open(tmp_path / f"{mode}.json") as f:
        return _json.load(f)


def test_cross_process_conformance(tmp_path):
    """The export-in-A / adopt-in-B leg of the conformance sweep: process A
    runs a batched program (fprintf + ticketed remote_malloc) and exports
    its RpcManifest; process B — a FRESH interpreter — adopts the manifest
    and issues the same program.  Host-visible effects (printf lines, heap
    pointers, watermark) and device-visible results (reply-arena pointers)
    must be bit-identical: durable identity means the transport binds the
    same ids in any process."""
    a = _run_xproc(tmp_path, "export")
    b = _run_xproc(tmp_path, "adopt")
    assert a == b


def test_manifest_round_trips_ids():
    """export -> JSON -> from_json -> adopt re-derives identical ids (the
    content-hash property, in one process)."""
    from repro.core import rpc as rpc_mod
    from repro.core.rpc import RpcManifest
    name, sig = "conf.roundtrip", (("val", (), "int32"),)
    REGISTRY.register(name, lambda x: np.int32(x))
    pid, _ = REGISTRY.landing_pad(name, sig)
    m = RpcManifest.from_json(rpc_mod.export_manifest().to_json())
    assert m.pads[pid]["callee"] == name
    rpc_mod.adopt_manifest(m)              # re-adoption in-place is a no-op
    assert REGISTRY.landing_pad(name, sig)[0] == pid


def test_adopt_manifest_rejects_mismatched_signature():
    """Acceptance gate: a manifest whose recorded signature no longer
    hashes to its pad id is rejected with an error NAMING the pad."""
    import json as _json
    from repro.core import rpc as rpc_mod
    from repro.core.rpc import RpcManifest
    name, sig = "conf.mismatch", (("val", (), "int32"),)
    REGISTRY.register(name, lambda x: np.int32(x))
    REGISTRY.landing_pad(name, sig)
    doc = _json.loads(rpc_mod.export_manifest().to_json())
    for entry in doc["pads"].values():
        if entry["callee"] == name:
            entry["signature"][0][2] = "float32"    # tamper the dtype
    tampered = RpcManifest.from_json(_json.dumps(doc))
    with pytest.raises(ValueError, match=name):
        rpc_mod.adopt_manifest(tampered)


# ---------------------------------------------------------------------------
# Transport v6: async double-buffered epoch queues
# ---------------------------------------------------------------------------

def test_async_flush_pipelines_epochs():
    """An async flush SUBMITS its epoch — the ticket reads PENDING — and
    the NEXT flush collects the replies."""
    from repro.core import rpc as rpc_mod
    REGISTRY.register("as.echo", lambda x: np.int32(x) + 1)

    q = RpcQueue.create(8, width=2, reply_capacity=8, mode="async")
    q, t = q.enqueue_ticketed("as.echo", jnp.int32(41), returns=I32)
    q = q.flush()                                  # submit only
    assert int(q.result_status(t)) == rpc_mod.STATUS_PENDING
    assert q.statuses_host([t]) == [rpc_mod.STATUS_PENDING]
    q = q.flush()                                  # collect the epoch
    assert int(q.result_status(t)) == rpc_mod.STATUS_OK
    assert int(q.result(t)) == 42
    (val, ok), = q.results_host([t])
    assert int(val) == 42 and ok
    assert q.join()


def test_async_flush_inside_jitted_loop():
    """The async flush lowers inside jit + fori_loop: every in-loop flush
    submits an epoch, the boundary collect publishes the LAST epoch."""
    from jax import lax
    REGISTRY.register("as.loop", lambda x: np.int32(x) + 100)

    @jax.jit
    def prog():
        q = RpcQueue.create(8, width=2, reply_capacity=8, mode="async")

        def body(i, carry):
            q, _t = carry
            q, t = q.enqueue_ticketed("as.loop", i, returns=I32)
            return (q.flush(), t)

        q0, t0 = q.enqueue_ticketed("as.loop", jnp.int32(0), returns=I32)
        q, t = lax.fori_loop(1, 4, body, (q0.flush(), t0))
        return q, t

    q, t = prog()
    q = q.flush()                      # collect the final in-loop epoch
    assert int(q.result(t)) == 103
    assert q.join()


def test_async_carry_redrives_across_epochs():
    """A failing idempotent record is carried under ``carry_budget``:
    PENDING while retrying, redriven once per subsequent epoch drain, and
    FINALIZED into the outcome table the host readers fold in."""
    from repro.core import rpc as rpc_mod
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return np.int32(x)

    REGISTRY.register("as.flaky", flaky, idempotent=True)
    q = RpcQueue.create(8, width=2, reply_capacity=8, mode="async",
                        carry_budget=3)
    q, t = q.enqueue_ticketed("as.flaky", jnp.int32(7), returns=I32)
    q = q.flush()                      # submit: attempt 1 fails -> carried
    q = q.flush()                      # collect: PENDING; redrive 2 fails
    assert q.statuses_host([t]) == [rpc_mod.STATUS_PENDING]
    # satellite: carried/retrying records fold into pressure(), so the
    # engine's spill ceiling sees a degrading host
    assert float(q.pressure()) > 0.0
    q = q.flush()                      # redrive 3 succeeds -> outcome
    assert q.join()
    assert calls["n"] == 3
    assert q.carry_outcomes()[int(t)][0] == rpc_mod.STATUS_OK
    assert q.statuses_host([t]) == [rpc_mod.STATUS_OK]
    (val, ok), = q.results_host([t])
    assert int(val) == 7 and ok


def test_async_carry_budget_exhaustion_finalizes_failure():
    """A record that fails every redrive finalizes with the FAILING
    status once the budget is spent — never stuck PENDING forever."""
    from repro.core import rpc as rpc_mod

    def always(x):
        raise RuntimeError("permanent")

    REGISTRY.register("as.perma", always, idempotent=True)
    q = RpcQueue.create(8, width=2, reply_capacity=8, mode="async",
                        carry_budget=2)
    q, t = q.enqueue_ticketed("as.perma", jnp.int32(1), returns=I32)
    q = q.flush()                      # submit: attempt 1 fails
    q = q.flush()                      # collect + redrive 1 (fails)
    q = q.flush()                      # redrive 2 (fails: budget spent)
    assert q.join()
    assert q.carry_outcomes()[int(t)][0] == rpc_mod.STATUS_CALLEE_RAISED
    assert q.statuses_host([t]) == [rpc_mod.STATUS_CALLEE_RAISED]


def test_async_create_validations():
    with pytest.raises(ValueError, match="mode"):
        RpcQueue.create(8, width=2, mode="turbo")
    with pytest.raises(ValueError, match="carry_budget requires mode"):
        RpcQueue.create(8, width=2, reply_capacity=8, carry_budget=2)
    with pytest.raises(ValueError, match="carry_budget requires reply"):
        RpcQueue.create(8, width=2, mode="async", carry_budget=2)
    with pytest.raises(ValueError, match="shard_deadline requires reply"):
        RpcQueue.create(8, width=2, shard_deadline=0.1)


def test_async_dispatch_detected_at_create():
    """Satellite bugfix: the hazardous jax_cpu_enable_async_dispatch
    config is detected where the queue is BORN — one pointed warning per
    process instead of every harness remembering the pin."""
    import warnings as _warnings
    from repro.core import rpc as rpc_mod
    saved = list(rpc_mod._ASYNC_DISPATCH_WARNED)
    rpc_mod._ASYNC_DISPATCH_WARNED.clear()
    jax.config.update("jax_cpu_enable_async_dispatch", True)
    try:
        with pytest.warns(RuntimeWarning,
                          match="jax_cpu_enable_async_dispatch"):
            RpcQueue.create(4, width=1)
        with _warnings.catch_warnings():           # latched: warned once
            _warnings.simplefilter("error")
            RpcQueue.create(4, width=1)
    finally:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
        rpc_mod._ASYNC_DISPATCH_WARNED.clear()
        rpc_mod._ASYNC_DISPATCH_WARNED.extend(saved)


def test_sharded_deadline_partial_epoch():
    """Satellite bugfix: one hung shard no longer stalls its siblings —
    with ``shard_deadline`` the gathered drain runs shards concurrently,
    stamps the stalled shard's records STATUS_TIMEOUT, and completes the
    rest of the epoch (regression: FaultPlan delay pinned to one shard)."""
    from repro.core import rpc as rpc_mod
    from repro.testing.faults import Fault, FaultPlan
    REGISTRY.register("as.sd", lambda x: np.int32(x) * 2)

    q = ShardedRpcQueue.create(2, 8, width=2, reply_capacity=8,
                               shard_deadline=0.25)
    locals_ = [q.local(d) for d in range(2)]
    tks = []
    for d in range(2):
        locals_[d], t = locals_[d].enqueue_ticketed(
            "as.sd", jnp.int32(10 + d), returns=I32)
        tks.append(t)
    sq = ShardedRpcQueue(jax.tree.map(lambda *xs: jnp.stack(xs), *locals_))
    # occurrence 1 in canonical (device, slot) order = device 1's record
    plan = FaultPlan([Fault("delay", "as.sd", call_index=1, delay=2.0)])
    with plan, pytest.warns(RuntimeWarning, match="partial-epoch"):
        sq = sq.flush()
    assert int(sq.result_status(0, tks[0])) == rpc_mod.STATUS_OK
    assert int(sq.result(0, tks[0])) == 20         # sibling completed
    assert int(sq.result_status(1, tks[1])) == rpc_mod.STATUS_TIMEOUT


def test_sharded_async_independent_drains():
    """Sharded async flush: per-device epochs drain on independent slot
    executors (no gather barrier); the collect flush publishes every
    device's replies."""
    from repro.core import rpc as rpc_mod
    REGISTRY.register("as.sh", lambda x: np.int32(x) + 5)

    q = ShardedRpcQueue.create(2, 8, width=2, reply_capacity=8,
                               mode="async")
    locals_ = [q.local(d) for d in range(2)]
    tks = []
    for d in range(2):
        locals_[d], t = locals_[d].enqueue_ticketed(
            "as.sh", jnp.int32(100 * (d + 1)), returns=I32)
        tks.append(t)
    sq = ShardedRpcQueue(jax.tree.map(lambda *xs: jnp.stack(xs), *locals_))
    sq = sq.flush()                                # submit per device
    sq = sq.flush()                                # collect per device
    assert sq.join()
    for d in range(2):
        assert int(sq.result_status(d, tks[d])) == rpc_mod.STATUS_OK
        assert int(sq.result(d, tks[d])) == 100 * (d + 1) + 5


def test_device_run_queue_async_boundary():
    """device_run(queue_async=True) owns the boundary protocol: hooks
    deliver identically to the sync queue, and every host effect has
    retired by the time the call returns (no trailing effects_barrier
    needed)."""
    seen = []
    hook = HostHook(every=2, extract=lambda i, s: s,
                    host_fn=lambda i, v: seen.append((i, v)),
                    name="hook.async_test", batched=True)
    final = device_run(lambda i, s: s + 1.0, jnp.float32(0.0), 6,
                       hooks=[hook], donate=False, queue_async=True)
    assert float(final) == 6.0
    assert seen == [(2, 2.0), (4, 4.0), (6, 6.0)]


def test_adopt_manifest_requires_hosts():
    """A manifest callee with no registered host function is a hard error
    naming the callee (silent no-op binding would drop its records)."""
    from repro.core import rpc as rpc_mod
    from repro.core.rpc import RpcManifest
    name = "conf.unbound_host"
    REGISTRY.register(name, lambda *a: None)
    cid = REGISTRY.batch_callee_id(name)
    m = RpcManifest.from_json(rpc_mod.export_manifest().to_json())
    REGISTRY.unregister(name)
    try:
        with pytest.raises(ValueError, match=name):
            rpc_mod.adopt_manifest(m)
        rpc_mod.adopt_manifest(m, require_hosts=False)   # explicit opt-out
        assert REGISTRY.batch_names[cid] == name
    finally:
        REGISTRY.unregister(name)


# ---------------------------------------------------------------------------
# The drain matrix: every queue kind through one flush and one drain core
# ---------------------------------------------------------------------------

def _flush_kind_cases():
    cases = []
    for shards in (1, 2):
        for reply in (False, True):
            for sanitize in (False, True):
                for mode in ("sync", "async"):
                    cases.append(pytest.param(
                        shards, reply, sanitize, mode, None,
                        id=f"{shards}sh-{'reply' if reply else 'noreply'}"
                           f"-{'san' if sanitize else 'plain'}-{mode}"))
    cases.append(pytest.param(2, True, False, "sync", 5.0,
                              id="2sh-reply-plain-sync-deadline"))
    return cases


def _flush_kind_expected(shards, reply, sanitize, mode):
    """Per-flush ``(flush_stats()['flushes'] delta, new sanitize_stats()
    epoch records as (records, declared_replies, sharded))``, as the
    transport recorded them before its drain variants were merged: a sync
    flush counts once whatever its shard count, an async one once per
    shard epoch; a sync sharded flush publishes ONE aggregated shadow
    record, an async one a record per shard."""
    per_shard = (2, 1 if reply else 0)
    if not sanitize:
        epochs = []
    elif mode == "async":
        epochs = [per_shard + (False,)] * shards
    else:
        epochs = [(per_shard[0] * shards, per_shard[1] * shards,
                   shards > 1)]
    return (shards if mode == "async" else 1), epochs


@pytest.mark.parametrize("shards,reply,sanitize,mode,deadline",
                         _flush_kind_cases())
def test_flush_kinds(shards, reply, sanitize, mode, deadline):
    """One case per queue kind (shard count x replies x sanitizer x mode,
    plus the sync sharded reply queue with a per-shard deadline): a traced
    flush hands ``io_callback`` one stable callable per geometry, a
    reply-less flush ships no ``rwant``/``rc`` operands, every flush
    moves ``flush_stats()``/``sanitize_stats()`` by the recorded amounts,
    and reply tickets resolve OK through the jitted and concrete paths."""
    from repro.core import rpc as rpc_mod
    REGISTRY.register("fk.sink", lambda i, v: None)
    REGISTRY.register("fk.echo", lambda x: np.int32(x) + 1)
    kw = dict(width=2, payload_capacity=64,
              reply_capacity=8 if reply else 0, sanitize=sanitize,
              mode=mode, shard_deadline=deadline)
    if shards == 1:
        q = RpcQueue.create(8, **kw)
    else:
        q = ShardedRpcQueue.create(shards, 8, **kw)

    def fill(q):
        """Two records per shard — one array payload, one ticketed reply
        on reply queues; returns the queue and each shard's ticket."""
        views = [q] if shards == 1 else [q.local(d) for d in range(shards)]
        tickets = []
        for d, v in enumerate(views):
            v = v.enqueue("fk.sink", jnp.int32(d),
                          jnp.arange(3, dtype=jnp.int32) + d)
            if reply:
                v, t = v.enqueue_ticketed("fk.echo", jnp.int32(10 * d),
                                          returns=I32)
            else:
                v, t = v.enqueue("fk.sink", jnp.int32(d),
                                 jnp.zeros((2,), jnp.int32)), None
            views[d] = v
            tickets.append(t)
        if shards == 1:
            return views[0], tickets
        return ShardedRpcQueue(jax.tree.map(lambda *xs: jnp.stack(xs),
                                            *views)), tickets

    def status(q, d, t):
        return int(q.result_status(t) if shards == 1
                   else q.result_status(d, t))

    q, tickets = fill(q)
    # one stable callable per geometry, and the operand tuple of its kind
    eqns = [[e for e in jax.make_jaxpr(lambda q: q.flush())(q).jaxpr.eqns
             if e.primitive.name == "io_callback"] for _ in range(2)]
    assert [len(e) for e in eqns] == [1, 1]
    (e1,), (e2,) = eqns
    assert e1.params["callback"].callback_func is \
        e2.params["callback"].callback_func
    # 8 record lanes + head, phead, adrops, base; replies add rwant and rc
    assert len(e1.invars) == (14 if reply else 12)

    traces = []

    @jax.jit
    def flush(q):
        traces.append(1)
        return q.flush()

    want = _flush_kind_expected(shards, reply, sanitize, mode)
    seen = []
    pending = []                 # tickets whose replies the next flush lands
    for concrete in (False, False, True):
        f0 = flush_stats()["flushes"]
        s0 = len(rpc_mod.sanitize_stats()["epochs"])
        q = q.flush() if concrete else flush(q)
        jax.effects_barrier()
        assert q.join()
        seen.append((flush_stats()["flushes"] - f0,
                     [(e["records"], e["declared_replies"], e["sharded"])
                      for e in rpc_mod.sanitize_stats()["epochs"][s0:]]))
        landed = tickets if mode == "sync" else pending
        if reply:
            for d, t in enumerate(landed):
                assert status(q, d, t) == rpc_mod.STATUS_OK
        pending = tickets
        q, tickets = fill(q)
    assert len(traces) == 1
    assert seen == [want] * 3
