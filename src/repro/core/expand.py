"""Parallelism expansion (paper §3.3): single-team code -> the whole machine.

Under OpenMP offload semantics a ``parallel`` region maps to ONE thread block;
the paper's compiler pass rewrites work-sharing, thread-id queries, and
barriers so the region runs across every team on the GPU, with *continuous*
thread ids.  The TPU analogue of "team" is a mesh device; of "thread within a
team", a vectorized lane.  This module provides:

* the **single-team semantics** primitives legacy-style code is written
  against: :func:`thread_id`, :func:`num_threads`, :func:`barrier`,
  :func:`ws_range` (the ``omp for`` static schedule);

* :func:`expand` — the multi-team rewrite: wraps a single-shard function in
  ``shard_map`` over *all* mesh axes so the same primitives now report global
  coordinates (continuous ids across teams, exactly Fig. 4), work-sharing
  distributes over every device, and ``barrier`` synchronizes the mesh;

* **team-local runtime state** — ``expand(..., heap=True, queue=True)``
  threads a :class:`~repro.core.allocator.ShardedHeap` and/or a
  :class:`~repro.core.rpc.ShardedRpcQueue` (or the ``LogRing`` riding it)
  through the region: inside, :func:`team_heap` / :func:`team_queue` hand
  the region THIS device's shard (mirroring :func:`thread_id`),
  :func:`set_team_heap` / :func:`set_team_queue` store the functionally
  updated state, and :func:`team_ptr` encodes a team-local heap offset as a
  global ``(device, offset)`` pointer that ``find_obj`` — and therefore the
  RPC ``ArenaRef`` marshalling — resolves after the region returns.  Since
  transport v3 the queue shard carries a per-device PAYLOAD ARENA: a team
  can enqueue array-carrying records (``libc.fprintf``/``fwrite`` data,
  histograms, bulk remote-malloc size vectors) as pure local array updates,
  and the one gathered flush replays them with payloads reattached.  Since
  transport v4 it can also carry a per-device REPLY ARENA
  (``reply_capacity > 0``): a team enqueues TICKETED records
  (``enqueue_ticketed(returns=...)``, ``remote_malloc_enqueue(...,
  device=team_id())``) and threads the tickets out of the region with its
  other outputs; after the program-boundary flush, ``q.local(d).result``
  / ``q.result(d, ticket, ...)`` reads device ``d``'s replies — e.g. the
  global ``(device, offset)`` pointers of a remote malloc it requested;

* :func:`parallel_for` / :func:`serial_for` — the measurable contrast the
  paper's Fig. 8–10 are built on: the *expanded* execution of an iteration
  space versus the *single-team* (sequential-outer-loop) execution.
  ``parallel_for`` supports ragged iteration spaces (``n`` not divisible by
  the team count) by padding the index range and masking the tail — the
  body never sees an out-of-range index.

The sequential part of the program stays single-team (one logical thread);
entering an expanded region corresponds to the paper's kernel split — in JAX
the "launch" is simply calling the expanded (shard_map) function, and the
result flowing back is the host-RPC completion of Fig. 4.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


class _Env(threading.local):
    def __init__(self):
        self.axes: Tuple[str, ...] = ()     # mesh axes visible to the region
        self.lanes: int = 1                  # vectorized lanes per device
        self.heap = None                     # this device's allocator shard
        self.queue = None                    # this device's RPC queue shard
        self.span: Optional[int] = None      # global-pointer stride
        self.sanitize: bool = False          # region runs sanitized transport


_ENV = _Env()


@contextlib.contextmanager
def _team_env(axes: Tuple[str, ...], lanes: int, sanitize: bool = False):
    old = (_ENV.axes, _ENV.lanes, _ENV.heap, _ENV.queue, _ENV.span,
           _ENV.sanitize)
    _ENV.axes, _ENV.lanes = axes, lanes
    _ENV.heap = _ENV.queue = _ENV.span = None
    _ENV.sanitize = sanitize
    try:
        yield
    finally:
        (_ENV.axes, _ENV.lanes, _ENV.heap, _ENV.queue, _ENV.span,
         _ENV.sanitize) = old


# ---------------------------------------------------------------------------
# Single-team semantics (the vocabulary legacy-style code is written in)
# ---------------------------------------------------------------------------

def team_id():
    """Continuous team id across the whole mesh (0 when unexpanded)."""
    if not _ENV.axes:
        return jnp.zeros((), jnp.int32)
    tid = jnp.zeros((), jnp.int32)
    for ax in _ENV.axes:
        tid = tid * lax.axis_size(ax) + lax.axis_index(ax)
    return tid


def num_teams() -> int:
    n = 1
    for ax in _ENV.axes:
        n *= lax.axis_size(ax)
    return n


def thread_id(lane=None):
    """Continuous global thread id = team_id * lanes + lane (paper Fig. 4:
    teams are 'bulked together as one large team')."""
    lane = jnp.zeros((), jnp.int32) if lane is None else lane
    return team_id() * _ENV.lanes + lane


def num_threads() -> int:
    return num_teams() * _ENV.lanes


def barrier():
    """Cross-team barrier.  The paper implements this with global atomic
    counters (outside the OpenMP standard); on TPU the idiomatic equivalent is
    a collective, which orders all shards of the expanded region."""
    if _ENV.axes:
        lax.psum(jnp.zeros((), jnp.float32), _ENV.axes)


def ws_range(n: int) -> Tuple[jax.Array, int]:
    """``omp for schedule(static)`` over [0, n): this team's (start, count)."""
    teams = num_teams()
    assert n % teams == 0, f"iteration space {n} must tile {teams} teams"
    per = n // teams
    return team_id() * per, per


# ---------------------------------------------------------------------------
# Team-local runtime state (sharded heap / sharded RPC queue accessors)
# ---------------------------------------------------------------------------

def team_heap():
    """THIS team's allocator shard (a plain per-device allocator state).

    Only available inside a region expanded with ``heap=True``; operate on
    it with the inner allocator's ops (team-local offsets) and store the
    updated state with :func:`set_team_heap` — JAX is functional, so the
    accessor pair is the in-region read/write of the paper's per-team heap.
    """
    if _ENV.heap is None:
        raise RuntimeError(
            "team_heap() outside a heap-carrying expanded region; wrap the "
            "region with expand(..., heap=True) and pass a ShardedHeap")
    return _ENV.heap


def set_team_heap(state) -> None:
    """Store this team's functionally-updated allocator shard."""
    if _ENV.heap is None:
        raise RuntimeError("set_team_heap() outside a heap-carrying region")
    _ENV.heap = state


def team_queue():
    """THIS team's RPC queue shard (a plain ``RpcQueue`` — or the local view
    of whatever sharded transport was threaded, e.g. a ``LogRing``)."""
    if _ENV.queue is None:
        raise RuntimeError(
            "team_queue() outside a queue-carrying expanded region; wrap "
            "the region with expand(..., queue=True) and pass a "
            "ShardedRpcQueue (or sharded LogRing)")
    return _ENV.queue


def set_team_queue(q) -> None:
    """Store this team's functionally-updated queue shard."""
    if _ENV.queue is None:
        raise RuntimeError("set_team_queue() outside a queue-carrying region")
    _ENV.queue = q


def team_ptr(local_ptr):
    """Encode a team-local heap offset as a GLOBAL pointer
    (``team_id() * span + offset``) that survives region exit:
    ``allocator.find_obj`` decodes the (device, offset) pair, so the RPC
    ``ArenaRef`` marshalling resolves it like any other heap pointer.
    FAIL stays FAIL."""
    if _ENV.span is None:
        raise RuntimeError("team_ptr() outside a heap-carrying region")
    local_ptr = jnp.asarray(local_ptr, jnp.int32)
    return jnp.where(local_ptr < 0, jnp.int32(-1),
                     team_id() * _ENV.span + local_ptr)


# ---------------------------------------------------------------------------
# Expansion
# ---------------------------------------------------------------------------

def _with_sanitize(q):
    """The sharded queue object with its transport ``sanitize`` flag set.

    Understands a :class:`~repro.core.rpc.ShardedRpcQueue` (flag lives on
    the inner ``RpcQueue``) or a bare ``RpcQueue``; anything else (e.g. a
    sharded ``LogRing``) is returned unchanged — it has no sanitized path.
    Flip the flag only on queues that have NOT enqueued yet: the sanitized
    payload layout brackets every reservation with canary words, so records
    enqueued before the flip would be checked against canaries they never
    wrote.
    """
    inner = getattr(q, "q", None)
    if inner is not None and hasattr(inner, "sanitize"):
        return dataclasses.replace(
            q, q=dataclasses.replace(inner, sanitize=True))
    if hasattr(q, "sanitize"):
        return dataclasses.replace(q, sanitize=True)
    return q


def _with_fault_policy(q, retry, timeout):
    """The sharded queue object with transport ``retry``/``timeout`` set.

    Same shape as :func:`_with_sanitize`: understands a
    :class:`~repro.core.rpc.ShardedRpcQueue` (policy lives on the inner
    ``RpcQueue``) or a bare ``RpcQueue``; duck-typed carriers (e.g. a
    sharded ``LogRing``) pass through unchanged.  Retry and timeout are
    static queue attributes consulted at drain time, so flipping them on
    an already-enqueued queue is safe (unlike ``sanitize``)."""
    if retry is None and timeout is None:
        return q
    inner = getattr(q, "q", None)
    if inner is not None and hasattr(inner, "retry"):
        return dataclasses.replace(
            q, q=dataclasses.replace(inner, retry=retry, timeout=timeout))
    if hasattr(q, "retry"):
        return dataclasses.replace(q, retry=retry, timeout=timeout)
    return q


def expand(fn: Callable, mesh: Mesh, in_specs, out_specs, *,
           lanes: int = 1, check_vma: bool = False,
           heap: bool = False, queue: bool = False,
           sanitize: bool = False, queue_retry=None,
           queue_timeout: Optional[float] = None,
           queue_async: bool = False) -> Callable:
    """Rewrite single-team ``fn`` for multi-team execution over ``mesh``.

    Inside ``fn`` the single-team primitives report *global* coordinates.
    This is the paper's compiler transformation; here it is a higher-order
    function because JAX programs are traced, not linked.

    ``heap=True`` / ``queue=True`` declare team-local runtime state: the
    wrapped callable then takes the sharded object(s) as leading
    argument(s) — ``wrapped(heap, [queue,] *args)`` — and returns them
    updated ahead of ``fn``'s result: ``(heap', [queue',] out)``.  The
    sharded objects (``ShardedHeap``, ``ShardedRpcQueue``, or anything with
    the same ``local_view``/``with_local`` protocol, e.g. a sharded
    ``LogRing``) are partitioned one shard per device; inside ``fn``,
    :func:`team_heap` / :func:`team_queue` read this device's shard and
    :func:`set_team_heap` / :func:`set_team_queue` write it back.

    ``sanitize=True`` turns on the runtime sanitizer for the region: the
    incoming RPC queue (when ``queue=True``) is switched to the sanitized
    transport — canary words bracket every payload reservation and freed-
    pattern scans run at flush — and misuse shows up in named
    :func:`repro.core.rpc.sanitize_stats` counters.  On hazard-free
    programs the region's outputs and delivered host records are
    bit-identical to ``sanitize=False``; only queue-internal arena layout
    differs.  Pass a queue that has not enqueued yet (see
    :func:`_with_sanitize`).

    ``queue_retry`` / ``queue_timeout`` (with ``queue=True``) set the
    region transport's fault policy: the threaded queue drains with the
    given :class:`~repro.core.rpc.RetryPolicy` and per-callee wall-clock
    timeout (see the transport's status lane).  Retry only redrives
    callees registered ``idempotent=True``.

    ``queue_async=True`` declares the region rides the v6 double-buffered
    transport: the passed queue must have been CREATED with
    ``mode="async"`` (the epoch slot — the host-side drain executor
    lineage — is allocated at create time; it cannot be grafted on per
    call without defeating the jit cache).  This is a validation, not a
    transform: it exists so a region written against epoch-late reply
    semantics fails loudly when handed a synchronous queue rather than
    silently blocking at every flush.
    """
    axes = tuple(mesh.axis_names)
    n_extra = int(heap) + int(queue)

    if not n_extra:
        @functools.wraps(fn)
        def wrapped(*args):
            def body(*shard_args):
                with _team_env(axes, lanes, sanitize):
                    return fn(*shard_args)
            return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs,
                                 check_vma=check_vma)(*args)

        return wrapped

    dev_spec = P(axes)
    full_in = (dev_spec,) * n_extra + tuple(in_specs)
    full_out = (dev_spec,) * n_extra + (out_specs,)

    @functools.wraps(fn)
    def wrapped(*call_args):
        assert len(call_args) >= n_extra, \
            f"expand(heap={heap}, queue={queue}) expects the sharded " \
            f"state as the leading {n_extra} argument(s)"
        if queue and queue_async:
            qi = int(heap)
            inner = getattr(call_args[qi], "q", call_args[qi])
            if getattr(inner, "mode", "sync") != "async":
                raise ValueError(
                    "expand(queue_async=True) was handed a synchronous "
                    "queue: the double-buffered transport's epoch slot is "
                    "allocated at create time, so build the queue with "
                    "RpcQueue.create(..., mode='async') (or "
                    "ShardedRpcQueue.create(..., mode='async')) instead "
                    "of flipping it per call")
        if queue and sanitize:
            qi = int(heap)
            call_args = call_args[:qi] + \
                (_with_sanitize(call_args[qi]),) + call_args[qi + 1:]
        if queue and (queue_retry is not None or queue_timeout is not None):
            qi = int(heap)
            call_args = call_args[:qi] + \
                (_with_fault_policy(call_args[qi], queue_retry,
                                    queue_timeout),) + call_args[qi + 1:]
        if queue:
            # record the region's team-queue geometry for the manifest
            # scheme: export_manifest() ships it so a cold-start process
            # rebuilds compatible shards without re-tracing this region.
            # Lazy import — expand must stay import-free of rpc (rpc
            # imports expand lazily for the mesh guard).
            from repro.core import rpc as _rpc
            try:
                _rpc.REGISTRY.note_queue_geometry(
                    _rpc.queue_geometry(call_args[int(heap)]))
            except (AttributeError, TypeError):
                pass               # duck-typed queue (e.g. sharded LogRing)

        def body(*shard_args):
            extra, rest = shard_args[:n_extra], shard_args[n_extra:]
            with _team_env(axes, lanes, sanitize):
                i = 0
                if heap:
                    _ENV.heap = extra[i].local_view()
                    _ENV.span = getattr(extra[i], "span", None)
                    i += 1
                if queue:
                    _ENV.queue = extra[i].local_view()
                out = fn(*rest)
                outs = []
                i = 0
                if heap:
                    outs.append(extra[i].with_local(_ENV.heap))
                    i += 1
                if queue:
                    outs.append(extra[i].with_local(_ENV.queue))
            return tuple(outs) + (out,)

        return jax.shard_map(body, mesh=mesh, in_specs=full_in,
                             out_specs=full_out,
                             check_vma=check_vma)(*call_args)

    return wrapped


def parallel_for(body: Callable, n: int, *arrays,
                 mesh: Optional[Mesh] = None):
    """Expanded execution of ``for i in range(n): out[i] = body(i, *arrays)``.

    Work is block-distributed over all mesh devices (teams) and vectorized
    within each block (threads) — ``omp distribute parallel for``.  Without a
    mesh it still vectorizes (one team, many threads).  The region runs
    under the ``parallel_for`` named scope (op metadata only).
    """
    with jax.named_scope("parallel_for"):
        return _parallel_for(body, n, arrays, mesh)


def _parallel_for(body, n, arrays, mesh):
    if mesh is None or mesh.size == 1 or n == 0:
        return jax.vmap(lambda i: body(i, *arrays))(jnp.arange(n))

    axes = tuple(mesh.axis_names)
    # ragged n: pad the index range to a full tile and mask the tail — the
    # body never sees an out-of-range i (tail lanes recompute i = n-1 and
    # their results are sliced off below).  NOTE: body must be pure — tail
    # lanes EXECUTE the i = n-1 computation again, so an effectful body
    # would observe up to mesh.size-1 duplicate runs on ragged n.
    per = -(-n // mesh.size)

    def shard_body():
        with _team_env(axes, per):
            start = team_id() * per
            idx = jnp.minimum(start + jnp.arange(per), n - 1)
            return jax.vmap(lambda i: body(i, *arrays))(idx)

    spec = P(axes)
    out = jax.shard_map(shard_body, mesh=mesh, in_specs=(),
                        out_specs=spec, check_vma=False)()
    return out if per * mesh.size == n else out[:n]


def serial_for(body: Callable, n: int, *arrays):
    """Single-team execution of the same loop: a sequential outer loop (the
    original direct-GPU-compilation limitation the paper fixes).  This is the
    baseline column of Fig. 8–10."""
    return lax.map(lambda i: body(i, *arrays), jnp.arange(n))
