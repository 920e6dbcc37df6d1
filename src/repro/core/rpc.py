"""Automatically generated host RPCs (paper §3.2), on JAX host callbacks.

The paper replaces host-only library calls in device code with generated RPC
stubs: arguments are marshalled into an ``RPCInfo`` object, pointer arguments
ship their *underlying object* (with offset/size and read/write/readwrite
access), variadic callees get one non-variadic **landing pad** per distinct
call-site argument-type tuple, and the device thread blocks until the host
acknowledges.

TPU/JAX translation: the transport is a host callback (``io_callback`` for
ordered, effectful calls; ``jax.pure_callback`` for pure ones) instead of
polled managed memory — the protocol (synchronous, stateless client/server,
opaque marshalled buffers) is the paper's.  "Compile time" is trace time: the
first trace of a call site with a new flattened signature *generates* its
landing pad, exactly like the LTO pass monomorphizing a variadic callee.

Immediate RPCs
==============

**Order-preserving marshalling.**  Arguments are flattened in *call-site
order*: each argument contributes its operands in place, so the host callee
receives ``fn(args...)`` exactly as written at the call site for any mix of
value / ``Ref`` / ``ArenaRef`` arguments.

**Landing-pad-keyed dispatch.**  ``REGISTRY.pads`` maps ``(callee, flattened
signature)`` to a pad id; each pad owns ONE cached host wrapper, created at
first trace and reused by every subsequent trace of any call site with that
signature, so ``io_callback`` always sees a stable callable (stable across
re-traces → the jit cache and the callback registry key on the same object).
The wrapper resolves ``REGISTRY.hosts[name]`` at *dispatch* time, so
re-registering a host function under the same name takes effect for
already-traced (and already-compiled) stubs.  Per-pad call/byte counters live
in ``REGISTRY.pad_stats``; per-callee aggregates in ``REGISTRY.stats``.

**Ordered vs pure dispatch.**  ``ordered=True`` (default) issues the RPC via
``io_callback(ordered=True)``: program order among all ordered RPCs is
preserved, and the call is never elided — use for anything effectful
(I/O, logging, checkpointing).  ``ordered=False`` still guarantees execution
but not cross-call ordering.  ``pure=True`` uses ``jax.pure_callback``: the
compiler may elide, cache, or reorder the call, so it is only sound for pure
host functions whose result is actually consumed; write-back refs are
rejected (there is no ordering to make a host-side mutation meaningful).

Batched transport
=================

**Records and the payload arena.**  :class:`RpcQueue` is an on-device ring
of RPC records plus a flat on-device **payload arena**.  Each record is
``(callee id, up to W arguments)``; a *scalar* argument packs into an int32
or float32 lane (``imask`` bit j records which lane argument ``j`` used, so
mixed int/float argument order survives the trip), while an *array*
argument rides the arena: its words are copied into ``pbuf`` at the current
payload watermark and the record stores a **descriptor** in argument
``j``'s lanes — offset in ``ivals[.., j]``, length in ``plens[.., j]``,
presence in ``pmask`` bit j, and dtype tag in ``imask`` bit j (set = int32
words, clear = float32 words bitcast into the i32 arena).  One watermark
bump reserves space for ALL of a record's payloads (per-payload offsets are
static partial sums of the lengths).

``enqueue`` is a pure array update inside jit — no host contact; ``flush``
drains records AND arena to the host in ONE ordered ``io_callback``,
replaying records (payloads reattached from their descriptors) in enqueue
order (generalizing the buffered-``fprintf`` trick that ``core/libc.py``'s
``LogRing`` applies to log records, and the antidote to the paper's Fig. 7
~975 µs per-call RPC cost).  The device has already executed past the
enqueue when the callee runs, so write-back refs are rejected; a callee
returns values to the device through the reply arena below.
:func:`rpc_call` exposes the same path as ``rpc_call(name, *args,
batched=True, queue=q)`` — value args only (scalars or arrays), returning
the updated queue (plus a ticket with ``returns=``).

Overflow is loud and two-sided.  If more than ``capacity`` records are
enqueued between flushes, the oldest are overwritten (their arena words are
simply left unread — the arena is append-only between flushes, so surviving
descriptors always point at their own data); every flush counts the records
it lost, warns, and publishes the counts through ``flush_stats()`` /
``queue_drops()``.  If the RING has room but the ARENA cannot hold a
record's payloads, the record is dropped **atomically** at enqueue time: no
arena words are written, no descriptor is stored, the head does not advance
— there can never be a descriptor pointing at unwritten space.  Arena drops
are counted on device and surfaced separately (``arena_drops`` /
``last_arena_drops`` in ``flush_stats()``).

**Reply arena: device-visible results for queued RPCs.**  The paper's RPC
is bidirectional — the host executes the call and hands the result back to
the device.  A queue created with ``reply_capacity > 0`` makes ``flush`` a
two-phase epoch: the one ordered ``io_callback`` drains records + payload
arena and replays the callees, and its RETURN value is a flat i32 **reply
buffer** (integer replies stored raw, float replies bitcast, mirroring the
request arena) plus a per-slot ``(offset, length)`` reply table, scattered
back into the queue's device-resident reply state.  Each enqueue is keyed
by a **ticket** — its enqueue order within the epoch (``head`` at enqueue
time; ``-1`` for records dropped at enqueue) — and ``enqueue_ticketed(...,
returns=ShapeDtypeStruct)`` declares the expected reply (count + dtype
stored in the record's ``rwant`` lane: ``+words`` integer, ``-words``
float).  After flush, device code reads ``queue.result(ticket, shape,
dtype)``: an O(1) dynamic slice of the reply buffer.  Tickets are GLOBAL
sequence numbers (they never reset), and each flush stamps the reply table
with its epoch's base — so a ticket only resolves against the flush that
serviced it: a stale ticket held across a later flush, or a dropped
ticket, reads zeros, never another record's bytes.  The one remaining
alias is ring overwrite WITHIN an epoch: an overwritten record's ticket
reads the surviving record in its slot (when the reply length matches).
A record whose declared reply does not fit the remaining reply arena is
dropped WHOLE at drain — its callee is NOT run (an effectful callee must
not consume input or reserve memory when its result can never reach the
requester), the reader sees zeros, and the drop is counted in
``flush_stats()['reply_drops']`` — the reply-side mirror of the request
arena's atomic enqueue drop.  ``rpc_call(name, *args, batched=True,
queue=q, returns=ShapeDtype)`` exposes the path generically, returning
``(queue, ticket)`` — the blocking-at-flush result path that makes
input-style libc (``fread``, ``fgets``) and device-usable remote-malloc
pointers possible.

**Fault-tolerant host boundary.**  The drain ISOLATES every callee: an
exception or per-callee wall-clock ``timeout`` overrun fails only that
record — traceback captured in :func:`error_log`, counts in
``flush_stats()['callee_errors']`` — while the remaining records replay in
the same deterministic order.  Reply-carrying queues add a per-slot STATUS
lane: ``result_status(ticket)`` distinguishes OK / CALLEE_RAISED / TIMEOUT
/ DROPPED / REPLY_OVERFLOW / STALE, and ``result_ok`` requires
``STATUS_OK``.  ``RpcQueue.create(retry=RetryPolicy(...), timeout=...)``
adds drain-side retry with exponential backoff, gated by the callee's
``register(idempotent=True)`` declaration.  ``queue.pressure()`` exposes
device-visible ring/arena/reply occupancy for cond-before-enqueue, and
:func:`set_fault_injector` is the deterministic fault-injection seam
(:mod:`repro.testing.faults`) the chaos suite drives.

**Sharded transport** (paper §3.3 applied to the transport).  Under
``expand`` every mesh device is a team, and funnelling all teams' records
through one logical queue would serialize the machine on a single ring.
:class:`ShardedRpcQueue` keeps ONE independent :class:`RpcQueue` shard per
device (leading device axis on every lane array AND on the payload arena,
partitioned by ``shard_map``); inside an expanded region each device
enqueues into its own shard — payload copies included — with zero
cross-device traffic, and ``flush`` replays records in ``(flush-order,
device, slot)`` order on the host — a deterministic total order, payloads
reattached per shard.  The reply arena stacks the same way: one reply
buffer + reply table PER DEVICE, so ``q.local(d).result(ticket, ...)`` (or
``q.result(d, ticket, ...)``) after the flush reads device ``d``'s replies
regardless of how the drain interleaved the shards.  ``core/libc.py``'s
``LogRing`` rides it unchanged (a sharded ring is a sharded queue of
width-3 records).  Flush of a *traced* sharded queue works in
single-program (vmapped logical devices) form; when the shards live on a
real multi-device mesh, flush at the program boundary instead
(``device_run(mesh=...)`` does) — XLA cannot lower a gathered callback
inside the same program as the partitioned loop.

**Async double-buffered epochs.**  A queue created with ``mode="async"``
stops paying the drain on the device clock: ``flush`` becomes a PING-PONG
epoch hand-off.  The callback SUBMITS the just-closed epoch's records (a
copied snapshot) to a host-side single-thread executor owned by the
queue's **slot** (allocated at ``create``; one per *(slot, device)*) and
immediately COLLECTS the previous epoch's finished drain as its return
value — so host-callee time overlaps the device compute that runs between
flushes.  Consequences, all visible in the API:

* **Replies land one epoch late.**  The reply window a flush installs is
  the PREVIOUS epoch's; tickets of the epoch just submitted read
  ``STATUS_PENDING`` from ``result_status()`` until the NEXT flush
  collects their drain (flushing an empty epoch is the explicit "collect
  the tail" idiom; ``join()`` waits for the background work without
  collecting).  The analyzer flags a raw ``result()`` of a pending ticket
  as ``PENDING_TICKET_READ``.
* **Per-device independent drains.**  Each shard's epoch drains on its own
  executor — no host-side gather barrier.  Each executor is FIFO over its
  shard's epochs, so per-shard replay order — and therefore every status
  and reply — is deterministic; only the cross-shard interleaving of host
  effects is not.
* **Cross-epoch retry carry.**  ``create(..., carry_budget=N)`` lets a
  failing record (``CALLEE_RAISED``/``TIMEOUT`` after in-drain retries,
  idempotent callees only) be CARRIED host-side into the next epoch's
  drain instead of finalizing: its slot stamps ``STATUS_PENDING``, the
  record replays FIRST (oldest first) at each subsequent drain of the
  slot, up to ``N`` extra rounds.  Final outcomes are host-visible via
  ``carry_outcomes()`` and folded into ``statuses_host()`` /
  ``results_host()``; the carried depth returns to the device as the
  ``cdepth`` leaf, which ``pressure()`` folds into the occupancy max —
  a degrading host IS backpressure.

**One drain core.**  Every flush — single or sharded, with or without
replies, sanitized or not, sync or async — goes through one function,
``_flush``, which hands ``io_callback`` the ONE host drain, ``_drain``,
bound to the queue's static fields (one cached callable per queue
geometry, so re-traces hand ``io_callback`` the same object).  The drain
treats a single queue as one shard and runs each shard's replay under one
of three schedules: **serial** in device order (sync); **concurrent**
under a shared wall-clock budget (sync sharded queues created with
``shard_deadline=secs``: a stalled shard's records read ``STATUS_TIMEOUT``
while its siblings complete — partial-epoch completion); or **submitted**
to and collected from the slot's executors (async; ``shard_deadline``
then bounds each shard's collect).  The concurrent and async schedules
reserve fault-occurrence indices up front in the serial ``(device,
slot)`` order (see :mod:`repro.testing.faults`), so fault plans stay
seed-deterministic whichever thread replays a record.

**CPU async-dispatch deadlock (why ``RpcQueue.create`` warns).**  Under
``jax_cpu_enable_async_dispatch=True`` the CPU backend enqueues programs
on a dispatch thread and materializes operands lazily.  An ordered
``io_callback`` drain then runs on a callback thread that calls
``np.asarray`` on its operands; for a LARGE operand (payload arenas past
~64K words) that materialization blocks on the operand's definition
event, which is queued BEHIND the very computation the callback belongs
to — while the main thread sits in ``block_until_ready`` waiting for
that computation.  Three threads, a cycle, no progress: a deterministic
deadlock on some containers (reproducible at the payload-1024 bench
point).  Synchronous dispatch removes the cycle without changing any
transport semantics, so ``RpcQueue.create`` detects the hazardous
config (CPU backend + async dispatch enabled) and warns ONCE per
process with the pin to apply; the test and bench harnesses
(``tests/conftest.py``, ``benchmarks/common.py``) pin it preemptively.

Argument categories (paper Fig. 3):
  * value args      — leaves passed by value; never written back.
  * ref args        — ``Ref(array, access=...)``: the underlying array ships
                      to the host; ``write``/``readwrite`` refs return the
                      mutated buffer, which the stub hands back to the caller
                      (device code must thread it into its carry — JAX is
                      functional; this *is* the paper's copy-back).
  * tracked refs    — ``ArenaRef(arena, ptr, allocator_state)``: a pointer
                      into the device heap; the underlying object is located
                      at **runtime** via the allocator's tracking table
                      (the paper's ``_FindObj`` — since allocator v2 an
                      O(log cap) ``searchsorted`` over the sorted-offset
                      index, paid once per marshalled pointer argument),
                      then shipped base+size.
                      On the host it expands *in place* to the five
                      positional arguments ``(ptr, base, size, found, arena)``.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import threading
import time
import traceback as traceback_mod
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from queue import Empty as _QueueEmpty, SimpleQueue as _SimpleQueue
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback

from repro.core import allocator as alloc_mod
from repro.core import events


# ---------------------------------------------------------------------------
# Sanitizer state (transport side of the GPU-First sanitizer)
# ---------------------------------------------------------------------------

#: Canary word written immediately before and after every payload-arena
#: reservation by a ``sanitize=True`` queue; verified at flush.
CANARY = np.int32(0x7FC0FFEE)
#: Poison pattern :func:`repro.analysis.sanitize.poison_free` stamps over a
#: freed heap block's words; a sanitized flush scans payloads for it, so a
#: freed block marshalled into the transport is caught AT FLUSH even though
#: the enqueue itself was a pure array copy.
POISON = np.int32(0x5A5A5A5A)


def _zero_san() -> Dict[str, Any]:
    return {"canary_stomps": 0,     # payload reservations with damaged canaries
            "poison_hits": 0,       # payloads carrying freed-block POISON words
            "uaf_marshals": 0,      # ArenaRef marshals whose lookup found no
            #                         live object (found == 0 at the pad)
            "stale_ticket_reads": 0,  # results_host reads outside the epoch
            #                           window on a sanitized queue
            "failed_ticket_reads": 0,  # result() consumed a failed/dropped
            #                            ticket's zeros as if they were a reply
            "epochs": []}           # per-sanitized-flush ticket shadow records


_SAN: Dict[str, Any] = _zero_san()
_SAN_LOCK = threading.Lock()


def sanitize_stats() -> Dict[str, Any]:
    """Snapshot of the runtime sanitizer counters (``sanitize=True`` queues:
    canary/poison checks at flush, UAF marshal counts, stale ticket reads,
    and the per-epoch ticket shadow records)."""
    with _SAN_LOCK:
        out = dict(_SAN)
        out["epochs"] = list(out["epochs"])
        return out


def reset_sanitize_stats() -> None:
    with _SAN_LOCK:
        _SAN.clear()
        _SAN.update(_zero_san())


def _san_bump(key: str, n: int = 1) -> None:
    if n:
        with _SAN_LOCK:
            _SAN[key] += n


# ---------------------------------------------------------------------------
# Fault-tolerant host boundary: reply statuses, error log, retry, timeout
# ---------------------------------------------------------------------------
#
# The host used to be treated as infallible: one raising callee inside the
# ordered drain aborted the whole device program, and a dropped or stale
# ticket read silent zeros indistinguishable from a real reply.  Every
# per-record callee invocation is now ISOLATED — an exception or wall-clock
# timeout fails only THAT record (traceback kept in ``error_log()``, count
# in ``flush_stats()['callee_errors']``) while the remaining records still
# replay in deterministic order — and every ticketed reply carries a status
# readable on device via ``result_status(ticket)``.

#: Reply statuses.  The drain stamps one per serviced ring slot; the
#: device-side ``result_status`` adds the two it can decide locally
#: (DROPPED for a ``-1`` ticket, STALE for a ticket outside the last
#: flush's window).
STATUS_OK = 0               # callee ran, reply (if declared) delivered
STATUS_CALLEE_RAISED = 1    # callee raised; traceback in error_log()
STATUS_TIMEOUT = 2          # callee exceeded the queue's per-callee timeout
STATUS_DROPPED = 3          # record dropped at enqueue (where=False / arena
#                             full), or its reply dropped by fault injection
STATUS_REPLY_OVERFLOW = 4   # reply arena full at drain: callee NOT run
STATUS_STALE = 5            # ticket from an epoch other than the last flush
STATUS_PENDING = 6          # async transport: the ticket's epoch is submitted
#                             but its drain has not been collected yet (reply
#                             lands one epoch late), or its record is being
#                             carried across epochs under a retry budget

STATUS_NAMES = {STATUS_OK: "OK", STATUS_CALLEE_RAISED: "CALLEE_RAISED",
                STATUS_TIMEOUT: "TIMEOUT", STATUS_DROPPED: "DROPPED",
                STATUS_REPLY_OVERFLOW: "REPLY_OVERFLOW",
                STATUS_STALE: "STALE", STATUS_PENDING: "PENDING"}

#: Bounded host-side error log (oldest entries evicted past the cap).
_ERROR_LOG_CAP = 256
_ERRORS: List[Dict[str, Any]] = []
_ERR_LOCK = threading.Lock()


def error_log() -> List[Dict[str, Any]]:
    """Snapshot of captured callee failures, oldest first.  Each entry:
    ``{"callee", "ticket", "attempt", "error", "traceback"}`` — ``ticket``
    is the record's global sequence number (``-1`` when unknown),
    ``attempt`` the 1-based attempt that failed, ``error`` the repr of the
    exception, ``traceback`` the formatted host-side traceback that
    ``io_callback`` would otherwise have destroyed."""
    with _ERR_LOCK:
        return [dict(e) for e in _ERRORS]


def clear_error_log() -> None:
    with _ERR_LOCK:
        _ERRORS.clear()


def _log_callee_error(name: str, ticket: int, attempt: int,
                      exc: BaseException) -> None:
    entry = {"callee": name, "ticket": int(ticket), "attempt": int(attempt),
             "error": repr(exc),
             "traceback": "".join(traceback_mod.format_exception(
                 type(exc), exc, exc.__traceback__))}
    with _ERR_LOCK:
        _ERRORS.append(entry)
        if len(_ERRORS) > _ERROR_LOG_CAP:
            del _ERRORS[:len(_ERRORS) - _ERROR_LOG_CAP]


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Host-side retry for transiently-failing batched callees.

    A queue created with ``retry=RetryPolicy(...)`` re-runs a failed
    record up to ``max_attempts`` times total WITHIN its drain, sleeping
    ``backoff * 2**(attempt-1)`` seconds between attempts (exponential
    backoff; ``backoff=0`` retries immediately).  ``retryable`` (optional
    ``exc -> bool``) filters which exceptions are worth retrying — by
    default every ``Exception`` is.  Retries are GATED by the callee's
    registration: only callees registered ``idempotent=True`` are re-run
    (re-running an effectful callee would duplicate its side effects; the
    analyzer flags the combination as ``RETRY_NON_IDEMPOTENT``).  A record
    that exhausts its attempts reads ``CALLEE_RAISED``/``TIMEOUT``; one
    that succeeds on a later attempt reads ``OK``.  Frozen (hashable): the
    policy is static queue metadata, part of the pytree aux."""
    max_attempts: int = 2
    backoff: float = 0.0
    retryable: Optional[Callable[[BaseException], bool]] = None


class _CalleeTimeout(Exception):
    """Raised (host-side, captured) when a callee exceeds the queue's
    per-callee wall-clock timeout."""


class _PipelinedCall:
    """One record in flight on a :class:`_CalleeWorker`'s inbox.

    The claim/cancel pair closes the double-execution race of a pipelined
    drain: when record j times out while record j+1 is already queued
    behind it, the drain must redrive j+1 on a FRESH worker — but only if
    the wedged worker has not started it.  ``claim()`` (worker side) and
    ``cancel()`` (drain side) race under the item's lock; exactly one
    wins, so every record's callee runs at most once."""

    __slots__ = ("fn", "args", "seq", "src", "_lk", "claimed", "cancelled")

    def __init__(self, fn, args, seq: int, src: "_CalleeWorker") -> None:
        self.fn = fn
        self.args = args
        self.seq = seq
        self.src = src          # the worker whose outbox holds the result
        self._lk = threading.Lock()
        self.claimed = False
        self.cancelled = False

    def claim(self) -> bool:
        with self._lk:
            if self.cancelled:
                return False
            self.claimed = True
            return True

    def cancel(self) -> bool:
        with self._lk:
            if self.claimed:
                return False
            self.cancelled = True
            return True


class _CalleeWorker:
    """One persistent daemon thread running a serial stream of callee
    invocations for the ``timeout=`` path.

    The old implementation paid a ``ThreadPoolExecutor.submit`` + future
    wakeup per record (~40µs: a lock handoff, a condition-variable round
    trip, and a future allocation each time), which put the guarded drain
    at ~2.5x the bare one.  A drain now CHECKS OUT one worker and streams
    every record of the epoch through a ``SimpleQueue`` inbox/outbox
    pair, and the fault-free drain PIPELINES the WHOLE EPOCH: every
    record is submitted before the first reply is settled, so the worker
    drains its inbox in one scheduling quantum and the drain pays O(1)
    context switches per epoch instead of O(records) — the decisive term
    on a single-core host, where a per-record ping-pong cannot overlap
    with anything (the ≤1.5x rpc_bench gate).  Results carry their
    submission sequence number so a collect can discard the stale entry
    a timed-out-but-late-completing callee leaves behind.  A timed-out
    callee wedges its worker (Python cannot safely kill a thread), so
    the worker is ABANDONED — its thread keeps running the callee to
    completion, skips any cancelled items still queued behind it, and
    idles forever on an unreachable inbox — and the next checkout spins
    up a fresh one."""

    def __init__(self) -> None:
        self._inbox: _SimpleQueue = _SimpleQueue()
        self._outbox: _SimpleQueue = _SimpleQueue()
        self._seq = 0
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="rpc-callee-worker")
        self._thread.start()

    def _loop(self) -> None:
        while True:
            item = self._inbox.get()
            if not item.claim():
                continue                 # cancelled before it ever ran
            try:
                out = (True, item.fn(*item.args), item.seq)
            except BaseException as exc:  # noqa: BLE001 — relayed to caller
                out = (False, exc, item.seq)
            self._outbox.put(out)

    def submit(self, fn, args) -> _PipelinedCall:
        self._seq += 1
        item = _PipelinedCall(fn, args, self._seq, self)
        self._inbox.put(item)
        return item

    def collect(self, seq: int, timeout: float):
        while True:
            try:
                # a pipelined drain usually finds the result already
                # posted (the worker ran it during the drain's own
                # unmarshalling of the next record) — the non-blocking
                # probe skips the timed-wait setup on that path
                ok, val, s = self._outbox.get_nowait()
            except _QueueEmpty:
                try:
                    ok, val, s = self._outbox.get(timeout=timeout)
                except _QueueEmpty:
                    raise _CalleeTimeout(
                        f"host callee exceeded the {timeout}s per-callee "
                        "timeout (still running in its worker thread; "
                        "record marked TIMEOUT)") from None
            if s != seq:
                continue   # stale result from an already-abandoned record
            if ok:
                return val
            raise val


_IDLE_WORKERS: List[_CalleeWorker] = []
_WORKER_LOCK = threading.Lock()


def _checkout_worker() -> _CalleeWorker:
    with _WORKER_LOCK:
        if _IDLE_WORKERS:
            return _IDLE_WORKERS.pop()
    return _CalleeWorker()


def _return_worker(w: _CalleeWorker) -> None:
    with _WORKER_LOCK:
        _IDLE_WORKERS.append(w)


class _WorkerLease:
    """A drain's handle on one checked-out :class:`_CalleeWorker`.

    Lazily checks a worker out on first use, streams every ``timeout=``
    record of the drain through it, and returns it to the idle pool at
    ``release()``.  ``submit()``/``collect()`` expose the pipelined
    protocol (one record executing, the next already queued behind it);
    ``call()`` is the strict ping-pong used when an injector or retry
    policy requires serial confirmation.  A timeout ABANDONS the wedged
    worker (dropped on the floor; its daemon thread finishes the callee
    and idles forever on an unreachable inbox) and the next record
    transparently gets a fresh one."""

    __slots__ = ("_w",)

    def __init__(self) -> None:
        self._w: Optional[_CalleeWorker] = None

    def submit(self, fn, args) -> _PipelinedCall:
        if self._w is None:
            self._w = _checkout_worker()
        return self._w.submit(fn, args)

    def collect(self, item: _PipelinedCall, timeout: float):
        return item.src.collect(item.seq, timeout)

    def call(self, fn, args, timeout: float):
        item = self.submit(fn, args)
        try:
            return item.src.collect(item.seq, timeout)
        except _CalleeTimeout:
            self._w = None           # wedged — abandon, never reuse
            raise

    def handle_timeout(self, pending: List[_PipelinedCall]
                       ) -> List[_PipelinedCall]:
        """Decide the worker's fate after the oldest in-flight record
        timed out.  ``pending`` holds the records still queued behind it,
        oldest first; the (possibly replaced) calls are returned in the
        same order.

        If the worker claimed the first pending record, the timed-out
        callee actually finished just past its deadline: the worker is
        healthy, everything stays where it is, and the stale predecessor
        entry in its outbox is discarded by the sequence check at
        collect.  Otherwise the worker is wedged: it is abandoned, and
        every record whose cancel wins its claim race is resubmitted (in
        order) on a fresh worker.  A record the old worker claims DURING
        the walk (it finished the wedging callee mid-cancellation) keeps
        its original call — ``src`` still points at the old worker, so
        its result is collected from there; such a record's callee may
        run concurrently with the redriven ones, the same degraded-path
        concurrency an abandoned callee already has today."""
        if not pending:
            self._w = None
            return pending
        if not pending[0].cancel():
            return pending           # late completion — worker is healthy
        self._w = None
        out = [self.submit(pending[0].fn, pending[0].args)]
        for item in pending[1:]:
            out.append(self.submit(item.fn, item.args) if item.cancel()
                       else item)
        return out

    def drop(self) -> None:
        """Forget the worker WITHOUT pooling it — used when a
        deadline-abandoned drain walks away mid-flight and the worker may
        still be executing a record whose result nobody will read."""
        self._w = None

    def release(self) -> None:
        if self._w is not None:
            _return_worker(self._w)
            self._w = None


def _call_with_timeout(fn, args, timeout: float, lease=None):
    """Run ``fn(*args)`` with a wall-clock deadline.  A timed-out callee
    keeps running in its (abandoned) worker thread but its record fails
    with ``STATUS_TIMEOUT`` and the drain moves on.  ``lease`` lets a
    drain stream many records through one checked-out worker (the batched
    path); without it a worker is checked out and returned per call."""
    if lease is not None:
        return lease.call(fn, args, timeout)
    one_shot = _WorkerLease()
    try:
        return one_shot.call(fn, args, timeout)
    finally:
        one_shot.release()


# The deterministic fault-injection seam (repro.testing.faults plugs in
# here).  At most one injector is active; it is consulted at DISPATCH time
# inside the drain, so a program traced once can run with and without
# faults.  Protocol: ``on_call(name, attempt, index=None) ->
# Optional[delay_seconds]`` (may raise to fail the record before its callee
# runs — host effects stay clean) and ``on_reply(name, words, index=None)
# -> Optional[int32 words]`` (``None`` drops the reply; a modified array
# corrupts it in place).  ``index`` is the record's per-callee occurrence
# index: synchronous drains omit it (the injector counts first attempts
# itself in replay order), while async/concurrent drains RESERVE indices
# up front via ``reserve(names) -> List[int]`` (optional; injectors
# without it run concurrent drains index-less, which is only racy for
# multi-shard plans) and pass them explicitly so per-shard threads and
# epoch-late carried redrives keep the same numbering the serial drain
# would produce.
_FAULT_INJECTOR: List[Any] = []


def set_fault_injector(inj=None) -> None:
    """Install (or with ``None`` remove) the process-wide drain fault
    injector.  Testing seam — see :mod:`repro.testing.faults`."""
    _FAULT_INJECTOR[:] = [] if inj is None else [inj]


def _invoke_record(name: str, fn, args, ticket: int, inj,
                   retry: Optional[RetryPolicy], timeout: Optional[float],
                   idempotent: bool, first_attempt: int = 1,
                   occ_index: Optional[int] = None, lease=None):
    """Run one record's callee with failure isolation, fault injection,
    timeout, and (idempotent-gated) retry.  Returns ``(status, out,
    n_retries)`` — ``out`` is None on failure, ``n_retries`` counts the
    attempts beyond the first made HERE.  ``first_attempt`` numbers the
    attempts for the injector and the retry budget (a carried record's
    redrive continues where its original drain stopped rather than
    restarting at 1); ``occ_index`` passes an explicitly reserved
    per-callee occurrence index (async/concurrent drains); ``lease``
    streams ``timeout=`` dispatches through one checked-out worker."""
    attempts = (first_attempt - 1 + retry.max_attempts
                if (retry is not None and idempotent) else first_attempt)
    attempt = first_attempt
    while True:
        try:
            if inj is None:
                delay = None
            elif occ_index is None:
                delay = inj.on_call(name, attempt)
            else:
                delay = inj.on_call(name, attempt, index=occ_index)
            if delay:
                call = (lambda *a: (time.sleep(delay), fn(*a))[1])
            else:
                call = fn
            if timeout is not None:
                out = _call_with_timeout(call, args, timeout, lease=lease)
            else:
                out = call(*args)
            return STATUS_OK, out, attempt - first_attempt
        except Exception as exc:         # noqa: BLE001 — the isolation point
            _log_callee_error(name, ticket, attempt, exc)
            timed_out = isinstance(exc, _CalleeTimeout)
            can_retry = (attempt < attempts
                         and (retry.retryable is None
                              or retry.retryable(exc)))
            if not can_retry:
                return (STATUS_TIMEOUT if timed_out
                        else STATUS_CALLEE_RAISED), None, attempt - first_attempt
            if retry.backoff:
                time.sleep(retry.backoff * (2.0 ** (attempt - 1)))
            attempt += 1


# ---------------------------------------------------------------------------
# Argument specs
# ---------------------------------------------------------------------------

READ, WRITE, READWRITE = "read", "write", "readwrite"

# marshalling kinds (also the first element of each signature entry)
VAL, REF, ARENA = "val", "ref", "arena"


@dataclasses.dataclass
class Ref:
    """A pointer-like argument: ships its underlying array to the host."""
    array: jax.Array
    access: str = READWRITE
    offset: Any = 0            # element offset of the "pointer" into the array

    def __post_init__(self):
        assert self.access in (READ, WRITE, READWRITE), self.access


@dataclasses.dataclass
class ArenaRef:
    """A heap pointer whose underlying object is found at runtime via the
    allocator's tracking table (the paper's dynamically-identified objects)."""
    arena: jax.Array           # the 1-D heap array
    ptr: Any                   # element offset returned by malloc
    state: Any                 # GenericState | BalancedState
    access: str = READWRITE


# ---------------------------------------------------------------------------
# Durable identity: content-hashed ids + the serializable manifest
# ---------------------------------------------------------------------------
#
# Identity used to be an in-memory accident: pad ids and batch-callee ids
# were handed out in arrival order (``_next_pad``), so a program traced in
# one process could never be replayed in another — the compiled artifact
# embedded ids that meant nothing outside the process that traced it.
# Every id is now a STABLE CONTENT HASH of what it names:
#
#   * pad id        = hash63("pad", callee name + flattened signature)
#   * batch callee  = hash31("callee", name)   — rides a device int32 lane
#   * format id     = hash31("fmt", string)    — rides a device int32 lane
#
# Two traces of the same program — in the same process or across a
# ``jax.export`` boundary — bind the same ids, and :class:`RpcManifest`
# makes the whole binding table a versioned, JSON-serializable artifact
# that a fresh process adopts before serving.

MANIFEST_VERSION = 1


def _stable_id(kind: str, key: str, bits: int) -> int:
    """Deterministic ``bits``-wide nonzero id for ``key`` (domain-separated
    by ``kind``).  sha256 prefix, so the id is stable across processes,
    platforms and Python hash randomization."""
    digest = hashlib.sha256(f"{kind}\x00{key}".encode("utf-8")).digest()
    v = int.from_bytes(digest[:8], "big") % (1 << bits)
    return v or 1         # 0 is reserved (empty ring slots read callee 0)


def _sig_to_json(sig: Tuple) -> list:
    """Canonical JSON form of a flattened signature (tuples -> lists)."""
    return [[e[0], list(e[1])] + list(e[2:]) for e in sig]


def _sig_from_json(obj) -> Tuple:
    """Inverse of :func:`_sig_to_json` (shapes back to int tuples)."""
    return tuple((e[0], tuple(int(d) for d in e[1])) + tuple(e[2:])
                 for e in obj)


def stable_pad_id(name: str, sig: Tuple) -> int:
    """Content-hashed landing-pad id: 63-bit (pad ids live host-side only)."""
    canon = json.dumps([name, _sig_to_json(sig)], separators=(",", ":"))
    return _stable_id("pad", canon, 63)


def stable_callee_id(name: str) -> int:
    """Content-hashed batch-callee id.  31-bit: callee ids travel in the
    queue's device-resident int32 ``callee`` lane."""
    return _stable_id("callee", name, 31)


def stable_format_id(text: str) -> int:
    """Content-hashed interned-string id (fprintf formats, heap names).
    31-bit: format ids travel in device int32 lanes too."""
    return _stable_id("fmt", text, 31)


def stable_hook_id(key: str) -> int:
    """Content-hashed auto-name suffix for ``device_run`` hooks (the
    manifest naming scheme for hooks without an explicit ``name=``)."""
    return _stable_id("hook", key, 31)


# libc registers these so the manifest can carry the interned format table
# without rpc importing libc (the one-way import discipline): export returns
# the current {fid: string} table, adopt restores one into a fresh process.
_FORMAT_SECTION: List[Callable] = []      # [export_fn, adopt_fn] once set


def register_format_section(export_fn: Callable[[], Dict[int, str]],
                            adopt_fn: Callable[[Dict[int, str]], None]):
    _FORMAT_SECTION[:] = [export_fn, adopt_fn]


def queue_geometry(q) -> Dict[str, int]:
    """The transport geometry of an :class:`RpcQueue` /
    :class:`ShardedRpcQueue` as a plain dict — what a fresh process needs
    to rebuild a compatible queue (ring/payload/reply capacities, record
    width, shard count)."""
    shards = q.n_devices if isinstance(q, ShardedRpcQueue) else 1
    return {"capacity": int(q.capacity), "width": int(q.width),
            "payload_capacity": int(q.payload_capacity),
            "reply_capacity": int(q.reply_capacity),
            "shards": int(shards)}


@dataclasses.dataclass
class RpcManifest:
    """Versioned, JSON-serializable table of every durable transport id.

    ``pads`` maps pad id -> ``{"callee": name, "signature": [...]}``;
    ``callees`` maps batch-callee id -> name; ``formats`` is the interned
    string table (fprintf formats + remote-heap names); ``queues`` records
    the geometry of the queues the exporting program used.  The manifest is
    the contract a ``jax.export``-serialized program ships next to its
    bytes: :meth:`_Registry.adopt_manifest` restores the tables in a fresh
    process so device-resident ids resolve without re-tracing."""
    version: int = MANIFEST_VERSION
    pads: Dict[int, dict] = dataclasses.field(default_factory=dict)
    callees: Dict[int, str] = dataclasses.field(default_factory=dict)
    formats: Dict[int, str] = dataclasses.field(default_factory=dict)
    queues: List[dict] = dataclasses.field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps(
            {"version": self.version,
             "pads": {str(k): {"callee": v["callee"],
                               "signature": v["signature"]}
                      for k, v in sorted(self.pads.items())},
             "callees": {str(k): v
                         for k, v in sorted(self.callees.items())},
             "formats": {str(k): v
                         for k, v in sorted(self.formats.items())},
             "queues": list(self.queues)},
            indent=2, sort_keys=True) + "\n"

    @staticmethod
    def from_json(text: str) -> "RpcManifest":
        obj = json.loads(text)
        version = int(obj.get("version", -1))
        if version != MANIFEST_VERSION:
            raise ValueError(
                f"RpcManifest version {version} is not supported "
                f"(this runtime speaks version {MANIFEST_VERSION})")
        return RpcManifest(
            version=version,
            pads={int(k): {"callee": v["callee"],
                           "signature": v["signature"]}
                  for k, v in obj.get("pads", {}).items()},
            callees={int(k): v for k, v in obj.get("callees", {}).items()},
            formats={int(k): v for k, v in obj.get("formats", {}).items()},
            queues=list(obj.get("queues", [])))

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    @staticmethod
    def load(path) -> "RpcManifest":
        with open(path) as f:
            return RpcManifest.from_json(f.read())


# ---------------------------------------------------------------------------
# Registry: host functions + per-signature landing pads + stats
# ---------------------------------------------------------------------------

def _zero_stats() -> Dict[str, float]:
    return {"calls": 0, "bytes_in": 0, "bytes_out": 0}


class _Registry:
    """Host-function table, landing-pad table, batch-callee table, stats.

    ``pads`` maps ``(callee,) + signature`` to a pad id; ``pad_wrappers``
    holds the ONE cached host wrapper per pad (the stable callable handed to
    ``io_callback``); ``pad_info``/``pad_stats`` expose per-pad metadata and
    call/byte counters.  ``batch_ids`` assigns small integer ids to host
    functions addressable from :class:`RpcQueue` records.
    """

    def __init__(self):
        self.lock = threading.Lock()
        self.hosts: Dict[str, Callable] = {}
        self.pads: Dict[Tuple, int] = {}           # (name,)+sig -> pad id
        self.pad_wrappers: Dict[int, Callable] = {}
        self.pad_info: Dict[int, Tuple] = {}       # pad id -> (name,)+sig
        self.pad_stats: Dict[int, Dict[str, float]] = {}
        self.stats: Dict[str, Dict[str, float]] = {}
        self.batch_ids: Dict[str, int] = {}        # name -> queue callee id
        self.batch_names: Dict[int, str] = {}      # queue callee id -> name
        self.idempotent: Dict[str, bool] = {}      # name -> safe to re-run
        self.queue_geoms: List[Dict[str, int]] = []  # geometries seen/adopted
        self.queue_drops = 0
        self.arena_drops = 0
        self.reply_drops = 0
        self.callee_errors = 0
        self.retries = 0
        self.flushes = 0
        self.last_flush_drops = 0
        self.last_flush_arena_drops = 0
        self.last_flush_reply_drops = 0
        self.last_flush_callee_errors = 0

    def register(self, name: str, fn: Callable, idempotent: bool = False):
        """(Re-)bind ``name`` to ``fn``.  Pads, pad wrappers and stats for
        ``name`` survive re-registration: already-traced stubs dispatch to the
        NEW function (wrappers resolve the callee at dispatch time).

        ``idempotent=True`` declares that re-running ``fn`` with the same
        arguments is safe — the gate for drain-side
        :class:`RetryPolicy` retries (a non-idempotent callee is never
        re-run; the record fails on its first exception)."""
        with self.lock:
            self.hosts[name] = fn
            self.idempotent[name] = bool(idempotent)
            self.stats.setdefault(name, dict(_zero_stats(), pads=0))

    def unregister(self, name: str):
        """Remove every trace of ``name``: host binding, stats, landing pads
        and batch callee id.  Used by ``device_run`` to retire auto-named
        per-instance hooks so repeated runs leave the registry the same
        size — only call once all pending callbacks referencing the name
        have drained.  (Ids are content hashes, so re-registering the same
        name later re-derives the SAME ids — nothing to recycle.)"""
        with self.lock:
            self.hosts.pop(name, None)
            self.idempotent.pop(name, None)
            self.stats.pop(name, None)
            for key in [k for k in self.pads if k[0] == name]:
                pid = self.pads.pop(key)
                self.pad_wrappers.pop(pid, None)
                self.pad_info.pop(pid, None)
                self.pad_stats.pop(pid, None)
            cid = self.batch_ids.pop(name, None)
            if cid is not None:
                self.batch_names.pop(cid, None)

    def landing_pad(self, name: str, sig: Tuple) -> Tuple[int, Callable]:
        """One pad — and one cached host wrapper — per (callee, flattened
        arg-type tuple): the variadic monomorphization of the paper.
        Returns ``(pad_id, wrapper)``; the wrapper object is identical for
        every trace with this signature.  The pad id is the stable content
        hash of ``(name, sig)`` — any process tracing this call site binds
        the same id."""
        with self.lock:
            key = (name,) + sig
            pid = self.pads.get(key)
            if pid is None:
                pid = stable_pad_id(name, sig)
                other = self.pad_info.get(pid)
                if other is not None and other != key:
                    raise RuntimeError(
                        f"landing-pad id collision: {key!r} and {other!r} "
                        f"both hash to pad id {pid} — rename one callee")
                self.pads[key] = pid
                self.pad_info[pid] = key
                self.pad_stats[pid] = _zero_stats()
                self.pad_wrappers[pid] = _make_pad_wrapper(name, pid, sig)
                self.stats[name]["pads"] += 1
            return pid, self.pad_wrappers[pid]

    def batch_callee_id(self, name: str) -> int:
        """Integer id addressing ``name`` from RpcQueue records — the
        stable 31-bit content hash of the name (it rides the device int32
        ``callee`` lane), so a re-trace in ANY process binds the same id.
        A hash collision between two registered names is detected here and
        is a hard error (rename one callee)."""
        with self.lock:
            if name not in self.hosts:
                raise KeyError(f"no host function registered for RPC {name!r}")
            cid = self.batch_ids.get(name)
            if cid is None:
                cid = stable_callee_id(name)
                other = self.batch_names.get(cid)
                if other is not None and other != name:
                    raise RuntimeError(
                        f"batch-callee id collision: {name!r} and {other!r} "
                        f"both hash to callee id {cid} — rename one callee")
                self.batch_names[cid] = name
                self.batch_ids[name] = cid
            return cid

    def note_queue_geometry(self, geom: Dict[str, int]) -> None:
        """Record one transport geometry (deduplicated) for the manifest's
        ``queues`` section.  Called by ``RpcQueue.create`` /
        ``ShardedRpcQueue.create`` and by ``expand(queue=True)`` regions,
        so export_manifest sees the geometry of queues built INSIDE
        runtime layers (``device_run``'s hook queue, an expanded region's
        team shards) that the exporting caller never held a handle to."""
        with self.lock:
            if geom not in self.queue_geoms:
                self.queue_geoms.append(dict(geom))

    def export_manifest(self, queues=()) -> RpcManifest:
        """Snapshot the durable identity of everything registered so far as
        an :class:`RpcManifest` — every landing pad (id + callee +
        flattened signature), every batch callee id, the interned format
        table, and the geometry of ``queues`` (RpcQueue / ShardedRpcQueue
        instances the exported program uses)."""
        with self.lock:
            pads = {pid: {"callee": key[0],
                          "signature": _sig_to_json(key[1:])}
                    for pid, key in self.pad_info.items()}
            callees = dict(self.batch_names)
            geoms = [dict(g) for g in self.queue_geoms]
        formats = _FORMAT_SECTION[0]() if _FORMAT_SECTION else {}
        for q in queues:
            g = queue_geometry(q)
            if g not in geoms:
                geoms.append(g)
        return RpcManifest(version=MANIFEST_VERSION, pads=pads,
                           callees=callees, formats=dict(formats),
                           queues=geoms)

    def adopt_manifest(self, manifest: RpcManifest,
                       require_hosts: bool = True) -> None:
        """Restore another process's identity tables from ``manifest`` so a
        deserialized program's device-resident ids resolve here with ZERO
        re-tracing.

        Validation is hard-nosed: every manifest entry is re-hashed and
        must reproduce its recorded id (a mismatched signature — manifest
        edited, or hashing scheme drift — names the offending pad), ids
        already bound locally must agree with the manifest, and with
        ``require_hosts=True`` (default) every manifest callee must have a
        host function registered before adoption — serving an artifact
        whose callees cannot dispatch is an error at adopt time, not a
        KeyError mid-drain."""
        if manifest.version != MANIFEST_VERSION:
            raise ValueError(
                f"cannot adopt RpcManifest version {manifest.version}: this "
                f"runtime speaks version {MANIFEST_VERSION}")
        # -- validate everything before touching any table ----------------
        for pid, entry in sorted(manifest.pads.items()):
            name = entry["callee"]
            sig = _sig_from_json(entry["signature"])
            want = stable_pad_id(name, sig)
            if want != pid:
                raise ValueError(
                    f"manifest pad {pid} ({name!r}) does not match its "
                    f"recorded signature: re-registration hashes to {want} "
                    "— mismatched signature for this pad")
            if require_hosts and name not in self.hosts:
                raise ValueError(
                    f"manifest pad {pid} needs host function {name!r}, "
                    "which is not registered in this process — register "
                    "it (or import the module that does) before "
                    "adopt_manifest()")
        for cid, name in sorted(manifest.callees.items()):
            want = stable_callee_id(name)
            if want != cid:
                raise ValueError(
                    f"manifest callee id {cid} ({name!r}) does not match "
                    f"its content hash {want} — mismatched re-registration "
                    "for this pad")
            if require_hosts and name not in self.hosts:
                raise ValueError(
                    f"manifest callee {name!r} (id {cid}) has no host "
                    "function registered in this process — register it "
                    "before adopt_manifest()")
        with self.lock:
            for cid, name in manifest.callees.items():
                local = self.batch_names.get(cid)
                if local is not None and local != name:
                    raise ValueError(
                        f"manifest callee id {cid} names {name!r} but is "
                        f"already bound to {local!r} in this process")
        # -- adopt: callees, pads (wrappers for registered hosts), formats
        with self.lock:
            for cid, name in manifest.callees.items():
                self.batch_names[cid] = name
                self.batch_ids[name] = cid
        for pid, entry in manifest.pads.items():
            name = entry["callee"]
            if name in self.hosts:
                self.landing_pad(name, _sig_from_json(entry["signature"]))
        if manifest.formats:
            if not _FORMAT_SECTION:
                raise RuntimeError(
                    "manifest carries interned format strings but no "
                    "format section is registered (import repro.core.libc "
                    "before adopt_manifest())")
            _FORMAT_SECTION[1](dict(manifest.formats))
        for g in manifest.queues:
            self.note_queue_geometry(dict(g))

    def bump(self, name: str, pad_id: Optional[int], bytes_in: int,
             bytes_out: int, calls: int = 1):
        with self.lock:
            s = self.stats[name]
            s["calls"] += calls
            s["bytes_in"] += bytes_in
            s["bytes_out"] += bytes_out
            if pad_id is not None:
                p = self.pad_stats[pad_id]
                p["calls"] += calls
                p["bytes_in"] += bytes_in
                p["bytes_out"] += bytes_out

    def bump_drops(self, n: int):
        with self.lock:
            self.queue_drops += n

    def bump_flush(self, drops: int, arena_drops: int = 0,
                   reply_drops: int = 0, callee_errors: int = 0,
                   retries: int = 0):
        with self.lock:
            self.flushes += 1
            self.last_flush_drops = drops
            self.arena_drops += arena_drops
            self.last_flush_arena_drops = arena_drops
            self.reply_drops += reply_drops
            self.last_flush_reply_drops = reply_drops
            self.callee_errors += callee_errors
            self.last_flush_callee_errors = callee_errors
            self.retries += retries


REGISTRY = _Registry()


def export_manifest(queues=()) -> RpcManifest:
    """Module-level alias for :meth:`_Registry.export_manifest`."""
    return REGISTRY.export_manifest(queues=queues)


def adopt_manifest(manifest: RpcManifest, require_hosts: bool = True) -> None:
    """Module-level alias for :meth:`_Registry.adopt_manifest`."""
    REGISTRY.adopt_manifest(manifest, require_hosts=require_hosts)


def rpc_stats(name: Optional[str] = None):
    """Per-callee aggregate stats (calls, bytes_in, bytes_out, pads)."""
    with REGISTRY.lock:
        if name is not None:
            return dict(REGISTRY.stats.get(name, {}))
        return {k: dict(v) for k, v in REGISTRY.stats.items()}


def pad_stats(pad_id: Optional[int] = None):
    """Per-landing-pad stats; ``pad_table()`` maps pad ids to signatures."""
    with REGISTRY.lock:
        if pad_id is not None:
            return dict(REGISTRY.pad_stats.get(pad_id, {}))
        return {k: dict(v) for k, v in REGISTRY.pad_stats.items()}


def pad_table():
    """Snapshot of the landing-pad table: pad id -> (callee, *signature)."""
    with REGISTRY.lock:
        return dict(REGISTRY.pad_info)


def queue_drops() -> int:
    """Total RpcQueue records overwritten before a flush could drain them."""
    with REGISTRY.lock:
        return REGISTRY.queue_drops


def flush_stats() -> Dict[str, int]:
    """Queue-flush accounting: total flushes, records lost to ring overwrite
    (``drops``), to a full payload arena (``arena_drops``, counted at
    enqueue time — the atomic-drop path), and result-bearing records lost
    to a full REPLY arena (``reply_drops``, counted at drain time: the
    reply could not fit, so the record's callee was NOT run and the
    reader sees zeros — the drain-side atomic drop), plus each count for
    the most recent flush alone (0 when nothing was lost).

    ``callee_errors`` / ``last_callee_errors`` count records whose callee
    raised or timed out during a drain AFTER any retries (the failure was
    isolated: the record read ``CALLEE_RAISED``/``TIMEOUT``, the rest of
    the flush completed — tracebacks in :func:`error_log`).  ``retries``
    counts extra attempts spent by :class:`RetryPolicy` queues."""
    with REGISTRY.lock:
        return {"flushes": REGISTRY.flushes,
                "drops": REGISTRY.queue_drops,
                "last_drops": REGISTRY.last_flush_drops,
                "arena_drops": REGISTRY.arena_drops,
                "last_arena_drops": REGISTRY.last_flush_arena_drops,
                "reply_drops": REGISTRY.reply_drops,
                "last_reply_drops": REGISTRY.last_flush_reply_drops,
                "callee_errors": REGISTRY.callee_errors,
                "last_callee_errors": REGISTRY.last_flush_callee_errors,
                "retries": REGISTRY.retries}


def _host_contacts() -> Dict[str, int]:
    """The transport's counters that the runtime recorder publishes: queue
    flushes and immediate (landing-pad) RPC calls."""
    with REGISTRY.lock:
        return {"flushes": REGISTRY.flushes,
                "immediate_calls": sum(p["calls"]
                                       for p in REGISTRY.pad_stats.values())}


events.publish("rpc", _host_contacts)


def reset_rpc_stats():
    with REGISTRY.lock:
        for s in REGISTRY.stats.values():
            for k in s:
                s[k] = 0
        for p in REGISTRY.pad_stats.values():
            for k in p:
                p[k] = 0
        REGISTRY.queue_drops = 0
        REGISTRY.arena_drops = 0
        REGISTRY.reply_drops = 0
        REGISTRY.callee_errors = 0
        REGISTRY.retries = 0
        REGISTRY.flushes = 0
        REGISTRY.last_flush_drops = 0
        REGISTRY.last_flush_arena_drops = 0
        REGISTRY.last_flush_reply_drops = 0
        REGISTRY.last_flush_callee_errors = 0


# ---------------------------------------------------------------------------
# Host-side wrapper generation
# ---------------------------------------------------------------------------

def _np_bytes(tree) -> int:
    return int(sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree)))


def _make_pad_wrapper(name: str, pad_id: int, sig: Tuple):
    """Generates the host landing pad: unpack RPCInfo -> call -> pack result +
    write-back refs (paper Fig. 3b).

    Created ONCE per pad and cached in ``REGISTRY.pad_wrappers`` so every
    trace with this signature hands ``io_callback`` the same callable.  The
    flat operands arrive in call-site order; ``sig`` says how many operands
    each original argument consumed, so the callee sees its arguments in the
    original positions.  The callee itself is resolved from
    ``REGISTRY.hosts`` at dispatch time (re-registration-safe).
    """

    def wrapper(*flat):
        fn = REGISTRY.hosts[name]
        pos = 0
        call_args = []
        ref_outs = []                    # (access, original, host copy)
        for entry in sig:
            kind = entry[0]
            if kind == VAL:
                call_args.append(np.asarray(flat[pos]))
                pos += 1
            elif kind == REF:
                orig = flat[pos]
                pos += 1
                copy = np.asarray(orig).copy()
                call_args.append(copy)
                ref_outs.append((entry[3], orig, copy))
            else:                        # ARENA: ptr, base, size, found, arena
                ptr, base, size, found = (np.asarray(x)
                                          for x in flat[pos:pos + 4])
                arena = flat[pos + 4]
                pos += 5
                if int(found) == 0:
                    # the runtime lookup found no live object under this
                    # pointer: a freed (or wild) pointer was marshalled.
                    # Counted unconditionally — the counter is only read
                    # through sanitize_stats(), so the hot path stays a
                    # single int compare.
                    _san_bump("uaf_marshals")
                copy = np.asarray(arena).copy()
                call_args.extend([ptr, base, size, found, copy])
                ref_outs.append((entry[3], arena, copy))
        result = fn(*call_args)
        ret = [jax.tree.map(np.asarray, result)]
        for acc, orig, copy in ref_outs:
            if acc in (WRITE, READWRITE):
                ret.append(copy)
            else:
                ret.append(np.asarray(orig))   # read-only: no copy-back
        REGISTRY.bump(name, pad_id, _np_bytes(flat), _np_bytes(ret))
        return tuple(ret)

    wrapper.__name__ = f"rpc_pad_{pad_id}_{name}"
    return wrapper


# ---------------------------------------------------------------------------
# Device-side stub
# ---------------------------------------------------------------------------

def _marshal(args) -> Tuple[Tuple, List, List]:
    """Flatten call-site arguments in ORIGINAL order.

    Returns ``(sig, operands, ref_shapes)`` where ``sig`` is the per-argument
    signature tuple (the landing-pad key and the wrapper's unpack recipe),
    ``operands`` is the flat operand list for the callback, and
    ``ref_shapes`` the ShapeDtypeStructs of write-back slots in arg order.
    """
    sig, operands, ref_shapes = [], [], []
    for a in args:
        if isinstance(a, Ref):
            sig.append((REF, tuple(np.shape(a.array)),
                        str(jnp.result_type(a.array)), a.access))
            operands.append(a.array)
            ref_shapes.append(jax.ShapeDtypeStruct(
                np.shape(a.array), jnp.result_type(a.array)))
        elif isinstance(a, ArenaRef):
            # runtime object lookup via the allocator tracking table: ship the
            # underlying object as (ptr, base, size, found, arena) — a single
            # level of indirection (§4.1)
            if events.active():
                pv = (None if isinstance(a.ptr, jax.core.Tracer)
                      else int(np.asarray(a.ptr)))
                events.emit("arena_marshal", _refs=(a.ptr,),
                            ptr_id=id(a.ptr), ptr=pv,
                            heap=getattr(a.state, "heap_size", None))
            found, base, size = _find_obj(a.state, a.ptr)
            sig.append((ARENA, tuple(np.shape(a.arena)),
                        str(jnp.result_type(a.arena)), a.access))
            operands.extend([jnp.asarray(a.ptr, jnp.int32),
                             jnp.asarray(base, jnp.int32),
                             jnp.asarray(size, jnp.int32),
                             jnp.asarray(found, jnp.int32),
                             a.arena])
            ref_shapes.append(jax.ShapeDtypeStruct(
                np.shape(a.arena), jnp.result_type(a.arena)))
        else:
            v = jnp.asarray(a)
            sig.append((VAL, tuple(np.shape(v)), str(jnp.result_type(v))))
            operands.append(v)
    return tuple(sig), operands, ref_shapes


def rpc_call(name: str, *args, result_shape=None, ordered: bool = True,
             pure: bool = False, batched: bool = False, queue=None,
             where=None, returns=None):
    """Issue a blocking host RPC from device code (traceable).

    ``args`` may mix plain arrays/scalars (value args), :class:`Ref`, and
    :class:`ArenaRef` in any order; the host function receives them in the
    SAME order.  Returns ``(result, updated_ref_arrays)`` — updated arrays
    appear for every Ref/ArenaRef in order (read-only refs are returned
    unchanged so the call-site structure is static).

    ``pure=True`` dispatches through ``jax.pure_callback`` (elidable,
    cacheable, unordered) — only for pure host functions; write-back refs are
    rejected.  Otherwise ``io_callback`` is used, with ``ordered`` as given.

    ``batched=True`` routes the call through the batched transport instead:
    the record (scalars in lanes, arrays in the payload arena) is enqueued
    on ``queue`` — a :class:`RpcQueue` — and the UPDATED QUEUE is returned.
    By default batched calls are fire-and-forget: no result reaches the
    device and no write-back refs are allowed (pass value args only), so
    ``result_shape`` is ignored; the host sees the call when the queue
    flushes.  ``where`` (optional traced bool) makes the enqueue
    conditional.  This is the paper-§3.5 path for array-carrying library
    calls — buffered ``fwrite``, bulk remote mallocs whose size vectors
    ride the arena — without a per-call ordered callback.

    ``batched=True, returns=jax.ShapeDtypeStruct(...)`` is the
    blocking-at-flush result path: the call returns ``(queue', ticket)``
    instead, and after the queue flushes the host function's return value
    is readable on device as ``queue.result(ticket, returns)`` — the reply
    rode the flush's reply arena (requires a queue created with
    ``reply_capacity > 0``).  ``returns`` is only meaningful with
    ``batched=True`` (immediate RPCs already return results via
    ``result_shape``).
    """
    if name not in REGISTRY.hosts:
        raise KeyError(f"no host function registered for RPC {name!r}")

    if batched:
        if queue is None:
            raise ValueError(
                "rpc_call(batched=True) needs queue=<RpcQueue>: batched "
                "RPCs live in the on-device ring until flush")
        if pure:
            raise ValueError("batched RPCs are effectful records; "
                             "pure=True does not apply")
        for j, a in enumerate(args):
            if isinstance(a, (Ref, ArenaRef)):
                raise ValueError(
                    f"batched RPC {name!r} arg {j}: Ref/ArenaRef arguments "
                    "need a synchronous round-trip (write-back / runtime "
                    "object lookup) that the batched transport does not "
                    "provide — pass value args (scalars or arrays) only; "
                    "host RESULTS do come back: use returns= for a ticket "
                    "readable via queue.result() after flush")
        if returns is not None:
            return queue.enqueue_ticketed(name, *args, returns=returns,
                                          where=where)
        return queue.enqueue(name, *args, where=where)
    if returns is not None:
        raise ValueError(
            "rpc_call(returns=...) is only meaningful with batched=True: "
            "immediate RPCs return results directly via result_shape")
    if where is not None:
        raise ValueError(
            "rpc_call(where=...) is only meaningful with batched=True: an "
            "immediate callback has no conditional form — wrap the call in "
            "lax.cond, or route it through a queue")
    if result_shape is None:
        raise TypeError("rpc_call() missing required keyword argument "
                        "'result_shape' (only batched=True may omit it)")

    if events.active():
        # lazy: expand imports nothing from rpc, but keep the one-way import
        # discipline symmetric with flush's guard below
        from repro.core.expand import _ENV as _team_env_state
        events.emit("rpc_immediate", name=name, ordered=ordered, pure=pure,
                    in_mesh=bool(_team_env_state.axes))
    sig, operands, ref_shapes = _marshal(args)
    if pure:
        writeback = [e for e in sig if e[0] in (REF, ARENA)
                     and e[3] in (WRITE, READWRITE)]
        if writeback:
            raise ValueError(
                f"pure RPC {name!r} cannot take write/readwrite refs: "
                "pure_callback may be elided or reordered, so host-side "
                "mutation has no defined meaning")

    _, wrapper = REGISTRY.landing_pad(name, sig)

    result_shapes = (jax.tree.map(lambda s: s, result_shape),) \
        + tuple(ref_shapes)
    if pure:
        out = jax.pure_callback(wrapper, result_shapes, *operands)
    else:
        out = io_callback(wrapper, result_shapes, *operands, ordered=ordered)
    result, updated = out[0], list(out[1:])
    return result, updated


# The allocator's sorted-offset index makes this O(log cap) per pointer
# argument (every ArenaRef marshalled pays for exactly one lookup, so this is
# the RPC hot path).  ``_FIND_OBJ_IMPL`` is swappable so benchmarks can trace
# the same marshalling path against the v1 linear scan
# (``allocator.find_obj_linear``) for a measured contrast.
_FIND_OBJ_IMPL = alloc_mod.find_obj


def set_find_obj_impl(fn=None):
    """Override the object-lookup used when marshalling ``ArenaRef`` args
    (``None`` restores the default O(log) path).  Benchmark/test hook: the
    choice is baked in at TRACE time, so trace under the impl you want."""
    global _FIND_OBJ_IMPL
    _FIND_OBJ_IMPL = fn if fn is not None else alloc_mod.find_obj


def _find_obj(state, ptr):
    return _FIND_OBJ_IMPL(state, ptr)


# ---------------------------------------------------------------------------
# Batched transport: on-device RPC queue, drained by ONE ordered callback
# ---------------------------------------------------------------------------

def _replay_shard(*args, **kwargs) -> Tuple[int, int, int, int]:
    """:func:`_replay_records` inside the ``rpc.drain`` runtime span: every
    schedule of :func:`_drain` replays each shard through here, on
    whichever host thread drains it."""
    with events.span("rpc.drain"):
        return _replay_records(*args, **kwargs)


def _replay_records(callee, nargs, imask, pmask, ivals, fvals, plens, pbuf,
                    rwant, n, overrides, names, hosts, per_name_calls,
                    per_name_bytes, reply=None, base=0, idem=None,
                    retry=None, timeout=None, occ=None, carry=None,
                    abandoned=None) -> Tuple[int, int, int, int]:
    """Replay one queue shard's records in enqueue order; returns ``(number
    of records overwritten before this flush could drain them, number of
    replies dropped because the reply arena was full, records whose callee
    failed after retries, retry attempts spent)``.

    Scalar arguments come out of the int/float lanes; payload arguments
    (``pmask`` bit set) are reattached from the arena via their descriptor —
    offset in the int lane, length in ``plens``, dtype from the ``imask``
    tag (set = int32 words, clear = float32 bitcast).

    ``reply`` (a ``(rwords, roff, rlen, rstat)`` quadruple of preallocated
    numpy arrays, or None on a reply-less drain) collects result-bearing
    records: a record whose ``rwant`` lane is nonzero has its callee's
    return value coerced to ``|rwant|`` words of the declared dtype (``+``
    = int32, ``-`` = float32 bitcast; short results zero-padded, long ones
    truncated, a None return reads as zeros) and appended at the reply
    watermark, with the slot's ``(offset, length)`` recorded for the
    device-side ``result()`` read and its STATUS stamped into ``rstat``.
    A result-bearing record whose reply cannot fit is dropped ATOMICALLY —
    callee not run, nothing written, ``REPLY_OVERFLOW`` stamped, counted.

    Every callee invocation is ISOLATED: an exception (or a wall-clock
    ``timeout`` overrun) fails only that record — ``CALLEE_RAISED`` /
    ``TIMEOUT`` stamped, traceback captured into :func:`error_log` — and
    the remaining records still replay in order.  ``retry`` (a
    :class:`RetryPolicy`) re-runs failed records for callees registered
    ``idempotent=True``.  ``base`` is the epoch's global ticket base (error
    log attribution); ``idem`` the registry idempotency snapshot.

    ``occ`` (optional, aligned to the surviving records ``[lo, n)``)
    carries per-callee occurrence indices reserved at submit time, so
    concurrent/async drains address faults identically to the serial one.
    ``carry`` (a :class:`_CarrySink`) lets a failing idempotent record be
    carried into the next epoch instead of finalizing — its slot stamps
    ``STATUS_PENDING``.  ``abandoned`` (a nullary callable) lets a
    deadline-exceeded drain stop early: its results are already discarded."""
    cap = callee.shape[0]
    lo = max(0, n - cap)
    fbuf = pbuf.view(np.float32)
    rhead = 0
    rdrops = 0
    cerrs = 0
    nretries = 0
    inj = _FAULT_INJECTOR[0] if _FAULT_INJECTOR else None
    # the fault-free default path stays a bare call in a try/except — no
    # thread pool, no injector lookup per record (the <10% overhead gate)
    fast = inj is None and retry is None and timeout is None
    lease = _WorkerLease() if timeout is not None else None
    # With a timeout but no injector/retry, the drain PIPELINES: the
    # whole epoch is submitted to the worker before the first reply is
    # settled, so the per-record thread hop collapses to O(1) context
    # switches per epoch (the only term that matters on a single-core
    # host).  An injector or retry policy forces the strict ping-pong:
    # both need record j's outcome confirmed before record j+1 may
    # dispatch (replay-order effect and occurrence determinism).
    pipelined = timeout is not None and inj is None and retry is None
    rsize = reply[0].shape[0] if reply is not None else 0
    # each entry: [call, j, k, name, args, want, occ_idx, is_idem, nbytes]
    inflight: List[list] = []
    ahead_words = 0    # reply words reserved by in-flight records

    def _post(j, k, name, args, want, occ_idx, is_idem, status, out, rr,
              nbytes):
        nonlocal rhead, cerrs, nretries
        nretries += rr
        if status != STATUS_OK:
            cerrs += 1
            if (carry is not None and is_idem
                    and status in (STATUS_CALLEE_RAISED, STATUS_TIMEOUT)
                    and carry.accept(name, args, int(base) + j,
                                     int(rwant[k]) if rwant is not None
                                     else 0, 1 + rr, occ_idx)):
                # the record will redrive at the next epoch's drain: its
                # slot reads PENDING and the final outcome lands host-side
                # (carry_outcomes / statuses_host)
                status = STATUS_PENDING
        if reply is not None:
            rwords, roff, rlen, rstat = reply
            if want != 0 and status == STATUS_OK:
                nw = abs(want)
                dt = np.int32 if want > 0 else np.float32
                try:
                    arr = (np.zeros((nw,), dt) if out is None
                           else np.asarray(out).reshape(-1).astype(dt))
                except (TypeError, ValueError):
                    # a non-numeric return must fail only THIS record's
                    # reply, not abort the drain and discard its siblings
                    warnings.warn(
                        f"RPC reply from {name!r} ({type(out).__name__}) "
                        f"is not coercible to {dt.__name__}; its reader "
                        "sees zeros", RuntimeWarning, stacklevel=2)
                    arr = np.zeros((nw,), dt)
                if arr.size < nw:
                    arr = np.pad(arr, (0, nw - arr.size))
                words = arr[:nw].view(np.int32)
                if inj is not None:
                    words = (inj.on_reply(name, words)
                             if occ_idx is None
                             else inj.on_reply(name, words, index=occ_idx))
                if words is None:
                    # injected reply drop: the callee RAN (host effects
                    # stand) but its reply never lands — reader sees
                    # zeros, status says DROPPED
                    status = STATUS_DROPPED
                else:
                    rwords[rhead:rhead + nw] = words
                    roff[k] = rhead
                    rlen[k] = nw
                    rhead += nw
                    nbytes += 4 * nw
            rstat[k] = status
        per_name_calls[name] = per_name_calls.get(name, 0) + 1
        per_name_bytes[name] = per_name_bytes.get(name, 0) + nbytes

    def _settle_oldest():
        nonlocal ahead_words
        rec = inflight.pop(0)
        call_obj, j, k, name, args, want, occ_idx, is_idem, nbytes = rec
        ahead_words -= abs(want)
        try:
            out = lease.collect(call_obj, timeout)
            status = STATUS_OK
        except _CalleeTimeout as exc:
            _log_callee_error(name, int(base) + j, 1, exc)
            status, out = STATUS_TIMEOUT, None
            redriven = lease.handle_timeout([r[0] for r in inflight])
            for r, c in zip(inflight, redriven):
                r[0] = c             # redriven on the replacement worker
        except Exception as exc:     # noqa: BLE001 — the isolation point
            _log_callee_error(name, int(base) + j, 1, exc)
            status, out = STATUS_CALLEE_RAISED, None
        _post(j, k, name, args, want, occ_idx, is_idem, status, out, 0,
              nbytes)

    for j in range(lo, n):
        if abandoned is not None and abandoned():
            if inflight:
                # the worker may still be executing a record whose result
                # nobody will read — never pool it
                lease.drop()
                inflight.clear()
            break
        k = j % cap
        cid = int(callee[k])
        name = names.get(cid)
        if name is None:
            raise KeyError(
                f"RpcQueue record carries unknown callee id {cid}: this "
                "process never bound it — a program traced elsewhere must "
                "ship its RpcManifest and the server must "
                "adopt_manifest() it before draining")
        fn = (overrides or {}).get(name) or hosts[name]
        na = int(nargs[k])
        mask = int(imask[k])
        pm = int(pmask[k])
        args = []
        nbytes = 12 + 4 * na
        for t in range(na):
            if (pm >> t) & 1:
                off, ln = int(ivals[k, t]), int(plens[k, t])
                buf = pbuf if (mask >> t) & 1 else fbuf
                args.append(buf[off:off + ln])
                nbytes += 4 * ln
            elif (mask >> t) & 1:
                args.append(int(ivals[k, t]))
            else:
                args.append(float(fvals[k, t]))
        want = int(rwant[k]) if reply is not None else 0
        if want != 0:
            # reply-arena overflow is checked BEFORE the callee runs, so
            # the drop is atomic like a request-arena drop: the record is
            # NOT executed (an effectful callee — fread consuming stream
            # bytes, remote malloc reserving heap — must not run when its
            # result can never reach the requester) and the reader sees
            # zeros with ok=False.  A pipelined record ahead may still
            # land its own words, so its reservation counts until it
            # settles; only if space is tight do we stall to learn the
            # exact watermark (sync-identical drop decisions).
            if rhead + ahead_words + abs(want) > rsize:
                while inflight:
                    _settle_oldest()
                if rhead + abs(want) > rsize:
                    rdrops += 1
                    reply[3][k] = STATUS_REPLY_OVERFLOW
                    continue
        occ_idx = occ[j - lo] if occ is not None else None
        is_idem = bool((idem or {}).get(name, False))
        if fast:
            try:
                out = fn(*args)
                status = STATUS_OK
            except Exception as exc:     # noqa: BLE001 — isolation point
                _log_callee_error(name, int(base) + j, 1, exc)
                status, out = STATUS_CALLEE_RAISED, None
            _post(j, k, name, args, want, occ_idx, is_idem, status, out,
                  0, nbytes)
        elif pipelined:
            # the whole epoch is submitted before the first settle: the
            # worker drains its inbox in one scheduling quantum and the
            # final `while inflight` loop finds nearly every result
            # already posted (O(1) context switches per epoch)
            inflight.append([lease.submit(fn, args), j, k, name, args,
                             want, occ_idx, is_idem, nbytes])
            ahead_words += abs(want)
        else:
            status, out, rr = _invoke_record(
                name, fn, args, int(base) + j, inj, retry, timeout,
                is_idem, occ_index=occ_idx, lease=lease)
            _post(j, k, name, args, want, occ_idx, is_idem, status, out,
                  rr, nbytes)
    while inflight:
        _settle_oldest()
    if lease is not None:
        lease.release()
    return lo, rdrops, cerrs, nretries


def _finish_flush(drops: int, arena_drops: int, per_name_calls,
                  per_name_bytes, reply_drops: int = 0,
                  callee_errors: int = 0, retries: int = 0):
    if drops:
        REGISTRY.bump_drops(drops)
        warnings.warn(
            f"RpcQueue flush dropped {drops} record(s): more records were "
            "enqueued than the queue capacity between flushes; the oldest "
            "were overwritten.  Flush more often or enlarge the queue.",
            RuntimeWarning, stacklevel=2)
    if arena_drops:
        warnings.warn(
            f"RpcQueue dropped {arena_drops} payload record(s) at enqueue: "
            "the payload arena was full (records dropped atomically — no "
            "partial payloads).  Flush more often or enlarge "
            "payload_capacity.", RuntimeWarning, stacklevel=2)
    if reply_drops:
        warnings.warn(
            f"RpcQueue flush dropped {reply_drops} result-bearing "
            "record(s): the reply arena was full (records dropped "
            "atomically — callee NOT run, readers see zeros).  Flush more "
            "often or enlarge reply_capacity.", RuntimeWarning,
            stacklevel=2)
    if callee_errors:
        warnings.warn(
            f"RpcQueue flush isolated {callee_errors} failing callee "
            "record(s): the callee raised or timed out, the record reads "
            "CALLEE_RAISED/TIMEOUT, and the rest of the flush completed — "
            "tracebacks in repro.core.rpc.error_log().", RuntimeWarning,
            stacklevel=2)
    REGISTRY.bump_flush(drops, arena_drops, reply_drops,
                        callee_errors=callee_errors, retries=retries)
    for name, calls in per_name_calls.items():
        REGISTRY.bump(name, None, per_name_bytes[name], 0, calls=calls)


def _san_scan_shard(cap: int, n: int, pmask, ivals, plens, pbuf
                    ) -> Tuple[int, int, int]:
    """Verify one shard's surviving payload reservations: canaries intact on
    both sides of every payload, no freed-block POISON words inside.
    Returns ``(canary_stomps, poison_hits, payloads_checked)``."""
    lo = max(0, n - cap)
    w = ivals.shape[1]
    stomps = poisons = checked = 0
    pc = pbuf.shape[0]
    can = int(CANARY)
    for j in range(lo, n):
        k = j % cap
        pm = int(pmask[k])
        for t in range(w):
            if not (pm >> t) & 1:
                continue
            off, ln = int(ivals[k, t]), int(plens[k, t])
            checked += 1
            if off < 1 or off + ln >= pc:
                # a sanitized reservation always leaves room for both
                # canaries; a descriptor outside that shape IS a stomp
                stomps += 1
                continue
            if int(pbuf[off - 1]) != can or int(pbuf[off + ln]) != can:
                stomps += 1
            if bool(np.any(pbuf[off:off + ln] == POISON)):
                poisons += 1
    return stomps, poisons, checked


def _san_record_epoch(records: int, declared: int, stomps: int, poisons: int,
                      checked: int, sharded: bool) -> None:
    """Publish one sanitized flush's shadow record + counters."""
    with _SAN_LOCK:
        _SAN["canary_stomps"] += stomps
        _SAN["poison_hits"] += poisons
        _SAN["epochs"].append({
            "records": records, "declared_replies": declared,
            "canary_stomps": stomps, "poison_hits": poisons,
            "payloads_checked": checked, "sharded": sharded})


def _reserve_occurrences(inj, names_in_order):
    """Reserve per-callee occurrence indices for a concurrent/async drain,
    in its canonical ``(device, slot)`` replay order.  Returns ``None``
    when no injector is installed or it predates ``reserve`` (legacy
    injectors then count occurrences themselves, which is only racy for
    plans that straddle concurrently-draining shards)."""
    if inj is None or not names_in_order:
        return None
    reserve = getattr(inj, "reserve", None)
    if reserve is None:
        return None
    return list(reserve(names_in_order))


def _surviving_names(callee_row, names, n: int) -> List[Optional[str]]:
    """Callee names of one shard's surviving records, in replay order."""
    cap = callee_row.shape[0]
    lo = max(0, n - cap)
    return [names.get(int(callee_row[j % cap])) for j in range(lo, n)]


#: Once-per-process latch for the CPU async-dispatch hazard warning.
_ASYNC_DISPATCH_WARNED: List[bool] = []


def _check_cpu_async_dispatch() -> None:
    """Detect the CPU async-dispatch configuration under which an ordered
    ``io_callback`` drain can deadlock (see the module docstring for the
    three-thread cycle) and warn ONCE with the pin to apply — at
    ``RpcQueue.create`` time, so the failure mode is named where the queue
    is born instead of depending on every harness remembering the pin."""
    if _ASYNC_DISPATCH_WARNED or jax.default_backend() != "cpu":
        return
    if jax.config.values["jax_cpu_enable_async_dispatch"]:
        _ASYNC_DISPATCH_WARNED.append(True)
        warnings.warn(
            "jax_cpu_enable_async_dispatch is ENABLED on the CPU backend: "
            "an ordered io_callback drain can DEADLOCK — the callback "
            "thread blocks materializing a large operand whose definition "
            "event is queued behind the computation the callback belongs "
            "to, while the main thread sits in block_until_ready.  Pin "
            'jax.config.update("jax_cpu_enable_async_dispatch", False) '
            "before creating RpcQueues (tests/conftest.py and "
            "benchmarks/common.py carry this pin).", RuntimeWarning,
            stacklevel=3)


class _CarryRec:
    """One record carried across epochs under the cross-epoch retry budget:
    its materialized args (copied out of the epoch's payload snapshot), its
    global ticket, reply declaration, how many attempts its drains have
    already spent, its reserved occurrence index, and how many carry
    rounds remain."""

    __slots__ = ("name", "args", "ticket", "want", "attempts_done",
                 "occ_index", "tries_left")

    def __init__(self, name, args, ticket, want, attempts_done, occ_index,
                 tries_left):
        self.name = name
        self.args = [np.array(a) if isinstance(a, np.ndarray) else a
                     for a in args]
        self.ticket = int(ticket)
        self.want = int(want)
        self.attempts_done = int(attempts_done)
        self.occ_index = occ_index
        self.tries_left = int(tries_left)


class _CarrySink:
    """Collects the records of ONE drain that failed and are eligible to
    carry into the next epoch (idempotent callees, ``carry_budget > 0``)."""

    def __init__(self, budget: int):
        self.budget = int(budget)
        self.records: List[_CarryRec] = []

    def accept(self, name, args, ticket, want, attempts_done, occ_index
               ) -> bool:
        if self.budget <= 0:
            return False
        self.records.append(_CarryRec(name, args, ticket, want,
                                      attempts_done, occ_index, self.budget))
        return True


#: Bound on per-(slot, device) finalized carry outcomes kept for host reads.
_OUTCOME_CAP = 4096


class _EpochJob:
    """One submitted epoch drain for one (slot, device): its reply
    quadruple once drained, the post-drain carry depth, and a done event.
    ``abandoned`` is set by a deadline-exceeded collect so the late drain
    stops early and skips its carry adds."""

    __slots__ = ("base", "out", "cdepth", "done", "abandoned")

    def __init__(self, base: int):
        self.base = int(base)
        self.out = None
        self.cdepth = 0
        self.done = threading.Event()
        self.abandoned = False


class _QueueSlot:
    """Host-side state of one async queue lineage (allocated at
    ``create``): per-device single-thread executors (the FIFO per-shard
    epoch sequence that makes independent drains deterministically
    replayable), the in-flight epoch jobs, the cross-epoch carry lists and
    the finalized carry outcomes."""

    def __init__(self, sid: int):
        self.id = sid
        self.lock = threading.Lock()
        self.execs: Dict[int, ThreadPoolExecutor] = {}
        self.pending: Dict[int, deque] = {}
        self.carry: Dict[int, List[_CarryRec]] = {}
        self.outcomes: Dict[int, Dict[int, Tuple[int, Optional[np.ndarray]]]] = {}

    # -- submit / collect ---------------------------------------------------

    def submit(self, dev: int, job: _EpochJob, runner: Callable
               ) -> Optional[_EpochJob]:
        """Queue ``runner`` on this (slot, dev)'s executor; returns the
        epoch job it should pipeline BEHIND (the previous uncollected
        one, if any)."""
        with self.lock:
            ex = self.execs.get(dev)
            if ex is None:
                ex = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"rpc-async-{self.id}-{dev}")
                self.execs[dev] = ex
            dq = self.pending.setdefault(dev, deque())
            prev = dq[-1] if dq else None
            dq.append(job)
        ex.submit(runner)
        return prev

    def collect(self, dev: int, prev: Optional[_EpochJob],
                deadline: Optional[float], cap: int, rc: int
                ) -> Tuple[Tuple[np.ndarray, ...], int]:
        """Wait for the PREVIOUS epoch's drain and return its reply
        quadruple + carry depth.  First flush (no previous epoch) returns
        zeros.  A ``deadline`` overrun abandons the job: fresh
        TIMEOUT-stamped arrays are returned (never the job's possibly
        still-being-written ones) and the late drain self-truncates."""
        if prev is None:
            with self.lock:
                return _reply_zeros(rc, cap), len(self.carry.get(dev, ()))
        ok = prev.done.wait(deadline) if deadline is not None else (
            prev.done.wait() or True)
        with self.lock:
            dq = self.pending.get(dev)
            if dq and dq[0] is prev:
                dq.popleft()
            cd = (prev.cdepth if ok else len(self.carry.get(dev, ())))
        if not ok:
            prev.abandoned = True
            return _reply_zeros(rc, cap, STATUS_TIMEOUT), cd
        out = prev.out if prev.out is not None else _reply_zeros(rc, cap)
        return out, cd

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted epoch drain (all devices) has
        completed; returns False on timeout.  Does NOT collect replies or
        advance carry rounds — those ride the next flush."""
        t0 = time.monotonic()
        with self.lock:
            jobs = [j for dq in self.pending.values() for j in dq]
        for j in jobs:
            left = (None if timeout is None
                    else max(0.0, timeout - (time.monotonic() - t0)))
            if not j.done.wait(left):
                return False
        return True

    # -- carry bookkeeping --------------------------------------------------

    def take_carry(self, dev: int) -> List[_CarryRec]:
        with self.lock:
            return self.carry.pop(dev, [])

    def put_carry(self, dev: int, recs: List[_CarryRec]) -> None:
        if not recs:
            return
        with self.lock:
            self.carry.setdefault(dev, []).extend(recs)

    def finalize(self, dev: int, ticket: int, status: int,
                 words: Optional[np.ndarray]) -> None:
        with self.lock:
            out = self.outcomes.setdefault(dev, {})
            out[ticket] = (int(status), words)
            while len(out) > _OUTCOME_CAP:
                out.pop(next(iter(out)))

    def carried_tickets(self, dev: int) -> List[int]:
        with self.lock:
            return [r.ticket for r in self.carry.get(dev, ())]

    def outcome(self, dev: int, ticket: int
                ) -> Optional[Tuple[int, Optional[np.ndarray]]]:
        with self.lock:
            return self.outcomes.get(dev, {}).get(ticket)


_SLOTS: Dict[int, _QueueSlot] = {}
_SLOT_LOCK = threading.Lock()
_NEXT_SLOT = [0]


def _new_slot() -> int:
    with _SLOT_LOCK:
        sid = _NEXT_SLOT[0]
        _NEXT_SLOT[0] += 1
        _SLOTS[sid] = _QueueSlot(sid)
        return sid


def _slot(sid: int) -> _QueueSlot:
    with _SLOT_LOCK:
        return _SLOTS[sid]


def _coerce_reply_words(name: str, out, want: int) -> Optional[np.ndarray]:
    """Coerce one callee return to ``|want|`` int32 reply words (the same
    pad/truncate/bitcast contract as the in-epoch reply path); None when
    ``want == 0``."""
    if want == 0:
        return None
    nw = abs(want)
    dt = np.int32 if want > 0 else np.float32
    try:
        arr = (np.zeros((nw,), dt) if out is None
               else np.asarray(out).reshape(-1).astype(dt))
    except (TypeError, ValueError):
        warnings.warn(
            f"RPC reply from {name!r} ({type(out).__name__}) is not "
            f"coercible to {dt.__name__}; its reader sees zeros",
            RuntimeWarning, stacklevel=2)
        arr = np.zeros((nw,), dt)
    if arr.size < nw:
        arr = np.pad(arr, (0, nw - arr.size))
    return np.array(arr[:nw].view(np.int32))


def _replay_carry(slot: _QueueSlot, dev: int, hosts, idem, overrides,
                  timeout) -> Tuple[int, int]:
    """Redrive the records carried into this epoch's drain, OLDEST FIRST,
    one attempt per carry round each.  A record that succeeds (or finally
    exhausts its budget / loses its reply to an injected drop) FINALIZES
    into the slot's outcome table; one that fails with budget left goes
    back on the carry list for the next epoch.  Returns ``(callee errors,
    records finalized)``."""
    recs = slot.take_carry(dev)
    if not recs:
        return 0, 0
    inj = _FAULT_INJECTOR[0] if _FAULT_INJECTOR else None
    cerrs = 0
    finalized = 0
    survivors: List[_CarryRec] = []
    for rec in recs:
        fn = (overrides or {}).get(rec.name) or hosts.get(rec.name)
        if fn is None:
            slot.finalize(dev, rec.ticket, STATUS_CALLEE_RAISED, None)
            finalized += 1
            continue
        status, out, _ = _invoke_record(
            rec.name, fn, rec.args, rec.ticket, inj, None, timeout,
            bool((idem or {}).get(rec.name, False)),
            first_attempt=rec.attempts_done + 1, occ_index=rec.occ_index)
        if status == STATUS_OK:
            words = _coerce_reply_words(rec.name, out, rec.want)
            if inj is not None and words is not None:
                words = (inj.on_reply(rec.name, words)
                         if rec.occ_index is None
                         else inj.on_reply(rec.name, words,
                                           index=rec.occ_index))
                if words is None:
                    status = STATUS_DROPPED
            slot.finalize(dev, rec.ticket, status, words)
            finalized += 1
            continue
        cerrs += 1
        rec.attempts_done += 1
        rec.tries_left -= 1
        if rec.tries_left <= 0:
            slot.finalize(dev, rec.ticket, status, None)
            finalized += 1
        else:
            survivors.append(rec)
    slot.put_carry(dev, survivors)
    return cerrs, finalized


def _run_async_epoch(slot: _QueueSlot, dev: int, job: _EpochJob, arrs,
                     rwant, n: int, adrops: int, base: int, rc: int,
                     cap: int, carry_budget: int, occ, overrides, retry,
                     timeout) -> None:
    """The background body of one async epoch drain for one (slot, dev):
    carry redrives first (oldest records), then this epoch's records, into
    a reply quadruple published on the job.  Runs on the (slot, dev)
    executor — strictly AFTER the previous epoch's drain, concurrently
    with the device compute that follows the flush."""
    callee, nargs, imask, pmask, ivals, fvals, plens, pbuf = arrs
    pnc: Dict[str, int] = {}
    pnb: Dict[str, int] = {}
    try:
        with REGISTRY.lock:
            names = dict(REGISTRY.batch_names)
            hosts = dict(REGISTRY.hosts)
            idem = dict(REGISTRY.idempotent)
        ccerrs, _ = _replay_carry(slot, dev, hosts, idem, overrides, timeout)
        reply = _reply_zeros(rc, cap) if rc else None
        sink = (_CarrySink(carry_budget)
                if (carry_budget and rc and not job.abandoned) else None)
        drops, rdrops, cerrs, nretries = _replay_shard(
            callee, nargs, imask, pmask, ivals, fvals, plens, pbuf,
            rwant, n, overrides, names, hosts, pnc, pnb, reply=reply,
            base=base, idem=idem, retry=retry, timeout=timeout, occ=occ,
            carry=sink, abandoned=(lambda: job.abandoned))
        if sink is not None and not job.abandoned:
            slot.put_carry(dev, sink.records)
        job.out = reply
        _finish_flush(drops, adrops, pnc, pnb, reply_drops=rdrops,
                      callee_errors=cerrs + ccerrs, retries=nretries)
    except BaseException as exc:  # noqa: BLE001 — background isolation
        _log_callee_error("<async-drain>", base, 1, exc)
        warnings.warn(
            f"async RpcQueue drain failed wholesale: {exc!r} (traceback "
            "in repro.core.rpc.error_log(); the epoch's records read "
            "status 0/zeros)", RuntimeWarning, stacklevel=2)
    finally:
        with slot.lock:
            job.cdepth = len(slot.carry.get(dev, ()))
        job.done.set()


# ---------------------------------------------------------------------------
# The drain core: the host side of every queue flush
# ---------------------------------------------------------------------------

class _DrainSpec(NamedTuple):
    """What a flush's drain does, read off the queue's static fields:
    ``sharded`` (every leaf carries a leading device axis), ``reply``
    (``reply_capacity > 0``: the operands carry ``rwant`` and ``rc``, and
    the drain returns the reply quadruple) and the queue's pytree aux.
    Hashable: it keys the cache of bound drains."""
    sharded: bool
    reply: bool
    sanitize: bool
    mode: str
    qslot: Optional[int]
    carry_budget: int
    shard_deadline: Optional[float]
    retry: Optional[RetryPolicy]
    timeout: Optional[float]


def _reply_zeros(rc: int, cap: int, status: int = STATUS_OK):
    """One shard's empty reply quadruple ``(rbuf, roff, rlen, rstat)``."""
    return (np.zeros((rc,), np.int32), np.zeros((cap,), np.int32),
            np.zeros((cap,), np.int32), np.full((cap,), status, np.int32))


def _san_check(spec: _DrainSpec, recs, rwant, heads: List[int]) -> None:
    """The sanitizer pass over the epoch's surviving payloads, run before
    any replay: one shadow record per sync flush (``sharded`` on a sharded
    queue, its counts summed over shards), one per shard epoch for async."""
    callee, _, _, pmask, ivals, _, plens, pbuf = recs
    cap = callee.shape[1]
    rows = []
    for d, n in enumerate(heads):
        lo = max(0, n - cap)
        declared = (0 if rwant is None else
                    sum(int(rwant[d, j % cap] != 0) for j in range(lo, n)))
        rows.append((min(n, cap), declared) + _san_scan_shard(
            cap, n, pmask[d], ivals[d], plens[d], pbuf[d]))
    if spec.mode == "async":
        for row in rows:
            _san_record_epoch(*row, sharded=False)
    else:
        _san_record_epoch(*(sum(c) for c in zip(*rows)),
                          sharded=spec.sharded)


def _run_shards_concurrently(run: Callable, D: int, deadline: float,
                             abandon: List[bool]) -> List[Any]:
    """Run ``run(d)`` for every shard on its own thread, all started
    together, under ONE wall-clock budget measured from the start.  Returns
    each shard's result in device order; a shard past the budget yields
    None and has its ``abandon`` flag set, so its replay stops early (its
    partial host effects stand, its private reply arrays are never read).
    A shard that raised re-raises here."""
    results: List[Any] = [None] * D
    done = [threading.Event() for _ in range(D)]

    def body(d: int) -> None:
        try:
            results[d] = run(d)
        except BaseException as exc:  # noqa: BLE001 — relayed to coordinator
            results[d] = exc
        finally:
            done[d].set()

    for d in range(D):
        threading.Thread(target=body, args=(d,), daemon=True,
                         name=f"rpc-shard-drain-{d}").start()
    t0 = time.monotonic()
    out = []
    for d in range(D):
        if done[d].wait(max(0.0, deadline - (time.monotonic() - t0))):
            if isinstance(results[d], BaseException):
                raise results[d]
            out.append(results[d])
        else:
            abandon[d] = True
            out.append(None)
    return out


def _drain_sync(spec: _DrainSpec, recs, rwant, heads, bases, adrops: int,
                rc: int, occs, overrides, registry) -> List[np.ndarray]:
    """Replay every shard inline and return the stacked reply quadruple
    (empty on a reply-less queue).  Shards replay serially in device
    order, or, on a sharded queue with ``shard_deadline``, concurrently
    under that shared budget: a shard past it reads ``STATUS_TIMEOUT``
    while its siblings' replies land (partial-epoch completion)."""
    D, cap = recs[0].shape[:2]
    names, hosts, idem = registry
    replies = [_reply_zeros(rc, cap) if spec.reply else None
               for _ in range(D)]
    concurrent = spec.sharded and spec.shard_deadline is not None
    abandon = [False] * D

    def run(d: int):
        pnc: Dict[str, int] = {}
        pnb: Dict[str, int] = {}
        counters = _replay_shard(
            *(a[d] for a in recs), None if rwant is None else rwant[d],
            heads[d], overrides, names, hosts, pnc, pnb, reply=replies[d],
            base=bases[d], idem=idem, retry=spec.retry, timeout=spec.timeout,
            occ=occs[d],
            abandoned=(lambda: abandon[d]) if concurrent else None)
        return counters, pnc, pnb

    results = (_run_shards_concurrently(run, D, spec.shard_deadline, abandon)
               if concurrent else [run(d) for d in range(D)])
    drops = rdrops = cerrs = nretries = stalled = 0
    per_name_calls: Dict[str, int] = {}
    per_name_bytes: Dict[str, int] = {}
    for d, res in enumerate(results):
        if res is None:
            stalled += 1
            replies[d] = _reply_zeros(rc, cap, STATUS_TIMEOUT)
            cerrs += min(heads[d], cap)
            continue
        (sh_drops, sh_rdrops, sh_cerrs, sh_rr), pnc, pnb = res
        drops += sh_drops
        rdrops += sh_rdrops
        cerrs += sh_cerrs
        nretries += sh_rr
        for nm, c in pnc.items():
            per_name_calls[nm] = per_name_calls.get(nm, 0) + c
            per_name_bytes[nm] = per_name_bytes.get(nm, 0) + pnb[nm]
    if stalled:
        warnings.warn(
            f"RpcQueue sharded flush abandoned {stalled} shard(s) past the "
            f"{spec.shard_deadline}s per-shard drain deadline: their "
            "records read STATUS_TIMEOUT while sibling shards completed "
            "(partial-epoch completion).", RuntimeWarning, stacklevel=2)
    _finish_flush(drops, adrops, per_name_calls, per_name_bytes,
                  reply_drops=rdrops, callee_errors=cerrs, retries=nretries)
    return [np.stack(x) for x in zip(*replies)] if spec.reply else []


def _drain_async(spec: _DrainSpec, recs, rwant, heads, bases, adrops, rc: int,
                 occs, overrides) -> List[np.ndarray]:
    """The double-buffered hand-off: SUBMIT each shard's epoch to its
    (slot, device) executor, then COLLECT each shard's previous epoch under
    the shared ``shard_deadline`` budget.  Returns the previous epochs'
    stacked reply quadruple (empty on a reply-less queue) and the carried
    depths.  Each executor is FIFO over its shard's epochs, so per-shard
    replay order is deterministic; only the cross-shard interleaving of
    host effects is not."""
    D, cap = recs[0].shape[:2]
    slot = _slot(spec.qslot)
    prevs = []
    for d in range(D):
        job = _EpochJob(bases[d])
        prevs.append(slot.submit(d, job, functools.partial(
            _run_async_epoch, slot, d, job, tuple(a[d] for a in recs),
            None if rwant is None else rwant[d], heads[d], int(adrops[d]),
            bases[d], rc, cap, spec.carry_budget, occs[d], overrides,
            spec.retry, spec.timeout)))
    t0 = time.monotonic()
    dl = spec.shard_deadline
    outs = [slot.collect(d, prevs[d],
                         None if dl is None
                         else max(0.0, dl - (time.monotonic() - t0)),
                         cap, rc)
            for d in range(D)]
    replies = ([np.stack(x) for x in zip(*(r for r, _ in outs))]
               if spec.reply else [])
    return replies + [np.array([cd for _, cd in outs], np.int32)]


def _drain(spec: _DrainSpec, overrides, *flat) -> Tuple[np.ndarray, ...]:
    """Host side of every queue flush.  ``flat`` is the flush's operand
    tuple: the eight record lanes, then ``rwant`` on a reply queue, the
    heads ``(head, phead, adrops)``, ``base``, then ``rc`` on a reply
    queue.  A reply-less flush ships neither ``rwant`` nor ``rc`` — they
    would be a dead ``(capacity,)`` device-to-host transfer on every
    fire-and-forget flush.

    A single queue drains as one shard: the leading device axis is added
    to the host views here and dropped from the results.  Each shard's
    records replay through :func:`_replay_shard` in ``(device, slot)``
    order, resolving its payloads against ITS arena slice and packing its
    replies into ITS reply buffer, under one of three schedules: serial
    (sync), concurrent under ``shard_deadline`` (sync sharded), or
    submitted to and collected from the slot's executors (async).  The
    concurrent and async schedules reserve fault-occurrence indices up
    front in the serial ``(device, slot)`` order, so faults land on the
    same records whichever thread replays them.  ``overrides`` maps callee
    names to the handlers a flush captured.

    Returns, without the device axis on a single queue: sync — the reply
    quadruple ``(rbuf, roff, rlen, rstat)``, or the drained record count
    on a reply-less queue; async — the previous epoch's quadruple (reply
    queues) and the carried depth ``cdepth``."""
    if spec.reply:
        *recs, rwant, head, _, adrops, base, rc = flat
    else:
        *recs, head, _, adrops, base = flat
        rwant, rc = None, 0
    # materialise to numpy ONCE so the per-record scalar indexing does not
    # pay a device sync each time; an async epoch drains after the callback
    # returns, when jax may reuse the operands' buffers, so it takes copies
    take = np.array if spec.mode == "async" else np.asarray
    recs = [take(x) for x in recs]
    rwant = None if rwant is None else take(rwant)
    head, adrops, base = (np.asarray(x) for x in (head, adrops, base))
    if not spec.sharded:
        recs = [x[None] for x in recs]
        rwant = None if rwant is None else rwant[None]
        head, adrops, base = head[None], adrops[None], base[None]
    heads = [int(h) for h in head]
    bases = [int(b) for b in base]
    rc = int(rc)
    if spec.sanitize:
        _san_check(spec, recs, rwant, heads)
    with REGISTRY.lock:                    # one snapshot, not per record
        registry = (dict(REGISTRY.batch_names), dict(REGISTRY.hosts),
                    dict(REGISTRY.idempotent))
    occs: List[Optional[List[int]]] = [None] * len(heads)
    if spec.mode == "async" or (spec.sharded
                                and spec.shard_deadline is not None):
        inj = _FAULT_INJECTOR[0] if _FAULT_INJECTOR else None
        per_dev = [_surviving_names(recs[0][d], registry[0], n)
                   for d, n in enumerate(heads)]
        flat_occ = _reserve_occurrences(inj, [nm for row in per_dev
                                              for nm in row])
        if flat_occ is not None:
            pos = 0
            for d, row in enumerate(per_dev):
                occs[d] = flat_occ[pos:pos + len(row)]
                pos += len(row)
    if spec.mode == "async":
        outs = _drain_async(spec, recs, rwant, heads, bases, adrops, rc,
                            occs, overrides)
    else:
        outs = _drain_sync(spec, recs, rwant, heads, bases,
                           int(adrops.sum()), rc, occs, overrides, registry)
        if not spec.reply:
            return (np.int32(sum(heads)),)
    return tuple(outs if spec.sharded else (x[0] for x in outs))


_DRAINS: Dict[_DrainSpec, Callable] = {}
_DRAINS_LOCK = threading.Lock()


def _bind_drain(spec: _DrainSpec, handlers) -> Callable:
    """The drain callable a flush hands ``io_callback``.  Handler-less
    flushes share ONE cached callable per spec, so every flush of a given
    queue geometry hands ``io_callback`` the same object (the jit cache
    and the callback registry key on callable identity); a flush with
    ``handlers`` captures them in its own.  The fault INJECTOR is
    deliberately not bound: it is looked up at dispatch time, so one
    traced program runs with and without faults."""
    if handlers:
        return functools.partial(_drain, spec, dict(handlers))
    with _DRAINS_LOCK:
        return _DRAINS.setdefault(spec, functools.partial(_drain, spec,
                                                          None))


def _flush(owner, handlers: Optional[Dict[str, Callable]]):
    """The one flush behind :meth:`RpcQueue.flush` and
    :meth:`ShardedRpcQueue.flush`: drain the epoch through ONE ordered
    ``io_callback`` when the operands are traced — or call the drain
    directly when a sharded queue's shards are concrete (the program
    boundary ``device_run(mesh=)`` flushes at: XLA cannot gather
    mesh-partitioned operands into a callback inside the partitioned
    program) — and return the emptied queue carrying the drain's replies
    (sync: this epoch's; async: the previous epoch's, with the epoch just
    closed becoming the pending window)."""
    sharded = isinstance(owner, ShardedRpcQueue)
    q = owner.q if sharded else owner
    records = (q.callee, q.nargs, q.imask, q.pmask, q.ivals, q.fvals,
               q.plens, q.pbuf)
    heads = (q.head, q.phead, q.adrops)
    traced = any(isinstance(x, jax.core.Tracer) for x in records + heads)
    if traced and not sharded:
        # lazy: rpc is imported by expand's siblings at package init
        from repro.core.expand import _ENV as _team_env_state
        if _team_env_state.axes:
            raise RuntimeError(
                "RpcQueue.flush() inside a shard_map-expanded region: "
                "XLA cannot lower the drain callback inside the "
                "partitioned program (fatal CHECK abort).  Enqueue in "
                "the region and flush at the program boundary — "
                "device_run(mesh=) and ShardedRpcQueue.flush on "
                "concrete shards do.")
    rc = q.reply_capacity
    cap = q.callee.shape[-1]
    lead = q.callee.shape[:1] if sharded else ()
    is_async = q.mode == "async"
    spec = _DrainSpec(sharded, rc > 0, bool(q.sanitize), q.mode, q.qslot,
                      q.carry_budget, q.shard_deadline, q.retry, q.timeout)
    drain = _bind_drain(spec, handlers)

    def i32(*shape):
        return jax.ShapeDtypeStruct(lead + shape, jnp.int32)

    if rc:
        operands = records + (q.rwant,) + heads + (q.base, jnp.int32(rc))
        shapes = (i32(rc), i32(cap), i32(cap), i32(cap))
    else:
        operands = records + heads + (q.base,)
        shapes = ()
    if is_async:
        shapes += (i32(),)
    elif not rc:
        shapes = (jax.ShapeDtypeStruct((), jnp.int32),)
    if traced or not sharded:
        outs = io_callback(drain, shapes, *operands, ordered=True)
    else:
        outs = tuple(jnp.asarray(a) for a in drain(*operands))
    z = jnp.zeros(lead, jnp.int32)
    fields = dict(head=z, phead=z, adrops=z, base=q.base + q.head,
                  fonce=jnp.ones_like(q.fonce))
    if rc:
        fields.update(zip(("rbuf", "roff", "rlen", "rstat"), outs))
        fields.update(rbase=q.pbase if is_async else q.base,
                      rcount=q.pcount if is_async else q.head)
    if is_async:
        fields.update(pbase=q.base, pcount=q.head, cdepth=outs[-1])
    new_q = dataclasses.replace(q, **fields)
    out = ShardedRpcQueue(new_q) if sharded else new_q
    if events.active():
        events.emit("rpc_flush", _refs=(owner, out), qid=id(q),
                    qid_out=id(new_q), capacity=cap,
                    payload_capacity=q.payload_capacity, reply_capacity=rc,
                    **({"sharded": True} if sharded else {}), mode=q.mode)
    return out


def _payload_words(a: jax.Array) -> Tuple[jax.Array, bool]:
    """Flatten an array argument to int32 arena words + its dtype tag
    (True = integer payload, False = float32 payload bitcast to i32)."""
    flat = a.reshape(-1)
    if jnp.issubdtype(flat.dtype, jnp.integer) or flat.dtype == jnp.bool_:
        return flat.astype(jnp.int32), True
    return lax.bitcast_convert_type(flat.astype(jnp.float32), jnp.int32), \
        False


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RpcQueue:
    """On-device ring of pending RPC records (the batched transport).

    Each record is ``(callee id, up to W args)``.  Scalar integer args live
    in int32 lanes, scalar floats in float32 lanes, and ``imask`` bit ``j``
    records which lane argument ``j`` used — so mixed int/float argument
    ORDER is reconstructed exactly on the host.  ARRAY args ride the flat
    payload arena ``pbuf``: one watermark (``phead``) bump reserves space
    for all of a record's payloads, each payload is copied in at a static
    partial-sum offset, and the argument's lanes hold the descriptor
    (offset in ``ivals``, length in ``plens``, presence in ``pmask`` bit j,
    int-vs-float tag in ``imask`` bit j; float payloads are bitcast into
    the i32 arena and bitcast back on the host).

    ``enqueue`` is a pure array update (zero host contact inside jit);
    ``flush`` drains every queued record AND the arena to the host in ONE
    ordered ``io_callback``, preserving enqueue order.  When more than
    ``capacity`` records accumulate, the oldest are overwritten (the drop
    is counted in :func:`queue_drops`; their arena words are simply never
    read — the arena is append-only between flushes, so surviving
    descriptors stay valid).  When the arena cannot hold a record's
    payloads, the record is dropped ATOMICALLY at enqueue: nothing is
    written, the head does not advance, and the drop is counted on device
    (``adrops``) and surfaced via ``flush_stats()['arena_drops']``.

    **Reply state.**  A queue created with ``reply_capacity > 0``
    carries a device-resident reply table: ``rwant`` declares each slot's
    expected reply (``+words`` int32, ``-words`` float32-bitcast, 0 none —
    set by ``enqueue_ticketed(returns=...)``), and after each flush
    ``rbuf``/``roff``/``rlen`` hold the host's reply words and the per-slot
    scatter of where each record's reply landed.  ``result(ticket, shape,
    dtype)`` reads them back.  Tickets are GLOBAL: ``base`` counts records
    across all epochs and never resets, each enqueue's ticket is its
    global sequence number, and flush stamps the reply table with the
    serviced epoch's ``(rbase, rcount)`` window — a ticket outside the
    window (stale, or from a dropped enqueue) reads zeros with
    ``ok=False``, it can never alias a later epoch's bytes.

    **Async epochs.**  ``create(..., mode="async")`` double-buffers
    the epochs: ``flush`` SUBMITS the closing epoch's drain to the
    queue's host slot and installs the PREVIOUS epoch's replies, so the
    reply window trails one epoch behind and ``pbase``/``pcount`` track
    the submitted-but-uncollected epoch (its tickets read
    ``STATUS_PENDING``).  ``cdepth`` mirrors the slot's carried-record
    depth (``carry_budget``) back onto the device for ``pressure()``.
    Sync queues keep all three at zero — nothing else changes shape.
    """
    callee: jax.Array    # (N,) int32 — batch callee id per record
    nargs: jax.Array     # (N,) int32 — args used in this record
    imask: jax.Array     # (N,) int32 — bit j: arg j int lane / int payload
    pmask: jax.Array     # (N,) int32 — bit j set => arg j is an array payload
    ivals: jax.Array     # (N, W) int32 — scalar value / payload offset
    fvals: jax.Array     # (N, W) float32
    plens: jax.Array     # (N, W) int32 — payload word length (0 for scalars)
    pbuf: jax.Array      # (PC,) int32 — flat payload arena (f32 bitcast in)
    head: jax.Array      # () int32 — total records ever enqueued
    phead: jax.Array     # () int32 — arena words reserved since last flush
    adrops: jax.Array    # () int32 — records dropped: arena full
    rwant: jax.Array     # (N,) int32 — expected reply words (+i32/-f32/0)
    rbuf: jax.Array      # (RC,) int32 — reply arena from the LAST flush
    roff: jax.Array      # (N,) int32 — reply offset per slot (last flush)
    rlen: jax.Array      # (N,) int32 — reply words per slot (0 = none)
    rstat: jax.Array     # (N,) int32 — reply STATUS per slot (last flush):
    #                       STATUS_OK / CALLEE_RAISED / TIMEOUT / DROPPED /
    #                       REPLY_OVERFLOW, read via result_status()
    #                       (rwant/roff/rlen/rstat are sized (0,) at RC == 0)
    base: jax.Array      # () int32 — global seq no. of this epoch's first
    #                       record (tickets = base + within-epoch order)
    rbase: jax.Array     # () int32 — base of the epoch the reply table
    #                       corresponds to (stamped at flush)
    rcount: jax.Array    # () int32 — records serviced by that flush
    fonce: jax.Array     # () int32 — 1 once this queue's lineage has flushed
    #                       (a device leaf, NOT static aux: a mid-loop flush
    #                       must not change the while_loop carry's treedef)
    pbase: jax.Array     # () int32 — async: base of the SUBMITTED epoch
    #                       whose drain has not been collected yet (its
    #                       tickets read STATUS_PENDING); sync: stays 0
    pcount: jax.Array    # () int32 — async: records in that pending epoch
    cdepth: jax.Array    # () int32 — async: carried-record depth reported
    #                       by the last collected drain (pressure() input)
    sanitize: bool = False  # static: canary-wrapped payload reservations +
    #                         sanitized drains (see sanitize_stats())
    retry: Optional[RetryPolicy] = None  # static: drain-side retry of
    #                                      idempotent callees' failures
    timeout: Optional[float] = None      # static: per-callee wall-clock
    #                                      deadline (seconds) at drain
    mode: str = "sync"   # static: "sync" (drain on the flush clock) or
    #                      "async" (double-buffered epochs)
    qslot: Optional[int] = None  # static: host slot id of an async lineage
    carry_budget: int = 0        # static: extra cross-epoch redrive rounds
    #                              for failed idempotent records (async)
    shard_deadline: Optional[float] = None  # static: per-shard drain
    #                              deadline (seconds) — concurrent sharded
    #                              drains / async collect budget

    def tree_flatten(self):
        return ((self.callee, self.nargs, self.imask, self.pmask, self.ivals,
                 self.fvals, self.plens, self.pbuf, self.head, self.phead,
                 self.adrops, self.rwant, self.rbuf, self.roff, self.rlen,
                 self.rstat, self.base, self.rbase, self.rcount, self.fonce,
                 self.pbase, self.pcount, self.cdepth),
                (bool(self.sanitize), self.retry, self.timeout, self.mode,
                 self.qslot, self.carry_budget, self.shard_deadline))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, sanitize=bool(aux[0]), retry=aux[1],
                   timeout=aux[2], mode=aux[3], qslot=aux[4],
                   carry_budget=aux[5], shard_deadline=aux[6])

    @property
    def capacity(self) -> int:
        return self.callee.shape[0]

    @property
    def width(self) -> int:
        return self.ivals.shape[1]

    @property
    def payload_capacity(self) -> int:
        return self.pbuf.shape[-1]

    @property
    def reply_capacity(self) -> int:
        return self.rbuf.shape[-1]

    # once-per-queue-object guard for the failed-ticket-read warning (a
    # plain class attribute, not a dataclass field: it is host-side
    # bookkeeping, never a pytree leaf)
    _failed_read_warned = False

    @staticmethod
    def create(capacity: int = 1024, width: int = 4,
               payload_capacity: int = 1024,
               reply_capacity: int = 0,
               sanitize: bool = False,
               retry: Optional[RetryPolicy] = None,
               timeout: Optional[float] = None,
               mode: str = "sync",
               carry_budget: int = 0,
               shard_deadline: Optional[float] = None) -> "RpcQueue":
        """``payload_capacity`` is the arena size in 4-byte words shared by
        every payload between two flushes (0 = scalar-only queue: array
        args are rejected at trace time).  ``reply_capacity`` is the REPLY
        arena size in words (0 = fire-and-forget queue: ``returns=`` is
        rejected at trace time, ``flush`` ships no reply operands, and the
        per-slot reply state is sized (0,) so the fire-and-forget
        enqueue/flush hot paths carry no dead weight).

        ``sanitize=True`` turns on the runtime sanitizer for this queue:
        every payload reservation is bracketed by :data:`CANARY` words
        (costing 2 extra arena words per payload — size the arena
        accordingly) and every flush verifies the canaries and scans
        payloads for the freed-block :data:`POISON` pattern, publishing
        findings through :func:`sanitize_stats`.  Delivered records,
        replies, and program results are bit-identical to an unsanitized
        queue as long as nothing stomps the arena.

        ``retry`` (a :class:`RetryPolicy`) re-runs records whose
        ``idempotent=True`` callee failed, with host-side exponential
        backoff; ``timeout`` (seconds) puts a wall-clock deadline on every
        callee this queue drains (overrun -> ``STATUS_TIMEOUT``, drain
        continues).  Both are static queue metadata (pytree aux).

        ``mode="async"`` switches to the double-buffered epoch
        transport: flushes submit + collect-previous instead of draining
        inline (replies land one epoch late; see the class docstring).
        ``carry_budget`` (async, reply-carrying queues) grants failed
        idempotent records that many extra cross-epoch redrive rounds;
        ``shard_deadline`` (seconds) bounds each shard's drain — a sync
        sharded flush then drains shards CONCURRENTLY with partial-epoch
        completion, an async flush bounds the previous epoch's collect."""
        if not 0 < width <= 31:
            raise ValueError(
                f"width must be in [1, 31] to fit the int32 interleave "
                f"mask; got {width}")
        if mode not in ("sync", "async"):
            raise ValueError(f"mode must be 'sync' or 'async'; got {mode!r}")
        if carry_budget:
            if mode != "async":
                raise ValueError(
                    "carry_budget requires mode='async' (the carry list "
                    "lives on the async slot; a sync drain has nowhere to "
                    "redrive from)")
            if not reply_capacity:
                raise ValueError(
                    "carry_budget requires reply_capacity > 0: a carried "
                    "record's PENDING stamp and final outcome need the "
                    "status lane")
        if shard_deadline is not None and not reply_capacity:
            raise ValueError(
                "shard_deadline requires reply_capacity > 0: a stalled "
                "shard's records are stamped STATUS_TIMEOUT in the status "
                "lane")
        _check_cpu_async_dispatch()
        rslots = capacity if reply_capacity else 0
        q = RpcQueue(
            jnp.zeros((capacity,), jnp.int32),
            jnp.zeros((capacity,), jnp.int32),
            jnp.zeros((capacity,), jnp.int32),
            jnp.zeros((capacity,), jnp.int32),
            jnp.zeros((capacity, width), jnp.int32),
            jnp.zeros((capacity, width), jnp.float32),
            jnp.zeros((capacity, width), jnp.int32),
            jnp.zeros((payload_capacity,), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((rslots,), jnp.int32),
            jnp.zeros((reply_capacity,), jnp.int32),
            jnp.zeros((rslots,), jnp.int32),
            jnp.zeros((rslots,), jnp.int32),
            jnp.zeros((rslots,), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
            sanitize=bool(sanitize), retry=retry, timeout=timeout,
            mode=mode, qslot=(_new_slot() if mode == "async" else None),
            carry_budget=int(carry_budget), shard_deadline=shard_deadline)
        events.emit("queue_create", _refs=(q,), qid=id(q),
                    capacity=capacity, width=width,
                    payload_capacity=payload_capacity,
                    reply_capacity=reply_capacity, sanitize=bool(sanitize),
                    retry=retry is not None, mode=mode)
        REGISTRY.note_queue_geometry(
            {"capacity": int(capacity), "width": int(width),
             "payload_capacity": int(payload_capacity),
             "reply_capacity": int(reply_capacity), "shards": 1})
        return q

    def enqueue(self, name: str, *args, where=None) -> "RpcQueue":
        """Queue one fire-and-forget RPC to host function ``name`` (pure
        device-side append); see :meth:`enqueue_ticketed` for the full
        semantics — this is the same append with the ticket discarded."""
        return self._enqueue(name, args, None, where)[0]

    def enqueue_ticketed(self, name: str, *args, returns=None, where=None
                         ) -> Tuple["RpcQueue", jax.Array]:
        """Queue one RPC and return ``(queue', ticket)``.

        ``args`` are scalars (ints/floats/bools, traced or concrete — which
        lane each lands in is decided by its dtype at trace time) and/or
        ARRAYS (any shape; flattened, copied into the payload arena, and
        delivered to the host as a 1-D numpy array of int32 or float32).

        ``returns`` (optional ``jax.ShapeDtypeStruct``, 32-bit-or-narrower
        dtype) declares that the callee's return value should come back
        through the reply arena: after the next flush,
        ``queue.result(ticket, returns)`` reads it.  Requires
        ``reply_capacity > 0``.  The ticket is the record's GLOBAL
        sequence number (int32, monotone across epochs; ``-1`` when the
        record was dropped — ``where=False`` or a full payload arena), so
        a ticket can only ever resolve against the flush that serviced
        its epoch.

        ``where`` (optional traced bool) makes the append conditional with
        O(record + payload) cost: the target ROW is selected against its old
        contents, payload slices read-modify-write their own reservation,
        and the heads only advance when true — no whole-queue select."""
        return self._enqueue(name, args, returns, where)

    def _enqueue(self, name: str, args, returns, where
                 ) -> Tuple["RpcQueue", jax.Array]:
        cid = REGISTRY.batch_callee_id(name)
        cap, w, pc = self.capacity, self.width, self.payload_capacity
        if len(args) > w:
            raise ValueError(
                f"RPC record for {name!r} has {len(args)} args; queue "
                f"width is {w}")
        rw = 0
        if returns is not None:
            rc = self.reply_capacity
            rshape = tuple(returns.shape)
            rdtype = jnp.dtype(returns.dtype)
            nw = int(np.prod(rshape)) if rshape else 1
            if rdtype.itemsize > 4:
                raise TypeError(
                    f"RPC record for {name!r}: reply dtype {rdtype} is "
                    "wider than the 32-bit reply arena words (a 64-bit "
                    "reply would be silently truncated); use int32/float32")
            if rc == 0:
                raise ValueError(
                    f"RPC record for {name!r} declares returns= but the "
                    "queue has no reply arena; create the queue with "
                    "reply_capacity > 0")
            if nw > rc:
                raise ValueError(
                    f"RPC record for {name!r} expects {nw} reply words but "
                    f"the reply arena only holds {rc}; enlarge "
                    "reply_capacity")
            if jnp.issubdtype(rdtype, jnp.floating):
                rw = -nw
            elif jnp.issubdtype(rdtype, jnp.integer) or rdtype == jnp.bool_:
                rw = nw
            else:
                raise TypeError(
                    f"RPC record for {name!r}: unsupported reply dtype "
                    f"{rdtype} (int, bool and float replies ride the i32 "
                    "reply arena)")
        i = self.head % cap
        iv = jnp.zeros((w,), jnp.int32)
        fv = jnp.zeros((w,), jnp.float32)
        pl = jnp.zeros((w,), jnp.int32)
        mask = 0
        pm = 0
        payloads = []                      # (words, static offset in record)
        npay = 0
        for j, s in enumerate(args):
            s = jnp.asarray(s)
            if np.shape(s) != ():
                if pc == 0:
                    raise ValueError(
                        f"RPC record arg {j} for {name!r} is an array but "
                        "the queue has no payload arena; create the queue "
                        "with payload_capacity > 0")
                words, is_int = _payload_words(s)
                if is_int:
                    mask |= 1 << j
                pm |= 1 << j
                # descriptor: offset rides the int lane, length in plens —
                # offsets are the prefix sums of this record's payloads
                # (one watermark bump reserves them all).  Under sanitize
                # each reservation is [CANARY][words][CANARY]: the
                # descriptor still points at the words (the host decode is
                # unchanged) and plens stays the true length, so the only
                # cost is 2 arena words per payload.
                iv = iv.at[j].set(self.phead + npay +
                                  (1 if self.sanitize else 0))
                pl = pl.at[j].set(words.shape[0])
                if self.sanitize:
                    cw = jnp.full((1,), CANARY, jnp.int32)
                    words = jnp.concatenate([cw, words, cw])
                payloads.append((words, npay))
                npay += words.shape[0]
            elif jnp.issubdtype(s.dtype, jnp.integer) or \
                    s.dtype == jnp.bool_:
                iv = iv.at[j].set(s.astype(jnp.int32))
                mask |= 1 << j
            else:
                fv = fv.at[j].set(s.astype(jnp.float32))
        if npay > pc:
            raise ValueError(
                f"RPC record for {name!r} carries {npay} payload words but "
                f"the arena only holds {pc}; enlarge payload_capacity")
        keep = jnp.bool_(True) if where is None else jnp.asarray(where)
        if npay:
            # atomic arena reservation: the record only exists if ALL its
            # payloads fit (no orphaned words, no dangling descriptor)
            fits = self.phead + npay <= pc
            dropped = keep & ~fits
            keep = keep & fits
        pbuf = self.pbuf
        for words, off in payloads:
            # contiguous copy-in (dynamic_update_slice, not a scatter).
            # Dropped records read-modify-write the same slice — a no-op —
            # and the automatic start clamp is only ever exercised on the
            # dropped path (a kept record's reservation fits by `fits`)
            start = (self.phead + off,)
            old = lax.dynamic_slice(pbuf, start, (words.shape[0],))
            pbuf = lax.dynamic_update_slice(
                pbuf, jnp.where(keep, words, old), start)
        cid_v = jnp.int32(cid)
        na_v = jnp.int32(len(args))
        mask_v = jnp.int32(mask)
        pm_v = jnp.int32(pm)
        rw_v = jnp.int32(rw)
        if where is None and not npay:
            step = 1
            ticket = self.base + self.head
        else:
            cid_v = jnp.where(keep, cid_v, self.callee[i])
            na_v = jnp.where(keep, na_v, self.nargs[i])
            mask_v = jnp.where(keep, mask_v, self.imask[i])
            pm_v = jnp.where(keep, pm_v, self.pmask[i])
            if self.rwant.shape[0]:
                rw_v = jnp.where(keep, rw_v, self.rwant[i])
            iv = jnp.where(keep, iv, self.ivals[i])
            fv = jnp.where(keep, fv, self.fvals[i])
            pl = jnp.where(keep, pl, self.plens[i])
            step = keep.astype(jnp.int32)
            ticket = jnp.where(keep, self.base + self.head, jnp.int32(-1))
        out = dataclasses.replace(
            self,
            callee=self.callee.at[i].set(cid_v),
            nargs=self.nargs.at[i].set(na_v),
            imask=self.imask.at[i].set(mask_v),
            pmask=self.pmask.at[i].set(pm_v),
            ivals=self.ivals.at[i].set(iv),
            fvals=self.fvals.at[i].set(fv),
            plens=self.plens.at[i].set(pl),
            pbuf=pbuf,
            head=self.head + step,
            phead=self.phead + (jnp.int32(npay) * step if npay else 0),
            adrops=(self.adrops + dropped.astype(jnp.int32) if npay
                    else self.adrops),
            # reply-less queues carry (0,) reply state: no dead scatter on
            # the fire-and-forget enqueue hot path
            rwant=(self.rwant.at[i].set(rw_v) if self.rwant.shape[0]
                   else self.rwant))
        if events.active():
            events.emit("rpc_enqueue", _refs=(self, out, ticket),
                        qid=id(self), qid_out=id(out), name=name,
                        payload_words=npay, reply_words=abs(rw),
                        ticketed=returns is not None, ticket_id=id(ticket),
                        conditional=where is not None, capacity=cap,
                        payload_capacity=pc,
                        reply_capacity=self.reply_capacity,
                        retry=self.retry is not None,
                        idempotent=REGISTRY.idempotent.get(name, False))
        return out, ticket

    def flush(self, handlers: Optional[Dict[str, Callable]] = None
              ) -> "RpcQueue":
        """Drain the queue (records + payload arena) to the host in ONE
        ordered RPC; returns the emptied queue.  Safe inside jit (ordered
        effect, never elided).

        On a reply-carrying queue (``reply_capacity > 0``) the flush is the
        TWO-PHASE epoch: the same single callback also returns the reply
        buffer + per-ticket reply table, which land in the returned queue's
        ``rbuf``/``roff``/``rlen`` — read them with :meth:`result`.  The
        returned queue therefore both starts the next epoch (heads zeroed)
        and carries the last epoch's results: thread it onward (including
        through ``lax.while_loop`` carries — flushing mid-loop and reading
        the reply on a later step is supported).

        ``handlers`` maps callee names to per-flush handlers, CAPTURED into
        this flush's compiled program — records
        for those names bypass the registry, so two compiled programs can
        drain same-named records to different handlers.  Without it, the
        drain dispatches through the registry via one stable callable.

        NOT callable inside a ``shard_map``-partitioned region: XLA aborts
        (fatally, a C++ CHECK) lowering the drain callback inside the
        partitioned program — flush at the program boundary instead
        (``device_run(mesh=)`` does).  Regions entered through this
        package (``expand(...)``, ``device_run(mesh=)``) are guarded here
        so the failure is a Python error, not a process abort; a DIRECT
        ``jax.shard_map`` of user code bypasses the guard and still hits
        the XLA abort."""
        return _flush(self, handlers)

    def join(self, timeout: Optional[float] = None) -> bool:
        """Async queues: block until every SUBMITTED epoch drain has
        completed on the host (all devices of the slot); True on success,
        False on ``timeout``.  Does not install replies or advance carry
        rounds — flush an (empty) epoch to collect; this only guarantees
        host effects and ``flush_stats()`` are settled.  Sync queues
        return True immediately (their flushes drain inline)."""
        if self.qslot is None:
            return True
        return _slot(self.qslot).join(timeout)

    def carry_outcomes(self, dev: int = 0) -> Dict[int, Tuple[int, Any]]:
        """Final outcomes of records that were CARRIED across epochs on
        this queue's slot: ``{ticket: (status, words-or-None)}``.  Only
        async queues with ``carry_budget > 0`` populate it; entries appear
        as carry rounds resolve (run ``join()`` after the final flush for
        a settled view) and the newest ``4096`` are kept."""
        if self.qslot is None:
            return {}
        slot = _slot(self.qslot)
        with slot.lock:
            return dict(slot.outcomes.get(dev, {}))

    def result(self, ticket, shape=(), dtype=None) -> jax.Array:
        """Read ticket ``ticket``'s reply from the LAST flush.

        ``shape``/``dtype`` must match the ``returns=`` declared at
        enqueue (``shape`` may be a ``jax.ShapeDtypeStruct``, in which case
        ``dtype`` is taken from it).  Returns the reply reshaped to
        ``shape``; a missing reply — dropped record (ticket ``-1``), reply
        arena overflow, stale ticket from an earlier epoch, or a length
        mismatch — reads as zeros.  Use :meth:`result_ok` for the validity
        mask.  O(1): one dynamic slice of the reply buffer."""
        return self.result_ok(ticket, shape, dtype, _via_result=True)[0]

    def result_ok(self, ticket, shape=(), dtype=None, *, _via_result=False
                  ) -> Tuple[jax.Array, jax.Array]:
        """:meth:`result` plus its validity mask: ``(value, ok)`` where
        ``ok`` is a traced bool — True iff the ticket's slot holds a
        ``STATUS_OK`` reply of exactly the expected length from the last
        flush (a record whose callee raised or timed out, whose reply was
        dropped, or whose ticket is stale reads ``ok=False`` — see
        :meth:`result_status` for WHICH failure it was)."""
        shape, dtype, nw = self._reply_spec(shape, dtype)
        never_flushed = None
        if not isinstance(self.fonce, jax.core.Tracer):
            f = np.asarray(self.fonce)
            never_flushed = bool(f.size) and not bool(f.any())
        if events.active():
            events.emit("rpc_result", _refs=(self, ticket), qid=id(self),
                        ticket_id=id(ticket), via_result=_via_result,
                        never_flushed=never_flushed)
        if never_flushed:
            warnings.warn(
                "RpcQueue.result() on a queue that has NEVER flushed: the "
                "reply table has never been written, so this read returns "
                "all-zeros indistinguishable from a real zero reply.  "
                "Flush the queue before reading tickets (the analyzer "
                "reports this as RESULT_BEFORE_FLUSH).",
                RuntimeWarning, stacklevel=3)
        rc = self.reply_capacity
        t = jnp.asarray(ticket, jnp.int32)
        # global ticket -> this reply table's epoch window: a ticket from
        # any OTHER epoch (stale or future) falls outside [rbase, rbase +
        # rcount) and reads zeros — it can never alias another epoch's
        # bytes.  Within the window, slot aliasing only happens under ring
        # overwrite (the documented caveat).
        local = t - self.rbase
        slot = jnp.where(local >= 0, local, 0) % self.capacity
        ok = (t >= 0) & (local >= 0) & (local < self.rcount) & \
            (self.rlen[slot] == nw)
        if self.rstat.shape[0]:
            ok = ok & (self.rstat[slot] == STATUS_OK)
        off = jnp.clip(self.roff[slot], 0, rc - nw)
        words = lax.dynamic_slice(self.rbuf, (off,), (nw,))
        if jnp.issubdtype(dtype, jnp.floating):
            vals = lax.bitcast_convert_type(words, jnp.float32).astype(dtype)
        else:
            vals = words.astype(dtype)
        vals = jnp.where(ok, vals, jnp.zeros_like(vals))
        if _via_result and not isinstance(ok, jax.core.Tracer):
            # concrete read through raw result(): a failed ticket's zeros
            # are about to be consumed AS IF they were a reply — say so
            # once per queue object, and let the sanitizer count it
            if not bool(np.asarray(ok)):
                if self.sanitize:
                    _san_bump("failed_ticket_reads")
                if not self._failed_read_warned:
                    self._failed_read_warned = True
                    tval = (int(np.asarray(t))
                            if not isinstance(t, jax.core.Tracer) else "?")
                    warnings.warn(
                        f"RpcQueue.result() on failed/dropped ticket "
                        f"{tval}: the read returns zeros indistinguishable "
                        "from a real zero reply — consult result_status() "
                        "or use result_ok() (warning once per queue).",
                        RuntimeWarning, stacklevel=3)
        return vals.reshape(shape), ok

    def result_status(self, ticket) -> jax.Array:
        """The STATUS of ``ticket`` against the LAST flush (traced int32):
        ``STATUS_OK`` when its callee ran and its reply (if declared)
        landed; ``STATUS_CALLEE_RAISED`` / ``STATUS_TIMEOUT`` when the
        callee failed (traceback in :func:`error_log`);
        ``STATUS_REPLY_OVERFLOW`` when the reply arena was full at drain
        (callee NOT run); ``STATUS_DROPPED`` for a ``-1`` ticket (dropped
        at enqueue) or an injected reply drop; ``STATUS_STALE`` for a
        ticket outside the last flush's window.  O(1), pure device read —
        the cond-able guard :meth:`result` lacks."""
        if self.reply_capacity == 0:
            raise ValueError(
                "result_status() on a queue with no reply arena; create "
                "the queue with reply_capacity > 0")
        if events.active():
            # a status consult counts as a guard: the analyzer's
            # UNCHECKED_STATUS rule looks for via_result=False reads
            events.emit("rpc_result", _refs=(self, ticket), qid=id(self),
                        ticket_id=id(ticket), via_result=False,
                        never_flushed=None)
        t = jnp.asarray(ticket, jnp.int32)
        local = t - self.rbase
        slot = jnp.where(local >= 0, local, 0) % self.capacity
        st = (self.rstat[slot] if self.rstat.shape[0]
              else jnp.int32(STATUS_OK))
        in_window = (local >= 0) & (local < self.rcount)
        # async: tickets of the SUBMITTED, not-yet-collected epoch read
        # PENDING (their drain may still be running on the slot executor);
        # sync queues keep pcount == 0 so this branch never fires
        plocal = t - self.pbase
        pend = (plocal >= 0) & (plocal < self.pcount)
        return jnp.where(
            t < 0, jnp.int32(STATUS_DROPPED),
            jnp.where(in_window, st,
                      jnp.where(pend, jnp.int32(STATUS_PENDING),
                                jnp.int32(STATUS_STALE))))

    def pressure(self) -> jax.Array:
        """Device-visible backpressure in ``[0, 1+)``: the max of ring,
        payload-arena, and declared-reply occupancy for the CURRENT epoch.
        Pure device arithmetic (no host contact) — cond on it before
        enqueueing, or flush early when it climbs.  ``>= 1.0`` means the
        next enqueue (or the drain) will drop records."""
        cap = self.capacity
        p = self.head.astype(jnp.float32) / cap
        if self.payload_capacity:
            p = jnp.maximum(
                p, self.phead.astype(jnp.float32) / self.payload_capacity)
        if self.reply_capacity and self.rwant.shape[0]:
            live = (jnp.arange(self.rwant.shape[0], dtype=jnp.int32)
                    < jnp.minimum(self.head, cap))
            declared = jnp.sum(jnp.abs(self.rwant) * live)
            p = jnp.maximum(
                p, declared.astype(jnp.float32) / self.reply_capacity)
        # retry-aware backpressure: records the host is CARRYING across
        # epochs (failing callees being redriven) occupy future drain
        # capacity — a degrading host pushes pressure up even when the
        # device-side ring is empty (sync queues keep cdepth == 0)
        p = jnp.maximum(p, self.cdepth.astype(jnp.float32) / cap)
        return p

    def _reply_spec(self, shape, dtype):
        """Normalize a reply read spec to ``(shape, dtype, nwords)`` with
        the arena-fit and 32-bit-width checks — the ONE place the
        ticket-read contract is validated (``result_ok`` and
        ``results_host`` both resolve through it)."""
        if hasattr(shape, "shape") and hasattr(shape, "dtype"):
            dtype = shape.dtype
            shape = tuple(shape.shape)
        shape = tuple(shape)
        dtype = jnp.dtype(dtype if dtype is not None else jnp.int32)
        nw = int(np.prod(shape)) if shape else 1
        rc = self.reply_capacity
        if rc == 0:
            raise ValueError(
                "result() on a queue with no reply arena; create the queue "
                "with reply_capacity > 0 and enqueue with returns=")
        if nw > rc:
            raise ValueError(
                f"result() reads {nw} words but the reply arena only holds "
                f"{rc}")
        if dtype.itemsize > 4:
            raise TypeError(
                f"result() dtype {dtype} is wider than the 32-bit reply "
                "arena words; use int32/float32")
        return shape, dtype, nw

    def results_host(self, tickets, shape=(), dtype=None):
        """Host-side batch read: ``[(numpy value, ok), ...]`` for many
        tickets with ONE device->host pull of the reply table.

        For concrete (post-flush, outside-jit) queues on driver/serving
        hot paths, where per-ticket :meth:`result` calls would each pay an
        eager program dispatch + transfer.  Same semantics as
        :meth:`result_ok`, ticket for ticket.

        On an async queue, a ticket whose record was CARRIED across
        epochs resolves through the slot's outcome table (its reply never
        lands in a device window), so a carried record that eventually
        succeeded reads its value here like any other — single-queue
        slots only (device 0); sharded consumers use
        :meth:`carry_outcomes` per device."""
        shape, dtype, nw = self._reply_spec(shape, dtype)
        rbuf = np.asarray(self.rbuf)
        roff = np.asarray(self.roff)
        rlen = np.asarray(self.rlen)
        rstat = np.asarray(self.rstat)
        rbase, rcount = int(self.rbase), int(self.rcount)
        np_dtype = np.dtype(dtype.name)
        outcomes = (self.carry_outcomes(0)
                    if (self.qslot is not None and self.carry_budget)
                    else {})
        out = []
        for t in tickets:
            t = int(t)
            oc = outcomes.get(t)
            if oc is not None:
                st, words = oc
                ok = (st == STATUS_OK and words is not None
                      and words.size == nw)
                if ok:
                    vals = (words.view(np.float32).astype(np_dtype)
                            if np.issubdtype(np_dtype, np.floating)
                            else words.astype(np_dtype))
                else:
                    vals = np.zeros((nw,), np_dtype)
                out.append((vals.reshape(shape), ok))
                continue
            local = t - rbase
            slot = local % self.capacity if local >= 0 else 0
            ok = (t >= 0 and 0 <= local < rcount and int(rlen[slot]) == nw
                  and (not rstat.size or int(rstat[slot]) == STATUS_OK))
            if self.sanitize and t >= 0 and not 0 <= local < rcount:
                # ticket shadow: a live ticket read outside the serviced
                # epoch's window is a stale (or dropped-epoch) read
                _san_bump("stale_ticket_reads")
            if ok:
                words = rbuf[int(roff[slot]):int(roff[slot]) + nw]
                vals = (words.view(np.float32).astype(np_dtype)
                        if np.issubdtype(np_dtype, np.floating)
                        else words.astype(np_dtype))
            else:
                vals = np.zeros((nw,), np_dtype)
            out.append((vals.reshape(shape), ok))
        return out

    def statuses_host(self, tickets) -> List[int]:
        """Host-side batch :meth:`result_status`: one int per ticket, with
        ONE device->host pull of the status lane (concrete queues on
        serving hot paths — the companion of :meth:`results_host`)."""
        if self.reply_capacity == 0:
            raise ValueError(
                "statuses_host() on a queue with no reply arena; create "
                "the queue with reply_capacity > 0")
        rstat = np.asarray(self.rstat)
        rbase, rcount = int(self.rbase), int(self.rcount)
        pbase, pcount = int(self.pbase), int(self.pcount)
        outcomes: Dict[int, Any] = {}
        carried: set = set()
        if self.qslot is not None and self.carry_budget:
            # carried records resolve host-side: a finalized outcome wins
            # over any (older) device window stamp, a still-carried ticket
            # reads PENDING (single-queue slots: device 0)
            outcomes = self.carry_outcomes(0)
            carried = set(_slot(self.qslot).carried_tickets(0))
        out = []
        for t in tickets:
            t = int(t)
            if t < 0:
                out.append(STATUS_DROPPED)
                continue
            oc = outcomes.get(t)
            if oc is not None:
                out.append(int(oc[0]))
                continue
            if t in carried:
                out.append(STATUS_PENDING)
                continue
            local = t - rbase
            if not 0 <= local < rcount:
                out.append(STATUS_PENDING if 0 <= t - pbase < pcount
                           else STATUS_STALE)
                continue
            slot = local % self.capacity
            out.append(int(rstat[slot]) if rstat.size else STATUS_OK)
        return out


# ---------------------------------------------------------------------------
# Sharded batched transport: one queue shard per mesh device
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedRpcQueue:
    """Per-device RPC queues for expanded regions (one shard per team).

    ``q`` is an :class:`RpcQueue` whose every leaf carries a leading device
    axis ``(D, ...)`` — under ``shard_map`` with a ``P(mesh_axes)`` spec on
    that axis, each device owns exactly one shard and ``enqueue`` on its
    :meth:`local_view` is a pure local array update (no cross-device
    traffic, the funnel the single-queue transport would force).

    ``flush`` gathers all shards and replays every record on the host in
    ``(flush-order, device, slot)`` order — deterministic across runs.  Two
    flush paths:

    * **concrete** (outside jit — e.g. ``device_run(mesh=...)`` flushing at
      the program boundary): the shards are materialized and drained
      directly; no callback program is built, which sidesteps XLA's refusal
      to gather mesh-partitioned operands into a maximal-device callback
      inside the partitioned program;
    * **traced** (inside jit, logical/vmapped shards on one device): ONE
      ordered ``io_callback`` over the stacked arrays.
    """
    q: RpcQueue                  # leaves: (D, ...) — device-major shards

    def tree_flatten(self):
        return ((self.q,), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0])

    @property
    def n_devices(self) -> int:
        return self.q.callee.shape[0]

    @property
    def capacity(self) -> int:
        return self.q.callee.shape[1]

    @property
    def width(self) -> int:
        return self.q.ivals.shape[2]

    @property
    def payload_capacity(self) -> int:
        return self.q.pbuf.shape[-1]

    @property
    def reply_capacity(self) -> int:
        return self.q.rbuf.shape[-1]

    @staticmethod
    def create(n_devices: int, capacity: int = 1024, width: int = 4,
               payload_capacity: int = 1024,
               reply_capacity: int = 0,
               sanitize: bool = False,
               retry: Optional[RetryPolicy] = None,
               timeout: Optional[float] = None,
               mode: str = "sync",
               carry_budget: int = 0,
               shard_deadline: Optional[float] = None
               ) -> "ShardedRpcQueue":
        q = RpcQueue.create(capacity, width, payload_capacity,
                            reply_capacity, sanitize=sanitize,
                            retry=retry, timeout=timeout, mode=mode,
                            carry_budget=carry_budget,
                            shard_deadline=shard_deadline)
        sq = ShardedRpcQueue(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_devices,) + a.shape), q))
        REGISTRY.note_queue_geometry(queue_geometry(sq))
        return sq

    # -- shard access (the expand/team protocol) -----------------------------
    def local_view(self) -> RpcQueue:
        """THIS device's shard as a plain :class:`RpcQueue` — valid inside a
        ``shard_map`` region (leading axis is the size-1 local block)."""
        assert self.q.callee.shape[0] == 1, \
            "local_view() is only meaningful on a single-device shard " \
            "(inside shard_map); use local(dev) outside"
        view = jax.tree.map(lambda a: a[0], self.q)
        if events.active():
            events.emit("queue_view", _refs=(view,), qid=id(view),
                        capacity=view.capacity, width=view.width,
                        payload_capacity=view.payload_capacity,
                        reply_capacity=view.reply_capacity,
                        sanitize=view.sanitize, mode=view.mode)
        return view

    def with_local(self, local: RpcQueue) -> "ShardedRpcQueue":
        """Inverse of :meth:`local_view`: re-wrap an updated local shard so
        ``shard_map`` out-specs can stitch the device axis back together."""
        return ShardedRpcQueue(jax.tree.map(lambda a: a[None], local))

    def local(self, dev) -> RpcQueue:
        """Device ``dev``'s shard (host-side / whole-array view)."""
        return jax.tree.map(lambda a: a[dev], self.q)

    def flush(self, handlers: Optional[Dict[str, Callable]] = None
              ) -> "ShardedRpcQueue":
        """Drain every shard (records + per-shard payload arenas) to the
        host; records replay in ``(device, slot)`` order.  Returns the
        emptied sharded queue — on a reply-carrying queue, with each
        device's reply buffer/table stacked along the device axis (read
        them with :meth:`result` or ``local(d).result``)."""
        return _flush(self, handlers)

    def join(self, timeout: Optional[float] = None) -> bool:
        """Async sharded queues: wait for every shard's submitted epoch
        drains (see :meth:`RpcQueue.join`)."""
        return self.q.join(timeout)

    def carry_outcomes(self, dev: int = 0) -> Dict[int, Tuple[int, Any]]:
        """Device ``dev``'s finalized cross-epoch carry outcomes (see
        :meth:`RpcQueue.carry_outcomes`)."""
        return self.q.carry_outcomes(dev)

    def result(self, dev, ticket, shape=(), dtype=None) -> jax.Array:
        """Device ``dev``'s reply for ``ticket`` from the last flush (the
        per-shard analogue of :meth:`RpcQueue.result`)."""
        return self.local(dev).result(ticket, shape, dtype)

    def result_status(self, dev, ticket) -> jax.Array:
        """Device ``dev``'s status for ``ticket`` (the per-shard analogue
        of :meth:`RpcQueue.result_status`)."""
        return self.local(dev).result_status(ticket)

    def pressure(self) -> jax.Array:
        """Per-device backpressure vector ``(D,)`` — each shard's
        :meth:`RpcQueue.pressure`."""
        return jax.vmap(RpcQueue.pressure)(self.q)


# ---------------------------------------------------------------------------
# Decorator: register + generate a typed device stub
# ---------------------------------------------------------------------------

def host_rpc(name: Optional[str] = None, *, result_shape,
             ordered: bool = True, pure: bool = False,
             idempotent: bool = False):
    """Register ``fn`` as host-only and return its device-callable stub.

    >>> @host_rpc(result_shape=jax.ShapeDtypeStruct((), jnp.int32))
    ... def fetch_seed(epoch):           # runs on the HOST
    ...     return np.int32(lookup(epoch))
    ...
    >>> seed, _ = fetch_seed.rpc(epoch)  # callable from jitted device code

    ``pure=True`` routes the stub through the elidable ``pure_callback``
    fast path — only for host functions with no side effects.
    ``idempotent=True`` declares re-running safe — the gate for
    :class:`RetryPolicy` retries when the callee rides a batched queue.
    """
    def deco(fn):
        rpc_name = name or fn.__name__
        REGISTRY.register(rpc_name, fn, idempotent=idempotent)

        def stub(*args):
            return rpc_call(rpc_name, *args, result_shape=result_shape,
                            ordered=ordered, pure=pure)

        fn.rpc = stub
        fn.rpc_name = rpc_name
        return fn

    return deco
