"""Trace-time instrumentation seam for the static analyzer (§5.3 tooling).

The runtime (``rpc``, ``allocator``, ``device_main``, ``expand``) emits
lightweight EVENTS at trace/dispatch time — enqueues, flushes, ticket reads,
heap ops, immediate RPC issues — and the analysis layer
(:mod:`repro.analysis`) subscribes to them while it traces a program.  The
dependency points one way only: core emits through this module and never
imports ``repro.analysis``; when nothing subscribes, :func:`emit` is a
single attribute check and the runtime pays nothing.

Events carry three things the rules need and the jaxpr alone cannot give:

* **call sites** — the innermost stack frame OUTSIDE the runtime (user code,
  or the driver layer that issued the RPC), so a hazard points at the
  offending enqueue/free, not at ``rpc.py``;
* **scope context** — the stack of enclosing loop/conditional regions at
  emit time.  ``loop_scope(trips)`` marks a trace region whose emissions
  execute ``trips`` times per outer execution (``device_run`` wraps its
  step loop, the analyzer's capture patches ``lax.scan``/``lax.fori_loop``);
  ``cond_scope(period)`` marks a conditionally-executed region (a
  ``lax.cond`` branch, or a ``where=`` enqueue that statistically fires
  every ``period`` iterations).  The capacity model multiplies/divides
  through this stack to bound worst-case records per epoch, and the
  RPC-in-loop lint exempts callbacks that only live in a taken branch;
* **object identity** — ``id()`` of the queue/ticket/pointer objects
  flowing through the program, so lineages (queue -> enqueue -> flush)
  and pointer lifetimes (malloc -> free -> marshal) chain across pure
  functional updates.  Captures hold strong references to every object an
  event names (``_refs``), so a recycled ``id()`` can never alias two
  distinct objects within one capture.

Scope frames are ``(kind, uid, value)`` tuples: ``("loop", n, trips)`` with
``trips`` an int or None (statically unbounded), and ``("cond", n, period)``
with ``period`` an int >= 1 or None (plain conditional).  ``uid`` makes
frames identity-comparable so a flush and an enqueue sharing the same
enclosing loop instance can be recognized (per-iteration epochs).

The same seam carries RUNTIME spans and counters.  ``span(name)`` marks a
host phase (an engine tick's refill, a drain of the RPC transport) and
``count(name)`` bumps a host counter; both do nothing unless a caller has
switched a :class:`Recorder` on with ``recording()``.  While one is on, a
span is also written as a ``jax.profiler.TraceAnnotation`` of the same
name, so it lands on the profiler's host clock beside the device events,
and kept in memory as ``(name, start_ns, end_ns, parent)``.  The recorder
is process-wide, not per thread: a drain on a background thread records
into it too.  Sources that keep counters of their own (the transport's
flush and pad counters) ``publish`` them, so one ``Recorder.snapshot()``
reads every counter and the recorder's edges give their change over the
recording.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

ScopeFrame = Tuple[str, int, Optional[int]]


class _State(threading.local):
    def __init__(self):
        self.sinks: List[list] = []
        self.stack: List[ScopeFrame] = []
        self.uids = itertools.count()


_S = _State()


def active() -> bool:
    """True iff at least one capture is recording on this thread."""
    return bool(_S.sinks)


def _user_site() -> str:
    """Innermost stack frame outside the runtime and JAX internals.

    The analyzer's seeded-hazard corpus (``repro/analysis/corpus.py``) is
    deliberately NOT filtered — its programs are the linted subject, so
    their frames are the hazard sites the golden file pins down.
    """
    for fr in reversed(traceback.extract_stack()):
        fn = (fr.filename or "").replace("\\", "/")
        if not fn or fn.startswith("<"):
            continue
        if "/repro/analysis/" in fn and not fn.endswith("corpus.py"):
            continue
        if "/repro/core/" in fn:
            continue
        if "/jax/" in fn or "/jaxlib/" in fn:
            continue
        if fn.endswith(("/contextlib.py", "/functools.py", "/threading.py",
                        "/runpy.py")):
            continue
        return f"{fn}:{fr.lineno}"
    return "<unknown>"


def emit(kind: str, _refs: Tuple = (), **data: Any) -> None:
    """Record one event on every active capture (no-op when none).

    ``_refs`` are objects the event names by ``id()`` — the capture keeps
    them alive so identities stay unique for the capture's lifetime.
    """
    if not _S.sinks:
        return
    ev: Dict[str, Any] = {"kind": kind, "site": _user_site(),
                          "scopes": tuple(_S.stack)}
    ev.update(data)
    if _refs:
        ev["_refs"] = tuple(_refs)
    for sink in _S.sinks:
        sink.append(ev)


@contextlib.contextmanager
def record(sink: list):
    """Subscribe ``sink`` (a plain list) to this thread's events."""
    _S.sinks.append(sink)
    try:
        yield sink
    finally:
        _S.sinks.remove(sink)


@contextlib.contextmanager
def loop_scope(trips: Optional[int]):
    """Mark a trace region whose body executes ``trips`` times per outer
    execution (None = statically unbounded)."""
    frame = ("loop", next(_S.uids),
             None if trips is None else max(int(trips), 0))
    _S.stack.append(frame)
    try:
        yield
    finally:
        _S.stack.pop()


@contextlib.contextmanager
def cond_scope(period: Optional[int] = None):
    """Mark a conditionally-executed trace region.  ``period`` (optional)
    declares the region fires at most once every ``period`` iterations of
    the innermost enclosing loop — ``device_run`` hooks pass their
    ``every`` so the capacity model divides instead of assuming
    fires-every-step."""
    frame = ("cond", next(_S.uids),
             None if period is None else max(int(period), 1))
    _S.stack.append(frame)
    try:
        yield
    finally:
        _S.stack.pop()


def scopes() -> Tuple[ScopeFrame, ...]:
    """Snapshot of the current scope stack (innermost last)."""
    return tuple(_S.stack)


# -- runtime spans and counters --------------------------------------------

Span = Tuple[str, int, int, Optional[str]]  # (name, start_ns, end_ns, parent)


class Recorder:
    """Spans and counters recorded while :func:`recording` is on.

    ``spans`` lists ``(name, start_ns, end_ns, parent)`` in the order they
    closed, times on the wall clock (``time.time_ns``) that the profiler
    stamps its host events with (an exported trace counts from its own
    start); ``parent`` is the span open around it on the same thread,
    unless the caller named one.  ``counters`` holds this
    recording's own counts; ``before`` and ``after`` are the
    :meth:`snapshot` at its two edges, and ``deltas`` their difference."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}
        self.before: Dict[str, int] = {}
        self.after: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._open = threading.local()

    def snapshot(self) -> Dict[str, int]:
        """This recording's counters and every published source's, the
        latter as ``<source>.<counter>``."""
        with self._lock:
            out = dict(self.counters)
        for source, read in list(_PUBLISHED.items()):
            for k, v in read().items():
                out[f"{source}.{k}"] = int(v)
        return out

    @property
    def deltas(self) -> Dict[str, int]:
        """Change of each counter between the recording's two edges."""
        return {k: v - self.before.get(k, 0) for k, v in self.after.items()}

    def _count(self, name: str, n: int) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def _stack(self) -> list:
        st = getattr(self._open, "names", None)
        if st is None:
            st = self._open.names = []
        return st


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


class _Span:
    __slots__ = ("rec", "name", "parent", "ann", "t0")

    def __init__(self, rec: Recorder, name: str, parent: Optional[str]):
        self.rec, self.name, self.parent = rec, name, parent

    def __enter__(self):
        stack = self.rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.name)
        self.ann = self.rec._annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.time_ns()
        return None

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self.ann.__exit__(*exc)
        self.rec._stack().pop()
        self.rec.spans.append((self.name, self.t0, t1, self.parent))
        return False


class _Runtime:
    rec: Optional[Recorder] = None


_RT = _Runtime()
_NULL_SPAN = _NullSpan()
_PUBLISHED: Dict[str, Callable[[], Dict[str, int]]] = {}


def span(name: str, parent: Optional[str] = None):
    """Context manager timing a host phase; the shared no-op when no
    recorder is on."""
    rec = _RT.rec
    if rec is None:
        return _NULL_SPAN
    return _Span(rec, name, parent)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to a host counter of the recorder that is on, if any."""
    rec = _RT.rec
    if rec is None:
        return
    rec._count(name, n)


def publish(source: str, read: Callable[[], Dict[str, int]]) -> None:
    """Expose counters a module keeps itself: ``read()`` returns them by
    name and every :meth:`Recorder.snapshot` includes them."""
    _PUBLISHED[source] = read


@contextlib.contextmanager
def recording():
    """Switch the process's recorder on for the block; yields the
    :class:`Recorder`.  One recorder at a time."""
    if _RT.rec is not None:
        raise RuntimeError("a recorder is already on")
    rec = Recorder()
    rec.before = rec.snapshot()
    _RT.rec = rec
    try:
        yield rec
    finally:
        _RT.rec = None
        rec.after = rec.snapshot()
