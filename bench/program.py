"""What a traced run reads from the program's own instrumentation
(``repro.core.events``): its host spans in the trace, its counters, and the
device self time of each op's ``tf_op`` scope path.

The readers in ``metrics/`` that use this find, beside the harness's keys,
``ctx["program_spans"]`` (the recorder's ``(name, start_ns, end_ns,
parent)``), ``ctx["counters"]`` (the recorder's change of every counter
over the window) and ``ctx["scopes"]`` (``{tf_op: device seconds}``), and
return None where one is missing: a program without the recorder has none.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import xplane
from bench.trace import Trace, op_label

#: host spans loaded from the trace: the harness's and the program's
SPAN_PREFIXES = ("bench.", "engine.", "rpc.")


def load_trace(path: str) -> Trace:
    """The trace (a file, or a directory holding one) with the program's
    spans beside the harness's, so that ``idle_by_span`` gives each device
    gap to the innermost of either."""
    return Trace.load(path, span_prefix=SPAN_PREFIXES)


def self_seconds(per_plane: Dict[str, Sequence[xplane.OpEvent]],
                 lo: float, hi: float) -> Dict[str, float]:
    """Device self time of each ``tf_op`` inside [lo, hi], averaged over
    the planes that ran any.  Each instant of busy time goes to the
    innermost op running then (the latest started of those still running),
    so a loop op does not count the ops of its body again."""
    tot: Dict[str, float] = {}
    planes = 0
    for evs in per_plane.values():
        evs = [(max(s, lo), min(e, hi), op) for s, e, op, *_ in evs
               if e > lo and s < hi]
        if not evs:
            continue
        planes += 1
        own: Dict[str, float] = {}
        stack: List[Tuple[float, str]] = []      # (end, tf_op) open ops
        for s, e, op in sorted(evs, key=lambda t: (t[0], -t[1])):
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                end, parent = stack[-1]
                own[parent] = own.get(parent, 0.0) - (min(e, end) - s)
            own[op] = own.get(op, 0.0) + (e - s)
            stack.append((e, op))
        for op, ns in own.items():
            tot[op] = tot.get(op, 0.0) + ns * 1e-9
    return {k: v / planes for k, v in tot.items()} if planes else {}


def in_scope(tf_op: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the op's name path (fused ops
    join their paths with ``;``)."""
    return any(scope in path.split("/") for path in tf_op.split(";"))


def scope_seconds(scopes: Dict[str, float], root: str,
                  scope: Optional[str] = None, inside: bool = True) -> float:
    """Seconds of the ops under the program ``root`` (``jit(<name>)``),
    of those within ``scope`` (``inside``) or outside it."""
    total = 0.0
    for op, s in scopes.items():
        if not op.startswith(root + "/"):
            continue
        if scope is None or in_scope(op, scope) == inside:
            total += s
    return total


def span_seconds(spans: Iterable[tuple], names: Iterable[str]) -> float:
    names = set(names)
    return sum(e - s for n, s, e, *_ in spans if n in names) * 1e-9


def clock_offsets_ms(trace: Trace, module: str = "jit_serve_step",
                     wait: str = "engine.wait") -> List[float]:
    """For each ``wait`` span in the trace, the host clock's end of the
    span less the device clock's end of the ``module`` run it waited for
    (the latest to start before the span ended), in ms.  What the span
    still did after the program ended (the token's own read) is included:
    the least of these bounds the offset between the two clocks."""
    runs = sorted((s, e) for evs in trace.device_modules.values()
                  for n, s, e in evs if n.startswith(module + "("))
    starts = [s for s, _ in runs]
    out = []
    for n, _, e in trace.host_spans:
        i = bisect.bisect_right(starts, e) - 1
        if n == wait and i >= 0:
            out.append((e - runs[i][1]) * 1e-6)
    return out


def untagged_in(per_plane: Dict[str, Sequence[xplane.OpEvent]],
                trace: Trace, module: str, root: str, lo: float,
                hi: float) -> Dict[str, float]:
    """Device self time, by op label, of the ops that ran inside a
    ``module`` run in [lo, hi] but whose ``tf_op`` is not under ``root``:
    ops that no scope of the program can name (an op XLA added keeps no
    metadata)."""
    runs = sorted((s, e) for evs in trace.device_modules.values()
                  for n, s, e in evs if n.startswith(module + "("))
    starts = [s for s, _ in runs]

    def inside(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= runs[i][1]

    keyed = {p: [(s, e, op if op.startswith(root + "/") else
                  "?" + op_label(name)) for s, e, op, name in evs
                 if inside(s, e)]
             for p, evs in per_plane.items()}
    return {k[1:]: v for k, v in self_seconds(keyed, lo, hi).items()
            if k.startswith("?")}


def spread(values: Sequence[float]) -> Tuple[float, float]:
    """Median and the distance between the first and third quartiles."""
    if len(values) < 2:
        return (values[0] if values else float("nan")), 0.0
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return q2, q3 - q1
