"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and idle gaps attributed to the host spans open at the time.

Device planes are those named ``/device:<platform>:<n>``; on each, the
``XLA Ops`` line holds one event per operation the device ran and the
``XLA Modules`` line one event per program.  Host spans are the
``TraceAnnotation`` events the harness writes on the host plane; they share
the trace's clock with the device events.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, sorted and non-overlapping."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] not covered by the merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Events of one trace, grouped for the reductions below."""

    def __init__(self, device_ops: Dict[str, List[Tuple[str, float, float]]],
                 device_modules: Dict[str, List[Tuple[str, float, float]]],
                 host_spans: List[Tuple[str, float, float]]):
        self.device_ops = device_ops          # plane -> [(name, start, end)]
        self.device_modules = device_modules
        self.host_spans = host_spans          # [(name, start, end)]

    @classmethod
    def load(cls, path: str, span_prefix: str = "bench.") -> "Trace":
        from jax._src.profiler import ProfileData
        if os.path.isdir(path):
            path = find_xplane(path)
        data = ProfileData.from_file(path)
        ops: Dict[str, list] = {}
        modules: Dict[str, list] = {}
        spans: list = []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        dst = ops.setdefault(plane.name, [])
                    elif line.name == "XLA Modules":
                        dst = modules.setdefault(plane.name, [])
                    else:
                        continue
                    for ev in line.events:
                        dst.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(span_prefix):
                            spans.append((ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
        return cls(ops, modules, spans)

    # -- window -------------------------------------------------------------
    def window(self, name: str = "bench.window") -> Optional[Interval]:
        hits = [(s, e) for n, s, e in self.host_spans if n == name]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)

    # -- reductions -----------------------------------------------------------
    def busy(self, lo: float, hi: float) -> Dict[str, List[Interval]]:
        """Merged busy intervals of each device plane inside [lo, hi]."""
        return {p: clip(merge((s, e) for _, s, e in evs), lo, hi)
                for p, evs in self.device_ops.items()}

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        per = [sum(e - s for s, e in iv) for iv in self.busy(lo, hi).values()]
        per = [b for b in per if b > 0]
        return (sum(per) / len(per)) * 1e-9 if per else 0.0

    def op_seconds(self, lo: float, hi: float) -> Dict[str, float]:
        """Device seconds per op name inside [lo, hi], averaged over the
        devices that ran any."""
        return _seconds(self.device_ops, lo, hi)

    def module_seconds(self, lo: float, hi: float) -> Dict[str, float]:
        """Device seconds per program inside [lo, hi], likewise."""
        return _seconds(self.device_modules, lo, hi)

    def idle_by_span(self, lo: float, hi: float,
                     window_name: str = "bench.window") -> Dict[str, float]:
        """Idle seconds of the first busy device inside [lo, hi], each gap
        split over the innermost harness span open on the host during it
        (``idle`` where none is)."""
        busy = self.busy(lo, hi)
        planes = [p for p, iv in sorted(busy.items()) if iv]
        if not planes:
            return {}
        spans = sorted((s, e, n) for n, s, e in self.host_spans
                       if n != window_name)
        out: Dict[str, float] = {}
        for gs, ge in gaps(busy[planes[0]], lo, hi):
            covered = 0.0
            # innermost = shortest span overlapping each piece; walk spans
            # and give each overlap to its span, shortest spans first
            pieces = [(gs, ge)]
            for s, e, n in sorted(spans, key=lambda t: t[1] - t[0]):
                if e <= gs or s >= ge:
                    continue
                rest = []
                for ps, pe in pieces:
                    a, b = max(ps, s), min(pe, e)
                    if b > a:
                        out[n] = out.get(n, 0.0) + (b - a) * 1e-9
                        covered += b - a
                        if a > ps:
                            rest.append((ps, a))
                        if pe > b:
                            rest.append((b, pe))
                    else:
                        rest.append((ps, pe))
                pieces = rest
                if not pieces:
                    break
            left = (ge - gs) - covered
            if left > 0:
                out["idle"] = out.get("idle", 0.0) + left * 1e-9
        return out


def _seconds(per_plane, lo: float, hi: float) -> Dict[str, float]:
    tot: Dict[str, float] = {}
    planes = [evs for evs in per_plane.values() if evs]
    for evs in planes:
        for name, s, e in evs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
    return {k: v / len(planes) for k, v in tot.items()}


def op_label(name: str) -> str:
    """A short label for an ``XLA Ops`` event, whose name is the whole HLO
    instruction: its name and its opcode, e.g. ``%fusion.12 fusion``."""
    head, _, rest = name.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9_-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head[:80]


def by_label(d: Dict[str, float]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in d.items():
        lab = op_label(k)
        out[lab] = out.get(lab, 0.0) + v
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
