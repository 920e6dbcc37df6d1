"""Read each device op's ``tf_op`` from a profiler trace (``.xplane.pb``).

``jax._src.profiler.ProfileData`` gives an op event's name and times but
not its metadata's stats, and the ``tf_op`` stat is where the op's name
path lives: ``jit(serve_step)/while/body/kvcache/scatter:``, with every
``jax.named_scope`` the op was traced under.  This reads the XSpace
protobuf's wire format directly, with the standard library alone; only the
device planes' ``XLA Ops`` lines are decoded, the rest is skipped by length.

Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:
XSpace.planes 1; XPlane.name 2, lines 3, event_metadata 4 (map entry: key
1, value 2), stat_metadata 5; XLine.name 2, timestamp_ns 3, events 4;
XEvent.metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata.id 1,
stats 5; XStat.metadata_id 1, str_value 5, ref_value 7; XStatMetadata.id
1, name 2.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

OpEvent = Tuple[float, float, str, str]   # (start_ns, end_ns, tf_op, name)


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for varints, the
    bytes for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _op_metadata(plane: bytes, stat_names: Dict[int, str]
                 ) -> Dict[int, Tuple[str, str]]:
    """Event metadata id -> ``(tf_op, name)``: the op's ``tf_op`` stat
    (empty where it has none) and its name, the HLO instruction."""
    tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"), None)
    out: Dict[int, Tuple[str, str]] = {}
    for f, entry in _fields(plane):
        if f != 4:
            continue
        mid, meta = _map_entry(entry)
        name, tf_op = "", ""
        for g, v in _fields(meta):
            if g == 2:
                name = v.decode("utf-8", "replace")
            elif g == 5:
                sid, value = None, None
                for h, x in _fields(v):
                    if h == 1:
                        sid = x
                    elif h == 5:
                        value = x.decode("utf-8", "replace")
                    elif h == 7:
                        value = stat_names.get(x, "")
                if sid == tf_op_id and value is not None:
                    tf_op = value
        out[mid] = (tf_op, name)
    return out


def _stat_names(plane: bytes) -> Dict[int, str]:
    out: Dict[int, str] = {}
    for f, entry in _fields(plane):
        if f == 5:
            sid, meta = _map_entry(entry)
            for g, v in _fields(meta):
                if g == 2:
                    out[sid] = v.decode("utf-8", "replace")
    return out


def _line_events(line: bytes, meta: Dict[int, Tuple[str, str]]
                 ) -> List[OpEvent]:
    """The line's events if it is the ``XLA Ops`` line, else none."""
    name, base_ns, events = b"", 0, []
    for f, v in _fields(line):
        if f == 2:
            name = v
        elif f == 3:
            base_ns = v
        elif f == 4:
            events.append(v)
    if name != b"XLA Ops":
        return []
    out = []
    for ev in events:
        mid = offset_ps = duration_ps = 0
        for f, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                offset_ps = v
            elif f == 3:
                duration_ps = v
        # whole nanoseconds, as ProfileData rounds them
        start = float(base_ns + offset_ps // 1000)
        out.append((start, start + duration_ps // 1000,
                    *meta.get(mid, ("", ""))))
    return out


def device_op_scopes(path: str) -> Dict[str, List[OpEvent]]:
    """Each device plane's ``XLA Ops`` events as ``(start_ns, end_ns,
    tf_op, name)``, on the clock ``ProfileData`` gives them (``tf_op`` is
    empty for an op without one)."""
    with open(path, "rb") as fh:
        space = fh.read()
    out: Dict[str, List[OpEvent]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, lines = "", []
        for g, v in _fields(plane):
            if g == 2:
                name = v.decode("utf-8", "replace")
            elif g == 3:
                lines.append(v)
        if not name.startswith("/device:"):
            continue
        meta = _op_metadata(plane, _stat_names(plane))
        ops = [e for ln in lines for e in _line_events(ln, meta)]
        if ops:
            out[name] = ops
    return out
