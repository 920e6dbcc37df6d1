"""One run of one cell: set up, measure for ``--seconds``, check the answers
against the plain reference, print the result line.

A system module (``bench/systems/<name>.py``, named by the configuration)
supplies ``Runner(config, traffic, seed, devices)`` with:

* ``setup()`` — data or weights from the seed, on the device, and a warm-up
  of every shape the window will use;
* ``window(seconds, spans) -> dict`` — the measured window: the cell's
  end-to-end metric values, ``attempted`` and ``failed``, and whatever the
  per-layer readers need;
* ``check() -> {name: (value, limit)}`` — run after the window and after
  ``memory_peak_bytes`` was read; a run is correct when every value is at
  most its limit.
"""
from __future__ import annotations

import json
import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

from bench import registry


class NoChip(RuntimeError):
    pass


def chips(n: int, require_tpu: bool = True):
    """The first ``n`` accelerator devices; raises :class:`NoChip` when JAX
    finds no TPU or fewer than ``n`` chips."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable backend: {e}") from e
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX reports {len(devs)}")
    return devs[:n]


def _compile_counter():
    """Counts backend compilations (persistent-cache hits are not counted)."""
    import jax
    box = {"n": 0}

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def _use_cache() -> str:
    import jax
    from repro.launch.compile_cache import use_persistent_cache
    path = use_persistent_cache()
    # every program that can be cached is: set-up then loads, not compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: Optional[float] = None, require_tpu: bool = True,
        spec: Optional[dict] = None, root: Optional[str] = None,
        config_overrides: Optional[dict] = None,
        traffic_overrides: Optional[dict] = None,
        runner_hook: Optional[Callable] = None) -> dict:
    """Run one cell and return the result line as a dict (not printed).

    ``spec``/``root``, ``config_overrides``/``traffic_overrides`` and
    ``require_tpu=False`` exist for the CPU tests, which drive a run at a
    tiny size;
    ``runner_hook(runner)`` lets a test break the timed path."""
    t0 = time.perf_counter() if t0 is None else t0
    root = root or registry.ROOT
    cell = registry.Cell(spec or registry.load_spec(root), workload, root)
    config = {**cell.config, **(config_overrides or {})}
    traffic = {**cell.traffic, **(traffic_overrides or {})}
    devices = chips(int(cell.workload["chips"]), require_tpu)
    import jax
    if require_tpu:
        _use_cache()
    compiles = _compile_counter()

    from bench.spans import Spans
    runner = cell.system.Runner(config, traffic, int(seed), devices)
    if runner_hook is not None:
        runner_hook(runner)
    runner.setup()
    setup_s = time.perf_counter() - t0
    compiles_setup = compiles["n"]

    spans = Spans(traced=bool(trace))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        with spans.span("bench.window"):
            stats = runner.window(float(seconds), spans)
        if trace:
            jax.profiler.stop_trace()
        compiles_window = compiles["n"] - compiles_setup
        peak = _peak_bytes(devices)
        reduced = None
        if trace:
            from bench.trace import Trace, find_xplane
            reduced = Trace.load(find_xplane(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    checks = runner.check()
    runner.close()
    correct = all(_ok(v, lim) for v, lim in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak}
    line: Dict[str, object] = {"correct": bool(correct),
                               "attempted": int(stats["attempted"]),
                               "failed": int(stats["failed"])}
    if not trace:
        values = dict(stats, setup_s=setup_s)
        line["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                       "unit": m["unit"]}
                           for m in cell.end_to_end}
    else:
        from bench import peaks, trace as trace_mod
        lo, hi = reduced.window() or (None, None)
        if lo is None:
            raise RuntimeError("traced window has no bench.window span")
        ctx = {"stats": stats, "trace": reduced, "window": (lo, hi),
               "window_s": (hi - lo) * 1e-9, "spans": spans,
               "config": config, "traffic": traffic,
               "peaks": peaks.peaks_for(devices[0].device_kind)}
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"] = reduced.busy_s(lo, hi)
        device["window_s"] = (hi - lo) * 1e-9
        line["breakdown"] = {
            "device_ops": trace_mod.top(trace_mod.by_label(
                reduced.op_seconds(lo, hi))),
            "idle_gaps": trace_mod.top(reduced.idle_by_span(lo, hi))}
    line["device"] = device
    line["compiles"] = {"setup": compiles_setup, "window": compiles_window}
    line["checks"] = {k: {"value": float(v), "limit": float(lim)}
                      for k, (v, lim) in checks.items()}
    return line


def _ok(value: float, limit: float) -> bool:
    return not math.isnan(value) and value <= limit


def print_line(line: dict, out=sys.stdout) -> None:
    """Stderr gets each compared number beside its limit as its last lines;
    stdout's last line is the result, with ``checks`` as its last key."""
    extra = {k: v for k, v in line.items() if k != "checks"}
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    extra["checks"] = line["checks"]
    print(json.dumps(extra), file=out, flush=True)
