"""Run one cell with the program's recorder on and a profile of the window
kept, and print what the program's own spans, scopes and counters say:

    python3 -m bench.profile --workload <cell> --seed <n> --seconds <s> \\
        [--out <dir>]

The cell runs as ``bench.run`` runs it, except that around the window the
JAX profiler writes its trace to ``--out`` and ``repro.core.events`` records
the program's spans and counters.  The last stdout line is JSON: the run's
end-to-end metrics (timed with the profiler on) and ``correct``, every
per-layer metric of the cell (on a chip with published peaks) and those of
``PROGRAM_METRICS``, the device's
idle time by the innermost host span open (the program's phases included),
the device self time of the leading ``tf_op`` scope paths, the counters'
change over the window, and the offset between the trace's host and device
clocks (also on stderr)."""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

#: per-layer metrics that read the program's spans, scopes and counters
PROGRAM_METRICS = {
    "serve-batch": ["engine.host_ms.batch", "engine.wait_ms.batch",
                    "engine.host_reads.batch", "kvcache.device_ms.batch",
                    "serve.step_device_ms.batch"],
    "xsbench-event": ["device_run.loop_ms.xs", "rpc.drain_ms.xs",
                      "device_run.host_contacts.xs"],
}

#: a cell's step program (its module, the root of its ops' ``tf_op``) and
#: the host span that waits for it: the clock offset is measured on these
STEP = {"serve-batch": ("jit_serve_step", "jit(serve_step)", "engine.wait")}


def _recording_hook(out_dir: str, box: dict):
    """Wraps the runner's window: profiler and recorder on around it; the
    runner and the recorder are left in ``box``.  A program without the
    recorder runs with the profiler alone (its recorder is None)."""
    import contextlib

    import jax

    from bench.spans import Spans
    from repro.core import events
    recording = getattr(events, "recording", contextlib.nullcontext)

    def hook(runner):
        box["runner"] = runner
        window = runner.window

        def profiled(seconds, _spans):
            spans = Spans(traced=True)
            jax.profiler.start_trace(out_dir)
            try:
                with recording() as rec:
                    with spans.span("bench.window"):
                        stats = window(seconds, spans)
            finally:
                jax.profiler.stop_trace()
            box["recorder"], box["spans"] = rec, spans
            return stats

        runner.window = profiled
    return hook


def summarize(cell, runner, line: dict, rec, spans, trace_dir: str) -> dict:
    """The profile's metrics and breakdowns of one run."""
    from bench import peaks, program, trace, xplane
    path = trace.find_xplane(trace_dir)
    tr = program.load_trace(path)
    lo, hi = tr.window()
    ops = xplane.device_op_scopes(path)
    scopes = program.self_seconds(ops, lo, hi)
    ctx = {"stats": runner.stats, "trace": tr, "window": (lo, hi),
           "window_s": (hi - lo) * 1e-9, "config": runner.c,
           "traffic": runner.t,
           "peaks": peaks.PEAKS.get(line["device"]["kind"]),
           "program_spans": rec.spans if rec else [],
           "counters": rec.deltas if rec else {}, "scopes": scopes}
    # the cell's own metrics need the chip's published peaks
    names = [m["name"] for m in cell.per_layer] if ctx["peaks"] else []
    names += [n for n in PROGRAM_METRICS.get(cell.name, []) if n not in names]
    metrics = {}
    for name in names:
        v = cell.reader(name).read(ctx)
        if v is not None:
            metrics[name] = float(v)
    units = runner.stats.get("ticks") or runner.stats.get("simulations")
    per_unit = {n: 1e3 * spans.total(n) / units for n in spans.seconds}
    for n, s, e, _ in ctx["program_spans"]:
        per_unit[n] = per_unit.get(n, 0.0) + (e - s) * 1e-6 / units
    out = {"metrics": metrics,
           "spans_ms_per_unit": per_unit,
           "counters": {k: v for k, v in ctx["counters"].items() if v},
           "device": {"busy_s": tr.busy_s(lo, hi),
                      "window_s": (hi - lo) * 1e-9},
           "idle_gaps": trace.top(tr.idle_by_span(lo, hi), 12),
           "device_scopes": trace.top(scopes, 15)}
    module, root, wait = STEP.get(cell.name, (None, None, None))
    if module is None:
        return out
    # device time inside the step program that no scope of it can name
    out["untagged_step"] = trace.top(program.untagged_in(
        ops, tr, module, root, lo, hi), 8)
    offs = program.clock_offsets_ms(tr, module, wait)
    if offs:
        med, iqr = program.spread(offs)
        out["clock_offset_ms"] = {"median": med, "iqr": iqr,
                                  "min": min(offs), "n": len(offs)}
        print(f"clock: host end of {wait} less device end of {module}: "
              f"median {med:.4f} ms, IQR {iqr:.4f} ms, least "
              f"{min(offs):.4f} ms over {len(offs)} ticks", file=sys.stderr)
    return out


def profile(workload: str, seed: int, seconds: float, out_dir: str,
            **run_kwargs) -> dict:
    """Run the cell profiled (``run_kwargs`` go to ``harness.run``) and
    return the result line as a dict."""
    from bench import harness, registry
    os.makedirs(out_dir, exist_ok=True)
    box: dict = {}
    cell = registry.Cell(registry.load_spec(), workload)
    line = harness.run(workload, seed, seconds, False,
                       runner_hook=_recording_hook(out_dir, box),
                       **run_kwargs)
    prof = summarize(cell, box["runner"], line, box["recorder"],
                     box["spans"], out_dir)
    return {"workload": workload, "seed": seed, "correct": line["correct"],
            "end_to_end": {k: v["value"] for k, v in line["metrics"].items()},
            **prof}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python3 -m bench.profile")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=None,
                   help="trace directory (default chiprun_out/profile/"
                        "<cell>-<seed>)")
    args = p.parse_args(argv)

    from bench import harness
    out_dir = args.out or os.path.join(
        "chiprun_out", "profile", f"{args.workload}-{args.seed}")
    try:
        result = profile(args.workload, args.seed, args.seconds, out_dir,
                         t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
