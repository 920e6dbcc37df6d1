"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  A suite whose ``run()``
returns a dict with a ``name`` key additionally emits a perf-trajectory
artifact ``BENCH_<name>.json`` (to ``$BENCH_ARTIFACT_DIR`` or cwd) that CI
uploads, so future PRs can diff performance — ``fig6_allocator`` emits
``BENCH_allocator.json`` (per-grid µs/alloc for generic vs balanced v1 vs
v2, the find_obj v1-vs-v2 contrast, the sharded-vs-funneled heap/queue
contrast — with the >=0.9x sharded-parity assertion — and the
``sharded_mesh`` entry: malloc_grid + sharded queue flush under a real
>=2-device mesh with bit-identical-to-single-heap verification);
``fig7_rpc`` emits ``BENCH_rpc.json`` (per-call vs batched scalar records,
the v3 payload contrast at 1/64/1024 elements, and the sharded queue
contrast).

  PYTHONPATH=src python -m benchmarks.run [--only fig6,fig7,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (allocator_bench, amgmk_pagerank_bench, hypterm_bench,
                        interleaved_bench, rpc_bench, rsbench_bench,
                        spec_bench, xsbench_bench)
from benchmarks.common import write_artifact
from repro.launch.compile_cache import use_persistent_cache

SUITES = {
    "fig6_allocator": allocator_bench.run,
    "fig7_rpc": rpc_bench.run,
    "fig8a_xsbench": xsbench_bench.run,
    "fig8b_rsbench": rsbench_bench.run,
    "fig9a_interleaved": interleaved_bench.run,
    "fig9b_hypterm": hypterm_bench.run,
    "fig9c_amgmk_pagerank": amgmk_pagerank_bench.run,
    "fig10_spec": spec_bench.run,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated suite prefixes")
    args = ap.parse_args(argv)
    only = args.only.split(",") if args.only else None
    use_persistent_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in SUITES.items():
        if only and not any(name.startswith(o) for o in only):
            continue
        print(f"# === {name} ===", flush=True)
        try:
            result = fn()
            if isinstance(result, dict) and result.get("name"):
                write_artifact(f"BENCH_{result['name']}.json", result)
        except Exception:
            failures += 1
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
