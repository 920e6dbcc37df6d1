"""Static analyzer (ISSUE 6 tentpole): hazard rules, walker, corpus, CLI.

Positive coverage: every seeded corpus program is flagged with exactly its
pinned hazard codes, at sites inside the corpus file (the offending
enqueue/free/read lines).  Negative coverage: every ``*_fixed`` corpus
program reports zero hazards — plus both ``examples/`` scripts in
``test_examples.py``.  The capacity multiplicity math and the jaxpr
walker's cond-exemption are unit-tested directly.
"""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (ALL_CODES, Hazard, HazardReport, analyze,
                            analyze_jaxpr, capture)
from repro.analysis import corpus
from repro.analysis.capacity import multiplicity
from repro.analysis.model import (CAPACITY_CODES, PERF_CODES,
                                  POINTER_CODES, TICKET_CODES)
from repro.core import events
from repro.core.rpc import REGISTRY, RpcQueue, rpc_call

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(ROOT, "tests", "data", "hazard_corpus.json")
I32 = jax.ShapeDtypeStruct((), jnp.int32)


# ---------------------------------------------------------------------------
# Corpus: positive AND negative coverage for every hazard class
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", corpus.CASES, ids=lambda c: c.name)
def test_corpus_case(case):
    report = corpus.run_case(case)
    assert report.codes == sorted(case.expect), \
        f"{case.name}: expected {sorted(case.expect)}, " \
        f"found {report.codes}\n{report.summary()}"


def test_corpus_covers_six_plus_classes_with_both_polarities():
    flagged = {code for c in corpus.CASES for code in c.expect}
    assert len(flagged) >= 6, flagged
    # every buggy case has a corrected twin (the walker-only mesh case
    # is trace-only: its "fix" is the runtime's boundary-drain design)
    buggy = {c.name for c in corpus.CASES if c.expect}
    fixed = {c.name for c in corpus.CASES if not c.expect}
    for name in buggy - {"callback_in_loop", "callback_in_mesh"}:
        assert f"{name}_fixed" in fixed, name
    assert all(code in ALL_CODES for code in flagged)


def test_corpus_sites_point_into_corpus():
    """A hazard blames the corpus line that seeded it, not the runtime."""
    for name in ("never_flushed", "use_after_free", "double_free",
                 "rpc_in_loop", "capacity_records"):
        case = next(c for c in corpus.CASES if c.name == name)
        report = corpus.run_case(case)
        assert report, name
        for h in report.hazards:
            assert "corpus.py" in h.site, (name, h)


def test_never_flushed_site_is_the_enqueue_line():
    src_file = corpus.__file__.replace(".pyc", ".py")
    with open(src_file) as f:
        lines = f.read().splitlines()
    lineno = next(i for i, ln in enumerate(lines, 1)
                  if "BUG: dropped, no flush" in ln)
    case = next(c for c in corpus.CASES if c.name == "never_flushed")
    (h,) = corpus.run_case(case).hazards
    assert h.site.endswith(f"corpus.py:{lineno}"), (h.site, lineno)


def test_golden_file_matches_corpus():
    with open(GOLDEN) as f:
        golden = json.load(f)["cases"]
    assert set(golden) == {c.name for c in corpus.CASES}
    for case in corpus.CASES:
        assert golden[case.name] == sorted(case.expect), case.name


# ---------------------------------------------------------------------------
# Capacity multiplicity math
# ---------------------------------------------------------------------------

def test_multiplicity_loop_and_cond():
    loop20 = ("loop", 1, 20)
    cond5 = ("cond", 2, 5)
    assert multiplicity((loop20,)) == 20
    assert multiplicity((loop20, cond5)) == 4
    assert multiplicity((("loop", 0, 10), loop20, cond5)) == 40
    # shared frames cancel: enqueue and flush in the same loop instance
    assert multiplicity((loop20, cond5), (loop20,)) == 1
    assert multiplicity((loop20,), (loop20,)) == 1
    # a DIFFERENT loop instance does not cancel
    assert multiplicity((loop20,), (("loop", 9, 20),)) == 20
    # unbounded loop -> inf; plain conditional divides by 1
    assert multiplicity((("loop", 3, None),)) == math.inf
    assert multiplicity((("cond", 4, None), loop20)) == 20


# ---------------------------------------------------------------------------
# Event rules: direct unit checks
# ---------------------------------------------------------------------------

def test_unknown_origin_lineage_suppresses_origin_rules():
    """A queue first seen mid-stream (local_view / passed in) must not be
    accused of never flushing — but is still capacity-checked."""
    from repro.analysis.rules import analyze_events
    ev = [
        {"kind": "rpc_enqueue", "qid": 1, "qid_out": 2, "site": "u.py:1",
         "scopes": (("loop", 0, 100),), "name": "f", "ticketed": False,
         "conditional": False, "payload_words": 0, "reply_words": 0,
         "capacity": 8, "payload_capacity": 64, "reply_capacity": 0},
    ]
    report = analyze_events(ev)
    assert report.codes == ["CAPACITY_RECORDS"]


def test_result_before_flush_runtime_flag():
    from repro.analysis.rules import analyze_events
    ev = [{"kind": "rpc_result", "qid": 7, "ticket_id": 9,
           "site": "u.py:2", "scopes": (), "via_result": True,
           "never_flushed": True}]
    assert analyze_events(ev).codes == ["RESULT_BEFORE_FLUSH"]


def test_report_dedupe_and_json():
    h = Hazard.make("DOUBLE_FREE", "msg", "a.py:1", ptr=3)
    report = HazardReport([h, h, Hazard.make("OOB_PTR", "m", "a.py:2")])
    deduped = report.deduped()
    assert len(deduped) == 2
    blob = json.loads(deduped.to_json())
    assert blob["count"] == 2
    assert blob["codes"] == ["DOUBLE_FREE", "OOB_PTR"]
    assert blob["hazards"][0]["detail"] == {"ptr": 3}


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------

def _echo_cb(x):
    return np.int32(x)


REGISTRY.register("analysis.echo", _echo_cb)


def test_walker_flags_callback_in_scan():
    def prog(xs):
        def body(c, x):
            r, _ = rpc_call("analysis.echo", x, result_shape=I32)
            return c + r, x
        return jax.lax.scan(body, jnp.int32(0), xs)

    report = analyze_jaxpr(prog, jnp.arange(4))
    assert "CALLBACK_IN_LOOP" in report.codes


def test_walker_exempts_cond_confined_callback():
    """A callback in a taken branch (device_run's immediate-hook shape)
    is data-dependent — not the every-iteration pathology."""
    def prog(xs):
        def body(c, x):
            def yes(_):
                r, _n = rpc_call("analysis.echo", x, result_shape=I32)
                return r
            r = jax.lax.cond(x % 2 == 0, yes, lambda _: jnp.int32(0), 0)
            return c + r, x
        return jax.lax.scan(body, jnp.int32(0), xs)

    report = analyze_jaxpr(prog, jnp.arange(4))
    assert "CALLBACK_IN_LOOP" not in report.codes


def test_walker_flags_callback_in_mesh():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))

    def region(x):
        r, _ = rpc_call("analysis.echo", x[0], result_shape=I32)
        return x + r

    def prog(x):
        return jax.shard_map(region, mesh=mesh, in_specs=(P("d"),),
                             out_specs=P("d"))(x)

    report = analyze_jaxpr(prog, jnp.zeros((1,), jnp.int32))
    assert "CALLBACK_IN_MESH" in report.codes
    assert "CALLBACK_IN_LOOP" not in report.codes


def test_clean_jit_program_walks_clean():
    def prog(x):
        return jax.jit(lambda v: jax.lax.scan(
            lambda c, y: (c + y, y), v, jnp.arange(4.0))[0])(x)

    assert not analyze_jaxpr(prog, jnp.float32(0))


# ---------------------------------------------------------------------------
# capture() plumbing
# ---------------------------------------------------------------------------

def test_capture_scopes_scan_and_restores_patches():
    orig = jax.lax.scan
    with capture() as cap:
        q = RpcQueue.create(4, 4, 64)

        def body(q, x):
            return q.enqueue("analysis.echo", x), x

        q, _ = jax.lax.scan(body, q, jnp.arange(6))
    assert jax.lax.scan is orig
    enq = [e for e in cap.events if e["kind"] == "rpc_enqueue"]
    assert enq and any(k == "loop" and v == 6
                       for k, _u, v in enq[0]["scopes"])
    assert cap.report().by_code("CAPACITY_RECORDS")


def test_analyze_negative_on_clean_flush_loop():
    """Mid-loop flush = per-iteration epochs: 1 record/epoch fits cap 4."""
    def prog():
        q = RpcQueue.create(4, 4, 64)

        def body(i, q):
            q = q.enqueue("analysis.echo", i)
            return q.flush()

        jax.lax.fori_loop(0, 8, body, q)

    assert not analyze(prog, jaxpr=False)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _run_lint(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)


def test_cli_buggy_target_exits_1(tmp_path):
    target = tmp_path / "buggy.py"
    target.write_text(
        "import jax.numpy as jnp\n"
        "from repro.core.rpc import REGISTRY, RpcQueue\n"
        "REGISTRY.register('cli.note', lambda *a: None)\n"
        "def main():\n"
        "    q = RpcQueue.create(8, 4, 64)\n"
        "    q = q.enqueue('cli.note', jnp.int32(1))\n")
    proc = _run_lint(f"{target}:main", "--json")
    assert proc.returncode == 1, proc.stderr
    blob = json.loads(proc.stdout)
    assert blob["codes"] == ["NEVER_FLUSHED"]
    assert "buggy.py" in blob["hazards"][0]["site"]


def test_cli_corpus_golden_passes():
    proc = _run_lint("--corpus", "--golden",
                     os.path.join("tests", "data", "hazard_corpus.json"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "26/26" in proc.stdout or "cases match" in proc.stdout
