"""Reading the program's own instrumentation from a traced run: the
``tf_op`` of each device op from the trace's wire format, device self time
given to the innermost op and then to its scopes, the program's spans beside
the harness's, the new per-layer readers, and ``bench.profile`` end to end
at a tiny size on the CPU."""
import os

import pytest

from bench import program, profile, registry, trace, xplane

import tiny

DATA = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                    "testdata")
SMALL = os.path.join(DATA, "small.xplane.pb")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")


def test_self_time_goes_to_the_innermost_op():
    ops = {"/device:TPU:0": [(0, 100, "jit(f)/while"),
                             (10, 30, "jit(f)/while/body/kvcache/add"),
                             (40, 60, "jit(f)/while/body/mul"),
                             (45, 50, "jit(f)/while/body/kvcache/add"),
                             (120, 140, "jit(g)/kvcache/select_n")]}
    s = program.self_seconds(ops, 0, 1000)
    assert s["jit(f)/while"] == pytest.approx(60e-9)
    assert s["jit(f)/while/body/mul"] == pytest.approx(15e-9)
    assert s["jit(f)/while/body/kvcache/add"] == pytest.approx(25e-9)
    # self times add up to the busy union
    assert sum(s.values()) == pytest.approx(120e-9)
    clipped = program.self_seconds(ops, 20, 130)
    assert clipped["jit(f)/while"] == pytest.approx(50e-9)
    assert clipped["jit(g)/kvcache/select_n"] == pytest.approx(10e-9)
    assert program.scope_seconds(s, "jit(f)", "kvcache") == \
        pytest.approx(25e-9)
    assert program.scope_seconds(s, "jit(f)", "kvcache", inside=False) == \
        pytest.approx(75e-9)
    assert program.in_scope("jit(f)/a;jit(f)/kvcache/b:", "kvcache")
    assert not program.in_scope("jit(f)/kvcache_x/b", "kvcache")


def test_wire_reader_matches_profile_data_on_the_recorded_trace():
    ops = xplane.device_op_scopes(SMALL)
    t = trace.Trace.load(SMALL)
    assert list(ops) == list(t.device_ops) == ["/device:TPU:0"]
    assert sorted((s, e) for s, e, *_ in ops["/device:TPU:0"]) == \
        sorted((s, e) for _, s, e in t.device_ops["/device:TPU:0"])
    assert {(op, trace.op_label(name)) for _, _, op, name
            in ops["/device:TPU:0"]} == {
        ("", "%copy-start copy-start"), ("", "%copy-done copy-done"),
        ("jit(<lambda>)/dot_general:", "%fusion fusion")}


def test_program_loader_keeps_every_existing_number():
    """With no program spans in it, the loader that adds them reads the
    recorded trace exactly as the harness's loader does."""
    old, new = trace.Trace.load(SMALL), program.load_trace(SMALL)
    lo, hi = old.window()
    assert new.window() == (lo, hi)
    assert new.busy_s(lo, hi) == old.busy_s(lo, hi)
    assert new.module_seconds(lo, hi) == old.module_seconds(lo, hi)
    assert new.op_seconds(lo, hi) == old.op_seconds(lo, hi)
    assert new.idle_by_span(lo, hi) == old.idle_by_span(lo, hi)
    assert new.idle_by_span(lo, hi)["bench.tick"] == \
        pytest.approx(0.0024375, rel=1e-4)


def test_recorded_scoped_trace():
    """A trace recorded on one v5e (``bench/testdata/record_scoped.py``): a
    program with a ``head`` scope and a ``loop`` scope in the body of a
    ``fori_loop`` of 8 steps, run three times inside the recorder's spans;
    its device clock reads about 1.1 ms behind the host's, so the window
    holds the last two runs whole."""
    t = program.load_trace(SCOPED)
    lo, hi = t.window()
    names = [n for n, *_ in sorted(t.host_spans, key=lambda x: x[1])]
    assert names == ["bench.window"] + ["engine.dispatch",
                                        "engine.wait"] * 3
    ops = xplane.device_op_scopes(SCOPED)
    scopes = program.self_seconds(ops, lo, hi)
    root = "jit(scoped)"
    assert set(scopes) == {"", "jit(dynamic_slice)/dynamic_slice:",
                           "jit(scoped)/head/dot_general:",
                           "jit(scoped)/while/body/closed_call/loop/"
                           "dot_general:"}
    head = program.scope_seconds(scopes, root, "head")
    loop = program.scope_seconds(scopes, root, "loop")
    # eight matmuls in the loop against one at the head, each of ~1.49 us
    assert head == pytest.approx(2 * 1.502e-6, rel=1e-3)
    assert loop == pytest.approx(16 * 1.485e-6, rel=1e-2)
    # self times add up to the busy union: the loop's own op keeps only
    # what its body leaves
    assert sum(scopes.values()) == pytest.approx(t.busy_s(lo, hi), rel=1e-9)
    # what runs inside the program without metadata: XLA's own copies, the
    # operand's conversion to bfloat16, the loop op itself
    untagged = program.untagged_in(ops, t, "jit_scoped", root, lo, hi)
    assert set(untagged) == {"%copy-start.1 copy-start", "%convert.1 convert",
                             "%copy-done.1 copy-done", "%while while",
                             "%copy.9 copy", "%copy-start copy-start",
                             "%copy-done copy-done"}
    # ... a part of the time without a tf_op: the rest is the eager read's
    assert 0 < sum(untagged.values()) < scopes[""]
    offs = program.clock_offsets_ms(t, "jit_scoped", "engine.wait")
    assert offs[:2] == pytest.approx([1.091751, 1.080964])
    # every device gap lies under a program span, or under none
    idle = t.idle_by_span(lo, hi)
    assert set(idle) == {"engine.dispatch", "engine.wait", "idle"}


def _ctx(**kw):
    base = {"stats": {"ticks": 4, "simulations": 2},
            "window": (0.0, 1e9), "window_s": 1.0}
    base.update(kw)
    return base


SPANS = [("engine.admit", 0, 1_000_000, None),
         ("engine.inputs", 1_000_000, 3_000_000, None),
         ("engine.dispatch", 3_000_000, 4_000_000, None),
         ("engine.wait", 4_000_000, 24_000_000, None),
         ("engine.harvest", 24_000_000, 30_000_000, None),
         ("engine.retire", 30_000_000, 32_000_000, None),
         ("rpc.drain", 0, 3_000_000, None)]
SCOPES = {"jit(serve_step)/while/body/kvcache/scatter:": 0.004,
          "jit(serve_step)/kvcache/add:": 0.002,
          "jit(serve_step)/while/body/dot_general:": 0.1,
          "jit(run_event_simulation)/while/body/parallel_for/gather:": 9.0,
          "jit(run_event_simulation)/while/body/add:": 0.02}


class _Modules:
    def module_seconds(self, lo, hi):
        return {"jit_serve_step(1)": 0.16, "jit_argmax(2)": 0.01}


@pytest.mark.parametrize("name,ctx,want", [
    ("engine.host_ms.batch", _ctx(program_spans=SPANS,
                                  counters={"engine.ticks": 2}), 6.0),
    ("engine.wait_ms.batch", _ctx(program_spans=SPANS,
                                  counters={"engine.ticks": 2}), 10.0),
    ("engine.host_reads.batch", _ctx(counters={"engine.ticks": 2,
                                               "engine.host_reads": 54}),
     27.0),
    ("kvcache.device_ms.batch", _ctx(scopes=SCOPES), 1.5),
    ("serve.step_device_ms.batch", _ctx(trace=_Modules()), 40.0),
    ("device_run.loop_ms.xs", _ctx(scopes=SCOPES), 10.0),
    ("rpc.drain_ms.xs", _ctx(program_spans=SPANS), 1.5),
    ("device_run.host_contacts.xs",
     _ctx(counters={"rpc.flushes": 2, "rpc.immediate_calls": 1}), 1.5),
])
def test_program_readers(name, ctx, want):
    cell = registry.Cell(registry.load_spec(), "serve-batch")
    reader = cell.reader(name)
    assert reader.read(ctx) == pytest.approx(want)


class _NoModules:
    def module_seconds(self, lo, hi):
        return {"jit__lambda(1)": 0.16}


@pytest.mark.parametrize("name", sorted(
    n for names in profile.PROGRAM_METRICS.values() for n in names))
def test_program_readers_find_nothing_without_the_program(name):
    """A program without the recorder, the scopes or the step's name (the
    parent of this seam) gives each reader nothing, and none raises."""
    cell = registry.Cell(registry.load_spec(), "serve-batch")
    assert cell.reader(name).read(_ctx(trace=_NoModules())) is None


def test_profile_tool_on_tiny_serving(tmp_path):
    line = profile.profile("serve-batch", 2**33 + 5, 0.5, str(tmp_path),
                           require_tpu=False,
                           config_overrides=tiny.qwen2_config(),
                           traffic_overrides=tiny.SERVE_TRAFFIC)
    assert line["correct"] is True
    m = line["metrics"]
    tick = line["spans_ms_per_unit"]["bench.tick"]
    assert 0.5 * tick < m["engine.host_ms.batch"] + \
        m["engine.wait_ms.batch"] <= tick
    assert 0 < m["engine.host_reads.batch"] <= 4
    assert line["counters"]["engine.ticks"] >= 1


def test_profile_tool_on_tiny_xsbench(tmp_path):
    line = profile.profile("xsbench-event", 2**33 + 6, 0.2, str(tmp_path),
                           require_tpu=False,
                           config_overrides=tiny.xsbench_config(),
                           traffic_overrides=tiny.XSBENCH_TRAFFIC)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["device_run.host_contacts.xs"] == 1.0
    assert m["rpc.drain_ms.xs"] > 0
