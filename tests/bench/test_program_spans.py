"""The program's runtime spans and counters on the tiny serving engine and
the tiny XSBench simulation: the engine tick is tiled by its six phase
spans, one host read is counted per decoding slot, and a simulation's host
contacts and drains are published through the recorder."""
import re

import jax
import jax.numpy as jnp
import pytest

from bench import gen, registry
from bench.apps import xsbench as app
from bench.systems import serve
from repro.core import events
from repro.core.libc import drain_printf
from repro.models.common import merge_params
from repro.models.model_zoo import build_model
from repro.serving.engine import ServingEngine

import tiny

PHASES = ["engine.admit", "engine.inputs", "engine.dispatch", "engine.wait",
          "engine.harvest", "engine.retire"]


@pytest.fixture(scope="module")
def engine_parts():
    c = tiny.qwen2_config("float32")
    cfg = serve.program_config(c)
    model = build_model(cfg)
    specs, axes = model.param_specs()
    values = serve.make_values(jax.random.PRNGKey(0), specs, cfg, c)
    return model, merge_params(values, axes)


def test_engine_tick_spans_and_host_reads(engine_parts):
    model, params = engine_parts
    eng = ServingEngine(model, params, batch_slots=4, max_len=64,
                        page_size=16)
    for n, new in [(3, 2), (1, 4), (5, 3), (2, 2), (4, 1), (2, 5)]:
        eng.submit(list(range(1, n + 1)), max_new=new)
    ticks = 0
    while eng.queue or any(s.request_id >= 0 for s in eng.slots):
        # slots that read a token this tick: those whose prompt ends now
        # or has ended, counting the refill from the queue
        free = sum(1 for s in eng.slots if s.request_id < 0)
        waiting = [len(p) for _, p, _ in eng.queue[:free]]
        decoding = sum(1 for s in eng.slots if s.request_id >= 0
                       and s.fed + 1 >= len(s.prompt))
        decoding += sum(1 for n in waiting if n <= 1)
        with events.recording() as rec:
            eng.step()
        ticks += 1
        assert [n for n, _, _, _ in rec.spans] == PHASES
        assert all(p is None for *_, p in rec.spans)
        starts = [s for _, s, _, _ in rec.spans]
        ends = [e for _, _, e, _ in rec.spans]
        assert starts == sorted(starts)
        assert all(e <= s for e, s in zip(ends, starts[1:]))
        assert rec.deltas["engine.ticks"] == 1
        assert rec.deltas.get("engine.host_reads", 0) == decoding
    assert ticks > 5 and len(eng.finished) == 6


def test_engine_step_program_is_named(engine_parts):
    model, params = engine_parts
    eng = ServingEngine(model, params, batch_slots=4, max_len=64,
                        page_size=16)
    text = eng._step.lower(eng._values, eng._axes_h, eng.kv,
                           jnp.zeros((4,), jnp.int32),
                           jnp.ones((4,), bool)).compile().as_text()
    assert text.startswith("HloModule jit_serve_step")
    # the page heap inside the layer scan (the writes) and outside it
    assert re.search(r'op_name="jit\(serve_step\)/while/body/[^"]*kvcache/',
                     text)
    assert 'op_name="jit(serve_step)/kvcache/' in text


def test_simulation_drains_and_host_contacts():
    cell = registry.Cell(registry.load_spec(), "xsbench-event")
    c = {**cell.config, **tiny.xsbench_config()}
    t = {**cell.traffic, **tiny.XSBENCH_TRAFFIC}
    nucs, mats, concs, width = gen.xs_materials(gen.device_key(5, 2),
                                                c["materials"]["nuclides"])
    grids = gen.xs_nuclide_grids(gen.device_key(5, 0),
                                 n_isotopes=c["n_isotopes"],
                                 n_gridpoints=c["n_gridpoints"])
    energies, materials = gen.xs_lookups(gen.device_key(5, 1),
                                         n=c["lookups"],
                                         dist=tuple(c["materials"]["dist"]))
    egrid, index = app.unionized_grid(
        grids, app.unionized_order(grids[0::app.POINT]),
        n_isotopes=c["n_isotopes"], n_gridpoints=c["n_gridpoints"],
        rows_per_block=int(t["index_rows_per_block"]))
    sim = jax.jit(app.run_event_simulation, static_argnames=(
        "n_isotopes", "n_gridpoints", "max_num_nucs", "block"))

    def run():
        _, out = sim(energies, materials, egrid, index, grids, nucs, concs,
                     mats, n_isotopes=c["n_isotopes"],
                     n_gridpoints=c["n_gridpoints"], max_num_nucs=width,
                     block=int(t["block_lanes"]))
        jax.block_until_ready(out)
        jax.effects_barrier()

    run()                                   # compile outside the recording
    with events.recording() as rec:
        run()
        run()
    assert len(drain_printf()) >= 2
    contacts = rec.deltas["rpc.flushes"] + rec.deltas["rpc.immediate_calls"]
    assert contacts == 2                    # one boundary flush a simulation
    drains = [s for s in rec.spans if s[0] == "rpc.drain"]
    assert len(drains) == 2
