"""The plain references agree with the program's own path at a tiny size
on the CPU, which shows each is right and independent of the program."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen
from bench.apps import xsbench as app
from bench.systems import serve
from bench.reference import qwen2 as qref
from bench.reference import xsbench as xref

import tiny


@pytest.fixture(scope="module")
def xs():
    c = tiny.xsbench_config()
    I, G = c["n_isotopes"], c["n_gridpoints"]
    grids = gen.xs_nuclide_grids(gen.device_key(3, 0), I, G)
    num, mats, concs, width = gen.xs_materials(gen.device_key(3, 2),
                                               c["materials"]["nuclides"])
    e, m = gen.xs_lookups(gen.device_key(3, 1), 3000, c["materials"]["dist"])
    egrid, index = app.unionized_grid(
        grids, app.unionized_order(grids[0::app.POINT]), I, G, 64)
    return c, grids, num, mats, concs, width, e, m, egrid, index


def test_unionized_index_grid_matches_searchsorted(xs):
    c, grids, *_, egrid, index = xs
    I, G = c["n_isotopes"], c["n_gridpoints"]
    g = np.asarray(grids).reshape(I, G, 6)[:, :, 0]
    idx = np.asarray(index).reshape(I * G, I)
    for j in range(I):
        want = np.clip(np.searchsorted(g[j], np.asarray(egrid), "right") - 1,
                       0, G - 2)
        assert np.array_equal(idx[:, j], want)


def test_xsbench_reference_agrees_with_the_port(xs):
    c, grids, num, mats, concs, width, e, m, egrid, index = xs
    sim = jax.jit(functools.partial(
        app.run_event_simulation, n_isotopes=c["n_isotopes"],
        n_gridpoints=c["n_gridpoints"], max_num_nucs=width, block=1024))
    verification, out = sim(e, m, egrid, index, grids, num, concs, mats)
    jax.effects_barrier()
    got = np.asarray(out)[:3000 * 5].reshape(3000, 5)
    want = xref.macro_xs(e, m, grids, c["n_isotopes"], c["n_gridpoints"],
                         num, mats, concs)
    assert xref.relative_error(got, want) < 1e-5
    assert int(verification) == int(np.sum(np.argmax(want, axis=1) + 1))


def test_xsbench_bfloat16_control_reads_far_off(xs):
    import ml_dtypes
    c, grids, num, mats, concs, width, e, m, *_ = xs
    args = (e, m, grids, c["n_isotopes"], c["n_gridpoints"], num, mats, concs)
    want = xref.macro_xs(*args)
    low = xref.macro_xs(*args, dtype=ml_dtypes.bfloat16)
    assert xref.relative_error(low, want) > 1e-3


def test_qwen2_reference_agrees_with_the_program():
    from repro.models.common import merge_params
    from repro.models.model_zoo import build_model
    config = dict(tiny.qwen2_config("float32"), rms_norm_eps=1e-6,
                  rope_theta=1e6)
    cfg = serve.program_config(config)
    model = build_model(cfg)
    specs, axes = model.param_specs()
    values = serve.make_values(gen.device_key(4, 0), specs, cfg, config)
    tokens = [5, 17, 300, 2, 9, 44, 101]
    logits, _ = model.forward(merge_params(values, axes),
                              {"tokens": jnp.asarray([tokens], jnp.int32)})
    runner = serve.Runner.__new__(serve.Runner)
    runner.values, runner.c = values, config
    want = qref.forward(tokens, values["embed"], runner._layer_weights,
                        values["ln_f"], values["lm_head"], config)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=2e-5)
    gap = qref.served_gap(want, jnp.argmax(want, axis=-1))
    assert float(jnp.max(gap)) == 0.0


def test_qwen2_fp8_control_reads_far_off():
    """The control, the reference with every matmul's operands in float8,
    puts first tokens that the float32 reference ranks well below its
    best: the served-token gap it reads is over the cell's limit."""
    config = dict(tiny.qwen2_config(), rms_norm_eps=1e-5, rope_theta=1e6)
    cfg = serve.program_config(config)
    from repro.models.model_zoo import build_model
    specs, _ = build_model(cfg).param_specs()
    values = serve.make_values(gen.device_key(6, 0), specs, cfg, config)
    runner = serve.Runner.__new__(serve.Runner)
    runner.values, runner.c = values, config
    tokens = gen.np_rng(6, 2).integers(0, 512, 96).tolist()
    args = (tokens, values["embed"], runner._layer_weights, values["ln_f"],
            values["lm_head"], config)
    want = qref.forward(*args)
    low = qref.forward(*args, quant="fp8")
    gap = qref.served_gap(want, jnp.argmax(low, axis=-1))
    assert float(jnp.max(gap)) > serve.GAP_LIMIT
