"""The ``serve-batch-dsv2`` cell at a tiny size on the CPU: the plain
DeepSeek-V2 reference agrees with the program's forward and with the
engine's decode through the latent cache; the cell runs from its own files;
a planted fault in the program's expert layer makes a run not correct; the
float8 control reads far off; the counts match hand-worked values."""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, harness, registry
from bench.counts import deepseek_v2 as dcounts
from bench.counts import mla as mcounts
from bench.systems import serve, serve_deepseek

import tiny
import tiny_dsv2

SEED = 2**35 + 15


def _cfg(name):
    with open(os.path.join(tiny.ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _runner(config):
    """A runner over seeded float32 weights, without the engine."""
    from repro.models.model_zoo import build_model
    cfg = serve.program_config(config)
    model = build_model(cfg)
    specs, axes = model.param_specs()
    runner = serve_deepseek.Runner.__new__(serve_deepseek.Runner)
    runner.c, runner.cfg = config, cfg
    runner.values = serve_deepseek.make_values(gen.device_key(4, 0), specs,
                                               config)
    return runner, model, axes


def _config(dtype="float32", **kw):
    """The cell's configuration at the tiny size, as a run merges them."""
    return {**_cfg("deepseek-v2-ep8"),
            **tiny_dsv2.deepseek_v2_config(dtype, **kw)}


@pytest.mark.parametrize("first", [0, 4])
def test_reference_agrees_with_the_program_forward(first):
    from repro.models.common import merge_params
    runner, model, axes = _runner(_config(first=first))
    tokens = [5, 17, 300, 2, 9, 44, 101, 7, 7, 250]
    logits, _ = model.forward(merge_params(runner.values, axes),
                              {"tokens": jnp.asarray([tokens], jnp.int32)})
    want = runner._reference(tokens)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_reference_agrees_with_the_engine_decode():
    """Prompts fed a token a tick, then greedy decoding through the latent
    pages: every served token is the reference's best, by a margin of
    float32 rounding."""
    from repro.models.common import merge_params
    from repro.serving.engine import ServingEngine
    runner, model, axes = _runner(_config())
    eng = ServingEngine(model, merge_params(runner.values, axes),
                        batch_slots=3, max_len=64, page_size=16)
    prompts = [[3, 9, 200, 41], list(range(20, 41)), [77]]
    for p in prompts:
        eng.submit(p, max_new=8)
    fin = eng.run_until_drained()
    runner.requests = [{"prompt": p} for p in prompts]
    gaps = runner.gaps([{"i": i, "tokens": fin[i]} for i in range(3)])
    assert gaps.size == 24 and float(gaps.max()) < 1e-4


def test_routes_of_the_reference_are_group_limited():
    runner, _, _ = _runner(_config())
    routes = []
    runner._reference(list(range(40)), routes=routes)
    assert len(routes) == 2                     # the two MoE layers
    for ids in routes:
        assert ids.shape == (40, 3)
        # 16 experts in 4 groups of 4, the best 2 groups kept
        assert all(len(set(row // 4)) <= 2 for row in ids)


def _dsv2_run(hook=None, seed=SEED):
    return harness.run("serve-batch-dsv2", seed, 0.5, False,
                       require_tpu=False,
                       config_overrides=tiny_dsv2.deepseek_v2_config(),
                       traffic_overrides=tiny.SERVE_TRAFFIC,
                       runner_hook=hook)


def test_cell_runs_from_its_own_files():
    spec = registry.load_spec()
    cell = registry.Cell(spec, "serve-batch-dsv2")
    assert cell.system.__name__ == "bench_system_serve_deepseek"
    assert cell.config["name"] == "deepseek-v2-ep8"
    assert cell.traffic["in_flight"] == 64 and cell.traffic["group"] == 64
    assert {m["name"] for m in cell.per_layer} == {
        "mla_attention_roofline", "serve.mfu.dsv2",
        "serve.step_device_ms.dsv2", "device_idle.serve_dsv2",
        "engine.tick_ms.dsv2"}
    line = _dsv2_run()
    assert line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    # float32 serving picks the float32 reference's best token
    assert line["checks"]["served_logit_gap_mean"]["value"] < 1e-5


def _drop_group_mask(runner):
    runner.cfg = dataclasses.replace(runner.cfg, n_group=1, topk_group=1)


def _hold_other_experts(runner):
    runner.cfg = dataclasses.replace(runner.cfg, first_expert=8)


@pytest.mark.parametrize("fault", [_drop_group_mask, _hold_other_experts])
def test_program_fault_is_not_correct(fault):
    line = _dsv2_run(fault)
    assert line["correct"] is False


def test_skipped_shared_experts_are_not_correct(monkeypatch):
    import repro.models.moe as moe
    monkeypatch.setattr(moe, "mlp_apply", lambda p, x: jnp.zeros_like(x))
    assert _dsv2_run()["correct"] is False


def test_fp8_control_reads_far_off():
    """The control, the reference with every matmul's operands in float8,
    puts first tokens that the float32 reference ranks well below its
    best: the mean served-token gap it reads is over the cell's limit."""
    runner, _, _ = _runner(_config("bfloat16"))
    tokens = gen.np_rng(6, 2).integers(0, 512, 96).tolist()
    runner.requests = [{"prompt": tokens[:1]}]
    sample = [{"i": 0, "tokens": tokens[1:]}]
    assert float(runner.gaps(sample, quant="fp8").mean()) > \
        serve_deepseek.GAP_LIMIT
    assert sum(runner.route_flips(sample, quant="fp8")) > 0


def test_deepseek_v2_cut_holds_4_73_b_parameters():
    c = _cfg("deepseek-v2-ep8")
    assert dcounts.mla_params(c) == 149_227_520
    assert dcounts.dense_layer_params(c) == 149_227_520 + 3 * 5120 * 12288 \
        + 2 * 5120
    # 20 experts of 1536, two shared experts (one MLP of 3072), a router of
    # 160 outputs
    assert dcounts.moe_layer_params(c) == 149_227_520 + 20 * 3 * 5120 * 1536 \
        + 3 * 5120 * 3072 + 5120 * 160 + 2 * 5120
    assert dcounts.held_params(c) == 4_732_072_960
    assert dcounts.expert_evaluations(c) == pytest.approx(0.75)
    from repro.configs import get_config
    cut = dataclasses.replace(get_config("deepseek-v2"), num_layers=6,
                              experts_held=20)
    # the program's count leaves out the final norm
    assert cut.num_params() + 5120 == dcounts.held_params(c)


def test_deepseek_v2_flops_per_token():
    c = dict(_cfg("deepseek-v2-ep8"), num_hidden_layers=2)
    mla = 149_227_520 - 1536 - 512
    dense = mla + 3 * 5120 * 12288
    moe = mla + 5120 * 160 + 3 * 5120 * 3072 + 0.75 * 3 * 5120 * 1536
    assert dcounts.flops(c, 1, 0) == pytest.approx(
        2 * (dense + moe + 5120 * 102400))
    # attention: 128 heads score 576-wide rows and read 512-wide values
    assert dcounts.flops(c, 0, 10) == 2 * 2 * 128 * (576 + 512) * 10


def test_mla_latent_row_and_kernel_counts():
    c = _cfg("deepseek-v2-ep8")
    assert mcounts.row_bytes(c) == 1152
    # two slots attending 3 and 5 positions: their rows, plus each slot's
    # 128 queries of 576 in and attended latents of 512 out
    assert mcounts.kernel_bytes([3, 5], 2, c) == \
        1152 * 8 + 2 * 128 * (576 + 512) * 2
    assert mcounts.kernel_flops([3, 5], c) == 2 * 128 * (576 + 512) * 8
