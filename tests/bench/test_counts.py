"""Analytic work counts and the peaks table, against hand-worked values."""
import json
import os

import pytest

from bench import peaks
from bench.counts import paged_attention, qwen2, xsbench

from tiny import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_xsbench_mean_nuclides_over_the_material_mix():
    mat = _cfg("xsbench-large")["materials"]
    nucs = [len(m) for m in mat["nuclides"]]
    assert nucs == [321, 5, 4, 4, 27, 21, 21, 21, 21, 21, 9, 9]
    # pick_mat picks fuel with what the other eleven leave, 1 - 0.861:
    # 0.139*321 + 0.052*5 + (0.275+0.134)*4 + 0.154*27 + 0.208*21 + 0.038*9
    # (XSBench's listed fuel share, 0.140, would give 55.704: its twelve
    # shares add up to 1.001)
    assert xsbench.mean_nuclides(mat["dist"], nucs) == pytest.approx(55.383)


def test_xsbench_bytes_per_lookup():
    c = _cfg("xsbench-large")
    assert xsbench.search_steps(355 * 11303) == 22
    # 22 float32 search reads, then per nuclide one int32 index entry and
    # two grid points of six float32
    assert xsbench.bytes_per_lookup(c) == pytest.approx(22 * 4 + 55.383 * 52)


def test_qwen2_14b_layer_is_550_5_mb():
    c = _cfg("qwen2.5-14b-pp8")
    assert qwen2.layer_params(c) == 275_268_608
    assert qwen2.layer_params(c) * 2 / 1e6 == pytest.approx(550.54, abs=0.01)
    # embedding and head: 152064 x 5120 bf16 each, 1.557 GB
    assert qwen2.weight_bytes(dict(c, num_hidden_layers=0)) \
        == 2 * 152064 * 5120 * 2 + 5120 * 2


def test_qwen2_flops_per_token():
    c = dict(_cfg("qwen2.5-14b-pp8"), num_hidden_layers=1)
    matmul = 5120 * 5120 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 13824
    head = 5120 * 152064
    assert qwen2.flops(c, 1, 0) == 2 * (matmul + head)
    # attention: QK^T and PV over 10 positions, 40 heads of 128
    assert qwen2.flops(c, 0, 10) == 4 * 5120 * 10


def test_paged_attention_kv_bytes():
    # two slots attending 3 and 5 positions, 8 KV heads of 128, bf16 K and V
    assert paged_attention.kv_bytes([3, 5], 8, 128) == 2 * 8 * 128 * 2 * 8
    assert qwen2.kv_bytes_per_token(dict(num_hidden_layers=1,
                                         num_key_value_heads=8,
                                         head_dim=128)) == 4096


def test_peaks_known_and_unknown_device():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
