"""Tiny configurations of the benchmark's cells for CPU tests: the same
system modules, generators and references, at sizes a test run can hold."""
import json
import os

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def xsbench_config():
    cfg = _cfg("xsbench-large")
    mats = [sorted({i % 8 for i in m}) for m in cfg["materials"]["nuclides"]]
    return {"n_isotopes": 8, "n_gridpoints": 64, "lookups": 3000,
            "materials": dict(cfg["materials"], nuclides=mats)}


XSBENCH_TRAFFIC = {"block_lanes": 1024, "index_rows_per_block": 64,
                   "check_sample": 3000}


def qwen2_config(dtype="bfloat16"):
    return {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512,
            "rms_norm_eps": 1e-6,
            "program": {"arch": "qwen2.5-14b", "num_layers": 2, "d_model": 64,
                        "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                        "d_ff": 128, "vocab_size": 512,
                        "head_pad_multiple": 1, "dtype": dtype,
                        "param_dtype": dtype},
            "engine": {"batch_slots": 4, "max_len": 128, "page_size": 16}}


SERVE_TRAFFIC = {"requests": 12, "group": 4, "in_flight": 0,
                 "prompt": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                 "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
                 "warmup_ticks": 4, "check_min_tokens": 40}
