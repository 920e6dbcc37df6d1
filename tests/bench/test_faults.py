"""A run with the timed path broken underneath comes out not correct, once
for each fault a cell can have; and the command refuses to run without a
chip, or without the program beside it."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import harness

import tiny


def _xs_run(fault):
    def hook(runner):
        runner.fault = fault
    return harness.run("xsbench-event", 2**35 + 1, 0.2, False,
                       require_tpu=False,
                       config_overrides=tiny.xsbench_config(),
                       traffic_overrides=tiny.XSBENCH_TRAFFIC,
                       runner_hook=hook)


def _serve_run(fault):
    def hook(runner):
        runner.fault = fault
    return harness.run("serve-batch", 2**35 + 2, 0.5, False,
                       require_tpu=False,
                       config_overrides=tiny.qwen2_config(),
                       traffic_overrides=tiny.SERVE_TRAFFIC,
                       runner_hook=hook)


def test_xsbench_sound_run_is_correct():
    line = _xs_run(None)
    assert line["correct"] is True
    assert line["checks"]["xs_rel_err"]["value"] < 1e-5


def _xs_half_left_out(verification, out):
    return verification, out.at[out.shape[0] // 2:].set(0.0)


def _xs_answer_altered(verification, out):
    return verification, out.at[7].add(0.25)


@pytest.mark.parametrize("fault", [_xs_half_left_out, _xs_answer_altered])
def test_xsbench_fault_is_not_correct(fault):
    line = _xs_run(fault)
    assert line["correct"] is False


def test_serve_sound_run_is_correct():
    line = _serve_run(None)
    assert line["correct"] is True
    # bfloat16 serving picks the float32 reference's best token, or one
    # within rounding of it
    assert line["checks"]["served_logit_gap"]["value"] < 0.01


def _serve_token_altered(engine):
    for s in engine.slots:
        if s.out:
            s.out[-1] = (s.out[-1] + 1) % 512


def _serve_half_batch_copied(engine):
    half = len(engine.slots) // 2
    for lo, hi in zip(engine.slots[:half], engine.slots[half:]):
        if lo.out and hi.out:
            hi.out[-1] = lo.out[-1] if lo.out[-1] != hi.out[-1] \
                else (hi.out[-1] + 1) % 512


@pytest.mark.parametrize("fault", [_serve_token_altered,
                                   _serve_half_batch_copied])
def test_serve_fault_is_not_correct(fault):
    line = _serve_run(fault)
    assert line["correct"] is False


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "xsbench-event",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_command_without_a_chip_exits_nonzero_with_no_result():
    p = _cli(tiny.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_command_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
