"""Multi-device semantics via subprocess (8 forced host devices): the MoE
EP dispatch vs its dropless oracle, expansion primitives over a real mesh,
and a miniature production dry-run."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_child(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # pin the cpu platform: forced host devices ARE cpu devices, and letting
    # the child probe for accelerators stalls for minutes on hosts that
    # carry a (here unusable) TPU runtime
    env["JAX_PLATFORMS"] = "cpu"
    # children build meshes with the repository's Auto-axes helper
    code = "from repro.launch.mesh import make_mesh\n" + code
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_moe_expanded_matches_reference():
    """shard_map EP dispatch == dropless dense oracle (ample capacity)."""
    out = run_child(r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import CONFIGS
from repro.distributed.sharding import ShardingCtx
from repro.models.moe import moe_apply, moe_init, moe_reference

mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(CONFIGS["phi3.5-moe-42b-a6.6b"].reduced(),
                          num_experts=8, experts_per_token=2,
                          capacity_factor=8.0)      # no drops
p = moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) * 0.5

y_ref, aux_ref = moe_reference({k: v.value for k, v in p.items()},
                               x.reshape(-1, cfg.d_model), cfg)
with ShardingCtx(mesh):
    y, aux = jax.jit(lambda x: moe_apply(p, x, cfg))(x)
err = float(jnp.max(jnp.abs(y.reshape(-1, cfg.d_model) - y_ref)))
print("ERR", err)
assert err < 2e-2, err
assert abs(float(aux) - float(aux_ref)) < 0.2
print("MOE_OK")
""")
    assert "MOE_OK" in out


def test_moe_decode_path_matches_reference():
    out = run_child(r"""
import dataclasses, jax, jax.numpy as jnp
from repro.configs import CONFIGS
from repro.distributed.sharding import ShardingCtx
from repro.models.moe import moe_apply, moe_init, moe_reference

mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(CONFIGS["phi3.5-moe-42b-a6.6b"].reduced(),
                          num_experts=8, experts_per_token=2,
                          capacity_factor=8.0)
p = moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model)) * 0.5
# T = 2 tokens: not divisible by mesh.size=8 -> decode path
y_ref, _ = moe_reference({k: v.value for k, v in p.items()},
                         x.reshape(-1, cfg.d_model), cfg)
with ShardingCtx(mesh):
    y, _ = jax.jit(lambda x: moe_apply(p, x, cfg))(x)
err = float(jnp.max(jnp.abs(y.reshape(-1, cfg.d_model) - y_ref)))
print("ERR", err)
assert err < 2e-2, err
print("MOE_DECODE_OK")
""")
    assert "MOE_DECODE_OK" in out


def test_expand_primitives_over_mesh():
    """Continuous thread ids, work sharing, barrier, parallel_for == serial."""
    out = run_child(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.expand import (barrier, expand, parallel_for, serial_for,
                               team_id, num_teams, ws_range)

mesh = make_mesh((2, 4), ("data", "model"))

def region():
    tid = team_id()
    n = num_teams()
    start, count = ws_range(32)
    barrier()
    return jnp.stack([tid, n, start, count])[None, :]

f = expand(region, mesh, in_specs=(), out_specs=P(("data", "model"), None))
# per-team outputs stack to (8, 4); check ids are continuous
out = np.asarray(jax.jit(f)()).reshape(8, 4)
assert sorted(out[:, 0].tolist()) == list(range(8)), out
assert (out[:, 1] == 8).all()
assert sorted(out[:, 2].tolist()) == [i * 4 for i in range(8)]

arr = jnp.arange(64.0)
body = lambda i, a: a[i] * 3.0
pf = parallel_for(body, 64, arr, mesh=mesh)
sf = serial_for(body, 64, arr)
np.testing.assert_allclose(np.asarray(pf), np.asarray(sf))
print("EXPAND_OK")
""")
    assert "EXPAND_OK" in out


def test_miniature_production_dryrun():
    """The full dry-run path (lower + compile + roofline) on a small mesh and
    a small model — exercises identical code to the 512-device run."""
    out = run_child(r"""
import jax
import repro.launch.dryrun as dr
from repro.configs import get_config, get_shape
import dataclasses
mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                          head_pad_multiple=4)
shape = dataclasses.replace(get_shape("train_4k"), seq_len=64, global_batch=8)
jitted, args, extra = dr.build_cell(cfg, shape, mesh)
compiled = jitted.lower(*args).compile()
cost = dr.hlocost.analyze(compiled.as_text())
assert cost["flops"] > 0
print("DRYRUN_OK", int(cost["flops"]))
""", devices=8)
    assert "DRYRUN_OK" in out


def test_hierarchical_psum_multipod():
    out = run_child(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import hierarchical_psum

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))

def f(x):
    return hierarchical_psum(x, intra_axis="data", inter_axis="pod")

g = jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                  out_specs=P(("pod", "data")), check_vma=False)
x = jnp.arange(8.0)
out = np.asarray(jax.jit(g)(x))
# psum over (pod,data) of per-shard values, replicated back per shard:
# shards hold [0,1],[2,3],[4,5],[6,7] pairs; model axis replicates
expect = np.asarray(jax.jit(jax.shard_map(
    lambda x: jax.lax.psum(x, ("pod", "data")), mesh=mesh,
    in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    check_vma=False))(x))
np.testing.assert_allclose(out, expect)
print("HPSUM_OK")
""", devices=8)
    assert "HPSUM_OK" in out
