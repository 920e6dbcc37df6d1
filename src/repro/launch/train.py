"""End-to-end training driver — the GPU First "loader".

The host process only: builds the mesh, compiles the device program (the
WHOLE multi-step training loop, `device_run`), places initial state, and
transfers control.  Everything else — data (on-device synthetic or host-RPC
feed), metrics (device log ring flushed by RPC), checkpoints (async RPC) —
happens from inside the device program, exactly the paper's execution model.

CPU-runnable:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --preset tiny \
      --steps 30 --ckpt-dir /tmp/ckpt --ckpt-every 10
Resume after a failure (picks up the latest manifest):
  ... --resume
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointManager, latest_step, restore_checkpoint
from repro.configs import get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.device_main import HostHook, device_run
from repro.core.libc import LogRing
from repro.data.pipeline import SyntheticLM
from repro.core.libc import rand_init
from repro.distributed.sharding import ShardingCtx
from repro.launch.compile_cache import use_persistent_cache
from repro.models.common import split_params
from repro.models.model_zoo import build_model
from repro.train.optimizer import OptConfig, adamw_init
from repro.train.step import make_train_step


def tiny_preset(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        cfg.reduced(), name=cfg.name + "-tiny", num_layers=4, d_model=128,
        d_ff=256, vocab_size=512)


def preset_config(arch: str, preset: str) -> ModelConfig:
    """``full``: the registered configuration; ``tiny``: a CPU-sized cut."""
    cfg = get_config(arch)
    return tiny_preset(cfg) if preset == "tiny" else cfg


def run(arch: str, *, preset: str = "tiny", steps: int = 50, batch: int = 8,
        seq_len: int = 64, lr: float = 1e-3, ckpt_dir: Optional[str] = None,
        ckpt_every: int = 0, log_every: int = 10, resume: bool = False,
        mesh=None, rules=None) -> Dict[str, Any]:
    cfg = preset_config(arch, preset)
    model = build_model(cfg)
    data = SyntheticLM(cfg.vocab_size, seq_len, batch)

    with ShardingCtx(mesh, rules):
        params = model.init(jax.random.PRNGKey(0))
        values, axes = split_params(params)
        opt_cfg = OptConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                            total_steps=steps)
        opt = adamw_init(values)
        step_fn = make_train_step(model, axes, opt_cfg)

        start_step = 0
        if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
            like = {"values": jax.tree.map(
                        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), values),
                    "opt": jax.tree.map(
                        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), opt)}
            start_step, restored = restore_checkpoint(ckpt_dir, like)
            values = restored["values"]
            opt = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(opt),
                jax.tree_util.tree_leaves(restored["opt"]))
            print(f"[train] resumed from step {start_step}")

        mgr = CheckpointManager(ckpt_dir) if (ckpt_dir and ckpt_every) else None
        hooks = []
        if mgr is not None:
            hooks.append(mgr.host_hook(
                ckpt_every,
                lambda step, s: {"values": s["values"], "opt": s["opt"]}))
        losses: list = []
        if log_every:
            hooks.append(HostHook(
                every=log_every,
                extract=lambda step, s: {"loss": s["loss"]},
                host_fn=lambda step, loss: losses.append(
                    (step, float(np.asarray(loss)))) or
                    print(f"[train] step {step} loss {float(np.asarray(loss)):.4f}",
                          flush=True)))

        rng0 = rand_init(1234)

        def step(i, state):
            with ShardingCtx(mesh, rules):
                rng, batch_d = data.batch_at(state["rng"], i + start_step)
                v, o, metrics = step_fn(state["values"], state["opt"], batch_d)
                return {"values": v, "opt": o, "rng": rng,
                        "loss": metrics["loss"]}

        t0 = time.time()
        state = device_run(
            step,
            {"values": values, "opt": opt, "rng": rng0,
             "loss": jnp.zeros((), jnp.float32)},
            steps, hooks=hooks)
        state = jax.block_until_ready(state)
        dt = time.time() - t0

        if mgr is not None:
            mgr.submit(start_step + steps,
                       {"values": state["values"], "opt": state["opt"]})
            mgr.wait()
            mgr.close()

    return {"final_loss": float(state["loss"]), "losses": losses,
            "seconds": dt, "steps": steps,
            "final_step": start_step + steps}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    use_persistent_cache()
    out = run(args.arch, preset=args.preset, steps=args.steps,
              batch=args.batch, seq_len=args.seq_len, lr=args.lr,
              ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
              log_every=args.log_every, resume=args.resume)
    print(f"[train] done: final_loss={out['final_loss']:.4f} "
          f"({out['steps']} steps in {out['seconds']:.1f}s)")


if __name__ == "__main__":
    main()
