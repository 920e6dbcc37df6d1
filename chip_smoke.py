"""Smoke test: the GPU First runtime and its serving and training paths on a
TPU, through the entry points a user calls, each checked against a plain
reference.

  python chip_smoke.py             # one chip: phases region, serve, train
  python chip_smoke.py --chips 4   # four chips: the mesh path only

One process holds the chip; no child process is started.  Each phase prints
one ``[smoke] <phase> {...}`` line with the device kind, compile seconds
(backend compiles, persistent-cache hits included), wall seconds and its
comparison results.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase met its
comparisons.  The script exits non-zero, with no such line, when JAX finds
no TPU, when the program is not beside it, or when any phase fails.

Phases (one chip):

* ``region`` — the paper's path.  An XSBench-style lookup loop runs
  serially, expanded (``parallel_for``) and hand-vectorised (``vmap``) on
  energies from the device libc's ``rand_uniform``; ``device_run`` drives a
  batched hook whose ticketed reply feeds back into the loop; an expanded
  region allocates from a team heap, prints through ``fprintf`` and asks the
  host for memory through ``remote_malloc``, on a synchronous and on an
  asynchronous queue.  Results, host effects and pointers are compared with
  NumPy references computed on the host.
* ``serve`` — llama3.2-3b at its registered size, seeded weights, through
  ``ServingEngine``, the paged KV cache and the Pallas paged-attention
  kernel, with a page-spill sink on the asynchronous transport.  Greedy
  tokens and first-token logits are compared with the model's plain
  forward over the whole sequence with the XLA reference attention.
* ``train`` — ``launch.train.run("mamba2-130m", preset="full")`` through
  ``device_run`` with the log and checkpoint hooks, against a run of the
  XLA reference path (``REPRO_FORCE_PALLAS=never``) on the same batch.

With ``--chips 4``: the allocator's mesh leg (``expand`` with a sharded heap
and queue) and ``device_run(mesh=)`` over four devices, each against the
single-device run, and three mamba2-130m training steps on a 2x2 (data,
model) mesh against the same steps on one chip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
KERNEL_MARK = "tpu_custom_call"

# serve: llama3.2-3b, 8 requests of 16-64 prompt tokens, 16 new tokens each
SERVE_ARCH, SERVE_REQUESTS, SERVE_NEW, SERVE_SLOTS = "llama3.2-3b", 8, 16, 4
SERVE_PROMPT = (16, 64)
SERVE_MATCH = 4                 # leading generated tokens that must agree
# Max |logit difference| over the vocabulary, relative to the reference
# row's largest |logit|.  bf16 keeps 8 mantissa bits (relative step 2^-8 =
# 0.4%); engine and reference round the same activations at different
# points (token-by-token bf16 KV vs one full-sequence pass, bf16 vs fp32
# attention probabilities), and a one-step difference in the residual
# stream grows through 28 layers: a 28-layer bf16 cut measured 2.3% on a
# CPU, so allow 5%.  Random weights give flat logit rows whose top two
# entries can sit closer than that, so a generated token also agrees with
# the reference when the reference scores it within this tolerance of its
# best token (a near tie that bf16 rounding decides); the phase reports
# how many agreed exactly.
SERVE_LOGIT_RTOL = 0.05

# train: mamba2-130m full size
TRAIN_ARCH, TRAIN_STEPS, TRAIN_SEQ, TRAIN_BATCH = "mamba2-130m", 10, 1024, 8
# |loss difference| relative to the loss (~ln 50280): Pallas SSD scan vs the
# XLA reference scan in bf16 over 24 layers; a mean over 8192 tokens
TRAIN_LOSS_RTOL = 1e-2
MESH_STEPS = 3


class _CompileLog:
    """Counts backend compiles, their seconds and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


@contextlib.contextmanager
def _reference_leg():
    """Trace with the XLA reference in place of every Pallas kernel."""
    os.environ["REPRO_FORCE_PALLAS"] = "never"
    try:
        yield
    finally:
        del os.environ["REPRO_FORCE_PALLAS"]


def _assert_kernel(text: str, what: str) -> None:
    if KERNEL_MARK not in text:
        raise AssertionError(f"{what}: no {KERNEL_MARK} in the compiled "
                             "program; a Pallas kernel did not run compiled")


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# region: the paper's path
# ---------------------------------------------------------------------------

def _np_balanced_grid(heap_size, n_slots, m_slots, cap, ratio, sizes):
    """Serial NumPy model of ``BalancedAllocator.malloc_grid`` on a fresh
    heap: chunk ``(tid % N) * M + team % M``, bump allocation in request
    order, a request that does not fit fails (-1) without moving the mark."""
    import numpy as np
    nc = n_slots * m_slots
    unit = heap_size / (nc - 1 + ratio)
    csize = [int(unit * ratio)] + [int(unit)] * (nc - 1)
    csize[-1] += heap_size - sum(csize)
    start = np.concatenate([[0], np.cumsum(csize)[:-1]])
    wm, count = [0] * nc, [0] * nc
    T, G = sizes.shape
    ptrs = np.full((T, G), -1, np.int64)
    for i in range(T // n_slots):
        for j in range(G // m_slots):
            for n in range(n_slots):
                for m in range(m_slots):
                    t, g = i * n_slots + n, j * m_slots + m
                    c, s = n * m_slots + m, int(sizes[t, g])
                    if s > 0 and wm[c] + s <= csize[c] and count[c] < cap:
                        ptrs[t, g] = start[c] + wm[c]
                        wm[c] += s
                        count[c] += 1
    return ptrs


def _np_bump(sizes, heap_size):
    """NumPy model of a fresh watermark heap's bulk ``malloc_many``."""
    import numpy as np
    out, wm = [], 0
    for s in sizes:
        if 0 < s and wm + s <= heap_size:
            out.append(wm)
            wm += s
        else:
            out.append(-1)
    return np.asarray(out)


def phase_region() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import gpu_first_port as port
    from repro.core import libc
    from repro.core.allocator import BalancedAllocator as BA
    from repro.core.allocator import GenericAllocator as GA, shard_heap
    from repro.core.device_main import HostHook, device_run
    from repro.core.expand import (expand, parallel_for, serial_for,
                                   set_team_heap, set_team_queue, team_heap,
                                   team_id, team_queue)
    from repro.core.rpc import ShardedRpcQueue
    from repro.launch.mesh import make_mesh

    out = {}
    # -- 1. legacy loop three ways, on device-libc random energies ----------
    egrid, xs = port.make_data()
    _, energies = jax.jit(lambda s: libc.rand_uniform(s, (port.N_LOOKUPS,)))(
        libc.rand_init(42))
    body = lambda i, e: port.lookup(e[i], egrid, xs)
    r_serial = jax.jit(lambda e: serial_for(body, port.N_LOOKUPS, e))(energies)
    r_expand = jax.jit(lambda e: parallel_for(body, port.N_LOOKUPS, e))(energies)
    r_vmap = jax.jit(jax.vmap(lambda x: port.lookup(x, egrid, xs)))(energies)
    e_np, g_np, x_np = (np.asarray(a, np.float64) for a in (energies, egrid, xs))
    _check(e_np.min() >= 0.0 and e_np.max() < 1.0 and
           abs(e_np.mean() - 0.5) < 0.05, f"rand_uniform out of law: "
           f"min {e_np.min()} max {e_np.max()} mean {e_np.mean()}")
    idx = np.clip(np.searchsorted(g_np, e_np) - 1, 0, port.N_GRID - 2)
    f = (e_np - g_np[idx]) / np.maximum(g_np[idx + 1] - g_np[idx], 1e-9)
    ref = (x_np[:, idx] + f * (x_np[:, idx + 1] - x_np[:, idx])).sum(0)
    errs = {k: float(np.max(np.abs(np.asarray(v, np.float64) - ref) /
                            np.abs(ref)))
            for k, v in (("serial", r_serial), ("parallel_for", r_expand),
                         ("vmap", r_vmap))}
    out["lookup_max_rel_err"] = errs
    _check(max(errs.values()) < 1e-5, f"lookup loop disagrees: {errs}")

    # -- 2. device_run: batched hook with a ticketed reply ------------------
    seen = []

    def host_fn(step, x):
        seen.append((int(step), float(x)))
        return np.float32(2.0 * float(x) + 1.0)

    hook = HostHook(
        every=2, extract=lambda i, s: s["x"], host_fn=host_fn,
        name="smoke.twice", batched=True,
        returns=jax.ShapeDtypeStruct((), jnp.float32),
        consume=lambda i, s, v, ok: {**s, "acc": s["acc"] + jnp.where(ok, v, 0.0)})
    final = device_run(lambda i, s: {**s, "x": s["x"] + 1.0},
                       {"x": jnp.float32(0.0), "acc": jnp.float32(0.0)}, 8,
                       hooks=[hook], donate=False)
    jax.effects_barrier()
    want_seen = [(k, float(k)) for k in (2, 4, 6, 8)]
    want_acc = sum(2.0 * k + 1.0 for k in (2, 4, 6, 8))
    out["device_run_hook"] = {"firings": len(seen), "acc": float(final["acc"])}
    _check(seen == want_seen, f"hook firings {seen} != {want_seen}")
    _check(float(final["acc"]) == want_acc,
           f"hook replies folded to {float(final['acc'])}, want {want_acc}")

    # -- 3. expand(heap, queue): malloc_grid, fprintf, remote_malloc --------
    T, G, HEAP, REMOTE = 8, 4, 4096, 1024
    sizes = (np.arange(T * G, dtype=np.int32).reshape(T, G) % 7) + 1
    rsizes = np.asarray([3, 5, 7, 11], np.int32)
    mesh = make_mesh((1,), ("dev",), devices=jax.devices()[:1])

    def region():
        st, ptrs = BA.malloc_grid(team_heap(), T, G, jnp.asarray(sizes))
        set_team_heap(st)
        q = libc.fprintf(team_queue(), "team %d ptr-sum %d",
                         team_id(), jnp.sum(jnp.where(ptrs >= 0, ptrs, 0)))
        q, t = libc.remote_malloc_enqueue(q, "smoke.remote", rsizes,
                                          device=team_id())
        set_team_queue(q)
        return ptrs[None], t[None]

    want_ptrs = _np_balanced_grid(HEAP, 4, 2, 64, 4.0, sizes)
    want_line = f"team 0 ptr-sum {int(np.where(want_ptrs >= 0, want_ptrs, 0).sum())}"
    want_remote = _np_bump(rsizes, REMOTE)
    for mode in ("sync", "async"):
        libc.drain_printf()
        libc.remote_heap_register("smoke.remote", GA.init(REMOTE, cap=64))
        f = jax.jit(expand(region, mesh, in_specs=(),
                           out_specs=(P("dev"), P("dev")), heap=True,
                           queue=True, queue_async=mode == "async"))
        q0 = ShardedRpcQueue.create(1, 16, width=4, payload_capacity=64,
                                    reply_capacity=16, mode=mode)
        heap = shard_heap(BA.init(HEAP, 4, 2, cap=64), 1)
        _, q, (ptrs, tix) = f(heap, q0)
        q = q.flush()
        if mode == "async":          # submit above; collect, then join
            jax.effects_barrier()
            q = q.flush()
            q.join()
        jax.effects_barrier()
        lines = libc.drain_printf()
        _, logged = libc.remote_malloc_results("smoke.remote")
        reply = np.asarray(q.result(0, tix[0], (len(rsizes),), jnp.int32))
        got = {"ptrs_match": bool((np.asarray(ptrs)[0] == want_ptrs).all()),
               "fprintf": lines, "remote_reply": reply.tolist()}
        out[f"expand_{mode}"] = got
        _check(got["ptrs_match"], f"{mode}: malloc_grid pointers differ "
               f"from the NumPy model:\n{np.asarray(ptrs)[0]}\n{want_ptrs}")
        _check(lines == [want_line], f"{mode}: fprintf {lines} != {[want_line]}")
        _check(len(logged) == 1 and (logged[0] == want_remote).all() and
               (reply == want_remote).all(),
               f"{mode}: remote_malloc {logged} / reply {reply} != {want_remote}")
    return out


# ---------------------------------------------------------------------------
# serve: llama3.2-3b at its registered size
# ---------------------------------------------------------------------------

def phase_serve(preset: str = "full") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.train import preset_config
    from repro.models import build_model
    from repro.models.common import split_params
    from repro.serving.engine import ServingEngine

    cfg = preset_config(SERVE_ARCH, preset)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size,
                            int(rng.integers(SERVE_PROMPT[0],
                                             SERVE_PROMPT[1] + 1))).tolist()
               for _ in range(SERVE_REQUESTS)]
    spilled = {}
    engine = ServingEngine(
        model, params, batch_slots=SERVE_SLOTS, max_len=128, page_size=16,
        spill_sink=lambda rid, n, pages: spilled.__setitem__(
            int(rid), (int(n), len(pages))))
    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(engine._values))
    param_bytes = sum(v.nbytes for v in jax.tree.leaves(engine._values))

    # the engine's compiled decode step must hold the paged kernel
    tok = jnp.zeros((SERVE_SLOTS,), jnp.int32)
    act = jnp.ones((SERVE_SLOTS,), bool)
    _assert_kernel(engine._step.lower(engine._values, engine._axes_h,
                                      engine.kv, tok, act).compile().as_text(),
                   "serve step")

    # record each request's first-token logits: the tick that feeds the
    # last prompt token of a slot produces them
    first_logits = {}
    step = engine._step

    def recording_step(values, axes_h, kv, tokens, active):
        logits, kv = step(values, axes_h, kv, tokens, active)
        for i, s in enumerate(engine.slots):
            if s.request_id >= 0 and s.fed == len(s.prompt) - 1:
                first_logits[s.request_id] = logits[i]
        return logits, kv

    engine._step = recording_step
    rids = [engine.submit(p, max_new=SERVE_NEW) for p in prompts]
    t0 = time.perf_counter()
    results = engine.run_until_drained()
    serve_s = time.perf_counter() - t0
    n_tokens = sum(len(results[r]) for r in rids)

    # reference: the plain forward over prompt + generated tokens, no paging,
    # XLA reference attention; causal, so right padding changes nothing
    seqs = [p + results[r] for p, r in zip(prompts, rids)]
    width = max(map(len, seqs))
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    values, axes = split_params(params)
    with _reference_leg():
        fwd = jax.jit(lambda v, t: model.forward_v(v, axes, {"tokens": t})[0])
        ref_logits = np.asarray(fwd(values, jnp.asarray(toks)), np.float32)

    mismatches, exact, logit_err = [], 0, 0.0
    for i, (p, r) in enumerate(zip(prompts, rids)):
        n = len(p)
        for k, tok in enumerate(results[r][:SERVE_MATCH]):
            row = ref_logits[i, n - 1 + k, :cfg.vocab_size]
            tol = SERVE_LOGIT_RTOL * np.max(np.abs(row))
            exact += int(tok == row.argmax())
            if row[tok] < row.max() - tol:
                mismatches.append((r, k, tok, int(row.argmax())))
        row = ref_logits[i, n - 1, :cfg.vocab_size]
        got = np.asarray(first_logits[r], np.float32)[:cfg.vocab_size]
        logit_err = max(logit_err, float(np.max(np.abs(got - row)) /
                                         np.max(np.abs(row))))
    out = {
        "arch": cfg.name, "layers": cfg.num_layers, "d_model": cfg.d_model,
        "params": n_params, "param_bytes": param_bytes,
        "requests": len(rids), "tokens": n_tokens,
        "decode_seconds": serve_s, "tokens_per_s": n_tokens / serve_s,
        "spilled_requests": len(spilled),
        "first_tokens_checked": len(rids) * SERVE_MATCH,
        "first_tokens_exact": exact,
        "first_tokens_mismatched": mismatches,
        "first_logits_max_rel_err": logit_err,
    }
    _check(not mismatches,
           f"greedy tokens differ from the reference forward: {mismatches}")
    _check(logit_err <= SERVE_LOGIT_RTOL,
           f"first-token logits differ by {logit_err:.4f} > {SERVE_LOGIT_RTOL}")
    _check(sorted(spilled) == sorted(rids) and
           all(v[0] > 0 for v in spilled.values()),
           f"page spills delivered for {sorted(spilled)}, want {sorted(rids)}")
    return out


# ---------------------------------------------------------------------------
# train: mamba2-130m full size through device_run
# ---------------------------------------------------------------------------

def _compiled_train_step(arch: str, preset: str) -> str:
    """Compiled text of one training step at the phase's shapes."""
    import jax
    from repro.core.libc import rand_init
    from repro.data.pipeline import SyntheticLM
    from repro.launch.train import preset_config
    from repro.models import build_model
    from repro.models.common import split_params
    from repro.train.optimizer import OptConfig, adamw_init
    from repro.train.step import make_train_step

    model = build_model(preset_config(arch, preset))
    values, axes = split_params(jax.eval_shape(model.init,
                                               jax.random.PRNGKey(0)))
    opt = jax.eval_shape(adamw_init, values)
    batch = jax.eval_shape(
        lambda s: SyntheticLM(model.cfg.vocab_size, TRAIN_SEQ,
                              TRAIN_BATCH).batch_at(s, jax.numpy.int32(0))[1],
        rand_init(1234))
    step = make_train_step(model, axes, OptConfig(total_steps=TRAIN_STEPS))
    return jax.jit(step).lower(values, opt, batch).compile().as_text()


def _train(arch, preset, steps, log_every=1, **kw):
    from repro.launch.train import run
    return run(arch, preset=preset, steps=steps, seq_len=TRAIN_SEQ,
               batch=TRAIN_BATCH, log_every=log_every, **kw)


def phase_train(preset: str = "full") -> dict:
    import math
    _assert_kernel(_compiled_train_step(TRAIN_ARCH, preset), "train step")
    ckpt = os.path.join(ROOT, ".smoke", "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    got = _train(TRAIN_ARCH, preset, TRAIN_STEPS, ckpt_dir=ckpt, ckpt_every=5)
    with _reference_leg():
        ref = _train(TRAIN_ARCH, preset, 1)
    losses = [l for _, l in got["losses"]]
    rel = abs(losses[0] - ref["losses"][0][1]) / abs(ref["losses"][0][1])
    saved = sorted(f for f in os.listdir(ckpt) if f.startswith("manifest"))
    out = {"arch": TRAIN_ARCH, "steps": got["steps"],
           "seconds": got["seconds"], "losses": losses,
           "reference_step1_loss": ref["losses"][0][1],
           "step1_loss_rel_err": rel, "checkpoint_manifests": saved}
    _check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
           f"losses not finite: {losses}")
    _check(rel <= TRAIN_LOSS_RTOL,
           f"step-1 loss {losses[0]} vs reference {ref['losses'][0][1]}")
    _check(len(saved) > 0, "no checkpoint written")
    return out


# ---------------------------------------------------------------------------
# four chips: the mesh path and what it is compared with
# ---------------------------------------------------------------------------

def _callback_in_mesh(mesh) -> str:
    """Compile (not run) a loop over ``mesh``-sharded data whose body makes
    an ordered, result-returning host RPC, as ``device_run``'s immediate
    hooks do."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.rpc import REGISTRY, rpc_call

    REGISTRY.register("smoke.cb", lambda i, v: np.int32(0))
    sharding = NamedSharding(mesh, P("data", "model"))

    def loop(x):
        def body(c):
            i, x = c
            x = jax.lax.with_sharding_constraint(x * 2.0, sharding)
            r, _ = rpc_call("smoke.cb", i, x.sum(),
                            result_shape=jax.ShapeDtypeStruct((), jnp.int32))
            return i + 1 + r, x
        return jax.lax.while_loop(lambda c: c[0] < 4, body, (0, x))[1]

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=sharding)
    try:
        jax.jit(loop).lower(x).compile()
    except jax.errors.JaxRuntimeError as e:
        return "refused at compile: " + str(e).splitlines()[0][:300]
    return "compiled"


def phase_mesh(devices) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ROOT)
    from benchmarks.allocator_bench import mesh_leg
    from repro.core.device_main import HostHook, device_run
    from repro.core.expand import team_id
    from repro.core.rpc import REGISTRY
    from repro.launch.mesh import make_mesh

    n = len(devices)
    out = {"allocator_mesh_leg": mesh_leg(devices)}
    leg = out["allocator_mesh_leg"]
    _check(leg["per_device_bit_identical_to_single_heap"] and
           leg["one_device_mesh_bit_identical"] and
           leg["queue_flush_device_major"], f"allocator mesh leg: {leg}")

    # device_run(mesh=): ticketed RPCs and a hook per device shard, one
    # boundary drain; each device's replies and records equal the 1-device
    # run shifted by its team id
    REGISTRY.register("smoke.sq", lambda x: np.int32(x) * np.int32(x))

    def run_on(devs):
        recs = []
        hook = HostHook(every=2, extract=lambda i, s: s["x"][0] + team_id(),
                        host_fn=lambda i, v: recs.append((int(i), float(v))),
                        name="smoke.mesh_hook")

        def step(i, s, lq):
            # tickets are the same on every device: the carry stays replicated
            lq, t = lq.enqueue_ticketed(
                "smoke.sq", (s["x"][0] + team_id()).astype(jnp.int32),
                returns=jax.ShapeDtypeStruct((), jnp.int32))
            return {"x": s["x"] + 1.0, "t": s["t"].at[i].set(t)}, lq

        mesh = make_mesh((len(devs),), ("dev",), devices=devs)
        final, q = device_run(
            step, {"x": jnp.zeros((1,), jnp.float32),
                   "t": jnp.zeros((4,), jnp.int32)}, 4,
            hooks=[hook], mesh=mesh, thread_queue=True, return_queue=True,
            queue_reply=16)
        jax.effects_barrier()
        replies = [[int(q.result(d, t)) for t in final["t"].tolist()]
                   for d in range(len(devs))]
        return float(final["x"][0]), replies, recs

    f1, rep1, recs1 = run_on(devices[:1])
    fn, repn, recsn = run_on(devices)
    want_rep = [[(i + d) ** 2 for i in range(4)] for d in range(n)]
    want_recs = [(i, float(i + d)) for d in range(n) for i in (2, 4)]
    out["device_run_mesh"] = {"replies": repn, "records": recsn}
    _check(f1 == fn == 4.0, f"final state {f1} / {fn}")
    _check(rep1 == want_rep[:1] and repn == want_rep,
           f"mesh replies {repn} (1 device: {rep1}) != {want_rep}")
    _check(recs1 == want_recs[:2] and recsn == want_recs,
           f"mesh hook records {recsn} (1 device: {recs1}) != {want_recs}")

    # mamba2-130m: 3 steps on a 2x2 (data, model) mesh vs one chip.  Its
    # hooks would be ordered host RPCs inside the partitioned program
    # (CALLBACK_IN_MESH): record whether the installed XLA compiles one for
    # this mesh (compiled, not run: a hang would hold all four chips), then
    # compare hook-free runs, whose last-step loss comes back with the state
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    out["callback_in_mesh"] = _callback_in_mesh(mesh)
    one = _train(TRAIN_ARCH, "full", MESH_STEPS, log_every=0)
    sharded = _train(TRAIN_ARCH, "full", MESH_STEPS, log_every=0, mesh=mesh)
    rel = abs(one["final_loss"] - sharded["final_loss"]) / abs(one["final_loss"])
    out["train_mesh"] = {
        "steps": MESH_STEPS, "one_chip_final_loss": one["final_loss"],
        "mesh_final_loss": sharded["final_loss"], "rel_err": rel,
        "one_chip_seconds": one["seconds"], "mesh_seconds": sharded["seconds"]}
    _check(rel <= TRAIN_LOSS_RTOL,
           f"2x2 mesh loss {sharded['final_loss']} vs one chip "
           f"{one['final_loss']} after {MESH_STEPS} steps")
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_FORCE_PALLAS"):
        print("chip_smoke: unset REPRO_FORCE_PALLAS; the smoke runs the "
              "compiled kernels and sets the reference path itself",
              file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_persistent_cache
    cache_dir = use_persistent_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    print(f"[smoke] device {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", flush=True)

    log = _CompileLog()
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(devices[:4]))]
    else:
        phases = [("region", phase_region), ("serve", phase_serve),
                  ("train", phase_train)]
    with jax.default_device(dev):
        for name, fn in phases:
            c0, n0, h0 = log.snapshot()
            t0 = time.perf_counter()
            result = fn()
            c1, n1, h1 = log.snapshot()
            print(f"[smoke] {name} " + json.dumps({
                "device_kind": dev.device_kind,
                "wall_seconds": time.perf_counter() - t0,
                "compile_seconds": c1 - c0, "compiles": n1 - n0,
                "cache_hits": h1 - h0, **result}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
