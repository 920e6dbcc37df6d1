"""A decoder LM served through ``repro.serving.engine.ServingEngine``.

Configuration keys: the model's published ``config.json`` keys (read by the
reference and the counts), ``program`` (the registered architecture and the
keys this chip's share changes) and ``engine`` (slots, cache length, page
size).  Traffic keys: ``arrival`` (``backlog``: every request is submitted
before the window), ``requests``, ``in_flight`` (requests already part-way
through when the window opens, ``bench.gen.in_flight_lengths``),
``prompt``/``output`` length distributions
(``bench.gen.lognormal_lengths``), ``warmup_ticks`` and the correctness
sample (``check_min_tokens``, ``check_max_requests``)."""
from __future__ import annotations

import dataclasses
import os
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench.reference import qwen2 as ref

#: widest gap by which a served token's logit lies below the float32
#: reference's best, in logits: sound bfloat16 runs read at most 0.0250
#: over 18 seeds, the float8 control at least 0.418 over 3 (see PERF.md)
GAP_LIMIT = 0.1


def program_config(config: dict):
    """The registered architecture with this chip's share of it, run at the
    configuration's ``rms_norm_eps``."""
    from repro.configs import get_config
    p = dict(config["program"])
    return dataclasses.replace(get_config(p.pop("arch")),
                               norm_eps=float(config["rms_norm_eps"]), **p)


def make_values(key, specs, cfg, config: dict):
    """Seeded weights in the program's value tree, in the type they are
    served in.  Pad heads (the program pads query heads to a multiple of
    ``head_pad_multiple``) are zero."""
    d = config["hidden_size"]
    f = config["intermediate_size"]
    real = config["num_attention_heads"]
    hd = config["head_dim"]
    std = {"embed": 0.02, "lm_head": d ** -0.5, "wq": d ** -0.5,
           "wk": d ** -0.5, "wv": d ** -0.5, "wo": (real * hd) ** -0.5,
           "bq": 0.5, "bk": 0.5, "bv": 0.5, "wi_gate": d ** -0.5,
           "wi_up": d ** -0.5}
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    out = []
    for i, (path, s) in enumerate(flat):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        leaf = names[-1]
        if leaf in ("ln1", "ln2", "ln_f"):
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        scale = f ** -0.5 if names[-2:] == ["mlp", "wo"] else std[leaf]
        v = jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
        v = v * jnp.asarray(scale, s.dtype)
        head_axis = {"wq": 2, "wo": 1, "bq": 1}.get(leaf) \
            if names[-2:-1] == ["attn"] else None
        if head_axis is not None and s.shape[head_axis] != real:
            mask = (jnp.arange(s.shape[head_axis]) < real).astype(s.dtype)
            shape = [1] * len(s.shape)
            shape[head_axis] = s.shape[head_axis]
            v = v * mask.reshape(shape)
        out.append(v)
    return jax.tree_util.tree_unflatten(treedef, out)


class Runner:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        if os.environ.get("REPRO_FORCE_PALLAS"):
            raise RuntimeError("REPRO_FORCE_PALLAS is set: the benchmark runs "
                               "the program's own kernel choice")
        self.c, self.t, self.seed = config, traffic, seed
        self.cfg = program_config(config)
        self.stats: dict = {}
        self._n_fin, self._fin_tokens = 0, 0    # finished requests, tokens
        self.fault = None             # tests break the timed path here

    # -- set-up ------------------------------------------------------------------
    def setup(self) -> None:
        from repro.models.common import merge_params
        from repro.models.model_zoo import build_model
        from repro.serving.engine import ServingEngine
        model = build_model(self.cfg)
        specs, axes = model.param_specs()
        self.values = jax.jit(
            lambda k: make_values(k, specs, self.cfg, self.c))(
                gen.device_key(self.seed, 0))
        e = self.c["engine"]
        self.engine = ServingEngine(
            model, merge_params(self.values, axes),
            batch_slots=int(e["batch_slots"]), max_len=int(e["max_len"]),
            page_size=int(e["page_size"]))
        self.requests = gen.requests(self.t, self.seed, self.c["vocab_size"],
                                     int(self.t["requests"]))
        self.rid_of: Dict[int, int] = {}
        for i, r in enumerate(self.requests):
            self.rid_of[self.engine.submit(r["prompt"],
                                           max_new=r["max_new"])] = i
        self._warm_eager()
        for _ in range(int(self.t["warmup_ticks"])):
            self._tick()
        jax.block_until_ready(self.engine.kv.k_pages)

    def _warm_eager(self) -> None:
        """Compile now the engine's eager host-side operations that the
        warm-up ticks may not reach: reading one slot's next token, and
        retiring finished slots (a mask scattered from a list of their
        indices, one shape per count, then the cache's release)."""
        from repro.serving import kvcache
        eng = self.engine
        B = eng.B
        nxt = jnp.argmax(jnp.zeros((B, 8), jnp.float32), axis=-1)
        int(nxt[B - 1])
        for n in range(1, B + 1):
            mask = jnp.zeros((B,), bool).at[
                jnp.asarray(list(range(n)), jnp.int32)].set(True)
        jax.block_until_ready(kvcache.release_slots(eng.kv, mask).lengths)

    def _tick(self) -> int:
        """One engine step; returns the tokens it generated."""
        before = self._generated()
        self.engine.step()
        if self.fault is not None:
            self.fault(self.engine)
        return self._generated() - before

    def _generated(self) -> int:
        eng = self.engine
        live = sum(len(s.out) for s in eng.slots if s.request_id >= 0)
        if len(eng.finished) != self._n_fin:
            self._n_fin = len(eng.finished)
            self._fin_tokens = sum(len(v) for v in eng.finished.values())
        return live + self._fin_tokens

    def _contexts(self) -> List[int]:
        """Positions each active slot attends to in the coming tick."""
        out = []
        eng = self.engine
        for s in eng.slots:
            if s.request_id >= 0:
                out.append(s.fed + max(len(s.out) - 1, 0) + 1)
        free = sum(1 for s in eng.slots if s.request_id < 0)
        # free slots are refilled from the queue at the start of the tick
        out += [1] * min(free, len(eng.queue))
        return out

    # -- window ------------------------------------------------------------------
    def window(self, seconds: float, spans) -> dict:
        """Ticks until ``seconds`` have passed."""
        ticks = tokens = processed = ctx_sum = ctx_max = 0
        marks = []                          # (seconds, tokens) after each tick
        t0 = time.perf_counter()
        while True:
            ctx = self._contexts()
            with spans.span("bench.tick"):
                tokens += self._tick()
            ticks += 1
            processed += len(ctx)
            ctx_sum += sum(ctx)
            ctx_max = max([ctx_max] + ctx)
            elapsed = time.perf_counter() - t0
            marks.append((elapsed, tokens))
            if elapsed >= seconds:
                break
        self.stats = {"serve_tokens_per_s": tokens / elapsed,
                      "attempted": len(self.served()), "failed": 0,
                      "ticks": ticks, "tokens": tokens,
                      "tokens_processed": processed, "context_sum": ctx_sum,
                      "elapsed_s": elapsed,
                      "tick_s": spans.total("bench.tick")}
        self._report(marks, ctx_max)
        return self.stats

    def _report(self, marks, ctx_max: int) -> None:
        """Stderr: tokens/s and tick in each third of the window, which
        agree when the window is steady, and the KV cache's fill."""
        thirds, start = [], (0.0, 0)
        for part in np.array_split(np.arange(len(marks)), 3):
            if not len(part):
                continue
            end = marks[part[-1]]
            dt = end[0] - start[0]
            thirds.append(f"{(end[1] - start[1]) / dt:.1f} tokens/s, "
                          f"{1e3 * dt / len(part):.1f} ms/tick")
            start = end
        s = self.stats
        max_len = int(self.c["engine"]["max_len"])
        fill = s["context_sum"] / s["tokens_processed"] / max_len
        print(f"window thirds: {'; '.join(thirds)}", file=sys.stderr)
        print(f"kv fill: mean context {100 * fill:.1f} % of {max_len}, "
              f"longest {ctx_max}", file=sys.stderr)

    # -- correctness ---------------------------------------------------------------
    def served(self) -> List[dict]:
        """Every request with served tokens: finished, or in a slot."""
        eng = self.engine
        out = [{"i": self.rid_of[rid], "tokens": list(toks)}
               for rid, toks in eng.finished.items()]
        out += [{"i": self.rid_of[s.request_id], "tokens": list(s.out)}
                for s in eng.slots if s.request_id >= 0 and s.out]
        return out

    def sample(self) -> List[dict]:
        """The request with the most served tokens, then others drawn from
        the seed, until ``check_min_tokens`` served tokens are covered."""
        served = sorted(self.served(), key=lambda r: (-len(r["tokens"]),
                                                      r["i"]))
        if not served:
            return []
        rng = gen.np_rng(self.seed, 3)
        pick = [served[0]] + [served[j] for j in
                              rng.permutation(len(served) - 1) + 1]
        out, n = [], 0
        for r in pick[:int(self.t["check_max_requests"])]:
            out.append(r)
            n += len(r["tokens"])
            if n >= int(self.t["check_min_tokens"]):
                break
        return out

    def _layer_weights(self, i: int) -> dict:
        L = self.values["layers"]
        H = self.c["num_attention_heads"]
        a, m = L["attn"], L["mlp"]
        return {"wq": a["wq"][i][:, :H], "wk": a["wk"][i], "wv": a["wv"][i],
                "wo": a["wo"][i][:H], "bq": a["bq"][i][:H], "bk": a["bk"][i],
                "bv": a["bv"][i], "ln1": L["ln1"][i], "ln2": L["ln2"][i],
                "w_gate": m["wi_gate"][i], "w_up": m["wi_up"][i],
                "w_down": m["wo"][i]}

    def gaps(self, sample, quant=None) -> np.ndarray:
        """Per served token of the sample, the reference's gap: of the
        served token itself, or (``quant`` set) of the token that the
        lower-precision reference puts first at the same position."""
        out = []
        for r in sample:
            prompt = self.requests[r["i"]]["prompt"]
            served = r["tokens"]
            seq = prompt + served[:-1]
            rows = slice(len(prompt) - 1, len(seq))
            logits = ref.forward(seq, self.values["embed"],
                                 self._layer_weights, self.values["ln_f"],
                                 self.values["lm_head"], self.c)[rows]
            if quant is None:
                target = served
            else:
                low = ref.forward(seq, self.values["embed"],
                                  self._layer_weights, self.values["ln_f"],
                                  self.values["lm_head"], self.c,
                                  quant=quant)[rows]
                target = jnp.argmax(low, axis=-1)
            out.append(np.asarray(ref.served_gap(logits, target)))
        return np.concatenate(out) if out else np.zeros((0,))

    def check(self) -> dict:
        sample = self.sample()
        self.engine = None                # frees the KV pages first
        g = self.gaps(sample)
        self.stats["checked_tokens"] = int(g.size)
        worst = float(np.max(g)) if g.size else float("nan")
        return {"served_logit_gap": (worst, GAP_LIMIT)}

    def close(self) -> None:
        self.engine = None
        self.values = None
