"""DeepSeek-V2 served through ``repro.serving.engine.ServingEngine``: the
latent paged cache, absorbed MLA decode and the expert layer that holds one
share of the routed experts.

The serving loop, the window, the sample and the check are
``bench.systems.serve``'s; this module gives the weights (every projection
of MLA and DeepSeekMoE), each layer's weights for the reference, and the
plain DeepSeek-V2 reference (``bench/reference/deepseek_v2.py``), given
the same share of the experts as the program.  Configuration and traffic
keys are ``serve``'s."""
from __future__ import annotations

import sys
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench.reference import deepseek_v2 as ref
from bench.systems import serve

#: mean over the sampled served tokens of the gap by which each one's logit
#: lies below the float32 reference's best, in logits (see PERF.md §2 for
#: the sound runs and the float8 control that set it).  Not the widest
#: gap, as ``serve`` takes: bfloat16 activations decide some of the
#: routing's near-ties the other way (about one (token, MoE layer) choice
#: in eight), and with routed weights scaled by 16 a flipped choice can
#: move a logit by 2, so sound runs and the float8 control overlap in
#: their widest gap but not in their mean
GAP_LIMIT = 0.1


def make_values(key, specs, config: dict):
    """Seeded weights in the program's value tree, in the type they are
    served in: normal with std 1/sqrt(fan_in) (see the configuration's
    ``assumed``), norms 1."""
    d = config["hidden_size"]
    H, vd = config["num_attention_heads"], config["v_head_dim"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    fs = config["n_shared_experts"] * fe
    std = {("attn", "wq_a"): d, ("attn", "wq_b"): config["q_lora_rank"],
           ("attn", "wkv_a"): d, ("attn", "wk_b"): config["kv_lora_rank"],
           ("attn", "wv_b"): config["kv_lora_rank"], ("attn", "wo"): H * vd,
           ("mlp", "wi_gate"): d, ("mlp", "wi_up"): d, ("mlp", "wo"): f,
           ("moe", "router"): d, ("moe", "wi_gate"): d, ("moe", "wi_up"): d,
           ("moe", "wo"): fe, ("shared", "wi_gate"): d,
           ("shared", "wi_up"): d, ("shared", "wo"): fs,
           ("", "lm_head"): d}
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    out = []
    for i, (path, s) in enumerate(flat):
        names = [""] + [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if names[-1] in ("ln1", "ln2", "ln_f", "q_norm", "kv_norm"):
            out.append(jnp.ones(s.shape, s.dtype))
            continue
        scale = 0.02 if names[-1] == "embed" else \
            std[tuple(names[-2:])] ** -0.5
        v = jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
        out.append(v * jnp.asarray(scale, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


class Runner(serve.Runner):
    def setup(self) -> None:
        from repro.models.common import merge_params
        from repro.models.model_zoo import build_model
        from repro.serving.engine import ServingEngine
        model = build_model(self.cfg)
        specs, axes = model.param_specs()
        self.values = jax.jit(lambda k: make_values(k, specs, self.c))(
            gen.device_key(self.seed, 0))
        e = self.c["engine"]
        self.engine = ServingEngine(
            model, merge_params(self.values, axes),
            batch_slots=int(e["batch_slots"]), max_len=int(e["max_len"]),
            page_size=int(e["page_size"]))
        self.requests = gen.requests(self.t, self.seed, self.c["vocab_size"],
                                     int(self.t["requests"]))
        self.rid_of = {}
        for i, r in enumerate(self.requests):
            self.rid_of[self.engine.submit(r["prompt"],
                                           max_new=r["max_new"])] = i
        self._warm_eager()
        for _ in range(int(self.t["warmup_ticks"])):
            self._tick()
        jax.block_until_ready(self.engine.kv.latent_pages)

    def _layer_weights(self, i: int) -> dict:
        k = int(self.c["first_k_dense_replace"])
        stack, j = (self.values["dense_layers"], i) if i < k \
            else (self.values["layers"], i - k)
        a = stack["attn"]
        w = {n: a[n][j] for n in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                  "kv_norm", "wk_b", "wv_b", "wo")}
        w.update(ln1=stack["ln1"][j], ln2=stack["ln2"][j])
        if i < k:
            m = stack["mlp"]
            w.update(w_gate=m["wi_gate"][j], w_up=m["wi_up"][j],
                     w_down=m["wo"][j])
        else:
            m = stack["moe"]
            s = m["shared"]
            w.update(router=m["router"][j], e_gate=m["wi_gate"][j],
                     e_up=m["wi_up"][j], e_down=m["wo"][j],
                     s_gate=s["wi_gate"][j], s_up=s["wi_up"][j],
                     s_down=s["wo"][j])
        return w

    def _reference(self, seq, quant=None, routes=None):
        v = self.values
        return ref.forward(seq, v["embed"], self._layer_weights, v["ln_f"],
                           v["lm_head"], self.c,
                           int(self.c["program"]["first_expert"]),
                           quant=quant, routes=routes)

    def gaps(self, sample, quant: Optional[str] = None) -> np.ndarray:
        """Per served token of the sample, the reference's gap: of the
        served token itself, or (``quant`` set) of the token that the
        lower-precision reference puts first at the same position."""
        out = []
        for r in sample:
            prompt = self.requests[r["i"]]["prompt"]
            seq = prompt + r["tokens"][:-1]
            rows = slice(len(prompt) - 1, len(seq))
            logits = self._reference(seq)[rows]
            target = r["tokens"] if quant is None else \
                jnp.argmax(self._reference(seq, quant)[rows], axis=-1)
            out.append(np.asarray(ref.served_gap(logits, target)))
        return np.concatenate(out) if out else np.zeros((0,))

    def route_flips(self, sample, quant: str = "bf16") -> List[int]:
        """Per sampled request, the (token, MoE layer) pairs whose chosen
        experts differ between the float32 reference and the reference
        with every matmul's operands rounded to ``quant``."""
        out = []
        for r in sample:
            seq = self.requests[r["i"]]["prompt"] + r["tokens"][:-1]
            hi, lo = [], []
            self._reference(seq, routes=hi)
            self._reference(seq, quant, routes=lo)
            out.append(sum(int(np.sum(np.any(np.sort(a, 1) != np.sort(b, 1),
                                              axis=1)))
                           for a, b in zip(hi, lo)))
        return out

    def check(self) -> dict:
        sample = self.sample()
        self.engine = None                # frees the cache's pages first
        g = self.gaps(sample)
        self.stats["checked_tokens"] = int(g.size)
        if not g.size:
            return {"served_logit_gap_mean": (float("nan"), GAP_LIMIT)}
        print(f"served gaps: widest {float(np.max(g))!r}, over 0.1 at "
              f"{int(np.sum(g > 0.1))} of {g.size} tokens", file=sys.stderr)
        return {"served_logit_gap_mean": (float(np.mean(g)), GAP_LIMIT)}
