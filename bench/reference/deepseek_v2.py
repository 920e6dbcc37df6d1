"""Plain DeepSeek-V2 decoder forward in ``jax.numpy`` float32, independent of
the program: MLA in its naive form (keys and values expanded per head from
the normed KV latent, a shared YaRN-roped key part, softmax scale
``(nope + rope)^-0.5 * mscale^2``), a dense SwiGLU MLP in the leading
layers, and in the others DeepSeekMoE: softmax router over every routed
expert, group-limited top-k (groups scored by their best expert), routed
weights times ``routed_scaling_factor``, the held experts evaluated densely,
plus the shared experts.  RMSNorm, untied head.  No kernels, no cache, no
batching.

A chip that holds a share of the experts computes their part of the
output only; so does this reference, given the same share (``first``
held expert, as many as the weights hold).

It runs a layer at a time under ``default_matmul_precision("highest")``,
attention in blocks of queries, so that one layer's float32 weights are
all it holds beside the activations.  ``quant="fp8"`` rounds both
operands of every matmul to float8 (e4m3, one scale per weight tensor and
per activation row): the benchmark's control, one precision step below
the bfloat16 the configuration serves in.  ``quant="bf16"`` rounds them to
bfloat16 instead, which shows how the routing's near-ties fall under the
served precision.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen2 import F8_MAX, rmsnorm, served_gap  # noqa: F401

#: queries per block of the attention
Q_BLOCK = 256


def _round(x, quant, axis):
    if quant == "fp8":
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
        scale = jnp.where(scale == 0, 1.0, scale)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _mm(x, w, quant, eq):
    """``einsum(eq, x, w)`` with the activation's rows (last axis reduced)
    and the whole weight optionally rounded."""
    return jnp.einsum(eq, _round(x, quant, -1), _round(w, quant, None))


def yarn(c: dict):
    """(inverse frequencies (rope/2,), factor on cos and sin, softmax scale)
    from the configuration's ``rope_scaling`` (YaRN, DeepSeek-V2's form)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    factor = float(rs["factor"])
    freq = base ** -(np.arange(0, dim, 2) / dim)

    def corr_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq = freq / factor * ramp + freq * (1 - ramp)

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    cos_sin = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    m = mscale(rs["mscale_all_dim"])
    scale = (c["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv_freq.astype(np.float32), cos_sin, scale


def _rope(x, inv_freq, cos_sin):
    """x: (T, ..., D) rotate-half, positions 0..T-1."""
    T, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    shape = (T,) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * cos_sin).reshape(shape)
    sin = (jnp.sin(ang) * cos_sin).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q_nope, q_pe, k_nope, k_pe, v, scale):
    """Causal attention in blocks of ``Q_BLOCK`` queries: (T, H, *) ->
    (T, H, v)."""
    T = q_nope.shape[0]
    out = []
    for s0 in range(0, T, Q_BLOCK):
        qn, qp = q_nope[s0:s0 + Q_BLOCK], q_pe[s0:s0 + Q_BLOCK]
        s = (jnp.einsum("qhn,khn->hqk", qn, k_nope)
             + jnp.einsum("qhr,kr->hqk", qp, k_pe)) * scale
        rows = s0 + jnp.arange(qn.shape[0])
        causal = rows[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0)


def route(logits, c: dict):
    """Routing weights (T, E), zero off the chosen experts, and the chosen
    experts (T, k) of the router's logits (T, E)."""
    scores = jax.nn.softmax(logits, axis=-1)
    T, E = scores.shape
    G = c["n_group"]
    groups = jnp.max(scores.reshape(T, G, E // G), axis=-1)
    _, top_g = jax.lax.top_k(groups, c["topk_group"])
    in_top = jnp.any(jnp.arange(G)[None, :, None] == top_g[:, None, :], -1)
    masked = jnp.where(jnp.repeat(in_top, E // G, axis=1), scores, 0.0)
    w, ids = jax.lax.top_k(masked, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    dense = jnp.sum(jnp.where(ids[..., None] == jnp.arange(E), w[..., None],
                              0.0), axis=1)
    return dense, ids


def _swiglu(h, g, u, d, quant):
    return _mm(jax.nn.silu(_mm(h, g, quant, "td,df->tf"))
               * _mm(h, u, quant, "td,df->tf"), d, quant, "tf,fd->td")


def _statics(c: dict) -> tuple:
    keys = ("rms_norm_eps", "qk_nope_head_dim", "kv_lora_rank", "n_group",
            "topk_group", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    return tuple((k, c[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("statics", "yarn_", "first",
                                              "quant"))
def layer(x, w: Dict[str, jax.Array], *, statics: tuple, yarn_: tuple,
          first: int, quant: Optional[str] = None):
    """One decoder layer on activations ``x`` (T, d); returns (x, the
    chosen experts (T, k), or (T, 0) in a dense layer)."""
    c = dict(statics)
    inv_freq, cos_sin, scale = np.asarray(yarn_[0], np.float32), *yarn_[1:]
    eps, nope, r = c["rms_norm_eps"], c["qk_nope_head_dim"], c["kv_lora_rank"]
    with jax.default_matmul_precision("highest"):
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        h = rmsnorm(x, f32["ln1"], eps)
        cq = rmsnorm(_mm(h, f32["wq_a"], quant, "td,dr->tr"), f32["q_norm"],
                     eps)
        q = _mm(cq, f32["wq_b"], quant, "tr,rhk->thk")
        q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], inv_freq, cos_sin)
        kv = _mm(h, f32["wkv_a"], quant, "td,dr->tr")
        lat = rmsnorm(kv[:, :r], f32["kv_norm"], eps)
        k_pe = _rope(kv[:, r:], inv_freq, cos_sin)
        k_nope = _mm(lat, f32["wk_b"], quant, "tr,rhn->thn")
        v = _mm(lat, f32["wv_b"], quant, "tr,rhv->thv")
        a = _attention(q_nope, q_pe, k_nope, k_pe, v, scale)
        x = x + _mm(a.reshape(a.shape[0], -1),
                    f32["wo"].reshape(-1, x.shape[1]), quant, "tq,qd->td")
        h = rmsnorm(x, f32["ln2"], eps)
        if "router" not in f32:
            ids = jnp.zeros((x.shape[0], 0), jnp.int32)
            return x + _swiglu(h, f32["w_gate"], f32["w_up"], f32["w_down"],
                               quant), ids
        weights, ids = route(_mm(h, f32["router"], quant, "td,de->te"), c)
        held = f32["e_gate"].shape[0]
        wh = weights[:, first:first + held]
        g = _mm(h, f32["e_gate"], quant, "td,edf->tef")
        u = _mm(h, f32["e_up"], quant, "td,edf->tef")
        y = _mm((jax.nn.silu(g) * u * wh[..., None]).reshape(h.shape[0], -1),
                f32["e_down"].reshape(-1, x.shape[1]), quant, "tq,qd->td")
        y = y + _swiglu(h, f32["s_gate"], f32["s_up"], f32["s_down"], quant)
        return x + y, ids


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, ln_f, w, *, eps: float, quant: Optional[str] = None):
    """Logits (T, V) of the final norm and one slice of the head."""
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, ln_f.astype(jnp.float32), eps)
        return _mm(h, w.astype(jnp.float32), quant, "td,dv->tv")


def forward(tokens, embed: jax.Array, layer_weights: Callable[[int], dict],
            ln_f: jax.Array, lm_head: jax.Array, config: dict, first: int,
            quant: Optional[str] = None, vocab_chunk: int = 16384,
            bucket: int = 256, routes: Optional[List] = None):
    """Logits (T, V) float32 of ``tokens`` (T,).  The sequence is padded to
    a multiple of ``bucket`` so that few lengths compile; causal attention
    keeps the padding out of the first T positions.  ``layer_weights(i)``
    returns layer ``i``'s weights as a dict: ``wq_a`` (d, rq), ``q_norm``,
    ``wq_b`` (rq, H, nope + rope), ``wkv_a`` (d, r + rope), ``kv_norm``,
    ``wk_b`` (r, H, nope), ``wv_b`` (r, H, v), ``wo`` (H, v, d),
    ``ln1``/``ln2``; then either ``w_gate``/``w_up`` (d, f), ``w_down``
    (f, d), or ``router`` (d, E), the held experts ``e_gate``/``e_up``
    (E_held, d, f), ``e_down`` (E_held, f, d) and the shared experts
    ``s_gate``/``s_up``/``s_down``.  ``first`` is the first held expert.
    ``routes``, when a list, gets each MoE layer's chosen experts (T, k)."""
    statics = _statics(config)
    inv_freq, cos_sin, scale = yarn(config)
    yarn_ = (tuple(float(f) for f in inv_freq), cos_sin, scale)
    T = len(tokens)
    padded = list(tokens) + [0] * (-T % bucket)
    x = jnp.take(embed, jnp.asarray(padded), axis=0).astype(jnp.float32)
    for i in range(int(config["num_hidden_layers"])):
        x, ids = layer(x, layer_weights(i), statics=statics, yarn_=yarn_,
                       first=first, quant=quant)
        if routes is not None and ids.shape[1]:
            routes.append(np.asarray(ids[:T]))
    V = lm_head.shape[1]
    eps = float(config["rms_norm_eps"])
    parts = [head(x, ln_f, lm_head[:, s:s + vocab_chunk], eps=eps,
                  quant=quant) for s in range(0, V, vocab_chunk)]
    return jnp.concatenate(parts, axis=1)[:T]
