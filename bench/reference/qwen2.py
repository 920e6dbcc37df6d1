"""Plain Qwen2 decoder forward in ``jax.numpy`` float32, independent of the
program: causal GQA attention with QKV bias and rotate-half RoPE, SwiGLU
MLP, RMSNorm, untied head.  No kernels, no cache, no batching.

It runs a layer at a time under ``default_matmul_precision("highest")``, so
that one layer's float32 weights are all it holds beside the activations.
``quant="fp8"`` rounds both operands of every matmul to float8 (e4m3, one
scale per weight tensor and per activation row): the benchmark's control,
one precision step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant, eq):
    """``einsum(eq, x, w)`` with the activation's rows (last axis reduced)
    and the whole weight optionally rounded to float8."""
    if quant == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=None)
    return jnp.einsum(eq, x, w)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (T, H, D), rotate-half convention, positions 0..T-1."""
    T, _, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def layer(x, w: Dict[str, jax.Array], *, eps: float, theta: float,
          quant: Optional[str] = None):
    """One decoder layer on activations ``x`` (T, d)."""
    with jax.default_matmul_precision("highest"):
        f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
        T = x.shape[0]
        h = rmsnorm(x, f32["ln1"], eps)
        q = _mm(h, f32["wq"], quant, "td,dhk->thk") + f32["bq"]
        k = _mm(h, f32["wk"], quant, "td,dhk->thk") + f32["bk"]
        v = _mm(h, f32["wv"], quant, "td,dhk->thk") + f32["bv"]
        q, k = rope(q, theta), rope(k, theta)
        H, G = q.shape[1], q.shape[1] // k.shape[1]
        k = jnp.repeat(k, G, axis=1)
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("thk,shk->hts", q, k) * q.shape[-1] ** -0.5
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
        x = x + _mm(a.reshape(T, -1), f32["wo"].reshape(-1, x.shape[1]),
                    quant, "tq,qd->td")
        h = rmsnorm(x, f32["ln2"], eps)
        g = _mm(h, f32["w_gate"], quant, "td,df->tf")
        u = _mm(h, f32["w_up"], quant, "td,df->tf")
        return x + _mm(jax.nn.silu(g) * u, f32["w_down"], quant, "tf,fd->td")


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, ln_f, w, *, eps: float, quant: Optional[str] = None):
    """Logits (T, V) of the final norm and one slice of the head."""
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, ln_f.astype(jnp.float32), eps)
        return _mm(h, w.astype(jnp.float32), quant, "td,dv->tv")


def forward(tokens, embed: jax.Array, layer_weights: Callable[[int], dict],
            ln_f: jax.Array, lm_head: jax.Array, config: dict,
            quant: Optional[str] = None, vocab_chunk: int = 16384,
            bucket: int = 256):
    """Logits (T, V) float32 of ``tokens`` (T,).  The sequence is padded
    to a multiple of ``bucket`` so that few lengths compile; causal
    attention keeps the padding out of the first T positions.  ``layer_weights(i)``
    returns layer ``i``'s weights as a dict: ``wq`` (d, H, D), ``wk``/``wv``
    (d, Hkv, D), ``wo`` (H, D, d), ``bq``/``bk``/``bv``, ``ln1``/``ln2``,
    ``w_gate``/``w_up`` (d, f), ``w_down`` (f, d)."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    T = len(tokens)
    padded = list(tokens) + [0] * (-T % bucket)
    x = jnp.take(embed, jnp.asarray(padded), axis=0).astype(jnp.float32)
    for i in range(int(config["num_hidden_layers"])):
        x = layer(x, layer_weights(i), eps=eps, theta=theta, quant=quant)
    V = lm_head.shape[1]
    parts = [head(x, ln_f, lm_head[:, s:s + vocab_chunk], eps=eps,
                  quant=quant) for s in range(0, V, vocab_chunk)]
    return jnp.concatenate(parts, axis=1)[:T]


def served_gap(logits, targets) -> jax.Array:
    """Per position, how far the reference's logit of the served token
    lies below the reference's best: ``max(logits) - logits[target]``."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, jnp.asarray(targets)[:, None], -1)[:, 0]
    return best - got
