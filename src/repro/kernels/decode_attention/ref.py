"""Pure-jnp oracle for single-token GQA decode attention over a KV cache."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_reference(
    q: jnp.ndarray,          # (B, Hq, D) — one new token per sequence
    k: jnp.ndarray,          # (B, Hkv, T, D) — KV cache (possibly padded)
    v: jnp.ndarray,          # (B, Hkv, T, D)
    lengths: jnp.ndarray,    # (B,) int32 — valid cache length per sequence
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    _, Hkv, T, _ = k.shape
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    # dtype-preserving: no fp32 materialization of the KV cache (decode is
    # bandwidth-bound; converting a 32k-token cache would double+ HBM traffic)
    qr = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhtd->bhgt", qr, k,
                   preferred_element_type=jnp.float32) * scale
    tpos = jnp.arange(T)[None, :]                          # (1, T)
    valid = tpos < lengths[:, None]                        # (B, T)
    if window is not None:
        valid &= tpos >= (lengths[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, D).astype(q.dtype)
