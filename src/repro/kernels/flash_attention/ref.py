"""Pure-jnp oracle for GQA flash attention (causal / windowed / offset).

Arrays are head-major: q (B, Hq, Sq, D), k/v (B, Hkv, Sk, D).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

NEG_INF = -1e30


def attention_reference(
    q: jnp.ndarray,                 # (B, Hq, Sq, D)
    k: jnp.ndarray,                 # (B, Hkv, Sk, D)
    v: jnp.ndarray,                 # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,   # local attention: attend to (q-window, q]
    q_offset: int = 0,              # global position of q[0] (prefill continuation)
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale

    qr = q.reshape(B, Hkv, G, Sq, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qr, kf) * scale

    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)

    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def attention_reference_chunked(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    blk_q: int = 512,
    blk_k: int = 1024,
) -> jnp.ndarray:
    """Memory-bounded XLA flash: online softmax over K blocks inside a scan
    over Q blocks — never materializes the (Sq, Sk) score matrix.  This is
    the non-Pallas production path for long sequences (the Pallas kernel's
    oracle stays the dense ``attention_reference``; this function is itself
    validated against it in the tests)."""
    import jax

    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    if Sq % blk_q or Sk % blk_k:
        return attention_reference(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, scale=scale)
    nq, nk = Sq // blk_q, Sk // blk_k
    # dtype-preserving streams: fp32 only in the (block-local) softmax state
    qr = q.reshape(B, Hkv, G, nq, blk_q, D)
    kr = k.reshape(B, Hkv, nk, blk_k, D)
    vr = v.reshape(B, Hkv, nk, blk_k, D)

    def q_block(iq):
        qb = qr[:, :, :, iq]                              # (B, Hkv, G, blk_q, D)
        qpos = q_offset + iq * blk_q + jnp.arange(blk_q)

        def k_step(carry, ik):
            m, l, acc = carry
            kb, vb = kr[:, :, ik], vr[:, :, ik]
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            kpos = ik * blk_k + jnp.arange(blk_k)
            mask = jnp.ones((blk_q, blk_k), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, G, blk_q, 1), NEG_INF)
        l0 = jnp.zeros((B, Hkv, G, blk_q, 1))
        a0 = jnp.zeros((B, Hkv, G, blk_q, D))
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), jnp.arange(nk))
        return acc / jnp.maximum(l, 1e-30)                 # (B,Hkv,G,blk_q,D)

    out = jax.lax.map(q_block, jnp.arange(nq))              # (nq,B,Hkv,G,blk_q,D)
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hq, Sq, D)
    return out.astype(q.dtype)
