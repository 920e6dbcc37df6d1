"""Pallas TPU flash-attention kernel (GQA, causal/windowed, online softmax).

Tiling: grid = (B, Hq, Sq/blk_q, Sk/blk_k); the k dimension is the innermost
("arbitrary") axis so the online-softmax running state lives in VMEM scratch
across k steps.  K/V blocks for query head ``h`` come from kv head ``h // G``
(GQA), so no repeated KV is ever materialized in HBM.  Arrays are head-major,
``(B, H, S, D)``, so every block is a whole ``(tokens, D)`` tile of one head,
as Mosaic's block tiling requires.

VMEM working set per step (bf16 in, fp32 accum):
  q (blk_q x D) + k,v (blk_k x D each) + acc (blk_q x D fp32) + m,l
  = e.g. blk 512/512, D=128: 0.125 + 2*0.125 + 0.25 + eps ≈ 0.65 MB  « 16 MB VMEM,
leaving room for double buffering of the K/V streams.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,   # blocked refs
                  acc_ref, m_ref, l_ref,        # VMEM scratch
                  *, scale: float, causal: bool, window: Optional[int],
                  q_offset: int, blk_q: int, blk_k: int, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    iq = pl.program_id(2)
    q_start = q_offset + iq * blk_q
    k_start = ik * blk_k

    # Block-level visibility test: skip fully-masked K blocks.
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + blk_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + blk_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :]                                # (blk_q, D)
        k = k_ref[0, 0, :, :]                                # (blk_k, D)
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (blk_q, blk_k)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]                               # (blk_q, 1)
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # (blk_q, blk_k)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc
        m_ref[:, 0:1] = m_new
        l_ref[:, 0:1] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "scale",
                     "blk_q", "blk_k", "interpret"))
def flash_attention_pallas(
    q: jax.Array,                  # (B, Hq, Sq, D)
    k: jax.Array,                  # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
    blk_q: int = 512,
    blk_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    assert Sq % blk_q == 0 and Sk % blk_k == 0, (Sq, blk_q, Sk, blk_k)
    nq, nk = Sq // blk_q, Sk // blk_k
    scale = D ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, blk_q=blk_q, blk_k=blk_k, nk=nk)

    grid = (B, Hq, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
            pltpu.VMEM((blk_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
