"""Pallas TPU decode-attention kernel: one query token vs. a long KV cache.

Decode is memory-bound (read T x Hkv x D x 2 cache bytes per step), so the
kernel streams KV blocks through VMEM once with an online softmax, processing
all G = Hq/Hkv query heads of a kv group together so each cache byte is read
exactly once.  Grid = (B, Hkv, T/blk_t); the T axis is innermost with running
(m, l, acc) scratch carried across steps.

Per-sequence valid ``lengths`` (ragged batch) are handled in-kernel: blocks
past the length are skipped entirely (no wasted HBM reads for short
sequences — the straggler mitigation for mixed-length decode batches).

The cache is head-major, ``(B, Hkv, T, D)``, so each K/V block is a whole
``(blk_t, D)`` tile of one kv head, as Mosaic's block tiling requires.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref,                     # scalar prefetch: (B,) lengths
                   q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref,
                   *, scale: float, window: Optional[int],
                   blk_t: int, nt: int, G: int):
    b = pl.program_id(0)
    it = pl.program_id(2)
    length = len_ref[b]

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    t_start = it * blk_t
    run = t_start < length
    if window is not None:
        run = jnp.logical_and(run, t_start + blk_t > length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :]                   # (G, D)
        k = k_ref[0, 0, :, :]                   # (blk_t, D)
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G, blk_t)

        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, (G, blk_t), 1)
        mask = tpos < length
        if window is not None:
            mask &= tpos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0:1] = m_new
        l_ref[:, 0:1] = l_new

    @pl.when(it == nt - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "blk_t", "interpret"))
def decode_attention_pallas(
    q: jax.Array,            # (B, Hq, D)
    k: jax.Array,            # (B, Hkv, T, D)
    v: jax.Array,
    lengths: jax.Array,      # (B,) int32
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    blk_t: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    blk_t = min(blk_t, T)
    assert T % blk_t == 0, (T, blk_t)
    nt = T // blk_t
    scale = D ** -0.5 if scale is None else scale

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, blk_t=blk_t, nt=nt, G=G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nt),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, it, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, blk_t, D), lambda b, h, it, lens: (b, h, it, 0)),
            pl.BlockSpec((1, 1, blk_t, D), lambda b, h, it, lens: (b, h, it, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, it, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, Hq, D)
