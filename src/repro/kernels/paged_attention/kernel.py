"""Pallas TPU paged decode attention: the page table drives the BlockSpec.

The page table and per-sequence lengths are **scalar-prefetch** operands, so
the K/V block index maps dereference ``page_table[b, it]`` when scheduling
HBM->VMEM copies — the kernel reads pages *in place*; no contiguous
materialization of the KV cache ever exists (that gather is exactly what the
XLA reference path has to do, and what this kernel deletes).

Grid = (B, Hkv, MAXP); online softmax carried in VMEM scratch across the page
axis; blocks past ``lengths[b]`` are skipped entirely, so HBM traffic per
step is ceil(len/page) pages — the roofline minimum.

Pages are stored head-major, ``(NP, Hkv, page, D)``, so each K/V block is a
whole ``(page, D)`` tile of one kv head: Mosaic requires a block's last two
dimensions to be (8, 128)-divisible or to span the array's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref,               # scalar prefetch
                  q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref,
                  *, scale: float, window: Optional[int],
                  page: int, maxp: int, G: int):
    b = pl.program_id(0)
    it = pl.program_id(2)
    length = len_ref[b]

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    t_start = it * page
    run = t_start < length
    if window is not None:
        run = jnp.logical_and(run, t_start + page > length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :]                    # (G, D)
        k = k_ref[0, 0, :, :]                    # (page, D)
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, (G, page), 1)
        mask = tpos < length
        if window is not None:
            mask &= tpos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0:1] = m_new

    @pl.when(it == maxp - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (NP, Hkv, page, D)
    v_pages: jax.Array,
    page_table: jax.Array,     # (B, MAXP) int32
    lengths: jax.Array,        # (B,) int32
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, D = q.shape
    NP, Hkv, page, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               page=page, maxp=maxp, G=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, it, pt, ln: (b, h, 0, 0)),
            # the page table drives which page streams into VMEM:
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, it, pt, ln: (pt[b, it], h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, it, pt, ln: (pt[b, it], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, it, pt, ln: (b, h, 0, 0)),
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
    )(jnp.clip(page_table, 0, NP - 1), lengths, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
