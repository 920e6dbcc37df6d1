"""Pallas TPU paged decode attention: the page table drives the BlockSpec.

The layer index, the page table and the per-slot lengths are
**scalar-prefetch** operands, so the K/V block index maps dereference
``page_table[b, j]`` when scheduling HBM->VMEM copies: the kernel reads the
pages of one layer *in place* out of the whole cache of every layer.  No
contiguous materialization of the KV cache exists, and no layer's pages are
sliced out (copied) before the kernel runs.

Pages are stored row-interleaved, ``(L, NP, page * Hkv, D)``: row
``t * Hkv + h`` holds token ``t`` of KV head ``h``.  A page is one block
whose last two dimensions, ``(page * Hkv, D)``, span the array's, and are
whole (16, 128) bf16 tiles for any ``Hkv`` at page 16, so no KV head count
pads the cache.

Grid = (B, MAXP / C): each step streams ``C`` pages of one slot, all KV
heads at once, into VMEM (one BlockSpec per page), and scores the slot's
``Hq`` queries against the ``C * page * Hkv`` rows in one matmul, masking
the pairs whose row belongs to another KV head than the query's group and
the positions outside ``[length - window, length)``.  The online softmax
is carried in VMEM scratch across the steps.  Page slots past a slot's last
page (and, with a window, before its first) map to that page, so the
pipeline fetches nothing new for them, and their compute is skipped: HBM
traffic per slot is about its live pages.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: pages each grid step streams in
PAGES_PER_STEP = 8


def _paged_kernel(layer_ref, pt_ref, len_ref, q_ref, *refs,
                  scale: float, window: Optional[int], page: int, hkv: int,
                  G: int, C: int, steps: int):
    k_refs, v_refs = refs[:C], refs[C:2 * C]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * C:]
    b = pl.program_id(0)
    it = pl.program_id(1)
    length = len_ref[b]

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    t_start = it * (C * page)
    run = t_start < length
    if window is not None:
        run = jnp.logical_and(run, t_start + C * page > length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                     # (Hq, D)
        k = jnp.concatenate([r[0, 0] for r in k_refs], axis=0)
        v = jnp.concatenate([r[0, 0] for r in v_refs], axis=0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Hq, C*page*Hkv)

        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        tpos = t_start + row // hkv
        mask = jnp.logical_and(row % hkv == head, tpos < length)
        if window is not None:
            mask = jnp.logical_and(mask, tpos >= length - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0:1] = m_new

    @pl.when(it == steps - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "window", "scale",
                                             "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (L, NP, page * Hkv, D), row-interleaved
    v_pages: jax.Array,
    layer: jax.Array,          # () int32
    page_table: jax.Array,     # (B, MAXP) int32
    lengths: jax.Array,        # (B,) int32
    *,
    page_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """(B, Hq, D): each slot's queries over its first ``lengths[b]`` tokens
    (the last ``window`` of them, if given) of layer ``layer``."""
    B, Hq, D = q.shape
    _, NP, rows, _ = k_pages.shape
    page = page_size
    hkv = rows // page
    maxp = page_table.shape[1]
    C = min(PAGES_PER_STEP, maxp)
    steps = pl.cdiv(maxp, C)
    scale = D ** -0.5 if scale is None else scale

    def page_spec(j):
        def index(b, it, ly, pt, ln):
            p = jnp.minimum(it * C + j,
                            jnp.minimum(jnp.maximum(ln[b] - 1, 0) // page,
                                        maxp - 1))
            if window is not None:
                p = jnp.maximum(p, jnp.maximum(ln[b] - window, 0) // page)
            return (ly[0], pt[b, p], 0, 0)
        return pl.BlockSpec((1, 1, rows, D), index)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               page=page, hkv=hkv, G=Hq // hkv, C=C,
                               steps=steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, steps),
        in_specs=[pl.BlockSpec((1, Hq, D), lambda b, it, *_: (b, 0, 0))]
        + [page_spec(j) for j in range(C)] * 2,
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, it, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.clip(page_table, 0, NP - 1), lengths, q,
      *([k_pages] * C), *([v_pages] * C))
