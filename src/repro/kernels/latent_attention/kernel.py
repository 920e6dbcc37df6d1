"""Pallas TPU latent paged decode attention (MLA's absorbed decode).

Each cache row is one token's ``[c | k_pe]`` (width ``W``): every head
scores the whole row and takes the first ``rank`` entries of it as its
value, so a page is read from HBM once for keys and values alike.  The
queries are the absorbed ones, ``[q_nope wk_b | q_pe]`` (B, H, W).

The kernel takes the whole cache of every layer, (L, NP, page, W), and the
layer to read as a scalar-prefetch operand, so that no layer's pages are
sliced out (copied) before it runs.  Grid = (B, MAXP / C): each step
streams ``C`` pages of one slot into VMEM, one BlockSpec per page whose
index map reads ``page_table[b, j]`` from the scalar-prefetched table, and runs all ``H`` heads over their
``C * page`` rows at once (an (H, W) x (W, C*page) matmul for the scores
and an (H, C*page) x (C*page, rank) one for the values), with the online
softmax carried in VMEM scratch.  Page slots past a slot's length map to
its last page, so the pipeline fetches nothing new for them, and their
compute is skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: pages each grid step streams in
PAGES_PER_STEP = 8


def _latent_kernel(layer_ref, pt_ref, len_ref, q_ref, *refs, scale: float, rank: int,
                   page: int, C: int, steps: int):
    page_refs, (o_ref, acc_ref, m_ref, l_ref) = refs[:C], refs[C:]
    b = pl.program_id(0)
    it = pl.program_id(1)
    length = len_ref[b]

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    t_start = it * (C * page)

    @pl.when(t_start < length)
    def _compute():
        q = q_ref[0]                                     # (H, W)
        rows = jnp.concatenate([r[0, 0] for r in page_refs], axis=0)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, C*page)
        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tpos < length, s, NEG_INF)

        m_prev, l_prev = m_ref[:, 0:1], l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, 0:1] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0:1] = m_new

    @pl.when(it == steps - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_paged_decode_attention_pallas(
    q: jax.Array,              # (B, H, W)
    pages: jax.Array,          # (L, NP, page, W)
    layer: jax.Array,          # () int32
    page_table: jax.Array,     # (B, MAXP) int32
    lengths: jax.Array,        # (B,) int32
    *,
    rank: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """(B, H, rank): each slot's heads over its first ``lengths[b]`` rows
    of layer ``layer``."""
    B, H, W = q.shape
    _, NP, page, _ = pages.shape
    maxp = page_table.shape[1]
    C = min(PAGES_PER_STEP, maxp)
    steps = pl.cdiv(maxp, C)

    def page_spec(j):
        def index(b, it, ly, pt, ln):
            last = jnp.maximum(ln[b] - 1, 0) // page
            return (ly[0], pt[b, jnp.minimum(jnp.minimum(it * C + j, last),
                                             maxp - 1)], 0, 0)
        return pl.BlockSpec((1, 1, page, W), index)

    kernel = functools.partial(_latent_kernel, scale=scale, rank=rank,
                               page=page, C=C, steps=steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, steps),
        in_specs=[pl.BlockSpec((1, H, W), lambda b, it, *_: (b, 0, 0))]
        + [page_spec(j) for j in range(C)],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, it, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, rank), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.clip(page_table, 0, NP - 1), lengths, q, *([pages] * C))
