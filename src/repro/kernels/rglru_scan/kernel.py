"""Pallas TPU kernel for the diagonal linear recurrence (RG-LRU core).

Grid = (B, W/blk_w, S/blk) with the sequence axis innermost; the carry h
(1, blk_w fp32) lives in VMEM scratch.  Channels are independent, so W is
tiled.  Within a block the inclusive scan is computed by log2(blk)
Hillis–Steele doubling steps on (blk, blk_w) tiles — each step is one
sublane roll plus a multiply-add, fully vectorized on the VPU (no MXU
needed; the op is bandwidth-bound, which is why fusing the scan into one HBM
pass matters).

VMEM: blk=256, blk_w=512 -> a,b tiles 2 x 0.5 MB fp32, double-buffered with
the output tile, plus the doubling temporaries: a few MB, well inside the
scoped limit at recurrentgemma-9b's W=4096.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_block(a: jnp.ndarray, b: jnp.ndarray, blk: int):
    """Inclusive scan over axis 0 of (blk, W) via Hillis–Steele doubling."""
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < blk:
        # rows < k have no predecessor k back: identity element (1, 0)
        has_prev = row >= k
        a_prev = jnp.where(has_prev, pltpu.roll(a, k, 0), 1.0)
        b_prev = jnp.where(has_prev, pltpu.roll(b, k, 0), 0.0)
        b = b + a * b_prev
        a = a * a_prev
        k *= 2
    return a, b


def _lru_kernel(a_ref, b_ref, h_ref, hl_ref, carry_ref, *, nb: int, blk: int):
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0].astype(jnp.float32)            # (blk, blk_w)
    b = b_ref[0].astype(jnp.float32)
    a_sc, b_sc = _scan_block(a, b, blk)
    h = b_sc + a_sc * carry_ref[...]             # carry broadcast (1, blk_w)
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = h[blk - 1:blk, :]

    @pl.when(ib == nb - 1)
    def _emit():
        hl_ref[0] = carry_ref[...]


@functools.partial(jax.jit, static_argnames=("blk", "blk_w", "interpret"))
def linear_scan_pallas(
    a: jax.Array,        # (B, S, W)
    b: jax.Array,        # (B, S, W)
    *,
    blk: int = 256,
    blk_w: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, W = a.shape
    blk = min(blk, S)
    assert S % blk == 0, (S, blk)
    nb = S // blk
    blk_w = blk_w if W % blk_w == 0 else W
    nw = W // blk_w

    kernel = functools.partial(_lru_kernel, nb=nb, blk=blk)
    h, hl = pl.pallas_call(
        kernel,
        grid=(B, nw, nb),
        in_specs=[
            pl.BlockSpec((1, blk, blk_w), lambda i, w, j: (i, j, w)),
            pl.BlockSpec((1, blk, blk_w), lambda i, w, j: (i, j, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, blk_w), lambda i, w, j: (i, j, w)),
            # (B, 1, W): the block's last two dims (1, blk_w) tile the array's
            pl.BlockSpec((1, 1, blk_w), lambda i, w, j: (i, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), b.dtype),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, blk_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return h, hl.reshape(B, W)
