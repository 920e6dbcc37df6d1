"""Pallas TPU kernel for the mamba2 SSD chunked scan.

Grid = (B, H, S/Q) with the chunk axis innermost ("arbitrary"): each head's
running SSM state (P, N fp32) lives in VMEM scratch and is carried across
chunks, so HBM traffic is exactly one read of (x, dt, B, C) and one write of
y — the scan itself never touches HBM.  Within a chunk the intra-chunk term
is the masked-quadratic duality form, which maps onto the MXU as
(Q x N)·(N x Q) and (Q x Q)·(Q x P) matmuls.

Mosaic has no cumsum, so the in-chunk prefix sum of dt*A is a masked
reduction against the lower-triangular ones matrix, done on the VPU in fp32.
The same trick (a reduction against the identity) turns a (1, Q) row into
a (Q, 1) column.  The wrapper lays x out head-major and dt as rows, so every
block's last two dimensions tile the array's.

VMEM: state 64x128x4 = 32 KB per head (mamba2-130m) + chunk blocks and a few
(Q, Q) fp32 temporaries (Q=256: 256 KB each) — well inside the scoped limit.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, d_ref,                    # scalar prefetch: (H,) each
                x_ref, dt_ref, b_ref, c_ref,
                y_ref, fs_ref,
                state_ref,
                *, nc: int, Q: int):
    h = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)         # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)   # (1, Q)
    B = b_ref[0].astype(jnp.float32)            # (Q, N)
    C = c_ref[0].astype(jnp.float32)            # (Q, N)

    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tril, eye = j <= i, i == j

    def to_col(row):                            # (1, Q) -> (Q, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    da_row = dt_row * a_ref[h]
    cs_col = jnp.sum(jnp.where(tril, da_row, 0.0), axis=1,
                     keepdims=True)                               # (Q, 1)
    cs_row = jnp.sum(jnp.where(eye, cs_col, 0.0), axis=0,
                     keepdims=True)                               # (1, Q)
    cs_last = jnp.sum(da_row, axis=1, keepdims=True)              # (1, 1)
    dt_col = to_col(dt_row)

    # intra-chunk masked quadratic term; mask BEFORE exp (j > i overflows)
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, Q) i,j
    seg = jnp.exp(jnp.where(tril, cs_col - cs_row, -1e30))
    M = G * seg * dt_row                                          # weight j->i
    y_intra = jnp.dot(M, x, preferred_element_type=jnp.float32)   # (Q, P)

    # inter-chunk: contribution of the carried state
    state = state_ref[...]                                        # (P, N)
    cstate = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, P)
    y = y_intra + jnp.exp(cs_col) * cstate + d_ref[h] * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: decay the whole chunk, add the chunk's contribution
    w = jnp.exp(cs_last - cs_col) * dt_col                        # (Q, 1)
    S_c = jax.lax.dot_general(x * w, B, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = jnp.exp(cs_last) * state + S_c

    @pl.when(ic == nc - 1)
    def _emit_state():
        fs_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jax.Array,       # (B, S, H, P)
    dt: jax.Array,      # (B, S, H)
    A: jax.Array,       # (H,)
    B: jax.Array,       # (B, S, N)
    C: jax.Array,       # (B, S, N)
    D: jax.Array,       # (H,)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    bsz, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    xh = jnp.swapaxes(x, 1, 2)                              # (B, H, S, P)
    dt_rows = jnp.swapaxes(dt, 1, 2)[:, :, None, :]         # (B, H, 1, S)
    kernel = functools.partial(_ssd_kernel, nc=nc, Q=Q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c, a, d: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c, a, d: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c, a, d: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c, a, d: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c, a, d: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c, a, d: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    y, fs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bsz, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((bsz, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), D.astype(jnp.float32), xh, dt_rows, B, C)
    return jnp.swapaxes(y, 1, 2), fs
