"""Public SSD ops with TPU/CPU dispatch and recompute VJP for training."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

import jax.numpy as jnp

from repro.distributed.sharding import shard_map_logical
from repro.kernels import use_pallas, interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_scan_reference, ssd_decode_reference


# per-batch, per-head independent: a Mosaic kernel cannot be partitioned by
# GSPMD, so under a mesh it runs per shard of batch and heads
_SSD_IN = (("batch", None, "ssm_heads", None), ("batch", None, "ssm_heads"),
           ("ssm_heads",), ("batch", None, None), ("batch", None, None),
           ("ssm_heads",))
_SSD_OUT = (("batch", None, "ssm_heads", None),
            ("batch", "ssm_heads", None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    if use_pallas():
        kernel = functools.partial(ssd_scan_pallas, chunk=chunk,
                                   interpret=interpret_mode())
        return shard_map_logical(kernel, _SSD_IN, _SSD_OUT)(
            x, dt, A, B, C, D)
    return ssd_scan_reference(x, dt, A, B, C, D, chunk=chunk)


def _ssd_fwd(x, dt, A, B, C, D, chunk):
    out = _ssd(x, dt, A, B, C, D, chunk)
    return out, (x, dt, A, B, C, D)


def _ssd_bwd(chunk, res, g):
    x, dt, A, B, C, D = res
    _, vjp = jax.vjp(
        lambda *a: ssd_scan_reference(*a, chunk=chunk), x, dt, A, B, C, D)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 256,
             initial_state=None) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y, final_state).

    Sequences that do not divide the chunk are zero-padded at the end
    (dt=0 => decay 1, zero input: the final state is unaffected).

    ``initial_state`` is only supported on the reference path (prefill
    continuation); the training path always starts from zero state.
    """
    S = x.shape[1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        pad2 = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = pad2(x), pad2(dt), pad2(B), pad2(C)
    if initial_state is not None:
        y, fs = ssd_scan_reference(x, dt, A, B, C, D, chunk=Q,
                                   initial_state=initial_state)
    else:
        y, fs = _ssd(x, dt, A, B, C, D, Q)
    if pad:
        y = y[:, :S]
    return y, fs


def ssd_decode_step(x, dt, A, B, C, D, state):
    """Single-token state update (O(1) per token; no kernel needed)."""
    return ssd_decode_reference(x, dt, A, B, C, D, state)
