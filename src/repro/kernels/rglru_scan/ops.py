"""Public linear-scan op with TPU/CPU dispatch and recompute VJP."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_map_logical
from repro.kernels import use_pallas, interpret_mode
from repro.kernels.rglru_scan.kernel import linear_scan_pallas
from repro.kernels.rglru_scan.ref import linear_scan_reference


# channel-independent: under a mesh the Mosaic kernel runs per shard
_LSCAN_IN = (("batch", None, "lru"), ("batch", None, "lru"))
_LSCAN_OUT = (("batch", None, "lru"), ("batch", "lru"))


@jax.custom_vjp
def _lscan(a, b):
    if use_pallas():
        kernel = functools.partial(linear_scan_pallas,
                                   interpret=interpret_mode())
        return shard_map_logical(kernel, _LSCAN_IN, _LSCAN_OUT)(a, b)
    return linear_scan_reference(a, b)


def _lscan_fwd(a, b):
    out = _lscan(a, b)
    return out, (a, b)


def _lscan_bwd(res, g):
    a, b = res
    _, vjp = jax.vjp(lambda a_, b_: linear_scan_reference(a_, b_), a, b)
    return vjp(g)


_lscan.defvjp(_lscan_fwd, _lscan_bwd)


def linear_scan(a: jax.Array, b: jax.Array,
                h0: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + b_t. Returns (h, h_last).

    A non-zero ``h0`` (prefill continuation) folds into the first step:
    b_0' = b_0 + a_0 * h0 — so the kernel itself always starts from zero.
    """
    if h0 is not None:
        b = b.at[:, 0, :].add(a[:, 0, :] * h0.astype(b.dtype))
    return _lscan(a, b)


def linear_scan_decode_step(a: jax.Array, b: jax.Array,
                            h: jax.Array) -> jax.Array:
    """Single-token update: h' = a*h + b (all (B, W))."""
    return (a.astype(jnp.float32) * h + b.astype(jnp.float32))
