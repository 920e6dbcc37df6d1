"""Public flash-attention op with TPU/CPU dispatch and a recompute VJP.

Forward: Pallas kernel on TPU, XLA reference elsewhere.  Backward: flash
recompute via the reference VJP (the canonical memory-saving trade: no
(Sq x Sk) score tensor is ever *saved*; it is recomputed from q,k,v).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas, interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import (
    attention_reference, attention_reference_chunked)

# beyond this many score-matrix elements the XLA path switches to the
# scan-chunked flash (never materializes (Sq, Sk))
_CHUNKED_THRESHOLD = 1 << 22


def _xla_attention(q, k, v, causal, window, q_offset, scale):
    if q.shape[2] * k.shape[2] > _CHUNKED_THRESHOLD:
        return attention_reference_chunked(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            scale=scale)
    return attention_reference(
        q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, q_offset, scale):
    if use_pallas():
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            scale=scale, interpret=interpret_mode())
    return _xla_attention(q, k, v, causal, window, q_offset, scale)


def _flash_fwd(q, k, v, causal, window, q_offset, scale):
    out = _flash(q, k, v, causal, window, q_offset, scale)
    return out, (q, k, v)


def _flash_bwd(causal, window, q_offset, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _xla_attention(
            q_, k_, v_, causal, window, q_offset, scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """GQA attention, head-major. q: (B,Hq,Sq,D); k/v: (B,Hkv,Sk,D) with
    Hq % Hkv == 0."""
    return _flash(q, k, v, causal, window, q_offset, scale)
