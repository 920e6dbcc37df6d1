"""Public decode-attention op with TPU/CPU dispatch (inference only)."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import use_pallas, interpret_mode
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_reference


def decode_attention(
    q: jax.Array,            # (B, Hq, D)
    k: jax.Array,            # (B, Hkv, T, D)
    v: jax.Array,
    lengths: jax.Array,      # (B,) int32 valid cache length
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    if use_pallas():
        return decode_attention_pallas(
            q, k, v, lengths, window=window, scale=scale,
            interpret=interpret_mode())
    return decode_attention_reference(
        q, k, v, lengths, window=window, scale=scale)
