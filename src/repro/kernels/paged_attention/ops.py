"""Public paged decode-attention op with TPU/CPU dispatch (inference only)."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import use_pallas, interpret_mode
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import paged_decode_attention_reference


def paged_decode_attention(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (L, NP, page * Hkv, D), row-interleaved
    v_pages: jax.Array,
    layer,                     # int or () int32
    page_table: jax.Array,     # (B, MAXP)
    lengths: jax.Array,        # (B,)
    *,
    page_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    if use_pallas():
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, layer, page_table, lengths,
            page_size=page_size, window=window, scale=scale,
            interpret=interpret_mode())
    return paged_decode_attention_reference(
        q, k_pages, v_pages, layer, page_table, lengths, page_size=page_size,
        window=window, scale=scale)
