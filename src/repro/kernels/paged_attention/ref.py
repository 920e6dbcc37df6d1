"""Pure-jnp oracle for paged decode attention: gather pages, then dense."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_attention_reference


def paged_decode_attention_reference(
    q: jnp.ndarray,            # (B, Hq, D)
    k_pages: jnp.ndarray,      # (L, NP, page * Hkv, D), row-interleaved
    v_pages: jnp.ndarray,
    layer,                     # int or () int32
    page_table: jnp.ndarray,   # (B, MAXP) int32 page ids (garbage past length)
    lengths: jnp.ndarray,      # (B,) int32
    *,
    page_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """A slot of length 0 attends to nothing and reads zeros."""
    B = q.shape[0]
    _, NP, rows, D = k_pages.shape
    hkv = rows // page_size
    maxp = page_table.shape[1]
    safe = jnp.clip(page_table, 0, NP - 1)

    def gather(pages):      # (B, maxp, page * Hkv, D) -> (B, Hkv, T, D)
        g = pages[layer][safe].reshape(B, maxp, page_size, hkv, D)
        return jnp.moveaxis(g, 3, 1).reshape(B, hkv, maxp * page_size, D)

    out = decode_attention_reference(q, gather(k_pages), gather(v_pages),
                                     lengths, window=window, scale=scale)
    return jnp.where(lengths[:, None, None] > 0, out, 0).astype(q.dtype)
