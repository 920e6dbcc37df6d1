"""Pure-jnp oracle for paged decode attention: gather pages, then dense."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_attention_reference


def paged_decode_attention_reference(
    q: jnp.ndarray,            # (B, Hq, D)
    k_pages: jnp.ndarray,      # (NP, Hkv, page, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # (B, MAXP) int32 page ids (garbage past length)
    lengths: jnp.ndarray,      # (B,) int32
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B = q.shape[0]
    NP, Hkv, page, D = k_pages.shape
    maxp = page_table.shape[1]
    safe = jnp.clip(page_table, 0, NP - 1)

    def gather(pages):                   # (B, maxp, Hkv, page, D) -> (B, Hkv, T, D)
        g = jnp.swapaxes(pages[safe], 1, 2)
        return g.reshape(B, Hkv, maxp * page, D)

    k, v = gather(k_pages), gather(v_pages)
    return decode_attention_reference(q, k, v, lengths, window=window,
                                      scale=scale)
