"""Pure-jnp oracle for latent paged decode attention: gather the pages,
then dense attention in float32."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.latent_attention.kernel import NEG_INF


def latent_paged_decode_attention_reference(
    q: jnp.ndarray,            # (B, H, W)
    pages: jnp.ndarray,        # (L, NP, page, W)
    layer,                     # int or () int32
    page_table: jnp.ndarray,   # (B, MAXP) int32 page ids (garbage past length)
    lengths: jnp.ndarray,      # (B,) int32
    *,
    rank: int,
    scale: float,
) -> jnp.ndarray:
    B, H, W = q.shape
    _, NP, page, _ = pages.shape
    maxp = page_table.shape[1]
    rows = pages[layer][jnp.clip(page_table, 0, NP - 1)].reshape(B, maxp * page, W)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * scale
    valid = jnp.arange(maxp * page)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum("bht,btr->bhr", p, rows[..., :rank])
    return (out / jnp.sum(p, axis=-1, keepdims=True)).astype(q.dtype)
