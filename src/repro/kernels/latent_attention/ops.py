"""Public latent paged decode-attention op with TPU/CPU dispatch
(inference only)."""
from __future__ import annotations

import jax

from repro.kernels import interpret_mode, use_pallas
from repro.kernels.latent_attention.kernel import (
    latent_paged_decode_attention_pallas)
from repro.kernels.latent_attention.ref import (
    latent_paged_decode_attention_reference)


def latent_paged_decode_attention(
    q: jax.Array,              # (B, H, W)
    pages: jax.Array,          # (L, NP, page, W)
    layer,                     # int or () int32
    page_table: jax.Array,     # (B, MAXP)
    lengths: jax.Array,        # (B,)
    *,
    rank: int,
    scale: float,
) -> jax.Array:
    if use_pallas():
        return latent_paged_decode_attention_pallas(
            q, pages, layer, page_table, lengths, rank=rank, scale=scale,
            interpret=interpret_mode())
    return latent_paged_decode_attention_reference(
        q, pages, layer, page_table, lengths, rank=rank, scale=scale)
