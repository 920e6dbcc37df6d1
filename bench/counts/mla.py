"""Bytes and operations of the latent paged decode-attention kernel (MLA's
absorbed decode): per active slot, every head scores each attended
position's whole latent row (``kv_lora_rank + qk_rope_head_dim``) and
takes its first ``kv_lora_rank`` entries as the value.  The bytes it has
to move are each attended row once, each slot's queries in and its
attended latents out; not the pages that hold the rows, nor their
padding."""
from __future__ import annotations

from typing import Iterable


def row_width(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def row_bytes(c: dict, bytes_per_elem: int = 2) -> int:
    """One token's cache row in one layer."""
    return row_width(c) * bytes_per_elem


def kernel_bytes(attended: Iterable[int], slots: int, c: dict,
                 bytes_per_elem: int = 2) -> int:
    """One call (one layer): ``attended`` lists, per slot, the positions it
    attends to; ``slots`` is the batch the call runs (queries and outputs
    of every slot move)."""
    per_slot = c["num_attention_heads"] * (row_width(c) + c["kv_lora_rank"])
    return (row_bytes(c, bytes_per_elem) * sum(attended)
            + slots * per_slot * bytes_per_elem)


def kernel_flops(attended: Iterable[int], c: dict) -> int:
    """One call: scores over the whole row and values over the latent, for
    every head at every attended position."""
    return (2 * c["num_attention_heads"] * (row_width(c) + c["kv_lora_rank"])
            * sum(attended))
