"""Bytes the paged decode-attention kernel has to read: the K and V of the
positions each active slot attends to (its context and the token just
written), not the pages that hold them."""
from __future__ import annotations

from typing import Iterable


def kv_bytes(attended: Iterable[int], num_kv_heads: int, head_dim: int,
             bytes_per_elem: int = 2) -> int:
    """One call (one layer): ``attended`` lists, per active slot, the
    positions it attends to."""
    return 2 * num_kv_heads * head_dim * bytes_per_elem * sum(attended)
