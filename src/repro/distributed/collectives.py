"""Mesh-aware collective helpers.

These wrap ``jax.lax`` collectives with the pod-hierarchical schedules used at
multi-pod scale: gradient reduction is reduce-scatter intra-pod, all-reduce on
the scattered shards across pods (the slow inter-pod links carry 1/data of the
bytes), then all-gather intra-pod.  Under GSPMD (jit) the same effect is
obtained by sharding rules; these explicit forms are used inside ``shard_map``
regions (the MoE dispatch and the paper-benchmark expansion path).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def hierarchical_psum(x, *, intra_axis: str = "data", inter_axis: Optional[str] = "pod"):
    """Pod-hierarchical all-reduce inside ``shard_map``.

    reduce-scatter over ``intra_axis`` -> psum over ``inter_axis`` -> all-gather
    over ``intra_axis``.  Falls back to flat psum when the tensor's leading dim
    does not divide or no inter axis exists.
    """
    if inter_axis is None or not _axis_bound(inter_axis):
        return lax.psum(x, intra_axis)
    n = lax.axis_size(intra_axis)
    if x.ndim == 0 or x.shape[0] % n != 0:
        return lax.psum(x, (intra_axis, inter_axis))
    shard = lax.psum_scatter(x, intra_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, inter_axis)
    return lax.all_gather(shard, intra_axis, axis=0, tiled=True)


def _axis_bound(name: str) -> bool:
    """Whether ``name`` is a mapped axis of the enclosing ``shard_map``:
    ``lax.axis_size`` reads the live axis environment and raises
    ``NameError`` for an unbound name."""
    try:
        lax.axis_size(name)
    except NameError:
        return False
    return True


def all_to_all_tokens(x, axis: str, *, split_dim: int, concat_dim: int):
    """Equal-split all-to-all used by the MoE dispatch (EP)."""
    return lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim,
                          tiled=True)


def barrier(axis) -> None:
    """Cross-device barrier: the paper's cross-team ``omp barrier`` analogue.

    On GPUs the paper realizes this with global atomic counters; on TPU the
    idiomatic equivalent is a trivial collective, which orders all shards.
    """
    lax.psum(jnp.zeros((), jnp.float32), axis)


def global_norm_sq(tree, axis=None):
    """Sum of squared L2 norms of a pytree; psum'd over ``axis`` if given."""
    leaves = jax.tree.leaves(tree)
    total = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    if axis is not None:
        total = lax.psum(total, axis)
    return total
