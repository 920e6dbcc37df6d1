"""Paged KV cache managed by the paper's balanced allocator (§3.4, applied).

The balanced allocator was designed for "balanced allocations and
deallocations at parallel-region boundaries"; a serving KV cache has exactly
that lifetime structure per request.  Mapping:

  chunk slot        <- request slot  (tid % N with N = max batch slots)
  allocation        <- one KV page (``page_size`` tokens, all layers)
  watermark reclaim <- request completion frees its whole chunk stack (O(1))

Both sides of the page lifecycle ride the allocator's bulk paths:
``ensure_pages`` is ONE prefix-sum ``malloc_grid`` (allocator v2 — no
``lax.scan`` over slots) and ``release_slots`` retires any number of
finished requests with ONE vectorized chunk reset.

Pages are shared across layers (a page id addresses every layer's page
arrays), as in vLLM.  Attention over the paged cache uses the
``paged_attention`` Pallas kernel on TPU (the page table drives BlockSpec
index maps) and a gather-based XLA reference elsewhere.

**Sharded page heaps.**  When a mesh is passed (``paged_cache_init(...,
mesh=)``), the page allocator becomes a per-device
:class:`~repro.core.allocator.ShardedHeap` of balanced states: the page-id
space is partitioned into one contiguous span per device, batch slots are
block-assigned to devices (slot ``b`` lives on device ``b // (B / D)``),
and both ``ensure_pages`` and ``release_slots`` run every device's shard in
parallel — no funnel through one allocator state when the engine itself is
expanded over the mesh.  Page ids stay global (``dev * span + local``), so
the page table, the attention kernels, and ``find_obj``-based ``ArenaRef``
marshalling are unchanged.  On a 1-device mesh the sharded path is
bit-identical to the single-heap path.

**Host-side page spill** (transport v3): when the engine is constructed
with a ``spill_sink``, every retiring request ships its page-id list
(:func:`live_pages`) to the host as ONE batched payload RPC — the ids ride
the RPC queue's on-device arena and the whole tick's retirements drain in
one ordered callback, instead of a per-page (or per-request) round-trip.

The page-heap operations (``ensure_pages``, ``write_token_kv``,
``advance``, ``release_slots``) run under the ``kvcache`` named scope, so
a profile gives their device time to the page heap.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.allocator import (BalancedAllocator, BalancedState,
                                  ShardedAllocator, ShardedHeap, shard_heap)
from repro.kernels.paged_attention import paged_decode_attention


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKV:
    k_pages: jax.Array       # (L, NP, Hkv, page, hd): head-major pages
    v_pages: jax.Array
    page_table: jax.Array    # (B, MAXP) int32
    lengths: jax.Array       # (B,) int32
    alloc: Union[BalancedState, ShardedHeap]  # page-slot allocator
    #                          (arena = page-id space; sharded under a mesh)
    page_size: int

    def tree_flatten(self):
        return ((self.k_pages, self.v_pages, self.page_table, self.lengths,
                 self.alloc), self.page_size)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, aux)


jax.export.register_pytree_node_serialization(
    PagedKV, serialized_name="repro.serving.kvcache.PagedKV",
    serialize_auxdata=lambda page_size: str(int(page_size)).encode("ascii"),
    deserialize_auxdata=lambda b: int(b.decode("ascii")))


def _scoped(fn):
    """Run ``fn`` under the ``kvcache`` named scope (op metadata only)."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.named_scope("kvcache"):
            return fn(*args, **kwargs)
    return wrapper


def _mesh_devices(mesh) -> int:
    """Device count of a mesh-like: a ``jax.sharding.Mesh`` or a plain int
    (logical shard count — lets tests/benches run D>1 shards on one physical
    device; the sharded heap is a data layout, not a placement)."""
    return int(mesh) if isinstance(mesh, int) else int(mesh.size)


def paged_cache_init(cfg: ModelConfig, batch_slots: int, max_len: int,
                     *, page_size: int = 64,
                     n_pages: Optional[int] = None, mesh=None) -> PagedKV:
    hd = cfg.resolved_head_dim
    maxp = (max_len + page_size - 1) // page_size
    n_pages = n_pages if n_pages is not None else batch_slots * maxp
    cdt = jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    if mesh is None:
        alloc = BalancedAllocator.init(
            n_pages, batch_slots, 1, cap=maxp, first_chunk_ratio=1.0)
    else:
        D = _mesh_devices(mesh)
        assert batch_slots % D == 0, \
            f"batch_slots={batch_slots} must tile the {D} mesh devices"
        assert n_pages % D == 0, \
            f"n_pages={n_pages} must tile the {D} mesh devices"
        local = BalancedAllocator.init(
            n_pages // D, batch_slots // D, 1, cap=maxp,
            first_chunk_ratio=1.0)
        alloc = shard_heap(local, D)      # span = pages per device
    return PagedKV(
        k_pages=jnp.zeros((L, n_pages, cfg.num_kv_heads, page_size, hd), cdt),
        v_pages=jnp.zeros((L, n_pages, cfg.num_kv_heads, page_size, hd), cdt),
        page_table=jnp.zeros((batch_slots, maxp), jnp.int32),
        lengths=jnp.zeros((batch_slots,), jnp.int32),
        alloc=alloc,
        page_size=page_size)


@_scoped
def ensure_pages(kv: PagedKV, active: jax.Array) -> PagedKV:
    """Allocate a page for every active slot whose next token crosses a page
    boundary.  One balanced-allocator grid call: chunks are per-slot, so the
    allocation is embarrassingly parallel (and a full slot fails safe: FAIL
    page ids are clipped by the kernel and masked by ``lengths``).  With a
    sharded page heap, each device's shard serves its block of slots — all
    devices in parallel, global page ids out."""
    B = kv.lengths.shape[0]
    need = active & (kv.lengths % kv.page_size == 0)
    sizes = jnp.where(need, 1, 0).astype(jnp.int32).reshape(B, 1)
    if isinstance(kv.alloc, ShardedHeap):
        D = kv.alloc.n_devices
        alloc, ptrs = ShardedAllocator.malloc_grid(
            kv.alloc, B // D, 1, sizes.reshape(D, B // D, 1))
    else:
        alloc, ptrs = BalancedAllocator.malloc_grid(kv.alloc, B, 1, sizes)
    ptrs = ptrs.reshape(B)
    slot_idx = kv.lengths // kv.page_size
    new_table = jnp.where(
        need, ptrs,
        kv.page_table[jnp.arange(B), jnp.minimum(slot_idx,
                                                 kv.page_table.shape[1] - 1)])
    page_table = kv.page_table.at[
        jnp.arange(B), jnp.minimum(slot_idx, kv.page_table.shape[1] - 1)
    ].set(new_table)
    return dataclasses.replace(kv, alloc=alloc, page_table=page_table)


@_scoped
def write_token_kv(kv: PagedKV, layer, k: jax.Array, v: jax.Array,
                   active: jax.Array) -> PagedKV:
    """Write one token's K/V (B, Hkv, hd) for ``layer`` (an int or a traced
    index, e.g. inside a scan over layers) at each active slot's current
    position."""
    B = kv.lengths.shape[0]
    pos = kv.lengths
    pidx = jnp.minimum(pos // kv.page_size, kv.page_table.shape[1] - 1)
    page = kv.page_table[jnp.arange(B), pidx]
    off = pos % kv.page_size
    # inactive slots write to an out-of-range page id: the scatter drops it
    NP = kv.k_pages.shape[1]
    page = jnp.where(active, page, NP)
    k_pages = kv.k_pages.at[layer, page, :, off, :].set(
        k.astype(kv.k_pages.dtype))
    v_pages = kv.v_pages.at[layer, page, :, off, :].set(
        v.astype(kv.v_pages.dtype))
    return dataclasses.replace(kv, k_pages=k_pages, v_pages=v_pages)


def paged_attend(kv: PagedKV, layer: int, q: jax.Array,
                 window: Optional[int] = None) -> jax.Array:
    """q: (B, Hq, hd) one token per slot -> (B, Hq, hd).  Attends over
    lengths+1 entries (the current token was just written)."""
    return paged_decode_attention(
        q, kv.k_pages[layer], kv.v_pages[layer], kv.page_table,
        kv.lengths + 1, window=window)


@_scoped
def advance(kv: PagedKV, active: jax.Array) -> PagedKV:
    return dataclasses.replace(
        kv, lengths=kv.lengths + active.astype(jnp.int32))


def live_pages(kv: PagedKV, slot: int) -> jax.Array:
    """Page ids currently backing ``slot`` (in position order): the page
    table's live prefix, one entry per started page.  The engine's
    host-side page-spill path ships this as ONE batched payload RPC per
    retiring request (transport v3) instead of a per-page round-trip —
    call BEFORE releasing the slot."""
    n = int((int(kv.lengths[slot]) + kv.page_size - 1) // kv.page_size)
    return kv.page_table[slot, :n]


def release_slot(kv: PagedKV, slot: int) -> PagedKV:
    """O(1) request completion: reset the slot's allocator chunk (watermark
    reclaim of the whole stack) and zero its table row."""
    if isinstance(kv.alloc, ShardedHeap):
        B = kv.lengths.shape[0]
        return release_slots(
            kv, jnp.zeros((B,), bool).at[slot].set(True))
    alloc = BalancedAllocator.reset_chunk(kv.alloc, slot)
    return dataclasses.replace(
        kv, alloc=alloc,
        page_table=kv.page_table.at[slot].set(0),
        lengths=kv.lengths.at[slot].set(0))


@_scoped
def release_slots(kv: PagedKV, mask: jax.Array) -> PagedKV:
    """Bulk request completion: release every slot where ``mask`` (B,) is
    true in ONE vectorized allocator reset — the free-side counterpart of
    :func:`ensure_pages`'s bulk page allocation (no per-slot loop, so a
    continuous-batching engine retiring many requests per step pays one
    dispatch).  With a sharded page heap, each device resets its own
    shard's chunks — all devices in parallel."""
    mask = jnp.asarray(mask)
    if isinstance(kv.alloc, ShardedHeap):
        D = kv.alloc.n_devices
        alloc = ShardedAllocator.reset_chunks(
            kv.alloc, mask.reshape(D, mask.shape[0] // D))
    else:
        alloc = BalancedAllocator.reset_chunks(kv.alloc, mask)
    return dataclasses.replace(
        kv,
        alloc=alloc,
        page_table=jnp.where(mask[:, None], 0, kv.page_table),
        lengths=jnp.where(mask, 0, kv.lengths))
