"""GQA multi-head attention: train/prefill path + KV-cache decode path.

Projections are (B, S, H, hd); the attention kernels and the KV caches are
head-major, (B, H, S, hd), so each kernel block is a whole (tokens, hd) tile
of one head.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.models import common
from repro.models.common import Param, normal, zeros


def attn_init(key, cfg: ModelConfig) -> dict:
    """Q heads are zero-padded to ``cfg.padded_heads`` so head-TP divides the
    model axis.  Exactness: pad-head outputs are masked in ``_mask_heads``,
    so pad weights receive zero gradient and never drift from zero."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.padded_heads, cfg.num_kv_heads
    real = cfg.num_heads
    ks = jax.random.split(key, 4)
    pd = jnp.dtype(cfg.param_dtype)

    def padded(key, shape, axes, scale=None, head_axis=None):
        prm = normal(key, shape, axes, pd, scale=scale)
        if hq != real and head_axis is not None:
            mask_shape = [1] * len(shape)
            mask_shape[head_axis] = shape[head_axis]
            mask = (jnp.arange(shape[head_axis]) < real).reshape(mask_shape)
            prm.value = prm.value * mask.astype(pd)
        return prm

    p = {
        "wq": padded(ks[0], (d, hq, hd), ("fsdp", "heads", "head_dim"),
                     head_axis=1),
        "wk": normal(ks[1], (d, hkv, hd), ("fsdp", "kv_heads", "head_dim"), pd),
        "wv": normal(ks[2], (d, hkv, hd), ("fsdp", "kv_heads", "head_dim"), pd),
        "wo": padded(ks[3], (hq, hd, d), ("heads", "head_dim", "fsdp"),
                     scale=(real * hd) ** -0.5, head_axis=0),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((hq, hd), ("heads", "head_dim"), pd)
        p["bk"] = zeros((hkv, hd), ("kv_heads", "head_dim"), pd)
        p["bv"] = zeros((hkv, hd), ("kv_heads", "head_dim"), pd)
    return p


def _mask_heads(out: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Zero pad-head outputs (dim -2 is heads): keeps padding exact AND
    gradient-isolated (d wo_pad = 0 because out_pad = 0)."""
    if cfg.padded_heads == cfg.num_heads:
        return out
    mask = jnp.arange(cfg.padded_heads) < cfg.num_heads
    return out * mask[:, None].astype(out.dtype)


def _project_qkv(p, x, cfg: ModelConfig, angles):
    """x: (B,S,d) -> q (B,S,Hq,hd), k/v (B,S,Hkv,hd), rotary applied."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].value.astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].value.astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].value.astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].value.astype(dt)
        k = k + p["bk"].value.astype(dt)
        v = v + p["bv"].value.astype(dt)
    if angles is not None:
        q = common.apply_rope(q, angles)
        k = common.apply_rope(k, angles)
    q = wlc(q, "batch", "seq", "heads", "head_dim")
    k = wlc(k, "batch", "seq", "kv_heads", "head_dim")
    v = wlc(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def attn_apply(
    p, x: jax.Array, cfg: ModelConfig, *,
    angles: Optional[jax.Array],
    causal: bool = True,
    window: Optional[int] = None,
    return_kv: bool = False,
):
    """Full-sequence attention (training / prefill).  ``return_kv`` also
    returns the head-major (B, Hkv, S, hd) K/V that fill a decode cache."""
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in _project_qkv(p, x, cfg, angles))
    out = flash_attention(q, k, v, causal=causal, window=window)
    out = _mask_heads(jnp.swapaxes(out, 1, 2), cfg)
    out = wlc(out, "batch", "seq", "heads", "head_dim")
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].value.astype(x.dtype))
    out = wlc(out, "batch", "seq", "embed")
    if return_kv:
        return out, (k, v)
    return out


def cross_kv(p, enc_out: jax.Array, cfg: ModelConfig
             ) -> Tuple[jax.Array, jax.Array]:
    """Project encoder output to head-major cross-attention K/V
    (B, Hkv, S, hd), cached for decode."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bhsk", enc_out, p["wk"].value.astype(dt))
    v = jnp.einsum("bsd,dhk->bhsk", enc_out, p["wv"].value.astype(dt))
    if cfg.qkv_bias:
        k = k + p["bk"].value.astype(dt)[:, None]
        v = v + p["bv"].value.astype(dt)[:, None]
    k = wlc(k, "batch", "kv_heads", "seq", "head_dim")
    v = wlc(v, "batch", "kv_heads", "seq", "head_dim")
    return k, v


def cross_attn_apply(p, xq: jax.Array, kv: Tuple[jax.Array, jax.Array],
                     cfg: ModelConfig) -> jax.Array:
    """Cross attention (no rotary, non-causal). xq: (B,Sq,d)."""
    dt = xq.dtype
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"].value.astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].value.astype(dt)
    q = wlc(q, "batch", "seq", "heads", "head_dim")
    k, v = kv
    out = flash_attention(jnp.swapaxes(q, 1, 2), k, v, causal=False)
    out = _mask_heads(jnp.swapaxes(out, 1, 2), cfg)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].value.astype(dt))
    return wlc(out, "batch", "seq", "embed")


def cross_attn_decode(p, x: jax.Array, kv: Tuple[jax.Array, jax.Array],
                      enc_lens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """One-token cross attention against the cached encoder K/V."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].value.astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].value.astype(dt)
    k, v = kv
    out = decode_attention(q[:, 0], k, v, enc_lens)
    out = _mask_heads(out[:, None], cfg)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].value.astype(dt))
    return wlc(out, "batch", "seq", "embed")


def attn_decode(
    p, x: jax.Array, cfg: ModelConfig, *,
    k_cache: jax.Array,            # (B, Hkv, T, hd)
    v_cache: jax.Array,
    lengths: jax.Array,            # (B,) current length BEFORE this token
    angles: Optional[jax.Array],   # (B, 1, hd//2)
    window: Optional[int] = None,
    write_pos: Optional[jax.Array] = None,   # ring-buffer write index (B,)
    valid_len: Optional[jax.Array] = None,   # valid entries AFTER the write (B,)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. Returns (out (B,1,d), k_cache, v_cache).

    The default is a contiguous cache (write at ``lengths``, attend over
    ``lengths+1``).  Passing ``write_pos``/``valid_len`` turns the cache into
    a ring buffer (local-attention windows): ring order is softmax-invariant
    because rotary phases are applied with absolute positions at write time.
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, cfg, angles)      # S == 1
    idx = jnp.arange(B)
    wp = lengths if write_pos is None else write_pos
    k_cache = k_cache.at[idx, :, wp].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[idx, :, wp].set(v[:, 0].astype(v_cache.dtype))
    k_cache = wlc(k_cache, "batch", None, "seq_kv", "head_dim")
    v_cache = wlc(v_cache, "batch", None, "seq_kv", "head_dim")
    vl = lengths + 1 if valid_len is None else valid_len
    out = decode_attention(q[:, 0], k_cache, v_cache, vl, window=window)
    out = _mask_heads(out[:, None], cfg)            # (B, 1, Hq, hd)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].value.astype(x.dtype))
    return wlc(out, "batch", "seq", "embed"), k_cache, v_cache
