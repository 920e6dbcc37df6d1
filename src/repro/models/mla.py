"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434 §2.1).

Queries pass through a normed low-rank latent (``wq_a`` -> ``q_norm`` ->
``wq_b``); keys and values through one shared latent ``c`` (``wkv_a`` ->
``kv_norm``), expanded per head by ``wk_b`` and ``wv_b``.  A separate 64-wide
rotary part of the key (``k_pe``) is shared by all heads.  So a token's
whole contribution to the cache is one row ``[c | rope(k_pe)]``.

Two forms of the same function:

* **naive** (:func:`mla_apply`) — keys and values expanded per head, plain
  causal softmax attention; the training / full-sequence path;
* **absorbed** (:func:`absorbed_query`, :func:`absorbed_output`) — decode
  against the latent rows: ``wk_b`` is folded into the query, so the
  scores are ``[q_nope wk_b | q_pe] . [c | k_pe]``, and ``wv_b`` is applied
  after attention to the attended latent.

Rotary angles follow YaRN (``yarn_inv_freq``); the softmax scale is
``(nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import common
from repro.models.common import normal, rmsnorm, rmsnorm_init

NEG_INF = -1e30


def mla_init(key, cfg: ModelConfig) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        "wq_a": normal(ks[0], (d, rq), ("fsdp", None), pd),
        "q_norm": rmsnorm_init(rq, pd),
        "wq_b": normal(ks[1], (rq, H, nope + rope), (None, "heads", "head_dim"),
                       pd),
        "wkv_a": normal(ks[2], (d, rkv + rope), ("fsdp", None), pd),
        "kv_norm": rmsnorm_init(rkv, pd),
        "wk_b": normal(ks[3], (rkv, H, nope), (None, "heads", "head_dim"), pd),
        "wv_b": normal(ks[4], (rkv, H, vd), (None, "heads", "head_dim"), pd),
        "wo": normal(ks[5], (H, vd, d), ("heads", "head_dim", "fsdp"), pd,
                     scale=(H * vd) ** -0.5),
    }


# ---------------------------------------------------------------------------
# YaRN rotary angles and the softmax scale
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Per pair of rotary dims, its frequency: the original ones above the
    high-frequency correction dim, divided by ``yarn_factor`` below the
    low one, and a linear ramp between (YaRN, as DeepSeek-V2 sets it)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.yarn_factor <= 1:
        return extra.astype(np.float32)

    def corr(rotations):
        return dim * math.log(cfg.yarn_original_max_pos
                              / (rotations * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp            # 1 where the original frequency is kept
    inv = extra / cfg.yarn_factor * (1 - keep) + extra * keep
    return inv.astype(np.float32)


def rope_scale(cfg: ModelConfig) -> float:
    """YaRN's factor on cos and sin."""
    if cfg.yarn_factor <= 1:
        return 1.0
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) / \
        yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)


def softmax_scale(cfg: ModelConfig) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s = s * m * m
    return s


def rope_angles(positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """positions (B, S) -> (B, S, rope/2) angles."""
    return positions[..., None].astype(jnp.float32) * \
        jnp.asarray(yarn_inv_freq(cfg))


def _rope(x, angles, cfg: ModelConfig):
    out = common.apply_rope(x, angles)
    m = rope_scale(cfg)
    return out if m == 1.0 else (out.astype(jnp.float32) * m).astype(x.dtype)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _queries(p, x, cfg: ModelConfig, angles) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> q_nope (B, S, H, nope), roped q_pe (B, S, H, rope)."""
    dt = x.dtype
    cq = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].value.astype(dt))
    cq = rmsnorm(cq, p["q_norm"].value, cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].value.astype(dt))
    q = wlc(q, "batch", "seq", "heads", "head_dim")
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], _rope(q[..., nope:], angles, cfg)


def latent_rows(p, x, cfg: ModelConfig, angles) -> jax.Array:
    """x (B, S, d) -> the cache rows (B, S, kv_lora_rank + rope):
    ``[rmsnorm(c_kv) | rope(k_pe)]``."""
    dt = x.dtype
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].value.astype(dt))
    r = cfg.kv_lora_rank
    c = rmsnorm(kv[..., :r], p["kv_norm"].value, cfg.norm_eps)
    k_pe = _rope(kv[..., None, r:], angles, cfg)[..., 0, :]
    return jnp.concatenate([c, k_pe], axis=-1)


def mla_apply(p, x: jax.Array, cfg: ModelConfig, *, angles: jax.Array,
              causal: bool = True, return_latent: bool = False):
    """Naive full-sequence MLA: per-head keys ``[c wk_b | k_pe]`` and values
    ``c wv_b``.  ``return_latent`` also returns the cache rows."""
    with jax.named_scope("mla"):
        dt = x.dtype
        S = x.shape[1]
        q_nope, q_pe = _queries(p, x, cfg, angles)
        rows = latent_rows(p, x, cfg, angles)
        c, k_pe = rows[..., :cfg.kv_lora_rank], rows[..., cfg.kv_lora_rank:]
        k_nope = jnp.einsum("bsr,rhn->bshn", c, p["wk_b"].value.astype(dt))
        v = jnp.einsum("bsr,rhv->bshv", c, p["wv_b"].value.astype(dt))
        s = jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                           preferred_element_type=jnp.float32)
        s = s * softmax_scale(cfg)
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("bhqk,bkhv->bqhv", w, v)
        o = wlc(o, "batch", "seq", "heads", "head_dim")
        out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].value.astype(dt))
        out = wlc(out, "batch", "seq", "embed")
    if return_latent:
        return out, rows
    return out


def absorbed_query(p, x: jax.Array, cfg: ModelConfig, angles
                   ) -> Tuple[jax.Array, jax.Array]:
    """One token per slot, x (B, 1, d) -> the latent-space query (B, H,
    kv_lora_rank + rope), ``[q_nope wk_b | q_pe]``, and the token's cache
    row (B, kv_lora_rank + rope)."""
    dt = x.dtype
    q_nope, q_pe = _queries(p, x, cfg, angles)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                       p["wk_b"].value.astype(dt))
    q = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)
    return q, latent_rows(p, x, cfg, angles)[:, 0]


def absorbed_output(p, o_lat: jax.Array, dt) -> jax.Array:
    """Attended latents (B, H, kv_lora_rank) -> the layer's output (B, d):
    ``wv_b`` per head, then ``wo``."""
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dt), p["wv_b"].value.astype(dt))
    return jnp.einsum("bhv,hvd->bd", o, p["wo"].value.astype(dt))


def latent_attention(q: jax.Array, rows: jax.Array, valid: jax.Array,
                     scale: float, rank: int) -> jax.Array:
    """Dense latent attention: q (B, H, W), rows (B, T, W), ``valid`` (B, T)
    -> (B, H, rank).  Scores use the whole row, values its first ``rank``."""
    s = jnp.einsum("bhw,btw->bht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", w, rows[..., :rank].astype(jnp.float32))


def mla_decode(p, x: jax.Array, cfg: ModelConfig, *, cache: jax.Array,
               lengths: jax.Array, angles: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """One-token absorbed decode against a contiguous latent cache
    (B, T, W): write the token's row at ``lengths``, attend over
    ``lengths + 1``.  Returns (out (B, 1, d), cache)."""
    with jax.named_scope("mla"):
        B, T = cache.shape[0], cache.shape[1]
        q, row = absorbed_query(p, x, cfg, angles)
        cache = cache.at[jnp.arange(B), lengths].set(row.astype(cache.dtype))
        valid = jnp.arange(T)[None, :] < (lengths + 1)[:, None]
        o = latent_attention(q, cache, valid, softmax_scale(cfg),
                             cfg.kv_lora_rank)
        out = absorbed_output(p, o, x.dtype)[:, None]
    return wlc(out, "batch", "seq", "embed"), cache
