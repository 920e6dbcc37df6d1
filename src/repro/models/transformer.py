"""Decoder-only LM assembly for dense / MoE / VLM / SSM / hybrid families.

Layers are stacked and iterated with ``lax.scan`` (small HLO, essential for
48–94-layer configs under GSPMD), with a configurable remat policy on the
layer body.  The same code path serves training (full sequence), prefill, and
single-token decode with per-family caches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import common
from repro.models import mla
from repro.models.attention import attn_init, attn_apply, attn_decode
from repro.models.common import (
    merge_params, rmsnorm, rmsnorm_init, split_params, stack_params)
from repro.models.mlp import mlp_init, mlp_apply
from repro.models.moe import moe_init, moe_apply
from repro.models.rglru import (
    rglru_apply, rglru_cache_axes, rglru_decode, rglru_init, rglru_init_cache)
from repro.models.ssd import (
    ssd_apply, ssd_cache_axes, ssd_decode, ssd_init, ssd_init_cache)


# ---------------------------------------------------------------------------
# Layer init
# ---------------------------------------------------------------------------

def _attn_layer_init(key, cfg: ModelConfig, moe: bool = False) -> dict:
    k1, k2 = jax.random.split(key)
    pd = jnp.dtype(cfg.param_dtype)
    layer = {
        "ln1": rmsnorm_init(cfg.d_model, pd),
        "attn": mla.mla_init(k1, cfg) if cfg.is_mla else attn_init(k1, cfg),
        "ln2": rmsnorm_init(cfg.d_model, pd),
    }
    if moe:
        layer["moe"] = moe_init(k2, cfg)
    else:
        layer["mlp"] = mlp_init(k2, cfg)
    return layer


def _ssm_layer_init(key, cfg: ModelConfig) -> dict:
    pd = jnp.dtype(cfg.param_dtype)
    return {"ln1": rmsnorm_init(cfg.d_model, pd), "ssd": ssd_init(key, cfg)}


def _rec_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": rmsnorm_init(cfg.d_model, pd),
        "rglru": rglru_init(k1, cfg),
        "ln2": rmsnorm_init(cfg.d_model, pd),
        "mlp": mlp_init(k2, cfg),
    }


def hybrid_layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    pat = cfg.block_pattern or ("rec", "rec", "attn")
    kinds = []
    while len(kinds) < cfg.num_layers:
        kinds.extend(pat)
    return tuple(kinds[: cfg.num_layers])


def lm_init(key, cfg: ModelConfig) -> dict:
    """Full parameter tree (leaves are Param)."""
    keys = jax.random.split(key, cfg.num_layers + 3)
    pd = jnp.dtype(cfg.param_dtype)
    params: Dict[str, Any] = {
        "embed": common.embedding_init(keys[0], cfg),
        "ln_f": rmsnorm_init(cfg.d_model, pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.lm_head_init(keys[1], cfg)

    if cfg.family == "ssm":
        layers = [_ssm_layer_init(keys[i + 2], cfg) for i in range(cfg.num_layers)]
        params["layers"] = stack_params(layers)
    elif cfg.family == "hybrid":
        kinds = hybrid_layer_kinds(cfg)
        rec = [_rec_layer_init(keys[i + 2], cfg)
               for i, k in enumerate(kinds) if k == "rec"]
        att = [_attn_layer_init(keys[i + 2], cfg)
               for i, k in enumerate(kinds) if k == "attn"]
        params["rec_layers"] = stack_params(rec)
        params["attn_layers"] = stack_params(att)
    else:
        k = cfg.first_k_dense
        layers = [_attn_layer_init(keys[i + 2], cfg, cfg.is_moe and i >= k)
                  for i in range(cfg.num_layers)]
        if k:
            params["dense_layers"] = stack_params(layers[:k])
        params["layers"] = stack_params(layers[k:])
    return params


def layer_stacks(cfg: ModelConfig) -> Tuple[Tuple[str, int, int, bool], ...]:
    """The decoder's stacks of layers in order, each (params key, first
    layer, layer count, MoE): the ``first_k_dense`` leading layers keep a
    dense MLP, the rest are ``cfg``'s kind."""
    k = cfg.first_k_dense
    dense = (("dense_layers", 0, k, False),) if k else ()
    return dense + (("layers", k, cfg.num_layers - k, cfg.is_moe),)



def _lm_head(params, cfg: ModelConfig) -> jax.Array:
    """LM head weights (d, V).  Tied embeddings live in gather-friendly
    layout (V@fsdp, d@model); the head matmul wants (d, V@model) — reshard
    ONCE here (77 MB for a 50k vocab) instead of letting GSPMD improvise
    full-logit materializations."""
    if cfg.tie_embeddings:
        head = params["embed"].value.T
        return wlc(head, None, "vocab")
    return params["lm_head"].value


# ---------------------------------------------------------------------------
# Remat policies
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:  # "dots"
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(fn, policy=policy)


def _slice_layer(stacked_axes, values_slice):
    """Re-attach per-layer axes (dropping the leading 'stack' axis name)."""
    axes = jax.tree.map(
        lambda a: a[1:], stacked_axes,
        is_leaf=lambda v: isinstance(v, tuple) and all(
            x is None or isinstance(x, str) for x in v))
    return merge_params(values_slice, axes)


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _embed_or_take(params, batch, cfg: ModelConfig) -> jax.Array:
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
        return wlc(x, "batch", "seq", "embed")
    return common.embed_tokens(params["embed"].value, batch["tokens"], cfg)


def _angles_for(cfg: ModelConfig, batch, B: int, S: int) -> Optional[jax.Array]:
    if cfg.family == "ssm":
        return None
    positions = batch.get("positions")
    if positions is None:
        positions = common.default_positions(B, S, cfg)
    if cfg.is_mla:
        return mla.rope_angles(positions, cfg)
    return common.rope_angles(positions, cfg.resolved_head_dim, cfg.rope_theta,
                              cfg.mrope_sections)


def lm_forward(params, batch, cfg: ModelConfig, *, causal: bool = True
               ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits (B,S,V) fp32, aux_loss)."""
    x = _embed_or_take(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    angles = _angles_for(cfg, batch, B, S)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        stacked_vals, stacked_axes = split_params(params["layers"])

        def body(x, layer_vals):
            layer = _slice_layer(stacked_axes, layer_vals)
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            x = x + ssd_apply(layer["ssd"], h, cfg)
            return wlc(x, "batch", "seq", "embed"), ()

        x, _ = lax.scan(_remat(body, cfg), x, stacked_vals)

    elif cfg.family == "hybrid":
        x, aux_total = _hybrid_forward(params, x, angles, cfg, causal)

    else:
        attend = mla.mla_apply if cfg.is_mla else attn_apply
        for name, _, _, moe in layer_stacks(cfg):
            stacked_vals, stacked_axes = split_params(params[name])

            def body(carry, layer_vals, stacked_axes=stacked_axes, moe=moe):
                x, aux = carry
                layer = _slice_layer(stacked_axes, layer_vals)
                h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
                x = x + attend(layer["attn"], h, cfg, angles=angles,
                               causal=causal)
                h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
                if moe:
                    y, a = moe_apply(layer["moe"], h, cfg)
                    aux = aux + a
                else:
                    y = mlp_apply(layer["mlp"], h)
                x = wlc(x + y, "batch", "seq", "embed")
                return (x, aux), ()

            (x, aux_total), _ = lax.scan(_remat(body, cfg), (x, aux_total),
                                         stacked_vals)

    x = rmsnorm(x, params["ln_f"].value, cfg.norm_eps)
    logits = common.lm_logits(x, _lm_head(params, cfg), cfg)
    return logits, aux_total


def _hybrid_forward(params, x, angles, cfg: ModelConfig, causal: bool):
    """Scan over (rec, rec, attn) groups + unrolled remainder layers."""
    kinds = hybrid_layer_kinds(cfg)
    pat = cfg.block_pattern or ("rec", "rec", "attn")
    glen = len(pat)
    n_groups = cfg.num_layers // glen
    rec_per_group = pat.count("rec")
    attn_per_group = pat.count("attn")

    rec_vals, rec_axes = split_params(params["rec_layers"])
    att_vals, att_axes = split_params(params["attn_layers"])
    n_rec_scan = n_groups * rec_per_group
    n_att_scan = n_groups * attn_per_group

    def reshape_group(tree, n_scan, per_group):
        return jax.tree.map(
            lambda v: v[:n_scan].reshape((n_groups, per_group) + v.shape[1:]),
            tree)

    rec_scan = reshape_group(rec_vals, n_rec_scan, rec_per_group)
    att_scan = reshape_group(att_vals, n_att_scan, attn_per_group)

    def apply_rec(x, layer):
        h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
        x = x + rglru_apply(layer["rglru"], h, cfg)
        h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
        return wlc(x + mlp_apply(layer["mlp"], h), "batch", "seq", "embed")

    def apply_att(x, layer):
        h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
        x = x + attn_apply(layer["attn"], h, cfg, angles=angles, causal=causal,
                           window=cfg.local_window)
        h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
        return wlc(x + mlp_apply(layer["mlp"], h), "batch", "seq", "embed")

    def body(x, group_vals):
        rec_g, att_g = group_vals
        ri, ai = 0, 0
        for k in pat:
            if k == "rec":
                layer = _slice_layer(
                    rec_axes, jax.tree.map(lambda v: v[ri], rec_g))
                x = apply_rec(x, layer)
                ri += 1
            else:
                layer = _slice_layer(
                    att_axes, jax.tree.map(lambda v: v[ai], att_g))
                x = apply_att(x, layer)
                ai += 1
        return x, ()

    if n_groups > 0:
        x, _ = lax.scan(_remat(body, cfg), x, (rec_scan, att_scan))

    # remainder layers (pattern prefix), unrolled
    ri, ai = n_rec_scan, n_att_scan
    for k in kinds[n_groups * glen:]:
        if k == "rec":
            layer = _slice_layer(rec_axes, jax.tree.map(lambda v, i=ri: v[i], rec_vals))
            x = apply_rec(x, layer)
            ri += 1
        else:
            layer = _slice_layer(att_axes, jax.tree.map(lambda v, i=ai: v[i], att_vals))
            x = apply_att(x, layer)
            ai += 1
    return x, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Vocab-sharded-safe CE. logits (B,S,V) fp32; labels, mask (B,S).

    The label logit is extracted with a fused masked-sum instead of
    ``take_along_axis``: a gather along the (vocab-)sharded dim would make
    GSPMD all-gather the logits; the masked reduction stays shard-local and
    psums a scalar per token."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    lab = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                  axis=-1)
    nll = (lse - lab) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll) / denom, denom


def lm_loss(params, batch, cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = lm_forward(params, batch, cfg)
    if "labels" in batch:
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
    else:
        tokens = batch["tokens"]
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
    ce, denom = cross_entropy(logits, labels, mask)
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"loss": loss, "ce": ce, "aux": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------

def lm_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Returns (cache values, cache logical axes)."""
    hd = cfg.resolved_head_dim
    cdt = jnp.dtype(cfg.dtype)
    if cfg.family == "ssm":
        one = ssd_init_cache(cfg, batch)
        vals = {
            "layers": jax.tree.map(
                lambda v: jnp.broadcast_to(v, (cfg.num_layers,) + v.shape), one),
            "lengths": jnp.zeros((batch,), jnp.int32),
        }
        axes = {
            "layers": jax.tree.map(lambda a: ("stack",) + a, ssd_cache_axes(cfg),
                                   is_leaf=lambda v: isinstance(v, tuple)),
            "lengths": ("batch",),
        }
        return vals, axes
    if cfg.family == "hybrid":
        kinds = hybrid_layer_kinds(cfg)
        n_rec = sum(1 for k in kinds if k == "rec")
        n_att = len(kinds) - n_rec
        w = min(cfg.local_window, max_len)
        rec_one = rglru_init_cache(cfg, batch)
        vals = {
            "rec": jax.tree.map(
                lambda v: jnp.broadcast_to(v, (n_rec,) + v.shape), rec_one),
            "k": jnp.zeros((n_att, batch, cfg.num_kv_heads, w, hd), cdt),
            "v": jnp.zeros((n_att, batch, cfg.num_kv_heads, w, hd), cdt),
            "lengths": jnp.zeros((batch,), jnp.int32),
        }
        axes = {
            "rec": jax.tree.map(lambda a: ("stack",) + a, rglru_cache_axes(cfg),
                                is_leaf=lambda v: isinstance(v, tuple)),
            "k": ("stack", "batch", "kv_heads", None, "head_dim"),
            "v": ("stack", "batch", "kv_heads", None, "head_dim"),
            "lengths": ("batch",),
        }
        return vals, axes
    if cfg.is_mla:
        vals = {"latent": jnp.zeros((cfg.num_layers, batch, max_len,
                                     cfg.latent_width), cdt),
                "lengths": jnp.zeros((batch,), jnp.int32)}
        axes = {"latent": ("stack", "batch", "seq_kv", None),
                "lengths": ("batch",)}
        return vals, axes
    vals = {
        "k": jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, max_len, hd), cdt),
        "v": jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, max_len, hd), cdt),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }
    axes = {
        "k": ("stack", "batch", None, "seq_kv", "head_dim"),
        "v": ("stack", "batch", None, "seq_kv", "head_dim"),
        "lengths": ("batch",),
    }
    return vals, axes


# ---------------------------------------------------------------------------
# Prefill: full-sequence forward that also fills the decode cache
# ---------------------------------------------------------------------------

def _ring_fill(cache_kv: jax.Array, kv: jax.Array, w: int) -> jax.Array:
    """Write the last ``w`` positions of kv (B,H,S,D) into a ring cache
    (B,H,w,D) at ring indices pos % w."""
    S = kv.shape[2]
    n = min(S, w)
    tail = kv[:, :, S - n:]
    idx = (jnp.arange(S - n, S) % w).astype(jnp.int32)
    return cache_kv.at[:, :, idx].set(tail.astype(cache_kv.dtype))


def lm_prefill(params, batch, cfg: ModelConfig, max_len: int
               ) -> Tuple[jax.Array, Dict]:
    """Returns (last-token logits (B,V), filled cache)."""
    x = _embed_or_take(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    angles = _angles_for(cfg, batch, B, S)
    cache, _ = lm_init_cache(cfg, B, max_len)
    lengths = jnp.full((B,), S, jnp.int32)

    if cfg.family == "ssm":
        stacked_vals, stacked_axes = split_params(params["layers"])

        def body(x, layer_vals):
            layer = _slice_layer(stacked_axes, layer_vals)
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            y, st = ssd_apply(layer["ssd"], h, cfg, return_state=True)
            return x + y, st

        x, states = lax.scan(body, x, stacked_vals)
        cache = {"layers": states, "lengths": lengths}

    elif cfg.family == "hybrid":
        kinds = hybrid_layer_kinds(cfg)
        rec_vals, rec_axes = split_params(params["rec_layers"])
        att_vals, att_axes = split_params(params["attn_layers"])
        w = cache["k"].shape[3]
        new_rec, new_k, new_v = [], [], []
        ri = ai = 0
        for kind in kinds:
            if kind == "rec":
                layer = _slice_layer(rec_axes,
                                     jax.tree.map(lambda v, i=ri: v[i], rec_vals))
                h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
                y, st = rglru_apply(layer["rglru"], h, cfg, return_state=True)
                x = x + y
                h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
                x = x + mlp_apply(layer["mlp"], h)
                new_rec.append(st)
                ri += 1
            else:
                layer = _slice_layer(att_axes,
                                     jax.tree.map(lambda v, i=ai: v[i], att_vals))
                h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
                a, (k, v) = attn_apply(layer["attn"], h, cfg, angles=angles,
                                       causal=True, window=cfg.local_window,
                                       return_kv=True)
                x = x + a
                h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
                x = x + mlp_apply(layer["mlp"], h)
                new_k.append(_ring_fill(cache["k"][ai], k, w))
                new_v.append(_ring_fill(cache["v"][ai], v, w))
                ai += 1
        cache = {
            "rec": jax.tree.map(lambda *vs: jnp.stack(vs), *new_rec),
            "k": jnp.stack(new_k), "v": jnp.stack(new_v),
            "lengths": lengths,
        }

    else:
        filled = []
        for name, _, _, moe in layer_stacks(cfg):
            stacked_vals, stacked_axes = split_params(params[name])

            def body(x, layer_vals, stacked_axes=stacked_axes, moe=moe):
                layer = _slice_layer(stacked_axes, layer_vals)
                h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
                if cfg.is_mla:
                    a, kv = mla.mla_apply(layer["attn"], h, cfg, angles=angles,
                                          return_latent=True)
                else:
                    a, kv = attn_apply(layer["attn"], h, cfg, angles=angles,
                                       return_kv=True)
                x = x + a
                h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
                if moe:
                    y, _ = moe_apply(layer["moe"], h, cfg)
                else:
                    y = mlp_apply(layer["mlp"], h)
                return wlc(x + y, "batch", "seq", "embed"), kv

            x, kv = lax.scan(body, x, stacked_vals)
            filled.append(kv)
        pad = max_len - S
        if cfg.is_mla:
            rows = jnp.concatenate(filled, axis=0)
            cache = {"latent": jnp.pad(rows, ((0, 0), (0, 0), (0, pad), (0, 0))
                                       ).astype(cache["latent"].dtype),
                     "lengths": lengths}
        else:
            (ks, vs), = filled
            kc = jnp.pad(ks, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
                         ).astype(cache["k"].dtype)
            vc = jnp.pad(vs, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
                         ).astype(cache["v"].dtype)
            cache = {"k": kc, "v": vc, "lengths": lengths}

    x = rmsnorm(x, params["ln_f"].value, cfg.norm_eps)
    logits = common.lm_logits(x[:, -1:], _lm_head(params, cfg), cfg)[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# Decode (one token)
# ---------------------------------------------------------------------------

def lm_decode_step(params, cache, tokens, cfg: ModelConfig,
                   embeds: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict]:
    """tokens: (B,) int32 (or ``embeds`` (B,1,d)). Returns (logits (B,V), cache)."""
    B = tokens.shape[0]
    lengths = cache["lengths"]
    if embeds is not None:
        x = wlc(embeds.astype(cfg.dtype), "batch", "seq", "embed")
    else:
        x = common.embed_tokens(params["embed"].value, tokens[:, None], cfg)

    if cfg.family == "ssm":
        angles = None
    elif cfg.is_mla:
        angles = mla.rope_angles(lengths[:, None], cfg)
    else:
        pos = lengths[:, None]
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[None], (len(cfg.mrope_sections), B, 1))
        angles = common.rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta,
                                    cfg.mrope_sections)

    if cfg.family == "ssm":
        stacked_vals, stacked_axes = split_params(params["layers"])

        def body(x, scanned):
            layer_vals, cache_slice = scanned
            layer = _slice_layer(stacked_axes, layer_vals)
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            y, new_cache = ssd_decode(layer["ssd"], h, cache_slice, cfg)
            return x + y, new_cache

        x, new_layers = lax.scan(body, x, (stacked_vals, cache["layers"]))
        new_cache = {"layers": new_layers, "lengths": lengths + 1}

    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, cache, x, angles, cfg)

    elif cfg.is_mla:
        x, new_cache = _latent_decode(params, cache, x, angles, cfg)

    else:
        stacked_vals, stacked_axes = split_params(params["layers"])

        def body(x, scanned):
            layer_vals, k_c, v_c = scanned
            layer = _slice_layer(stacked_axes, layer_vals)
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            a, k_c, v_c = attn_decode(layer["attn"], h, cfg, k_cache=k_c,
                                      v_cache=v_c, lengths=lengths,
                                      angles=angles)
            x = x + a
            h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
            if cfg.is_moe:
                y, _ = moe_apply(layer["moe"], h, cfg)
            else:
                y = mlp_apply(layer["mlp"], h)
            return x + y, (k_c, v_c)

        x, (new_k, new_v) = lax.scan(body, x, (stacked_vals, cache["k"],
                                               cache["v"]))
        new_cache = {"k": new_k, "v": new_v, "lengths": lengths + 1}

    x = rmsnorm(x, params["ln_f"].value, cfg.norm_eps)
    logits = common.lm_logits(x, _lm_head(params, cfg), cfg)[:, 0]
    return logits, new_cache


def _latent_decode(params, cache, x, angles, cfg: ModelConfig):
    """MLA decode over the layer stacks against the contiguous latent
    cache (L, B, T, W)."""
    lengths = cache["lengths"]
    new = []
    for name, first, n, moe in layer_stacks(cfg):
        stacked_vals, stacked_axes = split_params(params[name])

        def body(x, scanned, stacked_axes=stacked_axes, moe=moe):
            layer_vals, c = scanned
            layer = _slice_layer(stacked_axes, layer_vals)
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            a, c = mla.mla_decode(layer["attn"], h, cfg, cache=c,
                                  lengths=lengths, angles=angles)
            x = x + a
            h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
            y = moe_apply(layer["moe"], h, cfg)[0] if moe \
                else mlp_apply(layer["mlp"], h)
            return x + y, c

        x, c = lax.scan(body, x, (stacked_vals,
                                  cache["latent"][first:first + n]))
        new.append(c)
    return x, {"latent": jnp.concatenate(new, axis=0), "lengths": lengths + 1}


def _hybrid_decode(params, cache, x, angles, cfg: ModelConfig):
    """Unrolled decode over the layer pattern (38 layers: cheap for S=1)."""
    kinds = hybrid_layer_kinds(cfg)
    rec_vals, rec_axes = split_params(params["rec_layers"])
    att_vals, att_axes = split_params(params["attn_layers"])
    lengths = cache["lengths"]
    w = cache["k"].shape[3]
    ring = lengths % w
    eff_len = jnp.minimum(lengths + 1, w)

    new_rec, new_k, new_v = [], [], []
    ri = ai = 0
    for kind in kinds:
        if kind == "rec":
            layer = _slice_layer(rec_axes, jax.tree.map(lambda v, i=ri: v[i], rec_vals))
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            y, nc = rglru_decode(layer["rglru"], h,
                                 jax.tree.map(lambda v, i=ri: v[i], cache["rec"]), cfg)
            x = x + y
            h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
            x = x + mlp_apply(layer["mlp"], h)
            new_rec.append(nc)
            ri += 1
        else:
            layer = _slice_layer(att_axes, jax.tree.map(lambda v, i=ai: v[i], att_vals))
            h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
            # ring-buffer window cache: write at lengths % w, attend over all
            # valid entries (ring order is softmax-invariant; rope is applied
            # with absolute positions at write time)
            a, k_c, v_c = attn_decode(
                layer["attn"], h, cfg,
                k_cache=cache["k"][ai], v_cache=cache["v"][ai],
                lengths=lengths, angles=angles,
                write_pos=ring, valid_len=eff_len)
            x = x + a
            h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
            x = x + mlp_apply(layer["mlp"], h)
            new_k.append(k_c)
            new_v.append(v_c)
            ai += 1
    new_cache = {
        "rec": jax.tree.map(lambda *vs: jnp.stack(vs), *new_rec),
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
        "lengths": lengths + 1,
    }
    return x, new_cache
