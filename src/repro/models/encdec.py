"""Encoder-decoder assembly (seamless-m4t style, audio frontend stubbed).

Encoder: bidirectional self-attention over precomputed frame embeddings.
Decoder: causal self-attention + cross-attention into the encoder output.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import with_logical_constraint as wlc
from repro.models import common
from repro.models.attention import (
    attn_apply, attn_decode, attn_init, cross_attn_apply, cross_attn_decode,
    cross_kv)
from repro.models.common import (
    merge_params, rmsnorm, rmsnorm_init, split_params, stack_params)
from repro.models.mlp import mlp_init, mlp_apply
from repro.models.transformer import _remat, _slice_layer


def _enc_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": rmsnorm_init(cfg.d_model, pd),
        "attn": attn_init(k1, cfg),
        "ln2": rmsnorm_init(cfg.d_model, pd),
        "mlp": mlp_init(k2, cfg),
    }


def _dec_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": rmsnorm_init(cfg.d_model, pd),
        "self_attn": attn_init(k1, cfg),
        "ln_x": rmsnorm_init(cfg.d_model, pd),
        "cross_attn": attn_init(k2, cfg),
        "ln2": rmsnorm_init(cfg.d_model, pd),
        "mlp": mlp_init(k3, cfg),
    }


def encdec_init(key, cfg: ModelConfig) -> dict:
    n_enc, n_dec = cfg.encoder_layers, cfg.decoder_layers
    keys = jax.random.split(key, n_enc + n_dec + 3)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        "embed": common.embedding_init(keys[0], cfg),      # decoder tokens
        "lm_head": common.lm_head_init(keys[1], cfg),
        "enc_layers": stack_params(
            [_enc_layer_init(keys[2 + i], cfg) for i in range(n_enc)]),
        "dec_layers": stack_params(
            [_dec_layer_init(keys[2 + n_enc + i], cfg) for i in range(n_dec)]),
        "ln_enc": rmsnorm_init(cfg.d_model, pd),
        "ln_f": rmsnorm_init(cfg.d_model, pd),
    }


def encode(params, embeds: jax.Array, cfg: ModelConfig) -> jax.Array:
    """embeds: (B, S_enc, d) precomputed frame embeddings -> encoder output."""
    x = wlc(embeds.astype(cfg.dtype), "batch", "seq", "embed")
    B, S = x.shape[:2]
    pos = common.default_positions(B, S, cfg)
    angles = common.rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta)
    stacked_vals, stacked_axes = split_params(params["enc_layers"])

    def body(x, layer_vals):
        layer = _slice_layer(stacked_axes, layer_vals)
        h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
        x = x + attn_apply(layer["attn"], h, cfg, angles=angles, causal=False)
        h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
        return wlc(x + mlp_apply(layer["mlp"], h), "batch", "seq", "embed"), ()

    x, _ = lax.scan(_remat(body, cfg), x, stacked_vals)
    return rmsnorm(x, params["ln_enc"].value, cfg.norm_eps)


def decode_train(params, tokens: jax.Array, enc_out: jax.Array,
                 cfg: ModelConfig) -> jax.Array:
    """Teacher-forced decoder. tokens: (B, S_dec) -> logits (B, S_dec, V)."""
    x = common.embed_tokens(params["embed"].value, tokens, cfg)
    B, S = x.shape[:2]
    pos = common.default_positions(B, S, cfg)
    angles = common.rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta)
    stacked_vals, stacked_axes = split_params(params["dec_layers"])

    def body(x, layer_vals):
        layer = _slice_layer(stacked_axes, layer_vals)
        h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
        x = x + attn_apply(layer["self_attn"], h, cfg, angles=angles, causal=True)
        h = rmsnorm(x, layer["ln_x"].value, cfg.norm_eps)
        kv = cross_kv(layer["cross_attn"], enc_out, cfg)
        x = x + cross_attn_apply(layer["cross_attn"], h, kv, cfg)
        h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
        return wlc(x + mlp_apply(layer["mlp"], h), "batch", "seq", "embed"), ()

    x, _ = lax.scan(_remat(body, cfg), x, stacked_vals)
    x = rmsnorm(x, params["ln_f"].value, cfg.norm_eps)
    return common.lm_logits(x, params["lm_head"].value, cfg)


def encdec_forward(params, batch, cfg: ModelConfig
                   ) -> Tuple[jax.Array, jax.Array]:
    enc_out = encode(params, batch["embeds"], cfg)
    logits = decode_train(params, batch["tokens"], enc_out, cfg)
    return logits, jnp.zeros((), jnp.float32)


def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: Optional[int] = None):
    """Self-attn KV cache + cross-attn KV cache (filled at prefill)."""
    hd = cfg.resolved_head_dim
    cdt = jnp.dtype(cfg.dtype)
    n_dec = cfg.decoder_layers
    enc_len = enc_len if enc_len is not None else max_len
    vals = {
        "k": jnp.zeros((n_dec, batch, cfg.num_kv_heads, max_len, hd), cdt),
        "v": jnp.zeros((n_dec, batch, cfg.num_kv_heads, max_len, hd), cdt),
        "xk": jnp.zeros((n_dec, batch, cfg.num_kv_heads, enc_len, hd), cdt),
        "xv": jnp.zeros((n_dec, batch, cfg.num_kv_heads, enc_len, hd), cdt),
        "enc_lens": jnp.zeros((batch,), jnp.int32),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }
    axes = {
        "k": ("stack", "batch", None, "seq_kv", "head_dim"),
        "v": ("stack", "batch", None, "seq_kv", "head_dim"),
        "xk": ("stack", "batch", None, "seq_kv", "head_dim"),
        "xv": ("stack", "batch", None, "seq_kv", "head_dim"),
        "enc_lens": ("batch",),
        "lengths": ("batch",),
    }
    return vals, axes


def encdec_prefill_cross(params, cache: Dict, embeds: jax.Array,
                         enc_lens: jax.Array, cfg: ModelConfig) -> Dict:
    """Run the encoder and fill the cross-attention KV cache."""
    enc_out = encode(params, embeds, cfg)
    stacked_vals, stacked_axes = split_params(params["dec_layers"])

    def body(_, layer_vals):
        layer = _slice_layer(stacked_axes, layer_vals)
        k, v = cross_kv(layer["cross_attn"], enc_out, cfg)
        return (), (k.astype(cache["xk"].dtype), v.astype(cache["xv"].dtype))

    _, (xk, xv) = lax.scan(body, (), stacked_vals)
    return {**cache, "xk": xk, "xv": xv, "enc_lens": enc_lens}


def encdec_decode_step(params, cache: Dict, tokens: jax.Array,
                       cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """tokens: (B,) -> (logits (B,V), cache)."""
    lengths = cache["lengths"]
    B = tokens.shape[0]
    x = common.embed_tokens(params["embed"].value, tokens[:, None], cfg)
    angles = common.rope_angles(lengths[:, None], cfg.resolved_head_dim,
                                cfg.rope_theta)
    stacked_vals, stacked_axes = split_params(params["dec_layers"])

    def body(x, scanned):
        layer_vals, k_c, v_c, xk, xv = scanned
        layer = _slice_layer(stacked_axes, layer_vals)
        h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
        a, k_c, v_c = attn_decode(layer["self_attn"], h, cfg, k_cache=k_c,
                                  v_cache=v_c, lengths=lengths, angles=angles)
        x = x + a
        h = rmsnorm(x, layer["ln_x"].value, cfg.norm_eps)
        x = x + cross_attn_decode(layer["cross_attn"], h, (xk, xv),
                                  cache["enc_lens"], cfg)
        h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
        return x + mlp_apply(layer["mlp"], h), (k_c, v_c)

    x, (new_k, new_v) = lax.scan(
        body, x, (stacked_vals, cache["k"], cache["v"], cache["xk"],
                  cache["xv"]))
    x = rmsnorm(x, params["ln_f"].value, cfg.norm_eps)
    logits = common.lm_logits(x, params["lm_head"].value, cfg)[:, 0]
    return logits, {**cache, "k": new_k, "v": new_v, "lengths": lengths + 1}
