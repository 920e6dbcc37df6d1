"""Mixture-of-Experts layer: top-k routing, capacity-based EP dispatch.

Two execution paths, both driven by the same parameters:

* **Expanded (EP)** — when a mesh is installed: tokens are flattened over the
  whole mesh ("tokens" logical axis), and a ``shard_map`` region performs
  local top-k routing, sort-based packing into per-expert capacity buffers,
  an ``all_to_all`` over the ``model`` axis (experts are sharded there), the
  expert FFNs, and the reverse ``all_to_all`` + weighted combine.  This is the
  paper's multi-team kernel-split applied to MoE: the "parallel region" (the
  expert FFN) is extracted and run across the entire machine.

* **Reference** — without a mesh (single-team semantics): a dropless dense
  evaluation over all experts; the oracle used by the tests.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import current_mesh, with_logical_constraint as wlc
from repro.models.common import Param, normal


def moe_init(key, cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    pd = jnp.dtype(cfg.param_dtype)
    return {
        # router stays replicated: it is tiny and its output drives a
        # data-dependent dispatch (sharding it would all-gather logits anyway)
        "router": normal(ks[0], (d, E), (None, None), jnp.dtype("float32"), scale=0.02),
        "wi_gate": normal(ks[1], (E, d, f), ("experts", "fsdp", "expert_ffn"), pd),
        "wi_up": normal(ks[2], (E, d, f), ("experts", "fsdp", "expert_ffn"), pd),
        "wo": normal(ks[3], (E, f, d), ("experts", "expert_ffn", "fsdp"), pd,
                     scale=f ** -0.5),
    }


def _route(x_flat: jax.Array, router_w: jax.Array, k: int):
    """Returns (weights (T,k) fp32 renormalized, ids (T,k), probs (T,E) fp32)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, k)
    topv = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
    return topv, topi, probs


def _expert_ffn(xe: jax.Array, wg, wu, wo) -> jax.Array:
    """xe: (E_loc, C, d); weights (E_loc, d, f)/(E_loc, f, d)."""
    dt = xe.dtype
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))


def moe_reference(p_vals: dict, x_flat: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """Dropless oracle: evaluates every expert densely. (T, d) -> (T, d)."""
    E, K = cfg.num_experts, cfg.experts_per_token
    topv, topi, probs = _route(x_flat, p_vals["router"], K)
    T = x_flat.shape[0]
    w_full = jnp.zeros((T, E), jnp.float32)
    w_full = w_full.at[jnp.arange(T)[:, None], topi].set(topv)
    dt = x_flat.dtype
    g = jnp.einsum("td,edf->tef", x_flat, p_vals["wi_gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", x_flat, p_vals["wi_up"].astype(dt))
    h = jax.nn.silu(g) * u
    o = jnp.einsum("tef,efd->ted", h, p_vals["wo"].astype(dt))
    y = jnp.einsum("ted,te->td", o.astype(jnp.float32), w_full).astype(dt)
    counts = jnp.sum(w_full > 0, axis=0).astype(jnp.float32)
    f_e = counts / jnp.maximum(jnp.sum(counts), 1.0)
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e)
    return y, aux


def _moe_local(x_loc, router_w, wg, wu, wo, *, E: int, K: int, C: int,
               ep_axis: str):
    """Per-device body of the expanded path (inside shard_map)."""
    T_loc, d = x_loc.shape
    topv, topi, probs = _route(x_loc, router_w, K)

    # flatten (token, choice) assignments and sort by expert id
    e_f = topi.reshape(-1)                               # (T_loc*K,)
    w_f = topv.reshape(-1)
    t_f = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
    order = jnp.argsort(e_f)                             # stable
    se, st, sw = e_f[order], t_f[order], w_f[order]
    counts = jnp.bincount(e_f, length=E)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(se.shape[0], dtype=jnp.int32) - offsets[se].astype(jnp.int32)
    keep = pos < C
    slot = se.astype(jnp.int32) * C + pos                # (T_loc*K,)

    # pack into per-expert capacity buffers; OOB scatter indices are dropped
    buf = jnp.zeros((E * C, d), x_loc.dtype)
    buf = buf.at[jnp.where(keep, slot, E * C)].set(x_loc[st])
    buf = buf.reshape(E, C, d)

    # ship to expert shards, compute, ship back
    ep = lax.axis_size(ep_axis)
    recv = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1, tiled=True)
    out = _expert_ffn(recv, wg, wu, wo)                  # (E/ep, C*ep, d)
    send = lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0, tiled=True)
    flat = send.reshape(E * C, d)

    # combine: gather expert outputs back to tokens, weighted
    gathered = flat[jnp.minimum(slot, E * C - 1)]
    gathered = gathered.astype(jnp.float32) * (keep * sw)[:, None]
    y = jnp.zeros((T_loc, d), jnp.float32).at[st].add(gathered)

    f_e = counts.astype(jnp.float32) / jnp.maximum(se.shape[0], 1)
    p_e = jnp.mean(probs, axis=0)
    aux = (E * jnp.sum(f_e * p_e))[None]
    dropped = jnp.sum(~keep).astype(jnp.float32)[None]
    return y.astype(x_loc.dtype), aux, dropped


def _moe_local_replicated(x_row, router_w, wg, wu, wo, *, E: int, K: int,
                          C: int, ep_axis: str):
    """Decode-path body: tokens replicated over the expert axis; each device
    evaluates only (token, expert) pairs routed to its local experts, then a
    psum over the expert axis combines per-token outputs.  No all_to_all —
    right for tiny per-step token counts where dispatch latency dominates."""
    T_row, d = x_row.shape
    ep = lax.axis_size(ep_axis)
    my = lax.axis_index(ep_axis)
    E_loc = E // ep
    topv, topi, probs = _route(x_row, router_w, K)

    e_f = topi.reshape(-1)
    w_f = topv.reshape(-1)
    t_f = jnp.repeat(jnp.arange(T_row, dtype=jnp.int32), K)
    local = (e_f >= my * E_loc) & (e_f < (my + 1) * E_loc)
    le = jnp.where(local, e_f - my * E_loc, E_loc)       # E_loc == drop sentinel
    order = jnp.argsort(le)                              # locals first, by expert
    se, st, sw = le[order], t_f[order], w_f[order]
    counts = jnp.bincount(le, length=E_loc)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(se.shape[0], dtype=jnp.int32) - \
        offsets[jnp.minimum(se, E_loc - 1)].astype(jnp.int32)
    keep = (se < E_loc) & (pos < C)
    slot = jnp.minimum(se, E_loc - 1).astype(jnp.int32) * C + pos

    buf = jnp.zeros((E_loc * C, d), x_row.dtype)
    buf = buf.at[jnp.where(keep, slot, E_loc * C)].set(x_row[st])
    out = _expert_ffn(buf.reshape(E_loc, C, d), wg, wu, wo).reshape(E_loc * C, d)

    gathered = out[jnp.minimum(slot, E_loc * C - 1)]
    gathered = gathered.astype(jnp.float32) * (keep * sw)[:, None]
    y = jnp.zeros((T_row, d), jnp.float32).at[st].add(gathered)
    y = lax.psum(y, ep_axis)

    f_e = jnp.bincount(e_f, length=E).astype(jnp.float32) / jnp.maximum(e_f.shape[0], 1)
    p_e = jnp.mean(probs, axis=0)
    aux = (E * jnp.sum(f_e * p_e))[None]
    return y.astype(x_row.dtype), aux


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y (B,S,d), aux_loss scalar)."""
    vals = {k: v.value for k, v in p.items()}
    B, S, d = x.shape
    T = B * S
    mesh = current_mesh()
    E, K = cfg.num_experts, cfg.experts_per_token

    expanded = (mesh is not None and "model" in mesh.axis_names
                and E % mesh.shape["model"] == 0)
    if expanded and T % mesh.size != 0:
        # decode path: too few tokens to shard over the whole mesh
        dp_axes = tuple(a for a in mesh.axis_names if a != "model")
        dp_size = math.prod(mesh.shape[a] for a in dp_axes) if dp_axes else 1
        if dp_axes and T % dp_size == 0:
            T_row = T // dp_size
            C = max(8, ((int(math.ceil(T_row * K / E * cfg.capacity_factor))
                         + 7) // 8) * 8)
            x_flat = wlc(x.reshape(T, d), "batch", None)
            body = functools.partial(_moe_local_replicated, E=E, K=K, C=C,
                                     ep_axis="model")
            y_flat, aux_all = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(dp_axes, None), P(None, None),
                          P("model", None, None), P("model", None, None),
                          P("model", None, None)),
                out_specs=(P(dp_axes, None), P(dp_axes + ("model",))),
                check_vma=False,
            )(x_flat, vals["router"], vals["wi_gate"], vals["wi_up"], vals["wo"])
            y = wlc(y_flat.reshape(B, S, d), "batch", "seq", "embed")
            return y, jnp.mean(aux_all)
        expanded = False

    if not expanded:
        y, aux = moe_reference(vals, x.reshape(T, d), cfg)
        return y.reshape(B, S, d), aux

    n_dev = mesh.size
    T_loc = T // n_dev
    C = int(math.ceil(T_loc * K / E * cfg.capacity_factor))
    C = max(8, ((C + 7) // 8) * 8)
    all_axes = tuple(mesh.axis_names)

    x_flat = wlc(x.reshape(T, d), "tokens", None)
    body = functools.partial(_moe_local, E=E, K=K, C=C, ep_axis="model")
    y_flat, aux_all, dropped_all = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(all_axes, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(all_axes, None), P(all_axes), P(all_axes)),
        check_vma=False,
    )(x_flat, vals["router"], vals["wi_gate"], vals["wi_up"], vals["wo"])
    aux = jnp.mean(aux_all)
    y = wlc(y_flat.reshape(B, S, d), "batch", "seq", "embed")
    return y, aux
