"""Continuous-batching serving engine with paged KV (decoder-only LMs).

Slot-based continuous batching: a fixed grid of request slots decodes in
lock-step (one jitted ``serve_step`` for the whole batch); finished slots are
released in O(1) (balanced-allocator watermark reclaim) and refilled from the
request queue without disturbing in-flight neighbors.

Attention-family models use the paged KV cache; SSM/hybrid models have O(1)
recurrent state, so they use their native state caches through the same slot
machinery (paging is pointless for constant-size state — noted in DESIGN.md).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.attention import _mask_heads, _project_qkv
from repro.models.common import merge_params, rmsnorm, split_params
from repro.models.mlp import mlp_apply
from repro.models.moe import moe_apply
from repro.models.model_zoo import Model
from repro.models.transformer import _slice_layer
from repro.core import events
from repro.core import rpc as rpc_mod
from repro.core.rpc import REGISTRY, RpcQueue
from repro.serving import kvcache
from repro.serving.kvcache import PagedKV

#: Batched-transport callee for retiring-request page spills; the default
#: binding is a no-op so enqueue always resolves — each engine captures its
#: own sink as a per-flush handler (no cross-engine rebinding).
_SPILL_RPC = "kvcache.spill"
REGISTRY.register(_SPILL_RPC, lambda rid, n_tokens, pages: None,
                  idempotent=True)

#: Occupancy (ring/arena/reply, whichever is fullest) above which
#: ``_deliver_spills`` drains mid-batch before enqueueing more records.
_SPILL_PRESSURE = 0.75


# ---------------------------------------------------------------------------
# Paged decode step (dense / moe / vlm families)
# ---------------------------------------------------------------------------

def paged_decode_step(params, kv: PagedKV, tokens: jax.Array,
                      active: jax.Array, cfg: ModelConfig
                      ) -> Tuple[jax.Array, PagedKV]:
    """tokens: (B,) -> (logits (B, V), kv')."""
    B = tokens.shape[0]
    kv = kvcache.ensure_pages(kv, active)
    x = common.embed_tokens(params["embed"].value, tokens[:, None], cfg)
    pos = kv.lengths[:, None]
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[None], (len(cfg.mrope_sections), B, 1))
    angles = common.rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta,
                                cfg.mrope_sections)

    stacked_vals, stacked_axes = split_params(params["layers"])
    L = cfg.num_layers

    def body(carry, scanned):
        x, kv = carry
        layer_vals, li = scanned
        layer = _slice_layer(stacked_axes, layer_vals)
        h = rmsnorm(x, layer["ln1"].value, cfg.norm_eps)
        q, k, v = _project_qkv(layer["attn"], h, cfg, angles)
        kv = kvcache.write_token_kv(kv, li, k[:, 0], v[:, 0], active)
        a = kvcache.paged_attend(kv, li, q[:, 0])
        a = _mask_heads(a[:, None], cfg)[:, 0]
        a = jnp.einsum("bhk,hkd->bd", a, layer["attn"]["wo"].value.astype(x.dtype))
        x = x + a[:, None]
        h = rmsnorm(x, layer["ln2"].value, cfg.norm_eps)
        if cfg.is_moe:
            y, _ = moe_apply(layer["moe"], h, cfg)
        else:
            y = mlp_apply(layer["mlp"], h)
        return (x + y, kv), ()

    (x, kv), _ = lax.scan(body, (x, kv),
                          (stacked_vals, jnp.arange(L, dtype=jnp.int32)))
    kv = kvcache.advance(kv, active)
    x = rmsnorm(x, params["ln_f"].value, cfg.norm_eps)
    head = params["embed"].value.T if cfg.tie_embeddings \
        else params["lm_head"].value
    logits = common.lm_logits(x, head, cfg)[:, 0]
    return logits, kv


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Slot:
    request_id: int = -1
    prompt: List[int] = dataclasses.field(default_factory=list)
    fed: int = 0
    out: List[int] = dataclasses.field(default_factory=list)
    max_new: int = 0


class ServingEngine:
    """Host-side orchestration; all device work is one jitted step."""

    def __init__(self, model: Model, params, *, batch_slots: int = 4,
                 max_len: int = 256, page_size: int = 16,
                 eos_id: Optional[int] = None, mesh=None,
                 spill_sink: Optional[Any] = None,
                 spill_timeout: Optional[float] = None,
                 spill_retries: int = 1):
        """``mesh`` (a ``jax.sharding.Mesh`` or an int shard count) shards
        the KV page heap per device: each device's allocator shard serves
        its block of batch slots, so page alloc/release never funnel
        through one allocator state (see ``serving/kvcache.py``).

        ``spill_sink(request_id, n_tokens, pages)`` — optional host
        callback receiving every retiring request's page-id list (a 1-D
        int32 numpy array) BEFORE its slot is released.  Deliveries ride
        the batched payload transport: the page ids of all requests retired
        in a tick travel in one queue flush, not one RPC per request (the
        host-side page-spill bookkeeping path — eviction logs, tiered KV
        stores).  The flush is ACKNOWLEDGED through the v4 reply arena:
        each spill record carries a ticket whose reply is the sink's
        return value (or, when the sink returns None, the number of pages
        it was handed); acks land in ``self.spill_acks[request_id]`` after
        the tick — ``None`` when the ack was LOST (reply-arena overflow,
        in which case the sink was never invoked for that record), which
        is therefore distinguishable from a sink that legitimately
        returned 0.  Acks accumulate until the consumer
        collects them with :meth:`drain_spill_acks` (one entry per retired
        request — drain periodically in long-running processes).

        ``spill_timeout`` bounds each sink invocation's wall clock: a
        hung sink marks that record ``TIMEOUT`` in the reply status lane
        instead of wedging the tick loop.  Delivery rides the v6
        double-buffered transport with a cross-epoch carry budget of
        ``spill_retries``: a record whose sink raises or times out is
        stamped ``PENDING`` and redriven by the transport itself on the
        following epoch drains — the engine no longer hand-rolls
        re-enqueue rounds.  A record that exhausts the budget acks
        ``None`` and its request id lands in
        ``self.recompute_on_readmit`` — the tiered-KV consumer's signal
        that the pages were never durably spilled and a readmitted
        request must recompute from the prompt.  A LOST reply
        (reply-arena overflow, injected drop) is not redriven — the sink
        may already have run, so the record acks ``None`` and joins
        ``recompute_on_readmit`` conservatively.  Enqueues are gated on
        ``spill_q.pressure()`` (which counts carried records still
        retrying): when occupancy crosses :data:`_SPILL_PRESSURE`, the
        engine drains mid-batch so nothing drops."""
        self.model = model
        self.cfg = model.cfg
        assert self.cfg.family in ("dense", "moe", "vlm"), \
            "engine serves decoder-only attention LMs; SSM/hybrid use their" \
            " native state caches via Model.decode_step"
        self.params = params
        self.B = batch_slots
        self.kv = kvcache.paged_cache_init(
            self.cfg, batch_slots, max_len, page_size=page_size, mesh=mesh)
        self.eos_id = eos_id
        self.spill_sink = spill_sink
        self.spill_q: Optional[RpcQueue] = None
        self.spill_acks: Dict[int, Optional[int]] = {}
        self.spill_retries = int(spill_retries)
        self.recompute_on_readmit: set = set()
        if spill_sink is not None:
            maxp = (max_len + page_size - 1) // page_size
            self.spill_q = RpcQueue.create(
                capacity=max(2 * batch_slots, 8), width=3,
                payload_capacity=max(batch_slots * maxp, 8),
                reply_capacity=max(2 * batch_slots, 8),
                timeout=spill_timeout, mode="async",
                carry_budget=self.spill_retries)
        self.slots: List[_Slot] = [_Slot() for _ in range(batch_slots)]
        self.queue: List[Tuple[int, List[int], int]] = []
        self.finished: Dict[int, List[int]] = {}
        self._next_id = 0
        def serve_step(values, axes_h, kv, tokens, active):
            return paged_decode_step(merge_params(values, axes_h.tree), kv,
                                     tokens, active, self.cfg)

        self._step = jax.jit(serve_step, static_argnums=(1,))
        self._values, self._axes = split_params(params)
        self._axes_h = _Hashable(self._axes)
        self._geom = {"batch_slots": int(batch_slots),
                      "max_len": int(max_len), "page_size": int(page_size),
                      "eos_id": eos_id}
        self._step_source = "jit"

    # -- public API --------------------------------------------------------------
    def submit(self, prompt: List[int], max_new: int = 16) -> int:
        rid = self._next_id
        self._next_id += 1
        self.queue.append((rid, list(prompt), max_new))
        return rid

    def step(self) -> None:
        """One engine tick: refill slots, one batched decode step, harvest.

        The tick's phases are runtime spans (``repro.core.events``), in
        order: ``engine.admit``, ``engine.inputs``, ``engine.dispatch``,
        ``engine.wait`` (the first device read, which blocks until the
        step is done), ``engine.harvest`` and ``engine.retire``."""
        events.count("engine.ticks")
        with events.span("engine.admit"):
            for s in self.slots:
                if s.request_id < 0 and self.queue:
                    rid, prompt, max_new = self.queue.pop(0)
                    s.request_id, s.prompt, s.fed, s.out, s.max_new = \
                        rid, prompt, 0, [], max_new

        with events.span("engine.inputs"):
            tokens, active = [], []
            first = None            # the first slot that reads a token
            for i, s in enumerate(self.slots):
                if s.request_id < 0:
                    tokens.append(0)
                    active.append(False)
                    continue
                if s.fed < len(s.prompt):
                    tokens.append(s.prompt[s.fed])
                else:
                    tokens.append(s.out[-1] if s.out else s.prompt[-1])
                active.append(True)
                if first is None and s.fed + 1 >= len(s.prompt):
                    first = i
            tok = jnp.asarray(tokens, jnp.int32)
            act = jnp.asarray(active)

        with events.span("engine.dispatch"):
            logits, self.kv = self._step(self._values, self._axes_h,
                                         self.kv, tok, act)
            nxt = jnp.argmax(logits, axis=-1)

        with events.span("engine.wait"):
            head = None if first is None else _read_token(nxt, first)

        with events.span("engine.harvest"):
            done_slots = []
            done_rids = []
            for i, s in enumerate(self.slots):
                if s.request_id < 0:
                    continue
                if s.fed < len(s.prompt):
                    s.fed += 1
                    if s.fed < len(s.prompt):
                        continue
                if s.fed >= len(s.prompt):
                    t = head if i == first else _read_token(nxt, i)
                    s.out.append(t)
                    done = len(s.out) >= s.max_new or \
                        (self.eos_id is not None and t == self.eos_id)
                    if done:
                        self.finished[s.request_id] = s.out
                        done_slots.append(i)
                        done_rids.append(s.request_id)
                        self.slots[i] = _Slot()

        with events.span("engine.retire"):
            if done_slots:
                if self.spill_q is not None:
                    # page-spill: every retiring slot's page ids ride the
                    # payload arena; ONE flush delivers the whole tick and
                    # its replies ack every spill (sink return, or page
                    # count).  _deliver_spills retries failed records and
                    # degrades to recompute-on-readmit instead of wedging
                    # the tick loop.
                    self._deliver_spills(
                        [(int(rid), self.kv.lengths[i],
                          kvcache.live_pages(self.kv, i))
                         for i, rid in zip(done_slots, done_rids)])
                # every retired request this tick releases in ONE bulk reset
                mask = jnp.zeros((len(self.slots),), bool).at[
                    jnp.asarray(done_slots, jnp.int32)].set(True)
                self.kv = kvcache.release_slots(self.kv, mask)

    def _deliver_spills(self, records) -> None:
        """Deliver ``(rid, n_tokens, pages)`` spill records with retry
        and graceful degradation — the retry rounds now live in the
        TRANSPORT (v6 cross-epoch carry), not in this method.

        Records are enqueued — draining early whenever
        ``spill_q.pressure()`` crosses :data:`_SPILL_PRESSURE` so the
        ring/arenas never overflow — then each chunk takes one
        submit/collect flush pair: the first flush hands the epoch to
        the background drain, the second publishes its replies.  A
        record whose sink raised or timed out comes back ``PENDING``
        (the drain carried it under the ``spill_retries`` budget); the
        engine grants the carried set its remaining epoch drains, joins
        the slot, and reads the finalized outcomes.  A record whose
        carry budget still ends in failure — or whose reply was lost
        outright — acks ``None`` and joins ``recompute_on_readmit``."""
        sink = self.spill_sink

        def handler(rid, n_tokens, pages):
            # sinks written against the pre-ack contract may return
            # anything (or nothing): a None ack defaults to the
            # page count; other returns pass through untouched —
            # the drain's reply coercion handles shape/dtype
            out = sink(rid, n_tokens, pages)
            return np.int32(len(pages)) if out is None else out

        handlers = {_SPILL_RPC: handler}
        pending: List[Tuple[Any, Any]] = []     # (record, ticket) carried
        i = 0
        while i < len(records):
            chunk = []
            while i < len(records):
                rid, n_tok, pages = records[i]
                self.spill_q, t = self.spill_q.enqueue_ticketed(
                    _SPILL_RPC, jnp.int32(rid), n_tok, pages,
                    returns=jax.ShapeDtypeStruct((), jnp.int32))
                chunk.append((records[i], t))
                i += 1
                if float(self.spill_q.pressure()) >= _SPILL_PRESSURE:
                    break               # drain before enqueueing more
            self.spill_q = self.spill_q.flush(handlers=handlers)  # submit
            self.spill_q = self.spill_q.flush(handlers=handlers)  # collect
            tix = [t for _, t in chunk]
            statuses = self.spill_q.statuses_host(tix)
            acks = self.spill_q.results_host(tix)
            for (rec, t), st, (val, ok) in zip(chunk, statuses, acks):
                if st == rpc_mod.STATUS_OK and ok:
                    self.spill_acks[rec[0]] = int(val)
                elif st == rpc_mod.STATUS_PENDING:
                    pending.append((rec, t))
                else:
                    self._spill_failed(rec)
        if pending:
            # the collect flush above already submitted one carry-redrive
            # epoch; grant the rest of the budget, then join so every
            # carried record has FINALIZED into the slot's outcome table
            for _ in range(max(0, self.spill_retries - 1)):
                self.spill_q = self.spill_q.flush(handlers=handlers)
            self.spill_q.join()
            tix = [t for _, t in pending]
            statuses = self.spill_q.statuses_host(tix)
            acks = self.spill_q.results_host(tix)
            for (rec, _), st, (val, ok) in zip(pending, statuses, acks):
                if st == rpc_mod.STATUS_OK and ok:
                    self.spill_acks[rec[0]] = int(val)
                else:
                    self._spill_failed(rec)

    def _spill_failed(self, rec) -> None:
        # delivery exhausted the transport's carry budget (or the reply
        # was lost): the pages were never provably spilled — None ack
        # (distinct from a 0 ack) and the request must recompute from
        # the prompt if readmitted
        self.spill_acks[rec[0]] = None
        self.recompute_on_readmit.add(rec[0])

    def drain_spill_acks(self) -> Dict[int, Optional[int]]:
        """Collect-and-clear the accumulated spill acks (request id ->
        ack value, or None for a lost reply).  The eviction point that
        keeps steady-state memory flat on long-running engines."""
        acks, self.spill_acks = self.spill_acks, {}
        return acks

    def run_until_drained(self, max_ticks: int = 10_000) -> Dict[int, List[int]]:
        ticks = 0
        while (self.queue or any(s.request_id >= 0 for s in self.slots)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return dict(self.finished)

    # -- durable artifact: AOT export + cold start --------------------------------
    def export_artifact(self, directory: str,
                        extra_meta: Optional[dict] = None) -> str:
        """Export this engine as a durable cold-start artifact.

        Writes into ``directory``:

        * ``serve_step.bin`` — the jitted ``paged_decode_step`` (axes and
          config closed over) serialized via ``jax.export``: the compiled
          "CPU program on GPU" as portable bytes;
        * ``manifest.json`` — the :class:`repro.core.rpc.RpcManifest`:
          every pad/callee id, signature, interned format string, and
          queue geometry this process bound (including the engine's spill
          queue);
        * a step-0 checkpoint of the parameter values, whose manifest
          embeds the SAME transport section (checkpoint-as-artifact);
        * ``engine.json`` — the engine geometry (batch slots, max_len,
          page size, eos id) plus ``extra_meta``.

        :meth:`from_artifact` reloads all four in a fresh process with
        zero retrace."""
        from jax import export as jax_export
        from repro.ckpt import checkpoint as ckpt
        os.makedirs(directory, exist_ok=True)
        axes_tree, cfg = self._axes, self.cfg

        def serve_step(values, kv, tokens, active):
            return paged_decode_step(merge_params(values, axes_tree),
                                     kv, tokens, active, cfg)

        def _spec(x):
            return jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x))

        exported = jax_export.export(jax.jit(serve_step))(
            jax.tree.map(_spec, self._values), jax.tree.map(_spec, self.kv),
            jax.ShapeDtypeStruct((self.B,), jnp.int32),
            jax.ShapeDtypeStruct((self.B,), jnp.bool_))
        with open(os.path.join(directory, "serve_step.bin"), "wb") as f:
            f.write(exported.serialize())
        queues = [self.spill_q] if self.spill_q is not None else []
        manifest = rpc_mod.export_manifest(queues=queues)
        manifest.save(os.path.join(directory, "manifest.json"))
        ckpt.save_checkpoint(directory, 0, {"values": self._values},
                             transport=manifest)
        meta = dict(self._geom)
        meta.update(extra_meta or {})
        with open(os.path.join(directory, "engine.json"), "w") as f:
            json.dump(meta, f, indent=2, sort_keys=True)
            f.write("\n")
        return directory

    @classmethod
    def from_artifact(cls, directory: str, cfg: ModelConfig, *,
                      spill_sink: Optional[Any] = None,
                      spill_timeout: Optional[float] = None,
                      spill_retries: int = 1,
                      mesh=None) -> "ServingEngine":
        """Cold-start an engine from :meth:`export_artifact` output in a
        FRESH process: adopt the manifest (so every device-resident id
        resolves), deserialize ``serve_step.bin``, and restore parameter
        values into the exported input structure — the artifact is
        self-describing, so there is no model rebuild and NO re-trace
        (``engine._step_source == "artifact"``).  ``cfg`` must be the
        same model config the exporting process served (the KV cache is
        re-initialized from it)."""
        from jax import export as jax_export
        from repro.ckpt import checkpoint as ckpt
        with open(os.path.join(directory, "engine.json")) as f:
            meta = json.load(f)
        manifest = rpc_mod.RpcManifest.load(
            os.path.join(directory, "manifest.json"))
        rpc_mod.adopt_manifest(manifest)
        with open(os.path.join(directory, "serve_step.bin"), "rb") as f:
            exported = jax_export.deserialize(bytearray(f.read()))

        self = cls.__new__(cls)
        self.model = None
        self.cfg = cfg
        self.params = None
        self.B = int(meta["batch_slots"])
        max_len, page_size = int(meta["max_len"]), int(meta["page_size"])
        self.kv = kvcache.paged_cache_init(cfg, self.B, max_len,
                                           page_size=page_size, mesh=mesh)
        self.eos_id = meta.get("eos_id")
        self.spill_sink = spill_sink
        self.spill_q = None
        self.spill_acks = {}
        self.spill_retries = int(spill_retries)
        self.recompute_on_readmit = set()
        if spill_sink is not None:
            maxp = (max_len + page_size - 1) // page_size
            self.spill_q = RpcQueue.create(
                capacity=max(2 * self.B, 8), width=3,
                payload_capacity=max(self.B * maxp, 8),
                reply_capacity=max(2 * self.B, 8),
                timeout=spill_timeout, mode="async",
                carry_budget=self.spill_retries)
        self.slots = [_Slot() for _ in range(self.B)]
        self.queue = []
        self.finished = {}
        self._next_id = 0
        # the exported signature IS the values treedef — restore into it
        flat = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in exported.in_avals]
        args, _kwargs = jax.tree_util.tree_unflatten(exported.in_tree, flat)
        _, restored = ckpt.restore_checkpoint(
            directory, {"values": args[0]}, step=0)
        self._values = restored["values"]
        self._axes = None
        self._axes_h = None
        self._exported = exported
        self._step = (lambda values, _axes, kv, tokens, active:
                      exported.call(values, kv, tokens, active))
        self._geom = {"batch_slots": self.B, "max_len": max_len,
                      "page_size": page_size, "eos_id": self.eos_id}
        self._step_source = "artifact"
        return self


def _read_token(nxt: jax.Array, i: int) -> int:
    """Slot ``i``'s next token, read to the host: one device read."""
    events.count("engine.host_reads")
    return int(nxt[i])


class _Hashable:
    """Static-argnum wrapper for the axes tree."""

    def __init__(self, tree):
        self.tree = tree
        leaves, treedef = jax.tree_util.tree_flatten(
            tree, is_leaf=lambda v: isinstance(v, tuple))
        self._key = (treedef, tuple(map(tuple, leaves)))

    def __hash__(self):
        return hash(str(self._key))

    def __eq__(self, other):
        return isinstance(other, _Hashable) and str(self._key) == str(other._key)
