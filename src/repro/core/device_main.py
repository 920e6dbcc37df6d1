"""Whole-program device execution (paper §3.1): ``main()`` lives on the TPU.

Classical offload drives the accelerator step-by-step from a host loop — one
launch + sync per step (the analogue of the paper's "legacy" CPU-driven app).
GPU First inverts this: the *entire* program runs on the device, escaping to
the host only through RPCs.  Here that is a single jitted program containing
the full multi-step loop (``lax.while_loop`` over steps, donated carry), with
periodic host escapes (checkpoint, metrics, data refill) expressed as RPCs —
the loader below compiles it, transfers control, and only sees the device
again when the program returns.

Host escapes ride the v2 RPC transport (``repro.core.rpc``):

* **Immediate hooks** (default) dispatch through :func:`rpc_call` — the
  landing-pad table caches ONE host wrapper per hook signature, so re-traces
  reuse the same callable, and per-hook call/byte stats accumulate under the
  hook's RPC name.  Each *firing* is one ordered host round-trip; steps
  where the hook does NOT fire are **host-free** (the callback lives only in
  the taken branch of the firing conditional — there is no per-step noop
  RPC, so a 1000-step loop with ``every=100`` contacts the host 10 times,
  not 1000: the Fig. 7-class per-step sync the noop used to reintroduce).
* **Batched hooks** (``HostHook(batched=True)``) never touch the host during
  the loop: firings are enqueued into an on-device :class:`~repro.core.rpc.
  RpcQueue` (a pure array update), and ONE ordered flush at the end of the
  program replays them on the host in firing order.  Batched hooks are
  fire-and-forget; their payload may mix SCALAR leaves (record lanes) and
  ARRAY leaves — a histogram, a residual vector — which ride the queue's
  payload arena (transport v3) and reach ``host_fn`` as 1-D numpy arrays.
  Use them for metrics/logging, not for host interactions the next step
  depends on.
* **Sharded runs** (``device_run(..., mesh=)``) execute the step loop under
  parallelism expansion (§3.3): the whole loop runs inside ``shard_map``
  over every mesh axis, ``step_fn`` (and hook ``extract``) may use the
  expansion primitives (``team_id()`` etc.), and ALL hooks ride a
  per-device :class:`~repro.core.rpc.ShardedRpcQueue` shard — zero host
  contact during the loop, one gathered drain at the program boundary
  replaying records in (device, slot) order.

Hook hygiene: hooks without an explicit ``name`` get a per-instance derived
name whose registry entries (host binding, landing pads, batch callee id)
are retired when ``device_run`` returns — repeated runs with ad-hoc hooks
leave the registry at constant size, and a recycled ``id()`` can never
silently rebind a dead hook's pad to a new hook.

The host round-trip cost this architecture removes is measured by
``benchmarks/rpc_bench.py`` (the paper's Fig. 7).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import events
from repro.core import rpc as rpc_mod
from repro.core.expand import _team_env
from repro.core.rpc import REGISTRY, RpcQueue, ShardedRpcQueue, rpc_call

_I32 = jax.ShapeDtypeStruct((), jnp.int32)


@dataclasses.dataclass(frozen=True)
class HostHook:
    """A periodic host escape from the device main loop.

    every:    fire on steps where step % every == 0 (and step > 0)
    extract:  (step, state) -> pytree of arrays shipped to the host
    host_fn:  host callback receiving (step, *leaves); return value ignored
              unless ``returns`` declares one
    name:     RPC name for the pad table / stats.  Defaults to a derived
              name under the MANIFEST scheme — a stable content hash of
              the host_fn's (module, qualname, firstlineno) and ``every``
              — so a re-trace of the same program (even in another
              process, against an adopted :class:`~repro.core.rpc.
              RpcManifest`) binds the same RPC ids.  Only a host_fn with
              no code object (e.g. ``functools.partial``) falls back to a
              process-local ``id()`` name, which cannot round-trip a
              manifest — the analyzer flags it (``UNSTABLE_PAD_NAME``).
    batched:  queue firings on device; ONE flush at end of run replays them
              (scalar extract leaves reach host_fn as plain python
              ints/floats; array leaves ride the payload arena and arrive
              as 1-D numpy arrays)
    returns:  (batched only) ``jax.ShapeDtypeStruct`` declaring that
              host_fn RETURNS a value the device consumes: the firing
              step enqueues a ticketed record, flushes the queue mid-loop,
              and threads the reply into the next step's state via
              ``consume`` — no manual ``thread_queue`` plumbing.  Not
              available under ``mesh=`` (no mid-loop flush in a
              partitioned program).
    consume:  ``(step, state, value, ok) -> state`` — folds the reply into
              the carried state on firing steps (``ok`` is the v4
              validity mask: False when the record or its reply was
              dropped).  Required with ``returns``.
    idempotent: declares host_fn retry-safe: a queue draining with a
              :class:`~repro.core.rpc.RetryPolicy` may redrive a failed
              firing (at-least-once delivery).  Leave False for hooks
              with non-repeatable side effects — retry then skips them
              and the record surfaces as ``CALLEE_RAISED``.
    """
    every: int
    extract: Callable[[jax.Array, Any], Any]
    host_fn: Callable
    name: Optional[str] = None
    batched: bool = False
    returns: Optional[jax.ShapeDtypeStruct] = None
    consume: Optional[Callable] = None
    idempotent: bool = False


def _hook_key(hook: HostHook) -> Optional[str]:
    """The hook's durable identity under the manifest naming scheme, or
    None when host_fn has no code object to anchor one (a process-local
    ``id()`` name is the only fallback — and it cannot round-trip)."""
    code = getattr(hook.host_fn, "__code__", None)
    if code is None:
        return None
    mod = getattr(hook.host_fn, "__module__", "") or ""
    qual = getattr(hook.host_fn, "__qualname__",
                   getattr(hook.host_fn, "__name__", "fn"))
    return f"{mod}:{qual}:{code.co_firstlineno}:{int(hook.every)}"


def _hook_name(hook: HostHook) -> str:
    """Auto-name under the manifest scheme: ``hook.<fn>.<hash31 hex>``.
    Stable across processes — any trace of the same program derives the
    same name, hence (content-hashed) the same pad/callee ids."""
    if hook.name:
        return hook.name
    fn_name = getattr(hook.host_fn, "__name__", "fn")
    key = _hook_key(hook)
    if key is None:
        return f"hook.{fn_name}.{id(hook):x}"
    return f"hook.{fn_name}.{rpc_mod.stable_hook_id(key):08x}"


def _name_hooks(hooks: Sequence[HostHook]) -> list:
    """Name every hook, disambiguating same-named duplicates by their
    position in the hooks list (program order — deterministic, so a
    re-trace binds the same ids).  Returns ``[(hook, hname), ...]``."""
    named = []
    seen: dict = {}
    for h in hooks:
        base = _hook_name(h)
        occ = seen.get(base, 0)
        seen[base] = occ + 1
        named.append((h, base if occ == 0 else f"{base}.{occ + 1}"))
    return named


def _register_hook(hook: HostHook, hname: str) -> str:
    """Bind the hook's host_fn into the RPC registry (dispatch-time
    resolution: re-running device_run with a same-named hook rebinds)."""
    if hook.returns is not None:
        if not hook.batched:
            raise ValueError(
                f"hook {hname!r}: returns= is the batched reply path — "
                "construct it with batched=True (immediate hooks already "
                "run synchronously; return plumbing is only needed across "
                "the queue)")
        if hook.consume is None:
            raise ValueError(
                f"hook {hname!r}: returns= declares a device-consumed "
                "reply; pass consume=(step, state, value, ok) -> state "
                "to fold it into the carry")

        def adapter(step, *leaves):
            return hook.host_fn(int(step), *leaves)
    else:
        def adapter(step, *leaves):
            hook.host_fn(int(step), *leaves)
            return np.int32(0)

    adapter.__name__ = hname
    REGISTRY.register(hname, adapter, idempotent=hook.idempotent)
    return hname


def _fire(hook: HostHook, hname: str, step, state):
    """Immediate hook: one ordered RPC through the cached landing pad —
    issued ONLY on firing steps.

    The callback lives in the taken branch of the conditional; the
    non-firing branch is a pure no-op, so steps where the hook is silent
    never leave the device.  (v1 dispatched an ordered ``hook.noop`` RPC in
    the ``no`` branch — a hidden ~ms host sync on every single step.)"""
    payload = hook.extract(step, state)
    leaves = jax.tree.leaves(payload)

    def yes(_):
        r, _ = rpc_call(hname, step, *leaves, result_shape=_I32)
        return r

    should = (step % hook.every == 0) & (step > 0)
    # cond_scope declares the RPC fires once per `every` loop iterations —
    # the analyzer's capacity model divides through it, and the
    # RPC-in-loop lint exempts the taken-branch-only callback
    with events.cond_scope(int(hook.every)):
        return lax.cond(should, yes, lambda _: jnp.int32(0), 0)


def _fire_batched(hook: HostHook, hname: str, step, state,
                  q: RpcQueue) -> RpcQueue:
    """Batched hook: pure conditional enqueue (O(record), not O(queue))."""
    payload = hook.extract(step, state)
    leaves = jax.tree.leaves(payload)
    should = (step % hook.every == 0) & (step > 0)
    with events.cond_scope(int(hook.every)):
        return q.enqueue(hname, step, *leaves, where=should)


def _fire_returning(hook: HostHook, hname: str, step, state, q: RpcQueue):
    """Reply-consuming batched hook: ticketed enqueue, mid-loop flush in
    the firing branch, reply folded into the carried state via
    ``hook.consume`` — the v4 blocking-at-flush path without the caller
    threading the queue by hand.  Non-firing steps stay host-free (the
    flush callback lives only in the taken cond branch).  Returns
    ``(queue', state')``."""
    payload = hook.extract(step, state)
    leaves = jax.tree.leaves(payload)
    should = (step % hook.every == 0) & (step > 0)
    with events.cond_scope(int(hook.every)):
        q, ticket = q.enqueue_ticketed(hname, step, *leaves,
                                       returns=hook.returns, where=should)
        q = lax.cond(should, lambda qq: qq.flush(), lambda qq: qq, q)
        value, ok = q.result_ok(ticket, hook.returns)
        state = lax.cond(should,
                         lambda st: hook.consume(step, st, value, ok),
                         lambda st: st, state)
    return q, state


def device_run(step_fn: Callable[[jax.Array, Any], Any], state: Any,
               n_steps: int, *, hooks: Sequence[HostHook] = (),
               donate: bool = True, jit_kwargs: Optional[dict] = None,
               queue_capacity: int = 1024, queue_width: int = 8,
               queue_payload: int = 4096, queue_reply: int = 0,
               queue_retry=None, queue_timeout: Optional[float] = None,
               queue_async: bool = False,
               thread_queue: bool = False, return_queue: bool = False,
               mesh: Optional[Mesh] = None, state_spec=None) -> Any:
    """Run ``state = step_fn(step, state)`` for ``n_steps`` **on device**.

    The whole loop is one compiled program; ``hooks`` are the only host
    contact.  Batched hooks share one on-device :class:`RpcQueue`
    (``queue_capacity`` records of ``queue_width`` args, with a
    ``queue_payload``-word arena for array extract leaves and a
    ``queue_reply``-word REPLY arena — transport v4) flushed once after
    the loop.  Returns the final state.

    ``thread_queue=True`` hands the run's queue to the step itself:
    ``step_fn(step, state, queue) -> (state, queue)``.  Without ``mesh=``
    the step may enqueue ticketed RPCs, FLUSH mid-loop, and read replies
    on later steps (``queue.result`` after an in-loop ``queue.flush()``)
    — the v4 blocking-at-flush path threaded across steps; give the queue
    a reply arena via ``queue_reply``.  ``return_queue=True``
    additionally returns ``(final_state, flushed_queue)`` so post-loop
    code (or the caller) can read the LAST flush's replies by ticket.
    Both options also work with ``mesh=`` (the step sees its device's
    queue SHARD; the returned queue is the flushed sharded queue) with
    ONE restriction: no mid-loop flush — XLA cannot lower the drain
    callback inside the partitioned program, so under a mesh the step
    only ENQUEUES and every reply is read after the single
    program-boundary flush (``RpcQueue.flush`` raises a clear error if a
    step tries anyway).

    With ``mesh=``, the step loop runs under parallelism expansion
    (§3.3): one ``shard_map`` over every mesh axis contains the whole
    ``while_loop``, ``step_fn``/``extract`` may use the expansion
    primitives (``team_id()``, ...), and EVERY hook — immediate or batched
    — is delivered through a per-device :class:`ShardedRpcQueue` shard,
    drained once at the program boundary in (device, slot) order (hook
    payloads may mix scalar and array leaves, as for batched hooks — array
    leaves ride each shard's payload arena; ``donate`` is ignored).
    ``state_spec`` is the ``PartitionSpec`` of ``state``
    (default ``P()``: replicated — under that default ``step_fn`` must
    keep state identical on every device; a step that folds ``team_id()``
    into the CARRY diverges per device and needs an explicit per-device
    ``state_spec``, or the replicated out-spec silently keeps one
    device's copy.  Per-device hook *payloads* are fine either way — they
    live in the queue shards, not the carry).

    ``queue_retry`` (a :class:`~repro.core.rpc.RetryPolicy`) and
    ``queue_timeout`` (per-callee seconds) set the run queue's fault
    policy: the boundary drain isolates failing hook firings into the
    reply status lane, retries ``idempotent=True`` hooks, and bounds a
    hung host_fn's wall clock instead of wedging the drain.

    ``queue_async=True`` puts the run queue on the v6 double-buffered
    transport: each flush SUBMITS its epoch to a background host drain
    and returns without waiting, so host-callee time overlaps the
    following device compute.  ``device_run`` owns the boundary
    protocol — after the program returns it issues the collect flush
    (publishing the final epoch's replies into the returned queue's
    reply window) and joins the drain executor, so by the time the call
    returns every host effect has retired.  In-loop flushes (via
    ``thread_queue``) land replies ONE EPOCH LATE — guard reads with
    ``result_status`` against ``STATUS_PENDING``.  Incompatible with
    ``returns=`` hooks, whose consume step needs same-epoch replies.
    """
    named = _name_hooks(hooks)
    for h, hname in named:
        _register_hook(h, hname)
    if events.active():
        for h, hname in named:
            events.emit("hook_decl", name=hname, every=int(h.every),
                        n_steps=int(n_steps), batched=bool(h.batched),
                        mesh=mesh is not None,
                        unstable=h.name is None and _hook_key(h) is None)
    try:
        returning = [hname for h, hname in named if h.returns is not None]
        if queue_async and returning:
            raise ValueError(
                f"hook(s) {returning} use returns= with queue_async=True: "
                "the double-buffered transport lands replies one epoch "
                "late, but a consume step folds its reply into the SAME "
                "firing step's state — use the synchronous queue for "
                "reply-consuming hooks")
        if mesh is not None:
            if returning:
                raise ValueError(
                    f"hook(s) {returning} use returns= under mesh=: the "
                    "reply path needs a mid-loop flush, and XLA cannot "
                    "lower the gathered drain inside the partitioned "
                    "program — read replies after the boundary flush via "
                    "thread_queue/return_queue instead")
            return _device_run_mesh(step_fn, state, n_steps, named, mesh,
                                    state_spec, queue_capacity, queue_width,
                                    queue_payload, queue_reply, queue_retry,
                                    queue_timeout, queue_async, thread_queue,
                                    return_queue, dict(jit_kwargs or {}))

        jit_kwargs = dict(jit_kwargs or {})
        if donate:
            jit_kwargs.setdefault("donate_argnums", (0,))
        any_batched = any(h.batched for h in hooks)
        carries_queue = any_batched or thread_queue or return_queue
        if returning:
            # every reply-consuming hook flushes at its firing step, so one
            # epoch never holds more than one round of declared replies —
            # size the reply arena for all of them (plus caller's ask)
            need = sum(int(np.prod(h.returns.shape) or 1)
                       for h, _ in named if h.returns is not None)
            queue_reply = max(queue_reply, need)

        @functools.partial(jax.jit, **jit_kwargs)
        def program(state):
            def cond(carry):
                return carry[0] < n_steps

            if carries_queue:
                def body(carry):
                    step, state, q = carry
                    if thread_queue:
                        state, q = step_fn(step, state, q)
                    else:
                        state = step_fn(step, state)
                    for h, hname in named:
                        if h.returns is not None:
                            q, state = _fire_returning(h, hname, step + 1,
                                                       state, q)
                        elif h.batched:
                            q = _fire_batched(h, hname, step + 1, state, q)
                        else:
                            _fire(h, hname, step + 1, state)
                    return (step + 1, state, q)

                q0 = RpcQueue.create(queue_capacity, queue_width,
                                     queue_payload, queue_reply,
                                     retry=queue_retry,
                                     timeout=queue_timeout,
                                     mode="async" if queue_async else "sync")
                with events.loop_scope(int(n_steps)):
                    _, final, q = lax.while_loop(
                        cond, body, (jnp.zeros((), jnp.int32), state, q0))
                q = q.flush()
                if return_queue or queue_async:
                    return final, q
            else:
                def body(carry):
                    step, state = carry
                    state = step_fn(step, state)
                    for h, hname in named:
                        _fire(h, hname, step + 1, state)
                    return (step + 1, state)

                with events.loop_scope(int(n_steps)):
                    _, final = lax.while_loop(
                        cond, body, (jnp.zeros((), jnp.int32), state))
            return final

        out = program(state)
        if carries_queue and queue_async:
            final, q = out
            # boundary protocol: the in-program flush only SUBMITTED the
            # final epoch — collect it here (eager flush on the concrete
            # queue publishes its replies into the window), then join the
            # slot so every host effect has retired before we return.
            jax.effects_barrier()
            q = q.flush()
            jax.effects_barrier()
            q.join()
            return (final, q) if return_queue else final
        return out
    finally:
        _retire_auto_hooks(named)


def _device_run_mesh(step_fn, state, n_steps, named, mesh, state_spec,
                     queue_capacity, queue_width, queue_payload, queue_reply,
                     queue_retry, queue_timeout, queue_async,
                     thread_queue, return_queue, jit_kwargs):
    """The sharded step loop: whole ``while_loop`` inside one ``shard_map``,
    hooks enqueued into this device's queue shard, ONE gathered drain at the
    program boundary (the flush runs host-side on the materialized shards —
    XLA cannot lower a gathered callback inside the partitioned program).
    With ``thread_queue`` the step owns its device's shard; with
    ``return_queue`` the flushed sharded queue — reply tables stacked per
    device — is returned next to the final state."""
    axes = tuple(mesh.axis_names)
    spec = state_spec if state_spec is not None else P()
    q0 = ShardedRpcQueue.create(mesh.size, queue_capacity, queue_width,
                                queue_payload, queue_reply,
                                retry=queue_retry, timeout=queue_timeout,
                                mode="async" if queue_async else "sync")

    def region(state, q):
        lq = q.local_view()
        with _team_env(axes, 1):
            def cond(carry):
                return carry[0] < n_steps

            def body(carry):
                step, st, lq = carry
                if thread_queue:
                    st, lq = step_fn(step, st, lq)
                else:
                    st = step_fn(step, st)
                for h, hname in named:
                    lq = _fire_batched(h, hname, step + 1, st, lq)
                return (step + 1, st, lq)

            with events.loop_scope(int(n_steps)):
                _, final, lq = lax.while_loop(
                    cond, body, (jnp.zeros((), jnp.int32), state, lq))
        return final, q.with_local(lq)

    program = jax.jit(jax.shard_map(
        region, mesh=mesh, in_specs=(spec, P(axes)),
        out_specs=(spec, P(axes)), check_vma=False), **jit_kwargs)
    final, q = program(state, q0)
    q = q.flush()                  # concrete shards -> host-side drain
    if queue_async:
        # submit-only above: collect the boundary epoch's replies (each
        # device's drain runs on its own slot executor, no gather barrier),
        # then join so host effects retire before the run returns.
        jax.effects_barrier()
        q = q.flush()
        jax.effects_barrier()
        q.join()
    if return_queue:
        return final, q
    return final


def _retire_auto_hooks(named) -> None:
    """Drop registry entries of per-instance (auto-named) hooks once their
    run's callbacks have drained, so repeated ``device_run`` calls with
    ad-hoc hooks leave the registry at constant size and a recycled
    ``id()`` can never rebind a dead hook's pad.  Explicitly-named hooks
    keep their entries (documented rebind-on-rerun semantics)."""
    auto = [hname for h, hname in named if h.name is None]
    if not auto:
        return
    jax.effects_barrier()          # pending flush/RPC callbacks still
    for hname in auto:             # resolve the names — wait them out first
        REGISTRY.unregister(hname)


def host_driven_run(step_fn: Callable[[jax.Array, Any], Any], state: Any,
                    n_steps: int) -> Any:
    """The classical offload baseline: one jitted step per host-loop
    iteration, with a host sync every step.  Used by the benchmarks to
    measure what whole-program device execution saves."""
    step_jit = jax.jit(step_fn, donate_argnums=(1,))
    for i in range(n_steps):
        state = step_jit(jnp.int32(i), state)
        jax.block_until_ready(state)
    return state
