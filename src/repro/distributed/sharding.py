"""Logical-axis sharding: the GSPMD face of the paper's parallelism expansion.

The model code is written in *single-shard semantics*: every tensor dimension
carries a **logical axis name** ("batch", "heads", "ffn", ...), never a mesh
axis.  Expansion to the full machine (the paper's single-team -> multi-team
rewrite, Section 3.3) happens here, by mapping logical names onto mesh axes
through a rules table.  Changing the rules re-shards the whole model — that is
the hillclimbing control surface used in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Default rules: (logical axis -> mesh axes).  ``pod`` composes with ``data``
# for pure data parallelism; ``model`` carries TP/EP/SP.
# ---------------------------------------------------------------------------
LOGICAL_RULES: Tuple[Tuple[str, AxisVal], ...] = (
    ("batch",      ("pod", "data")),   # global batch (DP)
    ("seq",        None),              # activations keep full sequence by default
    ("seq_shard",  "model"),           # sequence-parallel alternative (SP)
    ("seq_kv",     "model"),           # KV-cache sequence dim (decode): the
                                       # cache is the decode working set; the
                                       # seq dim always divides the mesh,
                                       # unlike GQA kv-head counts
    ("embed",      None),              # d_model on activations: replicated
    ("embed_p",    "model"),           # d_model on the embedding table (local gather)
    ("fsdp",       "data"),            # weight in-dims: ZeRO-3/FSDP over data;
                                       # XLA all-gathers per layer, grads
                                       # reduce-scatter, opt state shards 16x
    ("vocab",      "model"),           # vocab-parallel embedding / lm head
    ("heads",      "model"),           # q heads (TP)
    ("kv_heads",   "model"),           # kv heads (TP); may be uneven -> GSPMD pads
    ("kv_heads_r", None),              # kv replicated (``kv_repl`` strategy)
    ("head_dim",   None),
    ("qkv",        "model"),           # flattened q/kv projection output dim
    ("ffn",        "model"),           # MLP hidden (TP)
    ("experts",    "model"),           # MoE expert dim (EP)
    ("expert_ffn", None),              # per-expert hidden: unsharded under EP
    ("ssm_inner",  "model"),           # SSM inner width
    ("ssm_heads",  "model"),           # SSD heads
    ("ssm_state",  None),
    ("lru",        "model"),           # RG-LRU width
    ("conv",       None),
    ("capacity",   None),
    ("tokens",     ("pod", "data", "model")),  # fully flattened token dim (MoE dispatch)
    ("stack",      None),              # scan-stacked layer dim
    ("window",     None),
)


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules = dict(LOGICAL_RULES)


_CTX = _Ctx()


@contextlib.contextmanager
def ShardingCtx(mesh: Optional[Mesh], rules: Optional[Sequence[Tuple[str, AxisVal]]] = None):
    """Install a mesh + logical rules for the enclosed trace.

    ``mesh=None`` disables constraints entirely (single-device smoke tests run
    the *same* model code unexpanded — the paper's single-team semantics).
    """
    old_mesh, old_rules = _CTX.mesh, _CTX.rules
    _CTX.mesh = mesh
    merged = dict(LOGICAL_RULES)
    if rules:
        merged.update(dict(rules))
    _CTX.rules = merged
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = old_mesh, old_rules


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _resolve(axis: Optional[str]) -> AxisVal:
    if axis is None:
        return None
    try:
        val = _CTX.rules[axis]
    except KeyError:
        raise KeyError(f"unknown logical axis {axis!r}") from None
    mesh = _CTX.mesh
    if mesh is None:
        return None
    # Drop mesh axes that the current mesh does not have (e.g. "pod" single-pod)
    if isinstance(val, tuple):
        kept = tuple(a for a in val if a in mesh.axis_names)
        return kept if kept else None
    if isinstance(val, str) and val not in mesh.axis_names:
        return None
    return val


def _axis_size(mesh: Mesh, val: AxisVal) -> int:
    if val is None:
        return 1
    if isinstance(val, tuple):
        n = 1
        for a in val:
            n *= mesh.shape[a]
        return n
    return mesh.shape[val]


def logical_spec(*logical_axes: Optional[str],
                 shape: Optional[Sequence[int]] = None) -> P:
    """Translate logical axis names (one per tensor dim) to a PartitionSpec.

    When ``shape`` is given, axes that do not divide their dimension are
    DROPPED (replicated): pjit rejects uneven in_shardings, and this is also
    the honest baseline for e.g. 40 q-heads on a 16-way model axis — the
    resulting replication shows up in the roofline's useful-compute ratio
    (and is what the §Perf hillclimb then fixes with a different rule set).
    """
    mesh = _CTX.mesh
    vals = [_resolve(a) for a in logical_axes]
    if shape is not None and mesh is not None:
        vals = [v if dim % _axis_size(mesh, v) == 0 else None
                for v, dim in zip(vals, shape)]
    return P(*vals)


def shard_map_logical(fn, in_axes: Sequence[Sequence[Optional[str]]],
                      out_axes: Sequence[Sequence[Optional[str]]]):
    """``fn`` run once per shard under the current mesh, its operands and
    results split along logical axes (one name per dim, one tuple per
    operand / result); ``fn`` itself when no mesh is installed.

    For ops the partitioner cannot split, such as Mosaic kernels.  A logical
    axis whose mesh axes do not divide every operand dim that carries it is
    replicated everywhere, so operands and results stay consistent."""
    def wrapped(*args):
        mesh = _CTX.mesh
        if mesh is None:
            return fn(*args)
        dims: dict = {}
        for a, names in zip(args, in_axes):
            for d, n in zip(a.shape, names):
                if n is not None:
                    dims.setdefault(n, []).append(d)

        def mesh_axes(n):
            v = _resolve(n)
            ok = all(d % _axis_size(mesh, v) == 0 for d in dims.get(n, ()))
            return v if ok else None

        def spec(names):
            return P(*(None if n is None else mesh_axes(n) for n in names))

        return jax.shard_map(
            fn, mesh=mesh, in_specs=tuple(spec(n) for n in in_axes),
            out_specs=tuple(spec(n) for n in out_axes),
            check_vma=False)(*args)

    return wrapped


def logical_sharding(*logical_axes: Optional[str],
                     shape: Optional[Sequence[int]] = None
                     ) -> Optional[NamedSharding]:
    mesh = _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_spec(*logical_axes, shape=shape))


def with_logical_constraint(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` in logical-axis vocabulary (no-op w/o mesh)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"constraint rank mismatch: {len(logical_axes)} axes for ndim={x.ndim}")
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(*logical_axes, shape=x.shape))


def device_axis_spec(mesh: Mesh) -> P:
    """Spec of the sharded-runtime leading DEVICE axis (``ShardedHeap`` /
    ``ShardedRpcQueue`` leaves, `repro.core` PR 3): dim 0 partitioned
    jointly over every mesh axis — the layout ``expand(..., heap=True,
    queue=True)`` and ``device_run(mesh=)`` partition their team-local
    state with."""
    return P(tuple(mesh.axis_names))


def place_sharded_state(obj, mesh: Mesh):
    """Pre-place a sharded-runtime pytree (ShardedHeap / ShardedRpcQueue /
    sharded LogRing) so its leading device axis already lives one-shard-
    per-device — entering the expanded program then reshards nothing."""
    sharding = NamedSharding(mesh, device_axis_spec(mesh))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), obj)


def _is_axes_leaf(v) -> bool:
    return isinstance(v, tuple) and all(
        a is None or isinstance(a, str) for a in v)


def param_sharding_tree(param_axes, mesh: Mesh, rules=None, like=None):
    """Map a pytree of logical-axis tuples to NamedShardings under ``mesh``.

    ``like`` (a matching tree of arrays/ShapeDtypeStructs) enables the
    divisibility guard per leaf.
    """
    with ShardingCtx(mesh, rules):
        if like is None:
            return jax.tree.map(
                lambda axes: logical_sharding(*axes), param_axes,
                is_leaf=_is_axes_leaf)
        return jax.tree.map(
            lambda axes, l: logical_sharding(*axes, shape=l.shape),
            param_axes, like, is_leaf=_is_axes_leaf)


def zero1_spec(spec: P, shape, mesh: Mesh, axis: str = "data") -> P:
    """ZeRO-1: additionally shard one unsharded, divisible dim over ``axis``
    (used for fp32 optimizer state whose parameter is replicated or only
    partially sharded — e.g. the replicated embedding table)."""
    if mesh is None or axis not in mesh.axis_names:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for e in entries:
        if isinstance(e, tuple):
            used.update(e)
        elif e is not None:
            used.add(e)
    if axis in used:
        return spec
    n = mesh.shape[axis]
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % n == 0:
            entries[i] = axis
            return P(*entries)
    return spec


def zero1_sharding_tree(v_shard, like, mesh: Mesh, axis: str = "data"):
    def one(sh, l):
        if sh is None:
            return None
        return NamedSharding(mesh, zero1_spec(sh.spec, l.shape, mesh, axis))
    return jax.tree.map(one, v_shard, like)
