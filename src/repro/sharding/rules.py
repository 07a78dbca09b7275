"""Logical-axis sharding rules — the TPU analogue of the paper's Glow
placement hints (T8): a table mapping logical tensor axes to mesh axes.

Models annotate params/activations with *logical* axes ('embed', 'heads',
'vocab', ...). ``ShardingRules`` maps those to mesh axes and is the single
knob the perf hillclimb turns. ``resolve()`` downgrades any rule whose mesh
axis does not evenly divide the tensor dim (the paper's "rejected hints":
unsatisfiable placement falls back to the compiler default).
"""
from __future__ import annotations

import dataclasses
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis -> mesh axis (or tuple of mesh axes)."""
    batch: AxisVal = ("pod", "data")
    seq: AxisVal = None              # 'data' under sequence/context parallelism
    embed: AxisVal = None            # 'data' under FSDP (params only)
    heads: AxisVal = "model"
    kv_heads: AxisVal = "model"
    mlp: AxisVal = "model"           # FFN hidden
    vocab: AxisVal = "model"         # embedding-table rows (paper T1)
    experts: AxisVal = "data"        # EP = DP (paper T1 for MoE)
    expert_mlp: AxisVal = "model"
    kv_seq: AxisVal = None           # 'data' for sequence-sharded decode cache
    ssm_inner: AxisVal = "model"     # mamba d_inner / lru width
    table_rows: AxisVal = ("data", "model")  # DLRM embedding rows: full mesh

    def with_(self, **kw) -> "ShardingRules":
        return dataclasses.replace(self, **kw)


# Presets for the dry-run's ``--preset``
BASELINE_RULES = ShardingRules()
FSDP_RULES = ShardingRules(embed="data")          # training: params over data
REPLICATED_ATTN = ShardingRules(heads=None, kv_heads=None)

# Winning training strategy from the perf hillclimb (EXPERIMENTS.md SecPerf):
# pure ZeRO-3 data parallelism over the whole mesh — batch sharded over all
# axes, params FSDP'd over both, no tensor parallelism (no activation
# all-reduces), experts spanning both axes. Valid when global_batch divides
# the mesh size.
ZERO3_RULES = ShardingRules(
    batch=("pod", "data", "model"), embed=("data", "model"),
    heads=None, kv_heads=None, mlp=None, vocab=None, ssm_inner=None,
    experts=("data", "model"), expert_mlp=None)

# Sequence-parallel inference (EXPERIMENTS.md SecPerf Cell 2 I3): the
# residual stream shards over 'model' along SEQ; attention output is
# seq-local (no all-reduce — only a small GQA K/V all-gather), the MLP AR
# splits into AG+RS, norms/residuals run on 1/16 of the tokens.
SEQ_PARALLEL_RULES = ShardingRules(seq="model", heads=None, kv_heads=None,
                                   vocab=None)

PRESETS = {
    "baseline": BASELINE_RULES,
    "fsdp": FSDP_RULES,
    "zero3": ZERO3_RULES,
    "seq_parallel": SEQ_PARALLEL_RULES,
}


class Logical:
    """Opaque wrapper for a tuple of logical axis names (a pytree *leaf*)."""
    __slots__ = ("axes",)

    def __init__(self, *axes: Optional[str]):
        self.axes = tuple(axes)

    def __repr__(self):
        return f"Logical{self.axes}"

    def prepend(self, axis: Optional[str]) -> "Logical":
        out = Logical()
        out.axes = (axis,) + self.axes
        return out


@dataclass
class MeshCtx:
    mesh: Mesh
    rules: ShardingRules


_CTX: ContextVar[Optional[MeshCtx]] = ContextVar("repro_mesh_ctx", default=None)
_SPEC_MODE: ContextVar[bool] = ContextVar("repro_spec_mode", default=False)


def current_ctx() -> Optional[MeshCtx]:
    return _CTX.get()


def current_mesh() -> Optional[Mesh]:
    ctx = _CTX.get()
    return ctx.mesh if ctx else None


def current_rules() -> ShardingRules:
    ctx = _CTX.get()
    return ctx.rules if ctx else BASELINE_RULES


@contextmanager
def use_mesh(mesh: Mesh, rules: ShardingRules = BASELINE_RULES):
    tok = _CTX.set(MeshCtx(mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(tok)


@contextmanager
def spec_mode():
    tok = _SPEC_MODE.set(True)
    try:
        yield
    finally:
        _SPEC_MODE.reset(tok)


def in_spec_mode() -> bool:
    return _SPEC_MODE.get()


# --------------------------------------------------------------------------
def _axis_size(mesh: Mesh, ax: AxisVal) -> int:
    if ax is None:
        return 1
    if isinstance(ax, str):
        return mesh.shape.get(ax, 1)
    n = 1
    for a in ax:
        n *= mesh.shape.get(a, 1)
    return n


def _filter_axes(mesh: Mesh, ax: AxisVal) -> AxisVal:
    """Drop mesh axes that don't exist in this mesh (e.g. 'pod' single-pod)."""
    names = set(mesh.axis_names)
    if ax is None:
        return None
    if isinstance(ax, str):
        return ax if ax in names else None
    kept = tuple(a for a in ax if a in names)
    return kept if kept else None


def logical_to_spec(axes: Logical, rules: Optional[ShardingRules] = None,
                    mesh: Optional[Mesh] = None,
                    dims: Optional[Tuple[int, ...]] = None) -> P:
    """Map a Logical axes tuple to a PartitionSpec.

    If ``dims`` is given, any mapping whose mesh-axis product does not divide
    the dim is downgraded to replication (paper: "rejected hints").
    """
    rules = rules or current_rules()
    mesh = mesh or current_mesh()
    spec = []
    used = set()
    for i, name in enumerate(axes.axes):
        ax = getattr(rules, name) if (name and hasattr(rules, name)) else None
        if mesh is not None:
            ax = _filter_axes(mesh, ax)
            if ax is not None and dims is not None:
                if dims[i] % _axis_size(mesh, ax) != 0:
                    ax = None          # rejected hint: not divisible
        # rejected hint: a mesh axis may shard at most one dim (e.g. MoE
        # expert weights under FSDP would map 'experts' and 'embed' -> data)
        if ax is not None:
            names = (ax,) if isinstance(ax, str) else tuple(ax)
            if any(n in used for n in names):
                kept = tuple(n for n in names if n not in used)
                ax = kept if kept else None
                if ax is not None and dims is not None and mesh is not None \
                        and dims[i] % _axis_size(mesh, ax) != 0:
                    ax = None
            if ax is not None:
                used.update((ax,) if isinstance(ax, str) else ax)
        if isinstance(ax, tuple) and len(ax) == 1:
            ax = ax[0]          # singleton tuple == bare axis (older jax
                                # PartitionSpec does not normalize this)
        spec.append(ax)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply a with_sharding_constraint for the given logical axes (no-op
    without a mesh context — smoke tests run unsharded)."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    spec = logical_to_spec(Logical(*axes), ctx.rules, ctx.mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def mesh_axis_size(name: str) -> int:
    """Size of the mesh axes a logical rule maps to (1 without a mesh)."""
    ctx = _CTX.get()
    if ctx is None:
        return 1
    ax = _filter_axes(ctx.mesh, getattr(ctx.rules, name))
    return _axis_size(ctx.mesh, ax)


def mesh_axis_names(name: str) -> Tuple[str, ...]:
    ctx = _CTX.get()
    if ctx is None:
        return ()
    ax = _filter_axes(ctx.mesh, getattr(ctx.rules, name))
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(ax)
