"""Logical-axis sharding: MaxText-style rules mapping model-semantic axis
names onto physical mesh axes.

Models annotate params/activations with *logical* names ("batch", "heads",
"ff", "embed", ...).  A :class:`ShardingRules` table maps each name to mesh
axes; :func:`spec_for` resolves a logical spec against a concrete mesh with
automatic divisibility fallback (an axis that doesn't divide is silently
replicated — e.g. gemma-2b's single KV head can't split 16 ways, grok's 8
experts can't split 16 ways; the roofline table shows the idle axis).

A thread-local context (:func:`use_sharding`) lets model code call
:func:`constrain` without threading the mesh through every function; outside
a context (CPU smoke tests) it is a no-op.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DEFAULT_RULES", "FSDP_RULES", "DP_TP_RULES", "FLEET_RULES",
    "ShardingRules", "use_sharding", "current_context", "spec_for",
    "constrain", "named_sharding", "tree_named_shardings",
    "shard_map_compat", "make_mesh_compat", "node_mesh_axes",
]


def make_mesh_compat(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``, as meshes that
    mix manual ``shard_map`` regions with auto sharding need."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` over the manual ``axis_names`` (default: every mesh
    axis), without the varying-manual-axes check."""
    manual = frozenset(mesh.axis_names) if axis_names is None \
        else frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)


# Logical axis -> mesh axis (or tuple of mesh axes).  Mesh axes that do not
# exist in the active mesh are dropped at resolution time, so one rule table
# serves both the single-pod ("data","model") and multi-pod
# ("pod","data","model") meshes.
ShardingRules = Mapping[str, tuple[str, ...] | str | None]

# FSDP flavour (default for the big models): weight embed-dim sharded over
# "data" => XLA inserts per-layer all-gathers (ZeRO-3 style); optimizer state
# inherits the same sharding (ZeRO-1 falls out for free).
FSDP_RULES: ShardingRules = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": "data",          # weight d_model dim (FSDP axis)
    "embed_act": None,        # activation d_model dim stays unsharded
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    # NOTE on non-divisible head counts (yi 56H, whisper/qwen 12H, gemma-2b
    # 8H): head_dim->model (Megatron contracted-dim sharding) was tried and
    # REJECTED — it psums the full attention-score tensors (yi prefill
    # collective term exploded 100x; see EXPERIMENTS.md §Perf iteration
    # history).  Instead the model zero-pads q-heads to the mesh quantum and
    # expands KV (exact math, bounded pad waste) — see transformer._attn_mix.
    "head_dim": None,
    "ff": "model",
    "experts": "model",
    "expert_ff": "model",
    "state": "model",         # SSM/RG-LRU inner state dim
    "conv": None,
    "layers": None,
    "seq_shard": "data",      # long-context activation sequence sharding
    # decode KV-cache sequence dim: split-KV ("flash-decode") sharding — the
    # cache shards over "model" when kv_heads can't (kv<16); attention over
    # the sharded axis becomes a distributed softmax (XLA inserts the small
    # max/sum all-reduces)
    "kv_seq": "model",
}

# Plain DP+TP flavour: weights replicated over "data" — the configuration in
# which gradient all-reduce dominates, i.e. where Seeker's coreset gradient
# compression acts (the paper-representative hillclimb cell).
DP_TP_RULES: ShardingRules = dict(FSDP_RULES, embed=None)

# Pure-DP flavour for models too small to feed a 16-way tensor axis
# (mamba2-130m, whisper-small): batch shards across the WHOLE mesh, weights
# FSDP over "data", the model axis carries no tensor parallelism at all —
# kills the intra-layer resharding collectives (§Perf mamba2 iteration log).
PURE_DP_RULES: ShardingRules = {
    **{k: None for k in FSDP_RULES},
    "batch": ("pod", "data", "model"),
    "embed": "data",
    "layers": None,
}

# Fleet-serving flavour: the ONLY sharded axis is the fleet's node axis.
# Stacked SeekerNodeState, per-node PRNG keys, harvest traces and per-node
# window streams all shard their leading "nodes" dim over ("pod", "data");
# the signature bank, DNN params, generator params and AAC table are
# replicated — every shard runs the full Seeker decision ladder for its
# local node tile and only fleet-level aggregates (bytes on wire, decision
# histograms, accuracy counts) cross shards via psum.  Consumed by
# :func:`repro.serving.fleet.seeker_fleet_simulate_sharded`.
FLEET_RULES: ShardingRules = {
    **{k: None for k in FSDP_RULES},
    "nodes": ("pod", "data"),
    "signatures": None,       # memo bank: replicated, streamed per shard
    "params": None,           # qDNN / host DNN / generator weights
}

DEFAULT_RULES = FSDP_RULES

_ctx = threading.local()


class _Context:
    def __init__(self, mesh: Mesh, rules: ShardingRules):
        self.mesh = mesh
        self.rules = dict(rules)


def current_context() -> _Context | None:
    return getattr(_ctx, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: ShardingRules = DEFAULT_RULES):
    prev = current_context()
    _ctx.ctx = _Context(mesh, rules)
    try:
        with mesh:
            yield _ctx.ctx
    finally:
        _ctx.ctx = prev


def _mesh_axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def node_mesh_axes(mesh: Mesh,
                   rules: ShardingRules = FLEET_RULES
                   ) -> tuple[tuple[str, ...], int]:
    """Resolve the "nodes" logical axis against ``mesh``.

    Returns ``(axes, quantum)``: the mesh axes the fleet's node dim shards
    over (rule axes absent from the mesh are dropped, so the same table
    serves ("data",) and ("pod", "data") meshes) and their total size — the
    shard quantum fleets are padded to a multiple of.
    """
    rule = rules.get("nodes") or ()
    axes = (rule,) if isinstance(rule, str) else tuple(rule)
    axes = tuple(a for a in axes if a in mesh.shape)
    return axes, (_mesh_axis_size(mesh, axes) if axes else 1)


def spec_for(logical: Sequence[str | None], shape: Sequence[int],
             mesh: Mesh | None = None,
             rules: ShardingRules | None = None) -> P:
    """Resolve a logical spec to a PartitionSpec for ``mesh``.

    Drops (a) mesh axes absent from the mesh, (b) assignments that do not
    divide the dimension, (c) duplicate uses of one mesh axis (first wins).
    """
    ctx = current_context()
    mesh = mesh or (ctx.mesh if ctx else None)
    rules = rules or (ctx.rules if ctx else DEFAULT_RULES)
    if mesh is None:
        return P(*([None] * len(shape)))
    used: set[str] = set()
    out = []
    for name, dim in zip(logical, shape):
        assignment = None
        if name is not None:
            rule = rules.get(name)
            if rule is not None:
                axes = (rule,) if isinstance(rule, str) else tuple(rule)
                axes = tuple(a for a in axes if a in mesh.shape and a not in used)
                # longest prefix of the rule that divides the dimension
                # (e.g. batch=32 on ("pod","data","model"): 32 % 512 != 0
                # but 32 % 32 == 0 -> shard over ("pod","data"))
                while axes and dim % _mesh_axis_size(mesh, axes) != 0:
                    axes = axes[:-1]
                if axes:
                    assignment = axes if len(axes) > 1 else axes[0]
                    used.update(axes)
        out.append(assignment)
    return P(*out)


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside a context."""
    ctx = current_context()
    if ctx is None:
        return x
    spec = spec_for(logical, x.shape, ctx.mesh, ctx.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def named_sharding(logical: Sequence[str | None], shape: Sequence[int],
                   mesh: Mesh | None = None,
                   rules: ShardingRules | None = None) -> NamedSharding:
    ctx = current_context()
    mesh = mesh or (ctx.mesh if ctx else None)
    if mesh is None:
        raise ValueError("named_sharding requires a mesh (or use_sharding ctx)")
    return NamedSharding(mesh, spec_for(logical, shape, mesh, rules))


def tree_named_shardings(spec_tree, shape_tree, mesh: Mesh,
                         rules: ShardingRules = DEFAULT_RULES):
    """Zip a logical-spec pytree against a ShapeDtypeStruct pytree ->
    NamedSharding pytree (for jit in_shardings / out_shardings)."""
    return jax.tree_util.tree_map(
        lambda spec, sds: NamedSharding(
            mesh, spec_for(spec, sds.shape, mesh, rules)),
        spec_tree, shape_tree,
        is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(e, (str, type(None))) for e in s),
    )
