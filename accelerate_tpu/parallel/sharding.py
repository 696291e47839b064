"""Parameter/optimizer sharding rules — the GSPMD replacement for the
reference's wrapper classes (DDP `accelerator.py:1450`, FSDP `:1455-1570`,
DeepSpeed ZeRO, Megatron TP).

Two ways a param gets its `NamedSharding`:
1. **Logical axis metadata** — flax modules annotated with
   ``nn.with_partitioning`` / ``nn.with_logical_partitioning`` carry axis
   names; we map them through ``axis_rules`` (Megatron-style TP/SP layouts).
2. **Heuristic ZeRO** — un-annotated params are sharded over the "fsdp"
   axis along their largest divisible dimension when big enough
   (min_weight_size_to_shard), else replicated — the FULL_SHARD analog
   without wrapper modules.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.dataclasses import ShardingConfig, ShardingStrategy

# logical axis name -> mesh axis (or tuple). Mirrors the scaling-book recipe:
# embed/mlp over tensor for TP; fsdp shards the "long" dim of each matrix.
DEFAULT_AXIS_RULES = (
    ("batch", ("replica", "data", "fsdp")),
    ("seq", "sequence"),
    ("embed", "fsdp"),
    ("mlp", "tensor"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("head_dim", None),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("expert_capacity", None),
    ("router_experts", None),
    ("stage", "stage"),
    ("norm", None),
)

# the residual stream where a block's tensor-parallel products exchange its
# rows (parallel/context.gather_einsum): rows over "tensor", which "embed"
# cannot take because "batch" has used "fsdp"
ROWS_OVER_TENSOR_RULES = DEFAULT_AXIS_RULES + (("seq", "tensor"),)


def logical_to_spec(logical_axes: tuple, rules=DEFAULT_AXIS_RULES, mesh: Optional[Mesh] = None) -> P:
    """("embed", "mlp") -> PartitionSpec per rules, dropping mesh axes of
    size 1 and duplicate uses within one spec (an axis can shard only one
    dim of a given array)."""
    table = dict(rules)
    used: set = set()
    parts = []
    for name in logical_axes:
        target = table.get(name, None)
        if target is None:
            parts.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        picked = []
        for ax in target:
            if ax in used:
                continue
            if mesh is not None and mesh.shape.get(ax, 1) == 1:
                continue
            picked.append(ax)
            used.add(ax)
        parts.append(tuple(picked) if len(picked) > 1 else (picked[0] if picked else None))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def _leaf_logical_axes(leaf) -> Optional[tuple]:
    """Extract logical axis names from flax Partitioned / our own metadata."""
    names = getattr(leaf, "names", None)
    if names is not None:
        return tuple(names)
    return None


def unbox_params(params):
    """Strip flax Partitioned boxes, returning (raw_params, logical_axes_tree)."""
    def _unbox(leaf):
        if hasattr(leaf, "unbox"):
            return leaf.unbox()
        return leaf

    def _axes(leaf):
        return _leaf_logical_axes(leaf)

    is_boxed = lambda l: hasattr(l, "unbox")
    raw = jax.tree_util.tree_map(_unbox, params, is_leaf=is_boxed)
    axes = jax.tree_util.tree_map(_axes, params, is_leaf=is_boxed)
    return raw, axes


def infer_param_sharding(
    params,
    mesh: Mesh,
    config: ShardingConfig,
    logical_axes=None,
) -> Any:
    """Pytree of NamedSharding for ``params`` (a pytree of arrays or
    ShapeDtypeStructs)."""
    rules = tuple(config.axis_rules) if config.axis_rules else DEFAULT_AXIS_RULES
    fsdp_size = mesh.shape.get("fsdp", 1)
    strategy = config.strategy

    def _one(leaf, axes):
        if axes is not None:
            return NamedSharding(mesh, logical_to_spec(axes, rules, mesh))
        shape = tuple(leaf.shape)
        size = int(np.prod(shape)) if shape else 1
        if (
            fsdp_size > 1
            and strategy in (ShardingStrategy.FSDP, ShardingStrategy.HYBRID, ShardingStrategy.AUTO, ShardingStrategy.GRAD_OP)
            and size >= config.min_weight_size_to_shard
        ):
            # ZeRO heuristic: shard the largest dim divisible by fsdp degree
            candidates = [(d, i) for i, d in enumerate(shape) if d % fsdp_size == 0]
            if candidates:
                _, dim = max(candidates)
                spec = [None] * len(shape)
                spec[dim] = "fsdp"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())  # replicated

    if logical_axes is None:
        logical_axes = jax.tree_util.tree_map(lambda _: None, params)
    return jax.tree_util.tree_map(_one, params, logical_axes)


def shard_params(params, shardings):
    """Place params into their distributed layout (the FSDP-wrap analog)."""
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, s) if hasattr(p, "shape") else p, params, shardings
    )


_MEMORY_KINDS = None


def _memory_kind_available(kind: str) -> bool:
    """Whether the local devices expose this memory kind (older-jax CPU
    backends only have "unpinned_host" — no "device"/"pinned_host")."""
    global _MEMORY_KINDS
    if _MEMORY_KINDS is None:
        try:
            _MEMORY_KINDS = frozenset(
                m.kind for m in jax.local_devices()[0].addressable_memories()
            )
        except Exception:
            _MEMORY_KINDS = frozenset()
    return kind in _MEMORY_KINDS


def with_memory_kind(sharding, kind: str):
    """The same sharding in another memory space (host-offload plumbing).
    On backends without the requested kind the sharding passes through
    unchanged — offload configs then degrade to plain device residency,
    which is semantically identical (just without the HBM savings)."""
    from jax.sharding import SingleDeviceSharding

    if not _memory_kind_available(kind):
        return sharding
    if isinstance(sharding, NamedSharding):
        return NamedSharding(sharding.mesh, sharding.spec, memory_kind=kind)
    if isinstance(sharding, SingleDeviceSharding):
        return SingleDeviceSharding(next(iter(sharding.device_set)), memory_kind=kind)
    return sharding


def tree_with_memory_kind(shardings, kind: str):
    return jax.tree_util.tree_map(lambda s: with_memory_kind(s, kind), shardings)


def transfer_tree(tree, space):
    """In-graph transfer of array leaves to a jax.memory.Space (call inside
    jit; XLA's latency-hiding scheduler places the copies). Scalars stay put
    — the SPMD partitioner rejects placement annotations on rank-0 buffers,
    and offloading a scalar saves nothing."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, space) if getattr(x, "ndim", 0) >= 1 else x, tree
    )


def infer_opt_state_sharding(optimizer, params, param_sharding, mesh: Mesh):
    """Deterministic shardings for an optax state pytree (the ZeRO
    optimizer-state-sharding analog, reference DeepSpeedPlugin zero stages):
    a state leaf whose tree path ends with a param's path and matches its
    shape inherits that param's sharding (momenta); everything else
    (counts, scalars) is replicated."""
    from ..utils.serialization import flatten_pytree

    shapes = jax.eval_shape(optimizer.init, params)
    param_flat = flatten_pytree(params)
    sharding_flat = flatten_pytree(param_sharding)
    by_path = {path: (tuple(p.shape), sharding_flat[path]) for path, p in param_flat.items()}
    replicated = NamedSharding(mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out_leaves = []
    for path, leaf in flat:
        from ..utils.serialization import _path_str

        pstr = _path_str(path)
        chosen = replicated
        for ppath, (pshape, psharding) in by_path.items():
            if pstr.endswith(ppath) and tuple(leaf.shape) == pshape:
                chosen = psharding
                break
        out_leaves.append(chosen)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def constrain_activation(x, logical_names: tuple, mesh: Optional[Mesh], rules=None):
    """Pin an activation's sharding via logical axis names (no-op without a
    multi-device mesh). Mesh axes that don't divide the actual dim are
    dropped — a batch of 1 at init/eval time must not demand
    fsdp-divisibility."""
    if mesh is None or mesh.size == 1:
        return x
    rules = rules or DEFAULT_AXIS_RULES
    spec = logical_to_spec(logical_names, rules, mesh)
    # under a shard_map (e.g. the compressed-replica train step or LocalSGD),
    # manual axes must not appear in sharding constraints — the body already
    # IS per-shard on those axes
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    parts = []
    for i, dim in enumerate(x.shape):
        entry = spec[i] if i < len(spec) else None
        if entry is None:
            parts.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept, prod = [], 1
        for ax in axes:
            if ax in manual:
                continue
            n = mesh.shape[ax]
            if dim % (prod * n) == 0:
                kept.append(ax)
                prod *= n
        parts.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    if all(p is None for p in parts):
        return x
    if manual:
        # inside the manual region only the non-manual sub-mesh is visible
        from jax.sharding import AbstractMesh  # noqa: F401  (doc pointer)

        return jax.lax.with_sharding_constraint(x, P(*parts))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def batch_spec(mesh: Mesh, extra_sequence_axis: bool = False) -> P:
    axes = tuple(a for a in ("replica", "data", "fsdp") if a in mesh.axis_names)
    if extra_sequence_axis and "sequence" in mesh.axis_names and mesh.shape["sequence"] > 1:
        return P(axes, "sequence")
    return P(axes)


def sharding_of(tree):
    """The shardings of actual arrays in a pytree."""
    return jax.tree_util.tree_map(
        lambda t: t.sharding if isinstance(t, jax.Array) else None, tree
    )


def replicate(tree, mesh: Mesh):
    return jax.device_put(tree, NamedSharding(mesh, P()))
