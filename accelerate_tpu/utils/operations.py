"""Collectives & pytree operations.

Parity target: /root/reference/src/accelerate/utils/operations.py (L1 of the
layer map): ``recursively_apply``, ``send_to_device``, ``gather``,
``gather_object``, ``broadcast``, ``broadcast_object_list``, ``reduce``,
``pad_across_processes``, ``slice``/``concatenate``, debug-mode shape
verification (operations.py:368-401).

TPU-native split:
- *outside jit* (this module's public fns): operate on global `jax.Array`s /
  numpy / python objects across hosts via `multihost_utils`. A "gather"
  materializes the full global value on every host.
- *inside jit*: users writing custom steps use :func:`psum` / :func:`pmean` /
  :func:`all_gather_axis` with mesh axis names — thin wrappers over `jax.lax`
  that tolerate being called outside any mapped axis (no-op), mirroring how
  reference collectives no-op when world_size == 1.
"""

from __future__ import annotations

import pickle
from functools import wraps
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class DistributedOperationException(Exception):
    """Raised by debug-mode verification when operand shapes mismatch across
    processes (reference operations.py:359)."""


# ---------------------------------------------------------------------------
# pytree plumbing
# ---------------------------------------------------------------------------

def recursively_apply(func, data, *args, test_type=None, error_on_other_type=False, **kwargs):
    """Apply ``func`` to every leaf (reference operations.py:85). JAX pytrees
    make this trivial; kept for API parity and for the type-gate semantics."""
    if test_type is None:
        test_type = lambda x: isinstance(x, (jax.Array, np.ndarray))

    def _apply(leaf):
        if test_type(leaf):
            return func(leaf, *args, **kwargs)
        if error_on_other_type:
            raise TypeError(f"Unsupported type {type(leaf)} passed to {func.__name__}.")
        return leaf

    return jax.tree_util.tree_map(_apply, data)


def is_array_like(x) -> bool:
    return isinstance(x, (jax.Array, np.ndarray))


def is_tensor_information(x) -> bool:
    return isinstance(x, jax.ShapeDtypeStruct)


def honor_type(obj, generator):
    """Rebuild ``obj``'s container type from ``generator`` (reference :49)."""
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # namedtuple
        return type(obj)(*list(generator))
    return type(obj)(generator)


def initialize_tensors(data_structure):
    """ShapeDtypeStruct skeleton → zero arrays (reference :131)."""
    return jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype) if is_tensor_information(t) else t,
        data_structure,
    )


def get_data_structure(data):
    """Arrays → ShapeDtypeStruct skeleton, for structure broadcast
    (reference :108)."""
    return jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype) if is_array_like(t) else t, data
    )


def get_shape(data):
    return jax.tree_util.tree_map(lambda t: list(t.shape) if is_array_like(t) else t, data)


def find_batch_size(data) -> int | None:
    """dim0 of the first array leaf (reference :263)."""
    leaves = [l for l in jax.tree_util.tree_leaves(data) if is_array_like(l)]
    if not leaves:
        return None
    return leaves[0].shape[0]


def listify(data):
    """Arrays → nested python lists (reference :281)."""
    return recursively_apply(lambda t: np.asarray(jax.device_get(t)).tolist(), data)


# ---------------------------------------------------------------------------
# device placement
# ---------------------------------------------------------------------------

def convert_to_jax(data):
    """torch tensors / lists-of-numbers / numpy → numpy-backed leaves ready
    for device put. Torch stays a supported *input* format (datasets commonly
    yield it); it is converted at the host boundary, never used on device."""

    def _is_leaf(x):
        return (
            isinstance(x, list)
            and len(x) > 0
            and all(isinstance(i, (int, float, bool)) for i in x)
        ) or type(x).__module__.startswith("torch")

    def _convert(x):
        if is_array_like(x):
            return x
        tp = type(x).__module__
        if tp.startswith("torch"):
            return np.asarray(x.detach().cpu().numpy())
        if isinstance(x, list):
            return np.asarray(x)
        return x

    return jax.tree_util.tree_map(_convert, data, is_leaf=_is_leaf)


def send_to_device(data, device_or_sharding, non_blocking: bool = False, skip_keys=None):
    """Move a pytree to a device or NamedSharding (reference :148). JAX
    transfers are always async ("non_blocking" is inherently true)."""
    data = convert_to_jax(data)

    def _put(t):
        return jax.device_put(t, device_or_sharding) if is_array_like(t) else t

    if skip_keys and isinstance(data, Mapping):
        moved = {
            k: (v if k in skip_keys else jax.tree_util.tree_map(_put, v))
            for k, v in data.items()
        }
        return moved if isinstance(data, dict) else type(data)(moved)
    return jax.tree_util.tree_map(_put, data)


def make_global_batch(
    data, mesh: Mesh, batch_axes=("replica", "data", "fsdp"), batch_dim: int = 0
):
    """Per-host local batch → global jax.Array sharded batch-dim over the
    data axes (the TPU-native DataLoaderShard device-placement step;
    replaces reference data_loader.py:566's `.to(device)`).

    Uses `jax.make_array_from_process_local_data` so each host contributes
    only its local shard — no cross-host traffic. ``batch_dim=1`` places a
    stacked [K, batch, ...] multi-step batch (build_train_step's
    steps_per_call): the steps axis is replicated, the batch axis sharded.
    """
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    sharding = NamedSharding(mesh, P(*([None] * batch_dim), batch_axes))
    # leaves too low-rank to carry the batch dim (e.g. a [K] per-step scalar
    # in a stacked multi-step batch) replicate instead of taking a spec
    # whose rank exceeds theirs
    replicated = NamedSharding(mesh, P())
    shard_degree = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    data = convert_to_jax(data)

    def _place(x):
        if not is_array_like(x):
            return x
        x = np.asarray(x)
        if x.ndim <= batch_dim:
            nproc1 = jax.process_count()
            if nproc1 == 1:
                return jax.device_put(x, replicated)
            return jax.make_array_from_process_local_data(replicated, x)
        nproc = jax.process_count()
        global_rows = x.shape[batch_dim] * nproc
        if global_rows % shard_degree != 0:
            raise ValueError(
                f"global batch dimension {global_rows} (= per-process "
                f"{x.shape[batch_dim]} x {nproc} processes) is not divisible by the "
                f"data-sharding degree {shard_degree} (mesh axes {batch_axes}). "
                "Pick a per-process batch size so that batch_size * num_processes "
                "is a multiple of the data/fsdp mesh axes product."
            )
        if nproc == 1:
            return jax.device_put(x, sharding)
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(_place, data)


# ---------------------------------------------------------------------------
# in-jit collectives (mesh-axis wrappers)
# ---------------------------------------------------------------------------

def _axis_is_bound(name) -> bool:
    """True iff ``name`` is a mapped axis in the current trace context
    (``jax.lax.axis_size`` raises NameError on an unbound name)."""
    try:
        jax.lax.axis_size(name)
        return True
    except NameError:
        return False


def _active_axes(axis_names):
    """Filter axis names down to those bound in the current trace context."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    return tuple(a for a in axis_names if _axis_is_bound(a))


def psum(x, axis_names=("replica", "data", "fsdp")):
    axes = _active_axes(axis_names)
    if not axes:
        return x
    return jax.lax.psum(x, axes)


def pmean(x, axis_names=("replica", "data", "fsdp")):
    axes = _active_axes(axis_names)
    if not axes:
        return x
    return jax.lax.pmean(x, axes)


def all_gather_axis(x, axis_name, *, axis=0, tiled=True):
    axes = _active_axes(axis_name)
    if not axes:
        return x
    return jax.lax.all_gather(x, axes[0], axis=axis, tiled=tiled)


# ---------------------------------------------------------------------------
# out-of-jit collectives (host-level, multihost_utils)
# ---------------------------------------------------------------------------

def verify_operation(function):
    """Debug-mode desync detector (reference operations.py:368-401): check
    every rank sees identical leaf shapes before the collective; raise
    DistributedOperationException naming mismatched ranks."""

    @wraps(function)
    def wrapper(*args, **kwargs):
        from ..state import PartialState

        state = PartialState()
        if not state.debug or state.num_processes == 1:
            return function(*args, **kwargs)
        operation = f"accelerate_tpu.utils.operations.{function.__name__}"
        tensor = kwargs.get("tensor", args[0] if args else None)
        shapes = get_shape(tensor)
        all_shapes = gather_object([shapes])
        if not all(s == all_shapes[0] for s in all_shapes):
            ranks = [i for i, s in enumerate(all_shapes) if s != all_shapes[0]]
            raise DistributedOperationException(
                f"Cannot apply desired operation due to shape mismatches. All shapes "
                f"across devices must be valid.\n\nOperation: `{operation}`\nInput "
                f"shapes:\n  - Process 0: {all_shapes[0]}\n  - Mismatched: {ranks}"
            )
        return function(*args, **kwargs)

    return wrapper


def _fully_replicate(t):
    """Make a (possibly host-sharded) global array fully addressable."""
    from jax.experimental import multihost_utils

    if isinstance(t, jax.Array):
        if t.is_fully_addressable:
            return t
        return multihost_utils.process_allgather(t, tiled=True)
    return t


@verify_operation
def gather(tensor):
    """Gather dim0 across the distributed data dimension (reference :423).

    Semantics on TPU:
    - a *global* `jax.Array` (produced inside the framework, possibly not
      fully addressable on this host) → the fully-materialized global value
      on every host;
    - a host-local array (numpy, or a single-device jax.Array created by this
      process) → reference semantics: every process's value concatenated on
      dim0 (process_allgather tiled);
    - a fully-addressable *multi-device* jax.Array is already global →
      returned as-is.
    """
    from ..state import PartialState

    state = PartialState()
    if state.num_processes == 1:
        return recursively_apply(lambda t: t, tensor)
    from jax.experimental import multihost_utils

    def _gather_one(t):
        if isinstance(t, jax.Array):
            if not t.is_fully_addressable:
                return multihost_utils.process_allgather(t, tiled=True)
            if len(t.devices()) > 1:
                return t  # already a global (replicated/sharded-local) array
        return multihost_utils.process_allgather(np.asarray(t), tiled=True)

    return recursively_apply(_gather_one, tensor)


def gather_object(object: Any):
    """Gather arbitrary picklables from all processes into a list
    (reference :449). Implemented as a byte-tensor allgather over hosts."""
    from ..state import PartialState

    state = PartialState()
    if state.num_processes == 1:
        return [object] if not isinstance(object, list) else object
    from jax.experimental import multihost_utils

    payload = pickle.dumps(object)
    n = np.zeros((state.num_processes,), np.int64)
    n[state.process_index] = len(payload)
    sizes = multihost_utils.process_allgather(n)
    sizes = np.max(sizes.reshape(state.num_processes, -1), axis=-1)
    maxlen = int(sizes.max())
    buf = np.zeros((state.num_processes, maxlen), np.uint8)
    buf[state.process_index, : len(payload)] = np.frombuffer(payload, np.uint8)
    allbuf = multihost_utils.process_allgather(buf)
    allbuf = allbuf.reshape(state.num_processes, state.num_processes, maxlen)
    out = []
    for i in range(state.num_processes):
        raw = allbuf[i, i, : int(sizes[i])].tobytes()
        obj = pickle.loads(raw)
        if isinstance(object, list):
            out.extend(obj)
        else:
            out.append(obj)
    return out


@verify_operation
def broadcast(tensor, from_process: int = 0):
    """Broadcast pytree of arrays from one process (reference :543)."""
    from ..state import PartialState

    state = PartialState()
    if state.num_processes == 1:
        return tensor
    from jax.experimental import multihost_utils

    return recursively_apply(
        lambda t: multihost_utils.broadcast_one_to_all(
            t, is_source=state.process_index == from_process
        ),
        tensor,
    )


def broadcast_object_list(object_list, from_process: int = 0):
    """Broadcast picklables (reference :564) — used to ship batch *structure*
    before tensors (data_loader dispatch mode)."""
    from ..state import PartialState

    state = PartialState()
    if state.num_processes == 1:
        return object_list
    gathered = gather_object([object_list])
    src = gathered[from_process]
    for i in range(len(object_list)):
        object_list[i] = src[i]
    return object_list


@verify_operation
def reduce(tensor, reduction: str = "mean", scale: float = 1.0):
    """Sum/mean a pytree across the data-parallel dimension (reference :725).

    Arrays here are global: per-host values are summed across processes; for
    fully-addressable single-process arrays this is the identity (matching
    reference behavior at world_size 1).
    """
    from ..state import PartialState

    state = PartialState()

    def _reduce_one(t):
        if state.num_processes > 1:
            from jax.experimental import multihost_utils

            stacked = multihost_utils.process_allgather(t)
            t = jnp.sum(stacked, axis=0)
            if reduction == "mean":
                t = t / state.num_processes
        return t * scale

    return recursively_apply(_reduce_one, tensor)


@verify_operation
def pad_across_processes(tensor, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
    """Pad each process's arrays to the max size along ``dim`` (reference
    :632) so a subsequent gather is rectangular."""
    from ..state import PartialState

    state = PartialState()

    def _pad_one(t):
        if dim >= t.ndim:
            return t
        size = np.asarray(t.shape)
        if state.num_processes > 1:
            from jax.experimental import multihost_utils

            sizes = multihost_utils.process_allgather(size)
            max_size = int(np.max(sizes.reshape(state.num_processes, -1)[:, dim]))
        else:
            max_size = int(size[dim])
        if max_size == t.shape[dim]:
            return t
        pad_width = [(0, 0)] * t.ndim
        pad_width[dim] = (max_size - t.shape[dim], 0) if pad_first else (0, max_size - t.shape[dim])
        return jnp.pad(t, pad_width, constant_values=pad_index)

    return recursively_apply(_pad_one, tensor)


def pad_input_tensors(tensor, batch_size: int, num_processes: int, dim: int = 0):
    """Pad dim0 so it divides evenly across processes (reference :686)."""
    remainder = batch_size % num_processes
    if remainder == 0:
        return tensor
    missing = num_processes - remainder

    def _pad_one(t):
        if t.shape[0] != batch_size:
            return t
        reps = jnp.concatenate([t] + [t[-1:]] * missing, axis=0)
        return reps

    return recursively_apply(_pad_one, tensor)


# ---------------------------------------------------------------------------
# slicing / concat (reference :585-625)
# ---------------------------------------------------------------------------

def slice_tensors(data, tensor_slice, process_index=None, num_processes=None):
    return recursively_apply(lambda t: t[tensor_slice], data)


def concatenate(data, dim: int = 0):
    """Concatenate a list of same-structure pytrees leafwise (reference :613)."""
    if isinstance(data[0], (tuple, list)):
        return honor_type(data[0], (concatenate([d[i] for d in data], dim=dim) for i in range(len(data[0]))))
    if isinstance(data[0], Mapping):
        return type(data[0])({k: concatenate([d[k] for d in data], dim=dim) for k in data[0].keys()})
    if not is_array_like(data[0]):
        raise TypeError(f"Can only concatenate arrays but got {type(data[0])}")
    return jnp.concatenate(data, axis=dim)


def drop_padding(tensor, num_real: int):
    """Slice dim0 to the first ``num_real`` rows — gather_for_metrics dedup."""
    return recursively_apply(lambda t: t[:num_real], tensor)


def convert_outputs_to_fp32(function):
    """Wrap a fn so float16/bfloat16 array outputs are upcast to fp32
    (reference :766-826)."""

    @wraps(function)
    def wrapper(*args, **kwargs):
        return convert_to_fp32(function(*args, **kwargs))

    return wrapper


def convert_to_fp32(tensor):
    def _is_half(t):
        return is_array_like(t) and t.dtype in (jnp.float16, jnp.bfloat16)

    return recursively_apply(lambda t: t.astype(jnp.float32), tensor, test_type=_is_half)


def find_device(data):
    """First device found in a pytree (reference :827)."""
    for leaf in jax.tree_util.tree_leaves(data):
        if isinstance(leaf, jax.Array):
            return list(leaf.devices())[0]
    return None
