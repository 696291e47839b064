"""Device-map machinery for big-model inference.

Parity target: /root/reference/src/accelerate/utils/modeling.py (1,945 LoC).
The torch version juggles per-GPU budgets and meta-device re-materialization;
on TPU the placement targets are three memory tiers —

  "device"  HBM, sharded over the mesh (GSPMD decides per-chip placement)
  "cpu"     pinned host RAM (XLA memory_kind="pinned_host", streams to HBM)
  "disk"    numpy memmap folder (utils/offload.py), loaded lazily

— and "auto" mapping is a greedy first-fit of module groups into those tiers
(reference infer_auto_device_map:1168), at the granularity of top-level
param-tree prefixes (the module-tree analog).
"""

from __future__ import annotations

import os
import re
from typing import Any, Mapping, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .serialization import flatten_pytree, load_flat_dict, unflatten_to_like

# HBM per chip by device kind (bytes) — used when memory_stats() is absent
# (the CPU simulator reports none).
HBM_BY_KIND = {
    "tpu v2": 8 << 30,
    "tpu v3": 16 << 30,
    "tpu v4": 32 << 30,
    "tpu v5 lite": 16 << 30,
    "tpu v5": 95 << 30,
    "tpu v6 lite": 32 << 30,
    "cpu": 8 << 30,
}


def dtype_byte_size(dtype) -> float:
    """Bytes per element (reference modeling.py:137 handles sub-byte)."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.dtype(bool):
        return 1.0 / 8
    m = re.search(r"(\d+)$", dtype.name)
    if m is None:
        raise ValueError(f"dtype without bit-width: {dtype}")
    return int(m.group(1)) / 8


def named_parameters(params) -> dict[str, Any]:
    """Flat {'a/b/c': leaf} view of a params pytree."""
    return flatten_pytree(params)


def compute_module_sizes(
    params, dtype=None, prefix_depth: Optional[int] = None
) -> dict[str, int]:
    """Bytes per module prefix, every ancestor counted (reference
    compute_module_sizes:776: sizes[''] is the total).

    Works on real arrays or ShapeDtypeStructs (abstract init)."""
    sizes: dict[str, int] = {}
    for path, leaf in flatten_pytree(params).items():
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        bytes_ = int(size * dtype_byte_size(dtype or leaf.dtype))
        parts = path.split("/")
        for i in range(len(parts) + 1):
            prefix = "/".join(parts[:i])
            sizes[prefix] = sizes.get(prefix, 0) + bytes_
    return sizes


def get_max_memory(max_memory: Optional[dict] = None) -> dict[str, int]:
    """{"device": HBM bytes across local chips, "cpu": host bytes, "disk": inf}
    (reference get_max_memory:869 probes each GPU and scales by 0.9)."""
    if max_memory is not None:
        return dict(max_memory)
    out = {}
    hbm = 0
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            pass
        if stats and stats.get("bytes_limit"):
            hbm += int(stats["bytes_limit"])
        else:
            kind = getattr(d, "device_kind", "cpu").lower()
            match = max(
                (k for k in HBM_BY_KIND if k in kind), key=len, default="cpu"
            )
            hbm += HBM_BY_KIND[match]
    out["device"] = int(hbm * 0.9)  # reference's 0.9 headroom factor
    try:
        host = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError):  # pragma: no cover
        host = 16 << 30
    out["cpu"] = int(host * 0.9)
    out["disk"] = 1 << 62
    return out


def find_tied_parameters(params) -> list[list[str]]:
    """Groups of paths sharing one underlying array (reference
    find_tied_parameters:677 identity-compares). JAX params are usually
    functionally pure so ties are by object identity (e.g. the same ndarray
    passed for embedding and lm_head)."""
    by_id: dict[int, list[str]] = {}
    for path, leaf in flatten_pytree(params).items():
        by_id.setdefault(id(leaf), []).append(path)
    return [paths for paths in by_id.values() if len(paths) > 1]


def _module_groups(params, split_depth: int = 1) -> list[str]:
    """Top-level placement units: unique path prefixes at ``split_depth``
    (scanned layer stacks count as ONE group — they are a single stacked
    array, the module-tree analog of a no-split block)."""
    groups = []
    seen = set()
    for path in flatten_pytree(params):
        parts = path.split("/")
        prefix = "/".join(parts[: min(split_depth, len(parts))])
        if prefix not in seen:
            seen.add(prefix)
            groups.append(prefix)
    return groups


def get_balanced_memory(
    params,
    max_memory: Optional[dict] = None,
    dtype=None,
    low_zero: bool = False,
) -> dict[str, int]:
    """Tier budgets for balanced placement (reference get_balanced_memory:1023).

    The torch version caps each GPU's budget so layers spread across all
    GPUs instead of filling gpu0. On TPU, per-chip balance of the "device"
    tier is GSPMD's job (device-tier params shard over the mesh), so the
    balancing that remains meaningful is *activation headroom*: reserve room
    in HBM for the working set so dispatch doesn't pack weights wall-to-wall.

    ``low_zero`` is the balanced_low_0 analog (reference :590: keep gpu0
    nearly free for the generate loop): it halves the device budget so the
    KV cache / decode buffers always fit.
    """
    budgets = get_max_memory(max_memory)
    sizes = compute_module_sizes(params, dtype=dtype)
    leaves = [sizes.get(g, 0) for g in _module_groups(params, split_depth=1)]
    largest = max(leaves) if leaves else 0
    out = dict(budgets)
    if low_zero:
        out["device"] = int(budgets["device"] * 0.5)
    else:
        out["device"] = int(budgets["device"]) - largest // 2
    return out


def _child_groups(all_paths: list[str], prefix: str) -> list[str]:
    """Next-depth prefixes strictly under ``prefix`` (split-on-overflow
    units, reference infer_auto_device_map:1261-1337)."""
    depth = len(prefix.split("/")) if prefix else 0
    children, seen = [], set()
    for path in all_paths:
        if prefix and not (path == prefix or path.startswith(prefix + "/")):
            continue
        parts = path.split("/")
        if len(parts) <= depth:
            continue
        child = "/".join(parts[: depth + 1])
        if child not in seen:
            seen.add(child)
            children.append(child)
    return children


def infer_auto_device_map(
    params,
    max_memory: Optional[dict] = None,
    no_split_module_classes=None,  # parity arg; groups never split further
    dtype=None,
    split_depth: int = 1,
    reserve_largest: bool = True,
    mode: str = "auto",
) -> dict[str, str]:
    """Fit module groups into device -> cpu -> disk in module order
    (reference infer_auto_device_map:1168).

    - The tier pointer only advances (reference's current_device): once a
      group spills to "cpu", later groups never jump back to "device" —
      placement follows execution order, which is what lets offloaded
      execution stream tiers sequentially.
    - A group that overflows the current tier is split into its child
      prefixes and re-fit (reference :1261-1337), down to single params.
    - Tied params co-locate with their first-placed partner at zero extra
      cost (reference :1340+).
    - ``mode``: "auto"/"balanced" reserve activation headroom on device;
      "balanced_low_0" halves the device budget (generate-loop headroom);
      "sequential" uses the raw budgets (fill HBM completely, then spill).
    """
    if mode in ("auto", "balanced"):
        budgets = get_balanced_memory(params, max_memory, dtype=dtype) if reserve_largest else get_max_memory(max_memory)
    elif mode == "balanced_low_0":
        budgets = get_balanced_memory(params, max_memory, dtype=dtype, low_zero=True)
    elif mode == "sequential":
        budgets = get_max_memory(max_memory)
    else:
        raise ValueError(f"unknown device-map mode {mode!r}")

    flat = flatten_pytree(params)
    all_paths = list(flat)
    sizes = compute_module_sizes(params, dtype=dtype)

    # tied-param co-location: every tied leaf points at its group leader
    tie_leader: dict[str, str] = {}
    for group in find_tied_parameters(params):
        for path in group[1:]:
            tie_leader[path] = group[0]

    def _leaves_of(prefix: str) -> list[str]:
        return [p for p in all_paths if p == prefix or p.startswith(prefix + "/")]

    device_map: dict[str, str] = {}
    placed_leaves: dict[str, str] = {}  # leaf path -> tier
    remaining = {k: int(v) for k, v in budgets.items()}
    tiers = [t for t in ("device", "cpu", "disk") if t in remaining]

    from collections import deque

    worklist = deque(_module_groups(params, split_depth))
    cur = 0
    while worklist:
        group = worklist.popleft()
        leaves = _leaves_of(group)
        # bytes this group actually adds: tied leaves whose leader is placed
        # ride along for free
        free_riders = [p for p in leaves if tie_leader.get(p) in placed_leaves]
        size = sizes.get(group, 0) - sum(sizes.get(p, 0) for p in free_riders)
        if size <= 0 and free_riders:
            tier = placed_leaves[tie_leader[free_riders[0]]]
            device_map[group] = tier
            for p in leaves:
                placed_leaves[p] = tier
            continue
        placed = False
        while cur < len(tiers):
            tier = tiers[cur]
            if size <= remaining[tier]:
                device_map[group] = tier
                remaining[tier] -= size
                for p in leaves:
                    placed_leaves[p] = tier
                placed = True
                break
            children = _child_groups(all_paths, group)
            # descend through single-child wrapper chains: the lone child is
            # the same bytes as its parent, so the split point that matters
            # is the first level with real fan-out (grandchildren may fit
            # where the wrapper as a whole does not)
            while len(children) == 1:
                children = _child_groups(all_paths, children[0])
            if len(children) > 1 and remaining[tier] > 0:
                # split on overflow: the front children may still fit here
                worklist.extendleft(reversed(children))
                placed = True
                break
            cur += 1  # this tier is exhausted for module-order placement
        if not placed:
            raise ValueError(
                f"module group {group!r} ({size} bytes) does not fit "
                f"any memory tier {remaining}"
            )
    # tied leaves placed on a different tier than their leader ride with the
    # leader: record the explicit leaf entry (longest prefix wins in
    # placement_of)
    for path, leader in tie_leader.items():
        if leader in placed_leaves and placed_leaves.get(path) != placed_leaves[leader]:
            device_map[path] = placed_leaves[leader]
    return device_map


def check_device_map(params, device_map: Mapping[str, str]) -> None:
    """Every param must be covered by exactly one prefix (reference
    check_device_map:1471)."""
    uncovered = []
    for path in flatten_pytree(params):
        hits = [p for p in device_map if path == p or path.startswith(p + "/") or p == ""]
        if not hits:
            uncovered.append(path)
    if uncovered:
        raise ValueError(f"device_map does not cover: {uncovered[:5]}{'...' if len(uncovered) > 5 else ''}")


def placement_of(path: str, device_map: Mapping[str, str]) -> str:
    """Longest-prefix lookup of a param's tier."""
    best, best_len = "device", -1
    for prefix, tier in device_map.items():
        if prefix == "" or path == prefix or path.startswith(prefix + "/"):
            if len(prefix) > best_len:
                best, best_len = tier, len(prefix)
    return best


def load_checkpoint_in_model(
    abstract_params,
    checkpoint: str,
    device_map: Optional[Mapping[str, str]] = None,
    offload_folder: Optional[str] = None,
    dtype=None,
    mesh=None,
    sharding_config=None,
    quantization_config=None,
):
    """Route each checkpoint weight to its tier as it is read (reference
    load_checkpoint_in_model:1683): device weights go straight to their
    mesh sharding (per-shard reads — no full-model host copy), cpu weights
    into pinned host memory, disk weights into the offload folder.

    ``checkpoint`` is a file or directory accepted by serialization.load_flat_dict
    (safetensors single/sharded or pickle). Returns the params pytree with
    mixed placements."""
    from ..parallel.sharding import infer_param_sharding
    from .dataclasses import ShardingConfig
    from .offload import offload_state_dict

    device_map = dict(device_map or {"": "device"})
    flat_abstract = flatten_pytree(abstract_params)
    flat_loaded = load_flat_dict(checkpoint)

    missing = [k for k in flat_abstract if k not in flat_loaded]
    if missing:
        raise ValueError(f"checkpoint {checkpoint} is missing weights: {missing[:5]}")

    shardings = None
    if mesh is not None:
        infer_tree = abstract_params
        if quantization_config is not None:
            # Infer shardings on the PACKED shapes (quantize_abstract), not
            # the fp shapes: int4 halves dim 0, so the fp-inferred spec can
            # pick a now-indivisible dim — and would disagree with
            # DispatchedModel._abstract_params (which infers from the packed
            # leaves), silently defeating the AOT fast path. Eligibility is
            # judged on the dtype the load loop will actually see (checkpoint
            # dtype + cast override), not the model's init dtype — a
            # disagreement would desync the flat keys below. QuantizedWeight
            # flattens to data/scale children, so quantized keys become
            # "<path>/0" (data) and "<path>/1" (scale) — same keys
            # _abstract_params sees.
            from .quantization import quantize_abstract_tree

            def _loaded_dtype(path, leaf):
                dt = jnp.dtype(flat_loaded[path].dtype)
                if dtype is not None and jnp.issubdtype(dt, jnp.floating):
                    dt = jnp.dtype(dtype)
                return dt

            infer_tree = quantize_abstract_tree(
                abstract_params,
                quantization_config,
                placement=lambda p: placement_of(p, device_map) == "device",
                leaf_dtype=_loaded_dtype,
            )
        shardings = flatten_pytree(
            infer_param_sharding(
                infer_tree, mesh, sharding_config or ShardingConfig()
            )
        )

    from .phases import phase

    disk_dict = {}
    out: dict[str, Any] = {}

    # cpu/disk tiers are handled inline (their values must STAY lazy memmap
    # views — disk offload's whole point is not holding those bytes in RAM);
    # device-tier leaves stream through the read -> quantize -> submit
    # pipeline below.
    device_paths: list[str] = []
    for path in flat_abstract:
        tier = placement_of(path, device_map)
        if tier == "device":
            device_paths.append(path)
            continue
        with phase("ckpt_read"):
            value = np.asarray(flat_loaded[path])
            if dtype is not None and jnp.issubdtype(jnp.dtype(value.dtype), jnp.floating):
                value = value.astype(dtype)
        if tier == "cpu":
            out[path] = _to_pinned_host(value)
        else:  # disk
            disk_dict[path.replace("/", ".")] = value
            out[path] = _DiskWeight(
                name=path.replace("/", "."),
                folder=offload_folder,
                shape=tuple(value.shape),
                dtype=value.dtype,
            )

    out.update(
        _stream_device_leaves(
            device_paths, flat_loaded, shardings, dtype, quantization_config,
            phase,
        )
    )
    if disk_dict:
        if offload_folder is None:
            raise ValueError("device_map places weights on disk but no offload_folder given")
        offload_state_dict(offload_folder, disk_dict)
    return unflatten_to_like(out, abstract_params)


# Device-tier placements are BATCHED: one jax.device_put over a list per
# ~64MB chunk instead of one call per leaf. Each device_put carries a
# fixed per-call dispatch cost, and a 150-leaf model was paying it 300
# times; chunking keeps the actual byte flush flowing early while cutting
# the per-call cost ~50x.
_CHUNK_BYTES = 64 << 20
# Read-ahead budget for the streaming pipeline: bytes materialized off the
# checkpoint but not yet handed to jax.device_put. Bounds peak host RAM to
# roughly budget + one flush chunk regardless of model size.
_READAHEAD_BYTES_DEFAULT = 256 << 20


class _ByteGate:
    """Byte-budget backpressure between the pipeline stages (the Python
    mirror of the csrc ring buffer's slots/condvar contract): the reader
    blocks while `outstanding + n` exceeds the budget — but never blocks an
    empty pipeline, so a single leaf larger than the whole budget still
    flows (serially)."""

    def __init__(self, limit: int):
        import threading

        self.limit = int(limit)
        self.outstanding = 0
        self._cv = threading.Condition()

    def acquire(self, n: int):
        with self._cv:
            while self.outstanding > 0 and self.outstanding + n > self.limit:
                self._cv.wait()
            self.outstanding += n

    def release(self, n: int):
        with self._cv:
            self.outstanding -= n
            self._cv.notify_all()


def _stream_device_leaves(device_paths, flat_loaded, shardings, dtype,
                          quantization_config, phase) -> dict:
    """Stream device-tier weights through a 3-stage pipeline so
    ``ckpt_read + host_quantize + transfer_submit`` overlap instead of
    summing (the round-5 phases showed host_quantize fully serial at 2.9 s
    while the csrc thread pool sat idle):

      reader thread     materializes checkpoint bytes (memmap page-in /
                        pread) + applies the dtype cast, one leaf ahead of
                        the quantizers, under the read-ahead byte gate
      quantize pool     packs eligible leaves int8/int4 via the native csrc
                        kernel (the ctypes call releases the GIL, so the
                        ``ATT_DISPATCH_QUANT_THREADS`` workers — default
                        min(4, cores) — really pack in parallel beside the
                        reader and the AOT thread; the round-5 phases
                        showed host_quantize fully serial at 2.9 s on ONE
                        thread while the kernel's pool sat idle). Workers
                        tag results with the reader's sequence number and
                        the caller reorders, so leaf submit order — and
                        therefore the ~64MB chunk grouping and every byte
                        placed — is identical to the serial path
      caller thread     groups results into ~64MB chunks and submits
                        batched async jax.device_put calls — the previous
                        chunk's h2d transfer is in flight while the next
                        chunk reads and quantizes

    Each stage times itself under its own phase name (contended wall — the
    stages run concurrently, so their sum can exceed the dispatch wall;
    that gap IS the measured overlap) and, when a telemetry span recorder
    is armed, emits per-leaf nested spans from its own thread, so the
    Chrome trace shows the three lanes interleaving. The ``transfer_flush``
    phase is measured HERE, per chunk (the stall until the previous
    chunk's async device_put lands, taken right before the next submit),
    so it is pure link wall on the dispatch critical path — not the old
    terminal whole-tree probe that also absorbed AOT-compile overlap.

    ``ATT_SERIAL_DISPATCH=1`` degrades to running the stages inline on the
    caller thread (bit-identical output; the A/B lever for the overlap and
    the bit-exactness test)."""
    import os
    import queue
    import threading

    from .quantization import _eligible, quantize_array_host

    serial = os.environ.get("ATT_SERIAL_DISPATCH", "0").lower() not in ("0", "false", "")
    # explicit-0 is honored (the gate never blocks an empty pipeline, so
    # limit 0 means fully-serial readahead); only unset/empty falls back —
    # `int(...) or default` would silently turn an explicit 0 into 256 MB
    # (the truthy-env-default class the audit host linter flags)
    readahead_mb = os.environ.get("ATT_DISPATCH_READAHEAD_MB")
    readahead = (
        int(float(readahead_mb) * (1 << 20)) if readahead_mb not in (None, "")
        else _READAHEAD_BYTES_DEFAULT
    )

    out: dict[str, Any] = {}
    pending: list = []  # ("plain", path, np_value, sharding|None)
    #                   | ("quant", path, qw_host, {childkey: sharding|None})
    pending_bytes = 0
    gate = _ByteGate(readahead)
    # the previous chunk's device arrays, awaited right before the next
    # chunk's submit (and once at the end of the stream). This measures
    # the link stall PER BATCH, on the dispatch critical path, instead of
    # one terminal whole-tree probe after dispatch returns — which also
    # absorbed the overlapped AOT compile and so reported the 13-22 s
    # "transfer_flush" wall the round-5 bench could neither reproduce nor
    # attribute. Awaiting chunk N before submitting N+1 costs nothing:
    # the link is busy with N's bytes either way.
    prev_placed: list = []

    def _await_prev():
        if not prev_placed:
            return
        with phase("transfer_flush"):
            import time as _time

            for arr in prev_placed:
                ready = getattr(arr, "is_ready", None)
                if ready is None:
                    jax.block_until_ready(arr)
                    continue
                while not ready():
                    _time.sleep(0.001)
        prev_placed.clear()

    def _flush_pending():
        nonlocal pending_bytes
        if not pending:
            return
        _await_prev()
        vals, shards = [], []
        for kind, path, obj, shard in pending:
            if kind == "plain":
                vals.append(obj)
                shards.append(shard)
            else:
                for ck, cv in flatten_pytree(obj).items():
                    vals.append(np.asarray(cv))
                    shards.append(shard[ck] if shard is not None else None)
        if any(s is not None for s in shards):
            placed = jax.device_put(vals, shards)
        else:
            placed = jax.device_put(vals)
        prev_placed.extend(
            a for a in placed if isinstance(a, jax.Array)
        )
        i = 0
        for kind, path, obj, shard in pending:
            if kind == "plain":
                out[path] = placed[i]
                i += 1
            else:
                sub = flatten_pytree(obj)
                placed_sub = {ck: placed[i + j] for j, ck in enumerate(sub)}
                out[path] = unflatten_to_like(placed_sub, obj)
                i += len(sub)
        pending.clear()
        pending_bytes = 0

    def _read_one(path):
        """Stage 1 body: checkpoint bytes -> a RAM-resident, cast ndarray."""
        with phase("ckpt_read"):
            value = np.asarray(flat_loaded[path])
            # jnp.issubdtype, not np: ml_dtypes bf16 is floating too (and the
            # dispatch AOT precompile predicts the cast with the same predicate)
            if dtype is not None and jnp.issubdtype(jnp.dtype(value.dtype), jnp.floating):
                value = value.astype(dtype)
            elif value.base is not None and isinstance(value.base, np.memmap):
                # lift mmap-backed views into RAM here so (a) the phase
                # breakdown attributes the disk read to ckpt_read, not to
                # whatever first touches the pages (the quantize kernel's
                # absmax scan), and (b) the runtime's h2d path cannot fall
                # off its fast path on mmap-backed/unaligned sources.
                value = np.array(value, copy=True)
        return value

    def _quantize_one(path, value):
        """Stage 2 body: (path, ndarray) -> a pending-queue entry."""
        if quantization_config is not None and _eligible(path, value, quantization_config):
            # quantize ON HOST, then ship only packed bytes + scales:
            # 2-4x fewer bytes over the (often link-bound) transfer
            with phase("host_quantize"):
                qw = quantize_array_host(
                    value, bits=quantization_config.bits,
                    group_size=quantization_config.group_size,
                    qtype=quantization_config.quant_type,
                    double_quant=quantization_config.double_quant,
                )
            if shardings is not None:
                # shardings were inferred on the packed shapes; every child
                # (data/scale, incl. nested QuantizedScale under double
                # quant) has its own "<path>/<child>" entry
                child_shards = {
                    k: shardings[f"{path}/{k}"] for k in flatten_pytree(qw)
                }
            else:
                child_shards = None
            return ("quant", path, qw, child_shards)
        return ("plain", path, value,
                shardings[path] if shardings is not None else None)

    def _submit_one(entry, gate_bytes):
        """Stage 3 body (caller thread): chunk-buffer + batched device_put.
        The gate releases on CONSUMPTION (not flush): the budget bounds
        bytes queued between the stages; the pending chunk is separately
        bounded by the ~64MB flush threshold."""
        nonlocal pending_bytes
        gate.release(gate_bytes)
        with phase("transfer_submit"):
            kind, path, obj, shard = entry
            if kind == "quant":
                nbytes = sum(
                    np.asarray(v).nbytes for v in flatten_pytree(obj).values()
                )
            else:
                nbytes = obj.nbytes
            pending.append((kind, path, obj, shard))
            pending_bytes += nbytes
            if pending_bytes >= _CHUNK_BYTES:
                _flush_pending()

    if serial or not device_paths:
        for path in device_paths:
            value = _read_one(path)
            _submit_one(_quantize_one(path, value), 0)
        with phase("transfer_submit"):
            _flush_pending()
        _await_prev()
        return out

    q_read: "queue.Queue" = queue.Queue(maxsize=4)
    q_quant: "queue.Queue" = queue.Queue(maxsize=4)
    errors: list = []
    stop = threading.Event()

    def _put(q, item):
        """Bounded put that aborts when the pipeline is shutting down, so a
        worker can never park forever on a full queue after a later stage
        died (the caller would otherwise only learn of the real error after
        its join timeouts expired)."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _leaf_nbytes(path):
        """Gate charge for one leaf: bytes as they will sit in RAM — the
        cast dtype when ``dtype=`` widens the checkpoint's — so the
        read-ahead budget bounds what the pipeline actually holds."""
        leaf = flat_loaded[path]
        itemsize = np.dtype(leaf.dtype).itemsize
        if dtype is not None and jnp.issubdtype(jnp.dtype(leaf.dtype), jnp.floating):
            itemsize = max(itemsize, jnp.dtype(dtype).itemsize)
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        return n * itemsize

    def _reader():
        try:
            for seq, path in enumerate(device_paths):
                nbytes = _leaf_nbytes(path)
                gate.acquire(nbytes)
                if stop.is_set():
                    gate.release(nbytes)
                    return
                value = _read_one(path)
                if not _put(q_read, (seq, path, value, nbytes)):
                    gate.release(nbytes)
                    return
        except BaseException as e:  # propagate into the caller thread
            errors.append(e)
        finally:
            _put(q_read, None)  # skipped when stopping: shutdown wakes consumers

    # quantize worker pool: the csrc pack kernel releases the GIL, so
    # several leaves really pack concurrently. One worker when nothing
    # quantizes (pass-through entries need no parallelism). Each worker
    # forwards the upstream None so its siblings also drain, then posts
    # its own completion sentinel to the caller.
    if quantization_config is not None:
        # int() BEFORE the fallback: an unset/empty/"0" knob means "use
        # the default pool", and "0" is a truthy *string*
        n_quant = int(os.environ.get("ATT_DISPATCH_QUANT_THREADS") or 0)
        n_quant = max(1, n_quant or min(4, os.cpu_count() or 1))
    else:
        n_quant = 1

    def _quantizer():
        try:
            while True:
                item = q_read.get()
                if item is None:
                    # wake the next worker. Non-blocking on purpose: after
                    # a shutdown drain `_put` would refuse (stop is set)
                    # and strand a sibling on get(); the drained queue
                    # always has room for the sentinel.
                    try:
                        q_read.put_nowait(None)
                    except queue.Full:
                        pass
                    break
                seq, path, value, nbytes = item
                if not _put(q_quant, (seq, _quantize_one(path, value), nbytes)):
                    return
        except BaseException as e:
            errors.append(e)
        finally:
            _put(q_quant, None)  # skipped when stopping: shutdown wakes consumers

    threads = [
        threading.Thread(target=_reader, name="att-dispatch-read", daemon=True),
    ] + [
        threading.Thread(target=_quantizer, name=f"att-dispatch-quantize-{i}",
                         daemon=True)
        for i in range(n_quant)
    ]
    for t in threads:
        t.start()
    try:
        # reorder buffer: workers finish out of order, but the submit
        # order (and so the chunk grouping and the transfer stream) must
        # be byte-identical to the serial path
        buf: dict = {}
        next_seq = 0
        workers_done = 0
        while workers_done < n_quant:
            item = q_quant.get()
            if item is None:
                workers_done += 1
                continue
            seq, entry, nbytes = item
            buf[seq] = (entry, nbytes)
            while next_seq in buf:
                entry, nbytes = buf.pop(next_seq)
                _submit_one(entry, nbytes)
                next_seq += 1
        if not errors:
            assert not buf, f"dispatch pipeline dropped leaves {sorted(buf)}"
            with phase("transfer_submit"):
                _flush_pending()
            _await_prev()
    finally:
        # shut the pipeline down (normal completion: both workers are
        # already done and every signal below is a no-op): stop first so no
        # worker refills, drain so nothing is parked on a full queue, then
        # sentinel so nothing is parked on an empty get()
        stop.set()
        gate.release(gate.limit)  # unblock a reader waiting on the budget
        for q in (q_read, q_quant):
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            try:
                q.put_nowait(None)
            except queue.Full:
                pass
        for t in threads:
            t.join(timeout=60)
    if errors:
        raise errors[0]
    return out


def _to_pinned_host(value: np.ndarray):
    """Place an array in pinned host memory (falls back to device default
    when the backend lacks the memory kind)."""
    from jax.sharding import SingleDeviceSharding

    dev = jax.local_devices()[0]
    try:
        if any(m.kind == "pinned_host" for m in dev.addressable_memories()):
            sharding = SingleDeviceSharding(dev, memory_kind="pinned_host")
            out = jax.device_put(jnp.asarray(value), sharding)
            assert out.sharding.memory_kind == "pinned_host"
            return out
    except Exception:  # pragma: no cover
        pass
    return jnp.asarray(value)


class _DiskWeight:
    """Lazy handle to a memmap-offloaded weight (pytree leaf)."""

    def __init__(self, name: str, folder: str, shape: tuple, dtype):
        self.name = name
        self.folder = folder
        self.shape = shape
        self.dtype = dtype

    def load(self) -> np.ndarray:
        from .offload import load_offload_index, load_offloaded_weight

        info = load_offload_index(self.folder)[self.name]
        return np.asarray(
            load_offloaded_weight(os.path.join(self.folder, f"{self.name}.dat"), info)
        )

    def __repr__(self):
        return f"_DiskWeight({self.name}, shape={self.shape}, dtype={self.dtype})"
