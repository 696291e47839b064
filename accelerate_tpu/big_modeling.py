"""Big-model inference: load models larger than HBM and run them.

Parity target: /root/reference/src/accelerate/big_modeling.py (633 LoC).
Mechanism swap (SURVEY §7 stage 5):

  reference                         TPU-native
  ---------                         ----------
  meta-device init (monkey-patched  `init_empty_weights` = jax.eval_shape
  register_parameter, :126-167)     over module.init — zero allocation
  infer_auto_device_map over GPUs   greedy fit over HBM/pinned-host/disk
  AlignDevicesHook pre/post forward  XLA streams pinned-host params into
  (D2H/H2D per layer, hooks.py:323)  the jit via in-graph device_put; disk
                                     weights memmap->host per call
  OffloadedWeightsLoader memmap      same design (utils/offload.py)

No wrapper classes, no forward patching: dispatch returns params with
mixed placements and a jitted apply whose transfers the XLA scheduler
overlaps with compute.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .utils.modeling import (
    _DiskWeight,
    _to_pinned_host,
    check_device_map,
    compute_module_sizes,
    get_max_memory,
    infer_auto_device_map,
    load_checkpoint_in_model,
    placement_of,
)
from .utils.serialization import flatten_pytree, unflatten_to_like


def _maybe_enable_weight_streaming(definition, device_map):
    """If the definition supports per-layer weight streaming
    (``config.stream_layer_weights``) and any params land off-device, turn
    the flag on via a rebuilt definition (flax modules are frozen)."""
    import dataclasses as _dc

    cfg = getattr(definition, "config", None)
    if cfg is None or not hasattr(cfg, "stream_layer_weights"):
        return definition
    tiers = set((device_map or {}).values())
    if not (tiers - {"device"}) or cfg.stream_layer_weights:
        return definition
    try:
        new_cfg = _dc.replace(cfg, stream_layer_weights=True)
        return definition.copy(config=new_cfg) if hasattr(definition, "copy") else _dc.replace(definition, config=new_cfg)
    except Exception:  # definition isn't a plain dataclass module
        return definition


def init_empty_weights(module, *sample_args, rng=None, **sample_kwargs):
    """Abstract (zero-allocation) init: the shapes/dtypes of every variable
    without materializing any (reference init_empty_weights:57 needs a
    meta-device monkey-patch; eval_shape is the JAX-native equivalent).

    Returns a pytree of jax.ShapeDtypeStruct."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    fn = functools.partial(module.init, rng, *sample_args, **sample_kwargs)
    abstract = jax.eval_shape(fn)
    # strip flax Partitioned boxes to plain ShapeDtypeStructs
    from .parallel.sharding import unbox_params

    raw, _ = unbox_params(abstract)
    return raw


class DispatchedModel:
    """Callable returned by dispatch_model: runs the module with
    mixed-placement params. Disk weights load per call (matching reference
    disk-offload semantics); host weights stream into HBM inside the jit."""

    def __init__(self, definition, params, mesh=None, device_map=None, output_device=None):
        self.definition = _maybe_enable_weight_streaming(definition, device_map)
        self.params = params
        self.mesh = mesh
        self.device_map = dict(device_map or {})
        # compiled programs and placement transforms keyed by placement
        # state, so materialize()/offload() ping-pong (CpuOffloadHook
        # pipelines) reuses the compile for each tier layout instead of
        # retracing every promote/demote
        self._jits: dict = {}
        self._placers: dict = {}
        # AOT executables from aot_compile(), keyed by (placement, avals):
        # __call__ uses one directly when the call signature matches
        self._aot: dict = {}
        self._aot_hits = 0

    def _placement_key(self):
        return tuple(sorted(self.device_map.items()))

    # sentinel "shardings" for host-tier params:
    _STREAM = "host_stream"      # model streams this subtree itself (per-layer)
    _TO_DEVICE = "host_to_device"  # in-graph transfer at the jit boundary

    def _target_shardings(self, all_device: bool = False):
        """Per-param placement plan.

        Device-tier params get an explicit device/mesh sharding (an in-jit
        device_put). Host-tier ("cpu"/"disk") params either stay in pinned
        host for the model to stream per-layer inside its scan (paths the
        definition declares via ``host_streamable_prefixes()`` — peak HBM is
        then one layer's weights, the per-layer-streaming capability of
        reference hooks.py:323-390), or get an in-graph host->HBM transfer
        that XLA's latency-hiding scheduler places near the consumer."""
        from .parallel.sharding import infer_param_sharding
        from .utils.dataclasses import ShardingConfig
        from .utils.serialization import flatten_pytree, unflatten_to_like

        abstract = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
            self._concrete(self.params),
            is_leaf=lambda l: isinstance(l, _DiskWeight),
        )
        flat = flatten_pytree(abstract)
        if self.mesh is not None:
            device_shardings = flatten_pytree(
                infer_param_sharding(abstract, self.mesh, ShardingConfig())
            )
        else:
            from jax.sharding import SingleDeviceSharding

            from .parallel.sharding import _memory_kind_available

            dev = jax.devices()[0]
            # some backends (older-jax CPU) expose no "device" memory kind;
            # the default placement is then the device memory anyway
            if _memory_kind_available("device"):
                sharding = SingleDeviceSharding(dev, memory_kind="device")
            else:
                sharding = SingleDeviceSharding(dev)
            device_shardings = {k: sharding for k in flat}
        streamable = []
        fn = getattr(self.definition, "host_streamable_prefixes", None)
        if fn is not None:
            streamable = list(fn())
        out = {}
        for path in flat:
            tier = placement_of(path, self.device_map) if self.device_map else "device"
            if all_device or tier == "device":
                out[path] = device_shardings[path]
            elif any(path == p or path.startswith(p + "/") for p in streamable):
                out[path] = self._STREAM
            else:
                out[path] = self._TO_DEVICE
        return unflatten_to_like(out, abstract)

    @staticmethod
    def _concrete(params):
        """Materialize _DiskWeight leaves into (pinned) host memory — not
        HBM; the jit streams them like any other host-tier param."""

        def _mat(leaf):
            if isinstance(leaf, _DiskWeight):
                return _to_pinned_host(leaf.load())
            return leaf

        return jax.tree_util.tree_map(
            _mat, params, is_leaf=lambda l: isinstance(l, _DiskWeight)
        )

    def _apply_for(self, key):
        """(apply, jitted) for the current placement key, built once."""
        if key not in self._jits:
            from .accelerator import _merge_static_call

            placer = self.param_placer()

            def apply(p, a, kw, s_args, s_kw):
                a, kw = _merge_static_call(a, kw, s_args, s_kw)
                return self.definition.apply({"params": placer(p)}, *a, **kw)

            self._jits[key] = (apply, jax.jit(apply, static_argnums=(3, 4)))
        return self._jits[key]

    @staticmethod
    def _aval_key(tree):
        # jnp.shape/result_type, not .shape/.dtype: traced leaves may be
        # Python scalars (ints/floats pass _split_static_call as traced)
        return tuple(
            (jnp.shape(l), str(jnp.result_type(l)))
            for l in jax.tree_util.tree_leaves(tree)
        )

    def _abstract_params(self):
        """ShapeDtypeStructs mirroring what ``_concrete(self.params)`` will
        be at call time: device-tier leaves carry the loader's mesh sharding
        (or stay uncommitted = default device single-chip), host/disk-tier
        leaves are committed to pinned host. Matching the real placements is
        what lets __call__ use the AOT executable instead of retracing."""
        from jax.sharding import SingleDeviceSharding

        flat = flatten_pytree(self.params)
        pinned = None
        dev = jax.local_devices()[0]
        try:
            if any(m.kind == "pinned_host" for m in dev.addressable_memories()):
                pinned = SingleDeviceSharding(dev, memory_kind="pinned_host")
        except Exception:  # pragma: no cover
            pinned = None
        mesh_shardings = None
        if self.mesh is not None:
            from .parallel.sharding import infer_param_sharding
            from .utils.dataclasses import ShardingConfig

            abstract = {
                p: jax.ShapeDtypeStruct(tuple(l.shape), l.dtype) for p, l in flat.items()
            }
            mesh_shardings = flatten_pytree(
                infer_param_sharding(
                    unflatten_to_like(abstract, self.params), self.mesh, ShardingConfig()
                )
            )
        out = {}
        for path, leaf in flat.items():
            tier = placement_of(path, self.device_map) if self.device_map else "device"
            shape, dtype = tuple(leaf.shape), leaf.dtype
            if tier == "device" and mesh_shardings is not None:
                out[path] = jax.ShapeDtypeStruct(shape, dtype, sharding=mesh_shardings[path])
            elif tier == "device" or pinned is None:
                out[path] = jax.ShapeDtypeStruct(shape, dtype)
            else:
                out[path] = jax.ShapeDtypeStruct(shape, dtype, sharding=pinned)
        return unflatten_to_like(out, self.params)

    def _export_cache_path(self, key, aval_key, static_args, static_kw, abstract):
        """Disk path for the serialized jax.export artifact of this AOT
        program, or None when the persistent cache is disabled. The key
        hashes everything the traced program depends on: model definition
        (flax repr includes the config), placements, param avals+shardings,
        call avals, statics, and the jax version."""
        import hashlib

        from .utils.compile_cache import ensure_persistent_compile_cache

        base = ensure_persistent_compile_cache()
        if base is None:
            return None
        from . import __version__ as att_version

        mat = repr((
            jax.__version__,
            # package version: param_placer/dequantize logic is baked into
            # the traced program, so an upgrade must invalidate artifacts
            att_version,
            repr(self.definition),
            key,
            aval_key,
            static_args,
            sorted(static_kw.items()) if isinstance(static_kw, dict) else static_kw,
            [
                (p, str(l.shape), str(l.dtype), str(getattr(l, "sharding", None)))
                for p, l in sorted(flatten_pytree(abstract).items())
            ],
        ))
        h = hashlib.sha256(mat.encode()).hexdigest()[:32]
        d = os.path.join(base, "exports")
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"dispatch-{h}.jaxexport")

    def aot_compile(self, *args, **kwargs):
        """Ahead-of-time compile the placed apply for these example args
        (shapes/dtypes only — values ignored). Runs in the calling thread, so
        ``load_checkpoint_and_dispatch`` overlaps it with checkpoint
        streaming; with the persistent compile cache on, the executable also
        serves every later process. Returns self.

        Two-level persistence: the XLA cache skips backend compilation, and a
        ``jax.export`` artifact on disk skips the Python TRACE of the model —
        which is the part a fresh process otherwise pays ~2 s of sole-core
        CPU for during dispatch. A cache-hit process deserializes StableHLO
        and compiles it (hitting the XLA cache), never running model code."""
        from .accelerator import _split_static_call

        traced_args, static_args, traced_kw, static_kw = _split_static_call(args, kwargs)
        key = self._placement_key()
        abstract = self._abstract_params()
        to_aval = lambda t: jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(jnp.shape(l), jnp.result_type(l)), t
        )
        a_args, a_kw = to_aval(traced_args), to_aval(traced_kw)
        aot_key = (key, self._aval_key((a_args, a_kw)), static_args, static_kw)
        cache_path = self._export_cache_path(
            key, aot_key[1], static_args, static_kw, abstract
        )

        compiled = None
        if cache_path is not None and os.path.exists(cache_path):
            try:
                from jax import export as jax_export

                with open(cache_path, "rb") as f:
                    exp = jax_export.deserialize(bytearray(f.read()))
                # cache the COMPILED AOT object (XLA-cache-served), not the
                # jit wrapper: a wrapper would re-trace on first __call__ and
                # silently recompile on placement drift instead of raising
                # into the documented jit fallback
                compiled = jax.jit(exp.call).lower(abstract, a_args, a_kw).compile()
            except Exception:  # stale/incompatible artifact — retrace below
                compiled = None
        if compiled is None and cache_path is not None:
            # trace ONCE through export: serialize for future processes, and
            # compile this process's executable from the same StableHLO
            try:
                from jax import export as jax_export

                def _bound(p, a, kw):
                    apply, _ = self._apply_for(key)
                    return apply(p, a, kw, static_args, static_kw)

                exp = jax_export.export(jax.jit(_bound))(abstract, a_args, a_kw)
                tmp = cache_path + f".tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(exp.serialize())
                os.replace(tmp, cache_path)
                compiled = jax.jit(exp.call).lower(abstract, a_args, a_kw).compile()
            except Exception:  # best-effort: export has feature gaps
                compiled = None
        if compiled is None:
            _, jitted = self._apply_for(key)
            compiled = jitted.lower(abstract, a_args, a_kw, static_args, static_kw).compile()
        # params avals are excluded from the key: they are determined by the
        # placement key, and walking every param leaf per call would put
        # O(num_params) Python work on the dispatch hot path; a placement
        # drift surfaces as TypeError/ValueError and falls back to jit
        self._aot[aot_key] = compiled
        from .telemetry import current_session

        session = current_session()
        if session is not None and getattr(session, "costs", None) is not None:
            session.costs.capture("dispatch_forward", compiled)
        return self

    def __call__(self, *args, **kwargs):
        # bool/str/None inputs go in as jit statics (Python control flow in
        # flax modules); same partition the TrainEngine uses.
        from .accelerator import _split_static_call

        params = self._concrete(self.params)
        traced_args, static_args, traced_kw, static_kw = _split_static_call(args, kwargs)
        key = self._placement_key()
        apply, jitted = self._apply_for(key)
        try:
            hash((static_args, static_kw))
        except TypeError:
            return apply(params, traced_args, traced_kw, static_args, static_kw)
        aot = None
        if self._aot:  # skip the key build entirely for non-AOT users
            aot = self._aot.get((key, self._aval_key((traced_args, traced_kw)),
                                 static_args, static_kw))
        if aot is not None:
            try:
                out = aot(params, traced_args, traced_kw)
                self._aot_hits += 1
                return out
            except (TypeError, ValueError):  # placement drifted from the AOT avals
                pass
        from .telemetry import forensics

        # the jit fallback is where AOT misses silently recompile — the
        # classic "dispatch was fast once, slow forever after a reshape"
        forensics.note_call(
            "dispatch_forward",
            {"args": traced_args, "kwargs": traced_kw,
             "statics": (static_args, static_kw)},
        )
        return jitted(params, traced_args, traced_kw, static_args, static_kw)

    def param_placer(self):
        """In-graph placement transform used by this model's jit (and by
        generation): device-tier leaves pin to their sharding, non-streamable
        host leaves transfer at the jit boundary, streamable subtrees stay in
        pinned host for the model's per-layer streaming, and quantized
        weights dequantize in-graph (fused into consumers).

        Cached per placement state so repeat calls (and generation's jitted
        loops, which key on placer identity) reuse compiled programs until
        the device_map actually changes."""
        from .utils.quantization import dequantize_params

        key = self._placement_key()
        cached = self._placers.get(key)
        if cached is not None:
            return cached

        shardings = self._target_shardings()
        stream = self._STREAM

        def _place(leaf, sh):
            if isinstance(sh, str):
                if sh == stream:
                    return leaf
                return jax.device_put(leaf, jax.memory.Space.Device)
            return jax.device_put(leaf, sh)

        def placer(p):
            p = jax.tree_util.tree_map(_place, p, shardings)
            return dequantize_params(p)

        self._placers[key] = placer
        return placer

    def materialize(self):
        """Force all params into device memory (drops offload tiers).
        No-op when already fully on device — a hooked pipeline calls this
        every forward; the compiled program for each placement state is
        cached (``_jits``/``_placers``), so ping-ponging between tiers does
        not retrace."""
        if self.device_map == {"": "device"}:
            return self
        params = self._concrete(self.params)
        shardings = self._target_shardings(all_device=True)
        params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        self.params = params
        self.device_map = {"": "device"}
        return self

    def offload(self):
        """Demote every param back to pinned host memory (the inverse of
        materialize; the CpuOffloadHook mechanism below relies on it)."""
        if self.device_map == {"": "cpu"}:
            return self
        params = self._concrete(self.params)
        self.params = jax.tree_util.tree_map(
            lambda p: _to_pinned_host(np.asarray(jax.device_get(p))), params
        )
        self.device_map = {"": "cpu"}
        return self


def dispatch_model(
    definition,
    params,
    device_map: Mapping[str, str],
    mesh=None,
    offload_folder: Optional[str] = None,
) -> DispatchedModel:
    """Place concrete params per ``device_map`` and return a runnable
    (reference dispatch_model:306). Params already on the right tier are
    left alone."""
    from .utils.modeling import _to_pinned_host
    from .utils.offload import offload_state_dict

    check_device_map(params, device_map)
    flat = flatten_pytree(params)
    disk_dict = {}
    out = {}
    for path, leaf in flat.items():
        tier = placement_of(path, device_map)
        if isinstance(leaf, _DiskWeight):
            out[path] = leaf  # already offloaded
            continue
        if tier == "device":
            out[path] = leaf  # device placement happens in the jit
        elif tier == "cpu":
            out[path] = _to_pinned_host(np.asarray(leaf))
        else:
            name = path.replace("/", ".")
            value = np.asarray(leaf)
            disk_dict[name] = value
            out[path] = _DiskWeight(name, offload_folder, tuple(value.shape), value.dtype)
    if disk_dict:
        if offload_folder is None:
            raise ValueError("device_map places weights on disk but no offload_folder given")
        offload_state_dict(offload_folder, disk_dict)
    placed = unflatten_to_like(out, params)
    return DispatchedModel(definition, placed, mesh=mesh, device_map=device_map)


def cpu_offload(definition, params, mesh=None) -> DispatchedModel:
    """Everything in pinned host RAM, streamed per call (reference :170)."""
    return dispatch_model(definition, params, {"": "cpu"}, mesh=mesh)


def disk_offload(definition, params, offload_folder: str, mesh=None) -> DispatchedModel:
    """Everything on disk (reference :260)."""
    return dispatch_model(definition, params, {"": "disk"}, mesh=mesh, offload_folder=offload_folder)


class CpuOffloadHook:
    """Handle returned by cpu_offload_with_hook: lets pipelines of models
    share HBM by explicitly demoting a model when the next one runs
    (reference UserCpuOffloadHook, big_modeling.py:199-258)."""

    def __init__(self, model: DispatchedModel, prev_hook: "CpuOffloadHook | None" = None):
        self.model = model
        self.prev_hook = prev_hook

    def pre_forward(self):
        if self.prev_hook is not None:
            self.prev_hook.offload()
        self.model.materialize()

    def offload(self):
        self.model.offload()


class _HookedModel:
    """Wraps a DispatchedModel so each call promotes this model's weights
    (and demotes the previous pipeline stage's) before running."""

    def __init__(self, model: DispatchedModel, hook: CpuOffloadHook):
        self._model = model
        self.hook = hook

    def __call__(self, *args, **kwargs):
        self.hook.pre_forward()
        return self._model(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._model, name)


def load_and_quantize_model(
    definition,
    weights,
    quantization_config,
    device_map: Any = None,
    offload_folder: Optional[str] = None,
    mesh=None,
) -> DispatchedModel:
    """Quantize a model's weights to int8/int4 and return a runnable
    (reference utils/bnb.py:44 load_and_quantize_model). ``weights`` is a
    params pytree or a checkpoint path; quantized tensors live on device in
    their packed form and dequantize in-graph per call."""
    from .utils.quantization import quantize_params
    from .utils.serialization import load_flat_dict, unflatten_to_like

    if isinstance(weights, str) or hasattr(weights, "__fspath__"):
        flat = load_flat_dict(str(weights))
        params = {k: jnp.asarray(v) for k, v in flat.items()}
        # checkpoint keys are flat paths; rebuild nesting
        nested: dict = {}
        for key, val in params.items():
            node = nested
            parts = key.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = val
        params = nested
    else:
        params = weights
    qparams = quantize_params(params, quantization_config)
    dm = device_map if isinstance(device_map, dict) else {"": "device"}
    return dispatch_model(definition, qparams, dm, mesh=mesh, offload_folder=offload_folder)


def cpu_offload_with_hook(definition, params, mesh=None, prev_module_hook: CpuOffloadHook | None = None):
    """Keep the model in pinned host RAM; promote it to HBM on call and give
    the caller a hook to demote it again (reference cpu_offload_with_hook:199:
    the pipeline pattern — running stage N+1 offloads stage N). Returns
    ``(model, hook)``."""
    dispatched = cpu_offload(definition, params, mesh=mesh)
    hook = CpuOffloadHook(dispatched, prev_hook=prev_module_hook)
    return _HookedModel(dispatched, hook), hook


def load_checkpoint_and_dispatch(
    definition,
    checkpoint: str,
    *sample_args,
    device_map: Any = "auto",
    max_memory: Optional[dict] = None,
    offload_folder: Optional[str] = None,
    dtype=None,
    mesh=None,
    rng=None,
    precompile: bool = True,
    quantization_config=None,
    **sample_kwargs,
) -> DispatchedModel:
    """Abstract-init -> auto device map -> stream checkpoint weights straight
    to their tier (reference load_checkpoint_and_dispatch:504; device-bound
    weights never make a full-model host copy).

    With ``precompile`` (default), the forward program for ``sample_args`` is
    XLA-compiled on a background thread *while* the checkpoint streams from
    disk to its tiers — compile time hides under I/O instead of adding to
    time-to-first-token, and the persistent compile cache makes it a one-time
    cost across processes.

    With ``quantization_config`` (the reference's from_pretrained
    load_in_8bit integration), eligible weights quantize ON THE HOST as they
    stream off disk, so only packed int8/int4 bytes + scales cross the
    host->device link and HBM holds the packed form; dequant fuses into the
    consuming matmuls in-graph."""
    from .utils.compile_cache import ensure_persistent_compile_cache

    ensure_persistent_compile_cache()
    abstract = init_empty_weights(definition, *sample_args, rng=rng, **sample_kwargs)
    abstract_params = abstract["params"] if isinstance(abstract, dict) and "params" in abstract else abstract
    if isinstance(device_map, str):
        if device_map in ("auto", "balanced", "balanced_low_0", "sequential"):
            budget_tree = abstract_params
            if quantization_config is not None:
                # budget with PACKED sizes so quantization actually helps a
                # model FIT (the load_in_8bit purpose): QuantizedWeight
                # nodes flatten to their int8 data + scale leaves, which is
                # exactly the bytes that will occupy HBM
                from .utils.quantization import quantize_abstract_tree

                budget_tree = quantize_abstract_tree(abstract_params, quantization_config)
            device_map = infer_auto_device_map(
                budget_tree,
                max_memory=max_memory,
                # a global dtype override would mis-scale the int8 leaves
                dtype=None if quantization_config is not None else dtype,
                mode=device_map,
            )
        else:
            device_map = {"": device_map}

    model = None
    compile_thread = None
    compile_err: list = []
    if precompile and sample_args:
        # the dispatched apply's input avals depend only on shapes/placements,
        # both known before any weight bytes move — compile concurrently.
        # Dtypes come from the checkpoint HEADER (a bf16 checkpoint loads as
        # bf16 regardless of the model's init dtype), with the explicit
        # ``dtype`` override applied the same way the loader applies it.
        from .utils.quantization import quantize_abstract_tree
        from .utils.serialization import peek_flat_structs

        peeked = peek_flat_structs(checkpoint) or {}

        def _header_dtype(path, leaf):
            out_dtype = peeked.get(path, leaf).dtype
            if dtype is not None and jnp.issubdtype(out_dtype, jnp.floating):
                out_dtype = dtype
            return out_dtype

        cast_abstract = quantize_abstract_tree(
            abstract_params,
            quantization_config,
            placement=lambda p: placement_of(p, device_map) == "device",
            leaf_dtype=_header_dtype,
        )
        model = DispatchedModel(definition, cast_abstract, mesh=mesh, device_map=device_map)
        import threading

        def _compile():
            try:
                model.aot_compile(*sample_args, **sample_kwargs)
            except Exception as e:  # pragma: no cover - AOT is best-effort
                compile_err.append(e)

        def _timed_compile():
            from .utils.phases import phase

            with phase("aot_compile_thread"):
                _compile()

        compile_thread = threading.Thread(target=_timed_compile, daemon=True)
        compile_thread.start()

    from .utils.phases import phase

    with phase("weight_stream_total"):
        params = load_checkpoint_in_model(
            abstract_params,
            checkpoint,
            device_map=device_map,
            offload_folder=offload_folder,
            dtype=dtype,
            mesh=mesh,
            quantization_config=quantization_config,
        )
    if compile_thread is not None:
        with phase("aot_join_wait"):
            compile_thread.join()
    if model is not None and not compile_err:
        model.params = params
        return model
    return DispatchedModel(definition, params, mesh=mesh, device_map=device_map)
