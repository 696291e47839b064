"""Weight-only int8/int4 quantization (the bitsandbytes analog).

Parity target: /root/reference/src/accelerate/utils/bnb.py:44
(`load_and_quantize_model` + BnbQuantizationConfig). The torch version swaps
Linear modules for bnb kernels; the TPU-native design quantizes the param
*pytree* instead — a ``QuantizedWeight`` node (int8 data / packed int4
nibbles + per-group fp32 scales) is a registered pytree, so it flows through
jit, device placement, and serialization untouched, and the dispatch layer
dequantizes in-graph right before apply. XLA fuses the
``data.astype(bf16) * scale`` dequant into the consuming matmul, so the
HBM-resident (and host->device streamed) form stays int8/int4 — which is
the point of weight-only quant: 2-4x less memory traffic for the
bandwidth-bound decode path.

Symmetric per-group quantization along the input (first) dim:
scale_g = amax(group) / qmax, data = round(w / scale_g).

4-bit supports two codebooks (reference bnb.py BnbQuantizationConfig
``bnb_4bit_quant_type``): "linear" (uniform int4) and "nf4" — the QLoRA
NormalFloat4 code whose 16 levels are the quantiles of a standard normal,
information-optimal for the approximately-normal weight distributions of
trained nets. With ``double_quant`` the per-group fp32 absmax scales are
themselves quantized (int8 over 256-scale blocks around their mean —
reference ``bnb_4bit_use_double_quant``), shaving the scale overhead from
32 to ~8.5 bits per group.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# NormalFloat4 code (QLoRA, Dettmers et al. 2023): 16 asymmetric levels,
# the quantiles of N(0,1) normalized to [-1, 1], with an exact zero
NF4_CODE = np.array(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
        -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
        0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
        0.33791524171829224, 0.4407098591327667, 0.5626170039176941,
        0.7229568362236023, 1.0,
    ],
    np.float32,
)
_NF4_MIDPOINTS = (NF4_CODE[1:] + NF4_CODE[:-1]) / 2
_DOUBLE_QUANT_BLOCK = 256  # scales per second-level absmax block (bnb default)


@dataclass
class QuantizationConfig:
    """reference BnbQuantizationConfig (utils/dataclasses.py). ``skip_modules``
    defaults to embedding/head-like params (quantizing tied embeddings hurts
    accuracy disproportionately, same default as bnb's llm_int8_skip_modules).
    ``quant_type`` ("linear"/"nf4") and ``double_quant`` mirror the
    reference's bnb_4bit_quant_type / bnb_4bit_use_double_quant and apply to
    4-bit only."""

    load_in_8bit: bool = False
    load_in_4bit: bool = False
    group_size: int = 128
    skip_modules: Optional[list] = None
    min_dims: int = 2  # only matrices quantize; norms/bias vectors never do
    quant_type: str = "linear"
    double_quant: bool = False

    def __post_init__(self):
        if self.load_in_8bit and self.load_in_4bit:
            raise ValueError("pick one of load_in_8bit / load_in_4bit")
        if not (self.load_in_8bit or self.load_in_4bit):
            raise ValueError("QuantizationConfig with neither 8bit nor 4bit enabled")
        if self.quant_type not in ("linear", "nf4"):
            raise ValueError(f"quant_type must be 'linear' or 'nf4', got {self.quant_type!r}")
        if self.quant_type == "nf4" and not self.load_in_4bit:
            raise ValueError("nf4 is a 4-bit code; set load_in_4bit=True")
        if self.double_quant and not self.load_in_4bit:
            raise ValueError("double_quant applies to 4-bit quantization only")
        if self.skip_modules is None:
            self.skip_modules = ["embedding", "lm_head", "embed", "classifier", "pooler"]

    @property
    def bits(self) -> int:
        return 8 if self.load_in_8bit else 4


class QuantizedScale:
    """Pytree node for double-quantized per-group scales: ``data`` int8
    (the centered scales over ``_DOUBLE_QUANT_BLOCK``-sized flat blocks),
    ``scale2`` fp32 per block, ``offset`` fp32 scalar (the mean removed
    before the symmetric int8 quant). Static: the original scale shape."""

    def __init__(self, data, scale2, offset, shape):
        self.data = data
        self.scale2 = scale2
        self.offset = offset
        self.shape = tuple(shape)

    def __repr__(self):
        return f"QuantizedScale(shape={self.shape})"


jax.tree_util.register_pytree_node(
    QuantizedScale,
    lambda qs: ((qs.data, qs.scale2, qs.offset), (qs.shape,)),
    lambda aux, ch: QuantizedScale(ch[0], ch[1], ch[2], aux[0]),
)


class QuantizedWeight:
    """Pytree node: ``data`` int8 ([K, N]; 4-bit packs two values per byte
    along K), ``scale`` fp32 [K/group, N] — or a nested ``QuantizedScale``
    under double quantization. Static: shape, bits, group, dtype, qtype
    ("linear" | "nf4")."""

    def __init__(self, data, scale, shape, bits, group, dtype, qtype="linear"):
        self.data = data
        self.scale = scale
        self.shape = tuple(shape)
        self.bits = int(bits)
        self.group = int(group)
        self.dtype = dtype
        self.qtype = qtype

    def __repr__(self):
        return (
            f"QuantizedWeight(shape={self.shape}, bits={self.bits}, "
            f"group={self.group}, qtype={self.qtype})"
        )


def _qw_flatten(qw):
    return (qw.data, qw.scale), (qw.shape, qw.bits, qw.group, qw.dtype, qw.qtype)


def _qw_unflatten(aux, children):
    data, scale = children
    shape, bits, group, dtype, qtype = aux
    return QuantizedWeight(data, scale, shape, bits, group, dtype, qtype)


jax.tree_util.register_pytree_node(QuantizedWeight, _qw_flatten, _qw_unflatten)


def _register_export_serialization():
    """Make the quantized pytree nodes serializable by jax.export — the
    dispatch path persists its AOT program as a StableHLO artifact so later
    processes skip the model trace; that serialization walks the params
    treedef, which contains these nodes."""
    import json

    try:
        from jax import export as jax_export

        reg = jax_export.register_pytree_node_serialization
    except Exception:  # pragma: no cover - old jax without the API
        return

    def _qs_ser(aux):
        (shape,) = aux
        return json.dumps({"shape": list(shape)}).encode()

    def _qs_de(b):
        d = json.loads(b.decode())
        return (tuple(d["shape"]),)

    def _qw_ser(aux):
        shape, bits, group, dtype, qtype = aux
        return json.dumps({
            "shape": list(shape), "bits": bits, "group": group,
            "dtype": np.dtype(dtype).name, "qtype": qtype,
        }).encode()

    def _qw_de(b):
        d = json.loads(b.decode())
        return (tuple(d["shape"]), d["bits"], d["group"], np.dtype(d["dtype"]), d["qtype"])

    try:
        reg(
            QuantizedScale,
            serialized_name="accelerate_tpu.QuantizedScale",
            serialize_auxdata=_qs_ser,
            deserialize_auxdata=_qs_de,
        )
        reg(
            QuantizedWeight,
            serialized_name="accelerate_tpu.QuantizedWeight",
            serialize_auxdata=_qw_ser,
            deserialize_auxdata=_qw_de,
        )
    except Exception:  # pragma: no cover - double registration
        pass


_register_export_serialization()


def quantize_array(w, bits: int = 8, group_size: int = 128,
                   qtype: str = "linear", double_quant: bool = False) -> QuantizedWeight:
    """Per-group quantization of a [K, ...] float array along dim 0.
    One implementation (quantize_array_host) owns the math; concrete inputs
    quantize on the host and the packed result moves to device."""
    import jax.core

    if isinstance(w, jax.core.Tracer):
        raise TypeError(
            "quantize_array is a load-time (host) transform, not a traceable "
            "op; quantize before jit and dequantize in-graph instead"
        )
    if isinstance(w, jax.Array):
        w = np.asarray(jax.device_get(w))
    qw = quantize_array_host(
        np.asarray(w), bits=bits, group_size=group_size,
        qtype=qtype, double_quant=double_quant,
    )
    return jax.tree_util.tree_map(jnp.asarray, qw)


def quantize_array_host(
    w: np.ndarray, bits: int = 8, group_size: int = 128,
    qtype: str = "linear", double_quant: bool = False,
) -> QuantizedWeight:
    """quantize_array in pure numpy — no device traffic. The load path uses
    this to quantize BEFORE the host->device transfer, so only the packed
    int8/int4 bytes + (possibly double-quantized) scales cross the link
    (2-4x fewer bytes than a bf16/fp32 checkpoint stream; the
    big-model-inference load metric is usually link-bound)."""
    if qtype == "nf4" and bits != 4:
        raise ValueError("nf4 is a 4-bit code")
    w = np.asarray(w)
    orig_dtype = w.dtype
    k = w.shape[0]
    g = group_size if (group_size > 0 and k % group_size == 0) else k

    # native single-pass kernel (csrc att_quantize_group) when available —
    # the numpy path below costs ~7 full passes over fp32 temporaries, which
    # is the serial host cost quantize-on-load pays before bytes can move
    from ..runtime.native import quantize_group_native

    native = quantize_group_native(w, g, bits, qtype == "nf4")
    if native is not None:
        q, scale = native
    else:
        w32 = np.asarray(w, np.float32).reshape(k // g, g, *w.shape[1:])
        amax = np.max(np.abs(w32), axis=1, keepdims=True)
        # reciprocal-MULTIPLY (not fdiv), matching the native kernel bit for
        # bit — and XLA-on-TPU semantics, which lowers fdiv the same way
        if qtype == "nf4":
            scale = np.where(amax > 0, amax, 1.0).astype(np.float32)
            normed = w32 * (np.float32(1.0) / scale)
            # nearest NF4 level via the midpoint boundaries (the code is sorted)
            q = np.searchsorted(_NF4_MIDPOINTS, normed).astype(np.int8)
        else:
            qmax = float(2 ** (bits - 1) - 1)
            scale = np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)
            q = np.clip(np.round(w32 * (np.float32(1.0) / scale)), -qmax, qmax).astype(np.int8)
        q = q.reshape(w.shape)
        scale = scale[:, 0]
        if bits == 4:
            if k % 2:
                q = np.concatenate([q, np.zeros((1,) + q.shape[1:], q.dtype)], axis=0)
            lo = q[0::2] & 0x0F
            hi = (q[1::2] & 0x0F) << 4
            q = (lo | hi).astype(np.int8)
    if double_quant:
        scale = _quantize_scales_host(scale)
    return QuantizedWeight(q, scale, w.shape, bits, g, orig_dtype, qtype)


def _quantize_scales_host(scale: np.ndarray) -> QuantizedScale:
    """Second-level quantization of the per-group scales (reference
    bnb_4bit_use_double_quant) — ~8.5 effective bits per scale instead
    of 32.

    Quantized in the LOG domain: absmax scales are positive with a heavy
    right tail (one outlier channel per block would ruin a linear int8 code
    for every other scale in its block — bnb uses a non-linear dynamic code
    for the same reason). log compresses that dynamic range, so the int8
    step is a small RELATIVE error on every scale: even a 2000x outlier
    spread costs at most exp(log_range/254) - 1 ≈ 3% per scale."""
    shape = scale.shape
    flat = np.log(np.maximum(scale.reshape(-1).astype(np.float32), 1e-30))
    offset = np.float32(flat.mean())
    centered = flat - offset
    n = flat.size
    nblocks = max(1, -(-n // _DOUBLE_QUANT_BLOCK))
    pad = nblocks * _DOUBLE_QUANT_BLOCK - n
    if pad:
        centered = np.concatenate([centered, np.zeros(pad, np.float32)])
    blocks = centered.reshape(nblocks, _DOUBLE_QUANT_BLOCK)
    amax = np.abs(blocks).max(axis=1, keepdims=True)
    scale2 = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q8 = np.clip(np.round(blocks / scale2), -127, 127).astype(np.int8)
    return QuantizedScale(q8.reshape(-1)[:n].reshape(shape), scale2[:, 0], offset, shape)


def _dequantize_scales(qs: QuantizedScale):
    """In-graph inverse of _quantize_scales_host (log-domain)."""
    n = int(np.prod(qs.shape)) if qs.shape else 1
    flat = qs.data.reshape(-1).astype(jnp.float32)
    nblocks = qs.scale2.shape[0]
    pad = nblocks * _DOUBLE_QUANT_BLOCK - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
    blocks = flat.reshape(nblocks, _DOUBLE_QUANT_BLOCK) * qs.scale2[:, None]
    return jnp.exp(blocks.reshape(-1)[:n] + qs.offset).reshape(qs.shape)


def quantize_abstract(leaf, config: QuantizationConfig) -> QuantizedWeight:
    """The ShapeDtypeStruct shadow of quantize_array_host: what an eligible
    leaf WILL look like after quantize-on-load — lets the dispatch AOT
    compile against the quantized avals while the checkpoint still streams."""
    shape = tuple(leaf.shape)
    k = shape[0]
    g = config.group_size if (config.group_size > 0 and k % config.group_size == 0) else k
    data_shape = shape
    if config.bits == 4:
        data_shape = ((k + 1) // 2,) + shape[1:]
    scale_shape = (k // g,) + shape[1:]
    scale = jax.ShapeDtypeStruct(scale_shape, jnp.float32)
    if config.double_quant:
        n = int(np.prod(scale_shape)) if scale_shape else 1
        nblocks = max(1, -(-n // _DOUBLE_QUANT_BLOCK))
        scale = QuantizedScale(
            jax.ShapeDtypeStruct(scale_shape, jnp.int8),
            jax.ShapeDtypeStruct((nblocks,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
            scale_shape,
        )
    return QuantizedWeight(
        jax.ShapeDtypeStruct(data_shape, jnp.int8),
        scale,
        shape, config.bits, g, leaf.dtype, config.quant_type,
    )


def quantize_abstract_tree(abstract_params, config, *, placement=None, leaf_dtype=None):
    """``abstract_params`` with every eligible leaf replaced by its
    ``quantize_abstract`` shadow — the single owner of the "which leaves get
    packed, and at what dtype" decision shared by the auto-device-map budget,
    the dispatch AOT precompile, and the loader's sharding inference (so they
    can never drift apart).

    ``placement(path) -> bool`` gates quantization (e.g. device-tier only);
    ``leaf_dtype(path, leaf) -> dtype`` overrides the dtype used BOTH for
    eligibility and for the returned struct (e.g. the checkpoint's on-disk
    dtype plus a cast override — eligibility must be judged on what will
    actually be loaded, not on the model's init dtype). With ``config=None``
    only the dtype adjustment applies."""
    from .serialization import flatten_pytree, unflatten_to_like

    flat = flatten_pytree(abstract_params)
    out = {}
    for path, leaf in flat.items():
        sds = leaf
        if leaf_dtype is not None:
            sds = jax.ShapeDtypeStruct(tuple(leaf.shape), jnp.dtype(leaf_dtype(path, leaf)))
        if (
            config is not None
            and (placement is None or placement(path))
            and _eligible(path, sds, config)
        ):
            out[path] = quantize_abstract(sds, config)
        else:
            out[path] = sds
    return unflatten_to_like(out, abstract_params)


def dequantize_array(qw: QuantizedWeight):
    """Inverse of quantize_array; XLA fuses this into the consumer matmul."""
    data = qw.data
    nf4 = getattr(qw, "qtype", "linear") == "nf4"
    if qw.bits == 4:
        if nf4:
            # UNSIGNED nibbles: codebook indices 0..15
            lo = data & 0x0F
            hi = (data >> 4) & 0x0F  # mask off the arithmetic-shift sign fill
        else:
            lo = (data << 4).astype(jnp.int8) >> 4  # sign-extend low nibble
            hi = data >> 4  # arithmetic shift sign-extends the high nibble
        k = qw.shape[0]
        data = jnp.stack([lo, hi], axis=1).reshape(2 * data.shape[0], *qw.shape[1:])
        data = data[:k]  # drop the pad row when K was odd
    scale = qw.scale
    if isinstance(scale, QuantizedScale):
        scale = _dequantize_scales(scale)
    k, g = qw.shape[0], qw.group
    if nf4:
        w = jnp.take(jnp.asarray(NF4_CODE), data.astype(jnp.int32), axis=0)
    else:
        w = data.astype(jnp.float32)
    w = w.reshape(k // g, g, *qw.shape[1:]) * scale[:, None]
    return w.reshape(qw.shape).astype(qw.dtype)


def _eligible(path: str, leaf, config: QuantizationConfig) -> bool:
    if not hasattr(leaf, "shape") or len(getattr(leaf, "shape", ())) < config.min_dims:
        return False
    dt = getattr(leaf, "dtype", None)  # arrays AND ShapeDtypeStructs
    if dt is None:
        dt = jnp.asarray(leaf).dtype
    if not jnp.issubdtype(jnp.dtype(dt), jnp.floating):
        return False
    lowered = path.lower()
    return not any(skip in lowered for skip in config.skip_modules)


def quantize_params(params, config: QuantizationConfig):
    """Quantize every eligible weight in a param pytree. Returns the tree
    with QuantizedWeight nodes in place of quantized matrices."""
    from .serialization import FLAT_SEP, flatten_pytree, unflatten_to_like

    flat = flatten_pytree(params)
    out = {}
    for path, leaf in flat.items():
        if _eligible(path, leaf, config):
            out[path] = quantize_array(
                leaf, bits=config.bits, group_size=config.group_size,
                qtype=config.quant_type, double_quant=config.double_quant,
            )
        else:
            out[path] = leaf
    return unflatten_to_like(out, params)


def dequantize_params(params):
    """Replace every QuantizedWeight node with its dequantized array."""
    return jax.tree_util.tree_map(
        lambda l: dequantize_array(l) if isinstance(l, QuantizedWeight) else l,
        params,
        is_leaf=lambda l: isinstance(l, QuantizedWeight),
    )


# ---------------------------------------------------------------------------
# KV-cache quantization (the serving arena's int8/int4 storage)
#
# Unlike the weight path above — a load-time host transform — KV quantization
# is IN-GRAPH: the decode step quantizes each freshly computed K/V token as it
# scatters into the cache (models/decoder.py), and the read side dequantizes
# either inside the pallas decode kernel (ops/attention.py, in-register) or as
# the fused ``payload.astype(f32) * scale`` the masked-dense reference runs.
# Scales are symmetric per (token, kv-head): one fp32 amax scale over the
# head_dim values a single cache write produces, so a write never has to
# re-quantize existing cache content (no double-quantization drift) and a page
# carries its scales beside it through CoW forks, prefix-cache shares, and
# preemption page-outs. int4 packs two values per byte along head_dim.
# ---------------------------------------------------------------------------

KV_CACHE_DTYPES = ("bf16", "int8", "int4")


def kv_cache_bits(kv_dtype) -> int:
    """Storage bits per K/V value for a ``kv_cache_dtype`` knob value
    (None/"bf16" -> 16). Raises on unknown dtypes so a typo'd config cannot
    silently serve full-precision."""
    if kv_dtype in (None, "bf16"):
        return 16
    if kv_dtype == "int8":
        return 8
    if kv_dtype == "int4":
        return 4
    raise ValueError(
        f"kv_cache_dtype must be one of {KV_CACHE_DTYPES}, got {kv_dtype!r}"
    )


def quantize_kv_values(x, bits: int):
    """The value half of :func:`quantize_kv`: ``x [..., D]`` -> ``(q fp32
    [..., D] — rounded, clipped integers in [-qmax, qmax] — , scale fp32
    [..., 1])``. One function for the jitted cache writes AND the ragged
    prefill kernel's in-register quantize-on-write, so both emit identical
    bytes by construction."""
    if bits not in (8, 4):
        raise ValueError(f"KV quantization supports 8 or 4 bits, got {bits}")
    qmax = float(2 ** (bits - 1) - 1)
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(x32 * (1.0 / scale)), -qmax, qmax)
    return q, scale


def pack_int4_kv(q):
    """[..., D] integer values in [-7, 7] -> [..., D//2] int8 bytes: value
    ``i`` of the FIRST half of head_dim in the low nibble, value
    ``i + D//2`` in the high nibble. Split-halves, not even/odd
    interleave: Mosaic cannot lay out a trailing-dimension interleave
    (``stack`` + ``reshape``), while two contiguous half-width lane slices
    compile. The arithmetic is int32 because the v5e vector unit has no
    int8 shifts; ``(hi << 4) | (lo & 0xF)`` of signed nibbles already fits
    int8, so the final cast never wraps."""
    if q.shape[-1] % 2:
        raise ValueError(
            f"int4 KV packing needs an even head_dim, got {q.shape[-1]}"
        )
    half = q.shape[-1] // 2
    q = q.astype(jnp.int32)
    return ((q[..., half:] << 4) | (q[..., :half] & 0x0F)).astype(jnp.int8)


def kv_payload(q, bits: int):
    """Storage bytes of quantized values ``q [..., D]``: int8 as they are,
    int4 packed two per byte (:func:`pack_int4_kv`)."""
    return pack_int4_kv(q) if bits == 4 else q.astype(jnp.int8)


def quantize_kv(x, bits: int):
    """In-graph symmetric quantization of fresh K/V values along the LAST
    axis (head_dim): ``x [..., D]`` -> ``(payload int8 [..., D] (int8) or
    [..., D//2] (int4, two nibbles per byte), scale fp32 [..., 1])`` with
    ``x ~= payload * scale``. Zero rows quantize to payload 0 / scale 1.0
    (exact round trip). Traced-friendly: this runs inside the jitted decode
    step / prefill chunk programs."""
    q, scale = quantize_kv_values(x, bits)
    return kv_payload(q, bits), scale


def unpack_int4_kv(payload):
    """[..., D//2] packed bytes -> [..., D] signed int32 values, the
    inverse of :func:`pack_int4_kv` (low nibbles are the first half of
    head_dim, high nibbles the second). Runs unchanged inside the pallas
    kernels: int32 shifts and a lane concatenate are what Mosaic accepts."""
    p = payload.astype(jnp.int32)
    lo = (p << 28) >> 28                                # sign-extend low nibble
    hi = p >> 4                                         # arithmetic shift
    return jnp.concatenate([lo, hi], axis=-1)


def dequantize_kv(payload, scale, bits: int, dtype):
    """Reference dequant — the EXACT op sequence the pallas decode kernels
    run in-register (``values.astype(f32) * scale`` then a cast to the
    compute dtype), so the gathered masked-dense fallback stays the
    bit-exactness oracle for the fused kernel path on identical quantized
    inputs."""
    if bits == 4:
        payload = unpack_int4_kv(payload)
    return (payload.astype(jnp.float32) * scale).astype(dtype)


def quantized_nbytes(params) -> int:
    """Device bytes of a (possibly quantized) tree — for map/memory math."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size"):
            total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total
