"""Flash attention for TPU in pallas, with an XLA reference fallback.

This is the one op where a hand kernel beats XLA fusion: materializing the
[S, S] score matrix in HBM is the memory wall, and the online-softmax
streaming formulation keeps everything in VMEM. Layout is [batch, heads,
seq, head_dim] (MXU-friendly: the last two dims tile onto the 128x128
systolic array).

The reference framework has no attention kernels at all (it delegates
compute to the wrapped torch model); this op exists because our framework
ships model implementations (models/) whose hot path must be TPU-native.
Long-context ring attention (parallel/context.py) composes with this
kernel as its per-shard inner step.

Capabilities:
- causal or full attention, fp32 accumulation, bf16 in/out
- GQA/MQA native: kv blocks are indexed per query-head group in the
  BlockSpec (`h // group`), so K/V are never expanded to full head count
  and the dk/dv pass sums the group's gradients in-kernel
- padding masks (`kv_mask`) and packed-sequence `segment_ids`, applied
  inside the kernels (padded/packed workloads stay on the flash path)
- custom VJP: pallas forward AND backward (dq and dk/dv kernels)
- `(out, lse)` residual export for the ring-attention inner step
- `interpret=True` runs the same kernels on CPU for tests
- ragged/paged DECODE kernels (`decode_attention` / `paged_decode_attention`
  dispatch): length-aware online-softmax walk over only each slot's live kv
  blocks — straight from the physical page arena through the slot's page
  table, or in fixed blocks over a dense arena — so decode HBM traffic
  scales with live tokens, not arena capacity. Masked-dense stays the
  fallback + bit-exactness reference (`DecoderConfig.decode_kernel`
  `paged|dense`, "interpret" for CPU tests). The paged kernel takes the layers' stacked
  arena and a layer index and, in a decode step, writes each slot's new
  row into its page itself, the stack aliased to its output: the step's
  cache write is no XLA scatter
- quantized KV arenas (`kv_quant_bits=8|4` + per-token scale operands):
  both decode kernels read int8/packed-int4 payloads from HBM and
  dequantize in-register before the flash inner product, so the byte
  shrink compounds with the live-token walk; the masked-dense fallback
  dequantizes via the same reference op sequence
  (utils/quantization.dequantize_kv) and stays the exactness oracle
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import importlib


class _LazyModule:
    """Deferred import: pallas costs ~0.2 s at import time, which lands on
    every process's startup (the TTFT bench counts it) even when the process
    never traces a kernel. Resolution happens at first attribute access —
    i.e. at trace time, inside the first jit."""

    def __init__(self, name):
        self._name = name
        self._mod = None

    def _resolve(self):
        if self._mod is None:
            self._mod = importlib.import_module(self._name)
        return self._mod

    def __getattr__(self, attr):
        return getattr(self._resolve(), attr)


pl = _LazyModule("jax.experimental.pallas")
pltpu = _LazyModule("jax.experimental.pallas.tpu")

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() semantics with no NaN risk


# ---------------------------------------------------------------------------
# XLA reference (CPU fallback + ground truth for kernel tests)
# ---------------------------------------------------------------------------


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    value_scale: float = 1.0,
) -> jax.Array:
    """Plain-XLA attention. q: [B, H, Sq, D]; k: [B, KVH, Skv, D]; v:
    [B, KVH, Skv, Dv] (the value width may differ from the key width; the
    output is [B, H, Sq, Dv]). ``bias`` is additive, broadcastable to
    [B, H, Sq, Skv] (use large negatives for padding masks).

    ``window`` (with ``causal``): a query at position i sees key j iff
    ``j <= i`` and ``i - j < window`` -- the window counts the query's own
    position. ``sink`` [H] float: a learned scalar per query head that
    joins the softmax's denominator and carries no value. ``value_scale``
    multiplies the values (by linearity, the output)."""
    orig_dtype = q.dtype
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = q.shape
    kvh, skv, dv = k.shape[1], k.shape[2], v.shape[-1]
    group = h // kvh
    qg = q.reshape(b, kvh, group, sq, d)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qg, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if bias is not None:
        bias32 = jnp.broadcast_to(bias.astype(jnp.float32), (b, h, sq, skv))
        s = s + bias32.reshape(b, kvh, group, sq, skv)
    if causal:
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
    elif window is not None:
        raise ValueError("window needs causal=True (or a bias that encodes it)")
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, kvh, group, 1, 1), (b, kvh, group, sq, 1))
        p = jax.nn.softmax(jnp.concatenate([s, col], axis=-1), axis=-1)[..., :skv]
    else:
        p = jax.nn.softmax(s, axis=-1)
    if value_scale != 1.0:
        out = jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32) * value_scale
    else:
        out = jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(v.dtype), v)
    return out.reshape(b, h, sq, dv).astype(orig_dtype)


# ---------------------------------------------------------------------------
# pallas kernels
#
# All kernels take the optional mask refs (kv_mask [B, Skv] int32 — nonzero
# = attend; q_seg/kv_seg [B, S] int32 — attend iff equal) threaded by
# compile-time has_* flags, and handle GQA by kv-head block indexing.
# ---------------------------------------------------------------------------


def _parse_refs(args, n_out, has_kv_mask, has_seg):
    """Split pallas's positional (in_refs..., out_refs..., scratch...) by
    the kernel's compile-time mask flags."""
    i = 3
    kv_mask_ref = q_seg_ref = kv_seg_ref = None
    if has_kv_mask:
        kv_mask_ref = args[i]
        i += 1
    if has_seg:
        q_seg_ref, kv_seg_ref = args[i], args[i + 1]
        i += 2
    outs = args[i : i + n_out]
    scratch = args[i + n_out :]
    return args[0], args[1], args[2], kv_mask_ref, q_seg_ref, kv_seg_ref, outs, scratch


def _mask_block(s, kv_mask_ref, q_seg_ref, kv_seg_ref, causal, iq, ik, bq, bk):
    """Apply causal / padding / segment masks to a [bq, bk] score block.
    Returns (masked scores, bool validity matrix or None)."""
    valid = None
    if causal:
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = cols <= rows
    if kv_mask_ref is not None:
        kvm = kv_mask_ref[0, 0] != 0  # [bk] (mask blocks are [1, 1, bk])
        m = jnp.broadcast_to(kvm[None, :], (bq, bk))
        valid = m if valid is None else (valid & m)
    if q_seg_ref is not None:
        qs = q_seg_ref[0, 0]  # [bq]
        ks = kv_seg_ref[0, 0]  # [bk]
        m = qs[:, None] == ks[None, :]
        valid = m if valid is None else (valid & m)
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    return s, valid


def _fwd_kernel(*args, sm_scale, causal, bq, bk, nk, has_kv_mask, has_seg):
    q_ref, k_ref, v_ref, kv_mask_ref, q_seg_ref, kv_seg_ref, outs, scratch = _parse_refs(
        args, 2, has_kv_mask, has_seg
    )
    o_ref, lse_ref = outs
    acc, m_scr, l_scr = scratch
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    # causal: skip kv blocks entirely above the diagonal
    run = (iq + 1) * bq > ik * bk if causal else ik >= 0

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        s, _ = _mask_block(s, kv_mask_ref, q_seg_ref, kv_seg_ref, causal, iq, ik, bq, bk)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _out():
        l = l_scr[...][:, :1]
        m = m_scr[...][:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / safe_l).astype(o_ref.dtype)
        # TPU tiling: lse lives as [B, H, 8, Sq] (one f32 sublane tile);
        # row 0 is the value, rows 1-7 are padding. Fully-masked rows keep
        # lse = NEG_INF (l == 0) so downstream merges treat them as empty.
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(safe_l))
        lse_ref[0, 0] = jnp.broadcast_to(lse[:, 0][None, :], lse_ref.shape[2:])


def _p_from_lse(s, lse, valid):
    """exp(s - lse) with masked entries forced to exactly 0 (a fully masked
    row has lse = NEG_INF, where s - lse would be 0 -> p 1 -> garbage)."""
    p = jnp.exp(s - lse)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    return p


def _dq_kernel(*args, sm_scale, causal, bq, bk, nk, has_kv_mask, has_seg):
    # in_refs: q, k, v, do, lse, delta, [kv_mask], [q_seg, kv_seg]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = args[:6]
    i = 6
    kv_mask_ref = q_seg_ref = kv_seg_ref = None
    if has_kv_mask:
        kv_mask_ref = args[i]
        i += 1
    if has_seg:
        q_seg_ref, kv_seg_ref = args[i], args[i + 1]
        i += 2
    dq_ref = args[i]
    dq_acc = args[i + 1]
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (iq + 1) * bq > ik * bk if causal else ik >= 0

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale
        s, valid = _mask_block(s, kv_mask_ref, q_seg_ref, kv_seg_ref, causal, iq, ik, bq, bk)
        p = _p_from_lse(s, lse, valid)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _out():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(*args, sm_scale, causal, bq, bk, nq_total, nq, has_kv_mask, has_seg):
    """dk/dv for one kv head. Grid dim 3 runs over nq_total = nq * group
    query blocks (all blocks of every query head in this kv head's group),
    so the group's gradients sum into the kv head in-kernel — GQA without
    expanding K/V."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = args[:6]
    i = 6
    kv_mask_ref = q_seg_ref = kv_seg_ref = None
    if has_kv_mask:
        kv_mask_ref = args[i]
        i += 1
    if has_seg:
        q_seg_ref, kv_seg_ref = args[i], args[i + 1]
        i += 2
    dk_ref, dv_ref = args[i], args[i + 1]
    dk_acc, dv_acc = args[i + 2], args[i + 3]
    ik, it = pl.program_id(2), pl.program_id(3)
    iq = it % nq  # query-block index within the current group member

    @pl.when(it == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (iq + 1) * bq > ik * bk if causal else it >= 0

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * sm_scale
        s, valid = _mask_block(s, kv_mask_ref, q_seg_ref, kv_seg_ref, causal, iq, ik, bq, bk)
        p = _p_from_lse(s, lse, valid)  # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(do, v.astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale  # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(it == nq_total - 1)
    def _out():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _pick_block(s: int, preferred: int) -> int:
    # 1024 first: measured ~30% faster than 512 blocks across 2k-16k
    # sequences on v5e (fwd+bwd); 2048 blocks exceed VMEM
    for cand in (preferred, 1024, 512, 256, 128):
        if cand <= s and s % cand == 0:
            return cand
    return 0  # no valid block → caller falls back to XLA


def _grid_params(
    interpret: bool,
    semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes: Optional[int] = None,
):
    kw = {"interpret": interpret}
    if not interpret:
        extra = {} if vmem_limit_bytes is None else {"vmem_limit_bytes": int(vmem_limit_bytes)}
        kw["compiler_params"] = pltpu.CompilerParams(dimension_semantics=semantics, **extra)
    return kw


def _mask_specs(masks, bq, bk, group):
    """(in_specs, arrays) for the optional kv_mask / segment-id inputs.
    kv-indexed arrays block over ik; q-indexed over iq. Masks carry an
    explicit singleton sublane dim ([B, 1, S], block (1, 1, blk)) to satisfy
    the TPU (8, 128) block-tiling rule."""
    kv_mask, q_seg, kv_seg = masks
    specs, arrays = [], []
    if kv_mask is not None:
        specs.append(pl.BlockSpec((1, 1, bk), lambda b_, h_, iq, ik: (b_, 0, ik)))
        arrays.append(kv_mask.astype(jnp.int32)[:, None, :])
    if q_seg is not None:
        specs.append(pl.BlockSpec((1, 1, bq), lambda b_, h_, iq, ik: (b_, 0, iq)))
        arrays.append(q_seg.astype(jnp.int32)[:, None, :])
        specs.append(pl.BlockSpec((1, 1, bk), lambda b_, h_, iq, ik: (b_, 0, ik)))
        arrays.append(kv_seg.astype(jnp.int32)[:, None, :])
    return specs, arrays


def _flash_fwd_call(q, k, v, masks, causal, sm_scale, bq, bk, interpret):
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    nq, nk = sq // bq, skv // bk
    kv_mask, q_seg, kv_seg = masks
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        bq=bq,
        bk=bk,
        nk=nk,
        has_kv_mask=kv_mask is not None,
        has_seg=q_seg is not None,
    )
    mask_specs, mask_arrays = _mask_specs(masks, bq, bk, group)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
            *mask_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, 8, bq), lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 8, sq), jnp.float32),
        ],
        scratch_shapes=[_vmem((bq, d)), _vmem((bq, 128)), _vmem((bq, 128))],
        name="flash_attn_fwd",
        **_grid_params(interpret),
    )(q, k, v, *mask_arrays)
    return out, lse


def _flash_bwd_call(q, k, v, out, lse, do, masks, causal, sm_scale, bq, bk, interpret):
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    group = h // kvh
    nq, nk = sq // bq, skv // bk
    kv_mask, q_seg, kv_seg = masks
    has_kv_mask, has_seg = kv_mask is not None, q_seg is not None
    lse = jnp.broadcast_to(lse, (b, h, 8, sq))  # residual stored [B,H,1,Sq]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,H,Sq]
    delta = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, sq))  # sublane-tile layout

    mask_specs, mask_arrays = _mask_specs(masks, bq, bk, group)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, nk=nk,
            has_kv_mask=has_kv_mask, has_seg=has_seg,
        ),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // group, ik, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, 8, bq), lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
            pl.BlockSpec((1, 1, 8, bq), lambda b_, h_, iq, ik: (b_, h_, 0, iq)),
            *mask_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_vmem((bq, d))],
        name="flash_attn_dq",
        **_grid_params(interpret),
    )(q, k, v, do, lse, delta, *mask_arrays)

    # dk/dv: grid over kv heads; innermost dim covers every (group member,
    # query block) pair so the group's grads accumulate into one kv block
    nq_total = nq * group

    def _qh(kv_, it):  # query head for this grid step
        return kv_ * group + it // nq

    # q-indexed mask specs need the (kv_, it) index layout of this grid
    mask_specs_kv = []
    if has_kv_mask:
        mask_specs_kv.append(pl.BlockSpec((1, 1, bk), lambda b_, kv_, ik, it: (b_, 0, ik)))
    if has_seg:
        mask_specs_kv.append(pl.BlockSpec((1, 1, bq), lambda b_, kv_, ik, it: (b_, 0, it % nq)))
        mask_specs_kv.append(pl.BlockSpec((1, 1, bk), lambda b_, kv_, ik, it: (b_, 0, ik)))

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk,
            nq_total=nq_total, nq=nq, has_kv_mask=has_kv_mask, has_seg=has_seg,
        ),
        grid=(b, kvh, nk, nq_total),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, kv_, ik, it: (b_, _qh(kv_, it), it % nq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, kv_, ik, it: (b_, kv_, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, kv_, ik, it: (b_, kv_, ik, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, kv_, ik, it: (b_, _qh(kv_, it), it % nq, 0)),
            pl.BlockSpec((1, 1, 8, bq), lambda b_, kv_, ik, it: (b_, _qh(kv_, it), 0, it % nq)),
            pl.BlockSpec((1, 1, 8, bq), lambda b_, kv_, ik, it: (b_, _qh(kv_, it), 0, it % nq)),
            *mask_specs_kv,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, kv_, ik, it: (b_, kv_, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, kv_, ik, it: (b_, kv_, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[_vmem((bk, d)), _vmem((bk, d))],
        name="flash_attn_dkv",
        **_grid_params(interpret),
    )(q, k, v, do, lse, delta, *mask_arrays)
    return dq, dk, dv


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)


# ---------------------------------------------------------------------------
# custom-VJP core. q [B, H, Sq, D]; k/v [B, KVH, Skv, D] (KVH divides H).
# ``masks`` is a tuple (kv_mask | None, q_seg | None, kv_seg | None) — int
# arrays are non-differentiable, their cotangent is None.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, masks, causal, sm_scale, bq, bk, interpret):
    out, _ = _flash_fwd_call(q, k, v, masks, causal, sm_scale, bq, bk, interpret)
    return out


def _flash_core_fwd(q, k, v, masks, causal, sm_scale, bq, bk, interpret):
    out, lse = _flash_fwd_call(q, k, v, masks, causal, sm_scale, bq, bk, interpret)
    # keep only the value row of the [B,H,8,Sq] tile layout as the residual.
    # checkpoint_name lets a remat policy (models/configs.remat_policy =
    # "save_attention") KEEP these residuals so the backward pass reuses the
    # kernel's out/lse instead of re-running the whole forward kernel —
    # at 16k+ tokens the attention recompute is the largest remat term.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse[:, :, :1], "flash_lse")
    return out, (q, k, v, masks, out, lse)


def _flash_core_bwd(causal, sm_scale, bq, bk, interpret, res, do):
    q, k, v, masks, out, lse = res
    dq, dk, dv = _flash_bwd_call(q, k, v, out, lse, do, masks, causal, sm_scale, bq, bk, interpret)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: int = 1024,
    block_kv: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention. q: [B, H, Sq, D]; k/v: [B, KVH, Skv, D]
    (KVH must divide H — kv blocks are shared across the query-head group in
    the kernel; K/V are never expanded).

    ``kv_mask`` [B, Skv]: nonzero = position may be attended (padding mask).
    ``q_segment_ids``/``kv_segment_ids`` [B, S]: tokens attend only within
    equal segment ids (packed sequences)."""
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    h, kvh = q.shape[1], k.shape[1]
    if h % kvh:
        raise ValueError(f"query heads ({h}) must be a multiple of kv heads ({kvh})")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("q_segment_ids and kv_segment_ids must be given together")
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(k.shape[2], block_kv)
    if not bq or not bk:
        raise ValueError(
            f"sequence lengths ({q.shape[2]}, {k.shape[2]}) need a 128-multiple block; "
            "pad inputs or use dot_product_attention (auto-fallback)"
        )
    masks = (kv_mask, q_segment_ids, kv_segment_ids)
    return _flash_core(q, k, v, masks, causal, sm_scale, bq, bk, interpret)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    block_q: int = 1024,
    block_kv: int = 1024,
    interpret: bool = False,
):
    """Forward-only flash attention returning (out, lse [B, H, Sq] fp32).
    The ring-attention inner step (parallel/context.py) builds its own
    ring-level VJP from this plus the dq/dkv kernels below."""
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(k.shape[2], block_kv)
    if not bq or not bk:
        raise ValueError("sequence lengths need a 128-multiple block")
    masks = (kv_mask, None, None)
    out, lse = _flash_fwd_call(q, k, v, masks, causal, sm_scale, bq, bk, interpret)
    return out, lse[:, :, 0]


def flash_attention_bwd(
    q, k, v, out, lse, do, *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    block_q: int = 1024,
    block_kv: int = 1024,
    interpret: bool = False,
):
    """Block gradients given a (possibly global) lse [B, H, Sq]: returns
    (dq, dk, dv) for this q/kv block pair. With p = exp(s - lse), partial
    contributions sum correctly across kv blocks — which is exactly what the
    ring backward needs."""
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    bq = _pick_block(q.shape[2], block_q)
    bk = _pick_block(k.shape[2], block_kv)
    if not bq or not bk:
        raise ValueError("sequence lengths need a 128-multiple block")
    masks = (kv_mask, None, None)
    return _flash_bwd_call(
        q, k, v, out, lse[:, :, None, :], do, masks, causal, sm_scale, bq, bk, interpret
    )


# ---------------------------------------------------------------------------
# pallas ragged/paged decode-attention kernel (ROADMAP item 2)
#
# The masked-dense decode read streams the WHOLE arena reservation through
# HBM every step — decode bandwidth scales with capacity, not live tokens.
# These kernels walk only each slot's live KV with flash-style online
# softmax. The paged variant reads K/V straight from the physical page
# arena ([num_pages, KVH, page_size, D], left in HBM) through each slot's
# device page table (scalar-prefetched): one grid step a slot, and inside
# it a loop over blocks of many consecutive table entries, each live page
# fetched by one asynchronous copy that brings all its kv heads, double
# buffered — its work is the live pages, and a slot with no live tokens
# costs nothing. The dense variant walks a [B, KVH, L, D] arena in fixed
# blocks over a (slots × kv-heads × kv-blocks) grid, blocks past a slot's
# frontier clamped in the BlockSpec index map (the pipeline elides the
# re-fetch) and skipped by ``pl.when`` — the same win for the
# single-stream decode loop. GQA folds the query
# head group (× the Sq query rows of the multi-query form, held on the
# chip by ``chip_smoke.py``; ROADMAP R12) into one [group*Sq, D] block per
# kv head, so K/V are never expanded.
# ---------------------------------------------------------------------------

_DECODE_KERNEL_MODES = ("paged", "dense", "interpret")
# multi-query width the kernel accepts: the decode step's is 1, the wider
# forms are held on the chip by ``chip_smoke.py`` (ROADMAP R12).
# Prefill-size chunks (64+) stay on the dense path by design — they are
# compute-shaped, and the row-position unroll below is linear in Sq.
_DECODE_KERNEL_MAX_SQ = 16
_decode_fallback_warned: set = set()


def resolve_decode_kernel(impl: Optional[str] = None) -> str:
    """Resolve the decode-attention implementation choice: the explicit
    ``impl`` (``DecoderConfig.decode_kernel``), else ``"paged"`` (the
    kernel, with a warn-once dense fallback off-TPU). ``"interpret"`` runs
    the same kernel through the pallas interpreter — the CPU test/CI mode."""
    mode = impl or "paged"
    if mode not in _DECODE_KERNEL_MODES:
        raise ValueError(
            f"decode_kernel must be one of "
            f"{_DECODE_KERNEL_MODES}, got {mode!r}"
        )
    return mode


def _warn_once(key: str, message: str, *args):
    if key in _decode_fallback_warned:
        return
    _decode_fallback_warned.add(key)
    import logging

    logging.getLogger(__name__).warning(message, *args)


def _warn_decode_fallback(reason: str):
    """Warn-once per distinct reason (mirrors the fp8-without-MXU warn):
    the paged decode kernel was requested (or defaulted) but this process
    silently runs the masked-dense path instead, so decode bandwidth
    scales with arena capacity, not live tokens."""
    _warn_once(
        reason,
        "paged decode-attention kernel unavailable (%s); falling back to "
        "the masked-dense read — decode HBM traffic will scale with the "
        "arena reservation, not live tokens. Set "
        "DecoderConfig.decode_kernel='dense' to silence, or "
        "'interpret' to run the kernel through the pallas interpreter.",
        reason,
    )


def cache_entry_widths(config) -> tuple:
    """``(kv heads, key lanes, value width)`` of what a model with this
    (one-kind) config keeps a token in a paged cache, the keys at the width
    their pages store (:func:`paged_key_lanes`). Latent attention
    (``kv_lora_rank``) keeps one entry that every query head shares: the
    latent and the rotated key side by side (576 -> 640 lanes), whose first
    ``kv_lora_rank`` lanes are the value too, so there is no value page."""
    rank = getattr(config, "kv_lora_rank", None)
    if rank is not None:
        return 1, paged_key_lanes(int(config.latent_dim)), int(rank)
    head_dim = int(getattr(config, "head_dim", 0) or 0)
    return (int(getattr(config, "num_kv_heads", 1)), paged_key_lanes(head_dim),
            int(getattr(config, "v_head_dim", None) or head_dim))


def paged_key_lanes(d: int) -> int:
    """Lanes a key of width ``d`` takes in a paged arena's pages. The paged
    decode kernel copies whole pages out of the arena in HBM, and Mosaic
    takes such a slice only where the last dimension is a 128-multiple
    (``_decode_kernel_gate``). A width over 128 that is no 128-multiple
    (192) is therefore stored zero-padded to the next one (256): the
    queries are padded alike, so the scores are the same numbers, and the
    page costs a third more key bytes. A 64-wide head keeps its 64 lanes
    and the masked-dense read, as before (padding it would double its
    bytes)."""
    return d if d <= 128 or d % 128 == 0 else -(-d // 128) * 128


def _decode_kernel_gate(mode: str, sq: int, d: int, blk: int,
                        quant_bits: int = 0, paged: bool = False,
                        dv: Optional[int] = None):
    """(use_kernel, interpret) for one dispatch. Falls back silently for
    by-design exclusions (``dense`` mode, prefill-size Sq) and with a
    warn-once for environment/shape gates. ``quant_bits`` extends the
    compiled-mode shape rule to the operands the quantized kernel
    actually loads: int4's packed payload blocks are ``d // 2`` wide, so
    the lane-multiple rule applies to THAT width — without it, an
    unsupported tiling would surface as a Mosaic compile error instead
    of the dense fallback.

    Compiled head_dim floor is 64, not 128: a 64-wide head block maps
    onto the 128-lane tile as a narrow tile Mosaic lane-pads internally,
    trading lane occupancy on the K/V loads for keeping the live-token
    walk — still far ahead of the masked-dense read that streams the
    whole arena reservation. int4 packs the payload to ``d // 2``, so
    its compiled floor is head_dim 128 (was 256).

    ``paged`` is the page-table kernel, which copies whole pages out of
    the arena in HBM itself: Mosaic refuses a slice of an HBM array whose
    last dimension is not a 128-multiple ("Slice shape along dimension 3
    must be aligned to tiling (128)"), so compiled it takes head_dim
    128-multiples of unquantized pages only. A 64-wide head, an int4
    payload (head_dim / 2 wide) and the ``[.., page_size, 1]`` scale
    pages of any quantized arena resolve to the dense path there
    (tests/test_tpu_compile.py holds both halves by name). ``d`` is the
    width of the key pages as stored and ``dv`` that of the value pages
    (absent: the same): a 192-wide key is refused as it is and taken
    padded to 256 lanes (:func:`paged_key_lanes`), the layout a model
    with such keys gives its pages."""
    if mode == "dense":
        return False, False
    if sq > _DECODE_KERNEL_MAX_SQ:
        return False, False
    if blk <= 0:
        _warn_decode_fallback("no valid kv block size for this cache length")
        return False, False
    if mode == "interpret":
        return True, True
    if jax.default_backend() != "tpu":
        _warn_decode_fallback(f"no TPU backend ({jax.default_backend()} process)")
        return False, False
    if d % 64 != 0 or blk % 8 != 0:
        _warn_decode_fallback(
            f"shape gate: head_dim {d} must be a 64-multiple (64 compiles "
            f"as a lane-padded narrow tile) and the kv block/page size "
            f"{blk} an 8-multiple for the compiled kernel; this dispatch "
            "resolves to the gathered dequant + masked-dense read"
        )
        return False, False
    if quant_bits == 4 and (d // 2) % 64 != 0:
        _warn_decode_fallback(
            f"shape gate: int4 KV packs the payload to head_dim/2 = "
            f"{d // 2}, which must itself be a 64-multiple for the "
            "compiled kernel (head_dim a 128-multiple); this dispatch "
            "resolves to the gathered dequant + masked-dense read"
        )
        return False, False
    dv = d if dv is None else dv
    if paged and (d % 128 != 0 or dv % 128 != 0 or quant_bits):
        _warn_decode_fallback(
            f"shape gate: the paged kernel copies whole pages out of the "
            f"arena in HBM, and Mosaic refuses a slice of an HBM array "
            f"whose last dimension is not a 128-multiple: head_dim {d}"
            + (f" (values {dv})" if dv != d else "")
            + (f", int{quant_bits} KV (its scale pages end in a dimension "
               "of 1)" if quant_bits else "")
            + "; this dispatch resolves to the gathered masked-dense read"
        )
        return False, False
    return True, False


def decode_kernel_active(config, sq: int = 1) -> bool:
    """Would a paged decode dispatch of query width ``sq`` (1 = the decode
    step) on a model with this config run the pallas kernel in this
    process? The serving engine's ``serving/decode_kernel_active`` gauge
    and bench read it — it must mirror :func:`paged_decode_attention`'s gate exactly, or the gauge
    would claim a kernel a fallback path never ran."""
    page_size = getattr(config, "kv_page_size", None)
    if not page_size:
        return False
    mode = resolve_decode_kernel(getattr(config, "decode_kernel", None))
    if mode == "dense":
        return False
    quant_bits = {"int8": 8, "int4": 4}.get(
        getattr(config, "kv_cache_dtype", "bf16"), 0
    )
    _, lanes, dv = cache_entry_widths(config)
    use, _ = _decode_kernel_gate(
        mode, sq, lanes, int(page_size), quant_bits, paged=True, dv=dv)
    return use


def _pick_decode_block(length: int, preferred: Optional[int], interpret: bool) -> int:
    """kv block for the dense-arena decode kernel: the largest candidate
    dividing the cache length. Smaller blocks exit earlier on short live
    lengths; bigger blocks amortize grid overhead — 256 measured best on
    2k-8k arenas (the same trade as ``_pick_block``, at decode's smaller
    working set). Interpret mode (CPU tests) admits tiny blocks the TPU
    tiling rules would reject."""
    cands = ([int(preferred)] if preferred else []) + [512, 256, 128, 64, 32, 16]
    if interpret:
        cands += [8, 4, 2, 1]
    for cand in cands:
        if 0 < cand <= length and length % cand == 0:
            return cand
    return 0


def _fold_row_positions(pos_ref, b, sq, shape, bound=None):
    """Position of each row of a ``[group x Sq, kv]`` score block: row r of
    the fold is query token t = r % sq of slot ``b``. ``sq`` is
    compile-time small (<= _DECODE_KERNEL_MAX_SQ), so the scalar reads
    unroll; ``bound`` caps every position (the paged walk's live length)."""
    def at(t):
        return pos_ref[b, t] if bound is None else jnp.minimum(pos_ref[b, t], bound)

    if sq == 1:
        return jnp.full(shape, at(0), jnp.int32)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0) % sq
    rowpos = jnp.zeros(shape, jnp.int32)
    for t in range(sq):
        rowpos = jnp.where(t_idx == t, at(t), rowpos)
    return rowpos


def _online_softmax_probs(s, m_scr, l_scr, valid=None):
    """Fold one block of masked fp32 scores ``s`` [G, kv] into the running
    max and sum (fp32) and return the block's probabilities [G, kv] with the
    factor [G, 1] that rescales what was accumulated before it. ``valid``
    (the mask ``s`` was made with) where a row may see nothing at all: such
    a row keeps its maximum at NEG_INF, where exp(s - m) is 1, not 0, so its
    masked entries are zeroed by name and its sum stays 0 (a row that sees
    something already underflows to 0 at the exp)."""
    m_prev = m_scr[...][:, :1]
    l_prev = l_scr[...][:, :1]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    l_scr[...] = jnp.broadcast_to(
        l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape
    )
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
    return p, alpha


def _online_softmax_step(s, v, m_scr, l_scr, acc, valid=None):
    """:func:`_online_softmax_probs`, and the block's values ``v`` [kv, D]
    into the accumulator (fp32; the probabilities are cast to the value
    dtype before PV)."""
    p, alpha = _online_softmax_probs(s, m_scr, l_scr, valid)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _decode_kernel_body(maxblk_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                        acc, m_scr, l_scr, *, sm_scale, bk, sq, group,
                        quant_bits=0, out_dtype=None,
                        ks_ref=None, vs_ref=None):
    """Online-softmax accumulation over one slot's kv blocks of the
    dense-arena kernel. Grid is (B, KVH, n_blocks) with the block dim innermost
    ("arbitrary"); blocks past ``maxblk_ref[b]`` (the slot's last live
    block) are skipped — their operand fetch was already elided by the
    clamped index map. Per-element validity is ``kv position <= the query
    row's position``, the exact mask of the dense reference, so parked /
    stale / rolled-back entries inside a live block contribute exactly
    zero probability.

    ``quant_bits`` (8/4) turns on KERNEL-FUSED DEQUANT: ``k_ref``/``v_ref``
    hold int8 payloads (int4 packs two values per byte along head_dim) and
    ``ks_ref``/``vs_ref`` the per-(token, kv-head) fp32 scales; blocks load
    quantized from HBM — the byte shrink compounds with the live-token walk
    — and dequantize in-register via ``utils.quantization.dequantize_kv``,
    the same op sequence the masked-dense reference runs, so the oracle
    contract survives quantization."""
    b, ib = pl.program_id(0), pl.program_id(2)
    nb = pl.num_programs(2)
    g = group * sq

    @pl.when(ib == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    @pl.when(ib <= maxblk_ref[b])
    def _body():
        q = q_ref[0, 0]  # [G, D] — the kv head's query group × Sq rows
        k = k_ref[0, 0]  # [bk, D] (quantized: int8 payload [bk, D or D/2])
        v = v_ref[0, 0]
        if quant_bits:
            from ..utils.quantization import dequantize_kv

            k = dequantize_kv(k, ks_ref[0, 0], quant_bits, out_dtype)
            v = dequantize_kv(v, vs_ref[0, 0], quant_bits, out_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        kvpos = ib * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        rowpos = _fold_row_positions(pos_ref, b, sq, (g, bk))
        s = jnp.where(kvpos <= rowpos, s, NEG_INF)
        _online_softmax_step(s, v, m_scr, l_scr, acc)

    @pl.when(ib == nb - 1)
    def _out():
        l = l_scr[...][:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / safe_l).astype(o_ref.dtype)


def _dense_quant_kernel_entry(maxblk_ref, pos_ref, q_ref, k_ref, v_ref,
                              ks_ref, vs_ref, o_ref, acc, m_scr, l_scr, **kw):
    _decode_kernel_body(maxblk_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                        acc, m_scr, l_scr, ks_ref=ks_ref, vs_ref=vs_ref, **kw)


def _decode_grid_params(interpret: bool):
    # the decode grid is 3-dim (slots, kv-heads, kv-blocks); only the
    # block walk is sequential
    return _grid_params(interpret, ("parallel", "parallel", "arbitrary"))


def _fold_q_heads(q, kvh):
    """[B, H, Sq, D] -> [B, KVH, group*Sq, D]: query heads of one kv head's
    group (plus their Sq rows) become one MXU-friendly block. Pure reshape
    — H is laid out [kv0's group, kv1's group, ...] (the ``h // group``
    BlockSpec convention of the flash kernels)."""
    b, h, sq, d = q.shape
    return q.reshape(b, kvh, (h // kvh) * sq, d)


def _positions_2d(q_positions, b):
    pos = jnp.asarray(q_positions, jnp.int32)
    if pos.ndim == 1:  # [Sq] shared across the batch
        pos = jnp.broadcast_to(pos[None, :], (b, pos.shape[0]))
    return pos


# VMEM the paged decode kernel may spend on its K/V page buffers (two
# halves each, scale pages included), and the most pages one block holds.
# From the chip sweep of PR 25 at 8 kv heads and four rows a product: the
# block size follows from the page's bytes, so a 64-wide head or an int4
# payload doubles the pages within the same budget. PR 43's sweep at the
# other cells' shapes (PERF.md section 6) left it: at 32 kv heads of one row
# (16 pages a block) 64 pages read 82% of the bytes' bound against 44, but
# by making four softmax chains a head of sixteen, which the gathered form
# (_decode_rows_gathered) does away with at 88% with no more VMEM; at 8 and
# at 1 kv heads 128 pages gained a point or none, 32 pages lost seven.
_PAGED_DECODE_VMEM_BUDGET = 8 * 1024 * 1024
_PAGED_DECODE_MAX_BLOCK_PAGES = 64


def _vmem_tile_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes a [rows, cols] slab takes in VMEM: lanes pad to 128, sublanes
    to the dtype's tile (8 rows of 32 bits, 16 of 16, 32 of 8)."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // itemsize)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def _paged_decode_block_pages(kvh: int, ps: int, pd: int, dtype,
                              quant_bits: int, table_len: int,
                              pdv: Optional[int] = None,
                              window_pages: Optional[int] = None,
                              budget: Optional[int] = None,
                              most: Optional[int] = None,
                              page_extra: int = 0) -> int:
    """Pages a block of the paged decode walk holds: the largest power of
    two whose double-buffered K and V pages (all kv heads of a page, plus
    their fp32 scale pages when quantized) fit the VMEM budget, at most
    ``_PAGED_DECODE_MAX_BLOCK_PAGES`` and no more than the page table is
    long. ``pdv`` is the value pages' width where it differs from the
    keys'. ``window_pages``: the most pages a window layer's walk spans;
    one block then holds them all where VMEM allows. ``budget``, ``most``
    and ``page_extra`` (bytes a page costs beside its buffers) are the
    ragged prefill kernel's, which walks the same way."""
    page = kvh * _vmem_tile_bytes(ps, pd, dtype)
    page_v = page if pdv is None else kvh * _vmem_tile_bytes(ps, pdv, dtype)
    if quant_bits:
        page += kvh * _vmem_tile_bytes(ps, 1, jnp.float32)
        page_v += kvh * _vmem_tile_bytes(ps, 1, jnp.float32)
    fit = (budget or _PAGED_DECODE_VMEM_BUDGET) // (2 * (page + page_v) + page_extra)
    cap = max(1, min(fit, most or _PAGED_DECODE_MAX_BLOCK_PAGES, table_len))
    n = 1 << (cap.bit_length() - 1)
    if window_pages is not None:
        n = min(n, 1 << (max(1, window_pages) - 1).bit_length())
    return n


def window_span_pages(window: int, ps: int, sq: int = 1) -> int:
    """The most pages that hold the ``window + sq - 1`` positions a window
    layer's ``sq`` query rows see between them."""
    return (window + sq - 3) // ps + 2 if window + sq > 2 else 1


def paged_decode_block_pages(config, table_len: int) -> int:
    """Pages a block of the paged decode kernel's walk holds for a model
    with this config and a page table ``table_len`` entries long: what the
    serving engine counts ``walked_blocks`` in."""
    if getattr(config, "kv_lora_rank", None) is not None:
        _, lanes, _ = cache_entry_widths(config)  # one entry a token, no value page
        return _paged_decode_block_pages(1, int(config.kv_page_size), lanes, config.dtype, 0, table_len)
    bits = {"int8": 8, "int4": 4}.get(getattr(config, "kv_cache_dtype", "bf16"), 0)
    width = config.head_dim // 2 if bits == 4 else paged_key_lanes(config.head_dim)
    dv = getattr(config, "v_head_dim", None)
    window = getattr(config, "attn_window", None)
    return _paged_decode_block_pages(
        config.num_kv_heads, int(config.kv_page_size), width,
        jnp.int8 if bits else config.dtype, bits, table_len,
        pdv=None if dv in (None, config.head_dim) else dv,
        window_pages=None if window is None else window_span_pages(
            window, int(config.kv_page_size)),
    )


def _decode_rows_gathered(kvh: int, g: int) -> bool:
    """Whether the paged decode kernel runs a block's softmax once over
    every kv head's rows (its ``gathered`` form) and not once a head: where
    a head's ``g`` folded query rows leave most of a sublane tile (8 rows of
    fp32) empty and there are heads to share it. From the chip sweep of
    PR 43 (PERF.md section 6): 32 kv heads x 1 row read 44% of the bytes'
    bound a head at a time and 88% gathered, 16 x 2 64% and 84%, 8 x 4 57%
    and 76%; from 8 rows on a head's softmax works on whole tiles."""
    return kvh > 1 and g < 8


def _decode_kv_heads(config) -> int:
    return 1 if getattr(config, "kv_lora_rank", None) is not None else config.num_kv_heads


def paged_decode_rows_per_product(config) -> int:
    """Query rows one product of a decode step's kernel holds: the query
    heads folded over one kv head (all of them in the latent mode, whose
    one entry a token every head shares)."""
    return config.num_heads // _decode_kv_heads(config)


def paged_decode_gathers_rows(config) -> bool:
    """Whether a decode step's kernel takes its ``gathered`` form for a
    model with this config (``_decode_rows_gathered`` at one query row a
    slot)."""
    return _decode_rows_gathered(_decode_kv_heads(config), paged_decode_rows_per_product(config))


def _paged_decode_kernel(len_ref, pos_ref, table_ref, layer_ref, q_ref, *refs,
                         sm_scale, sq, group, block_pages, quant_bits,
                         out_dtype, window=None, has_sink=False,
                         value_scale=1.0, write=False, latent=0,
                         gathered=False):
    """One slot a grid step; inside, a loop over blocks of ``block_pages``
    consecutive table entries. Every live page of a block comes from the
    arena (left in HBM) by one asynchronous copy that brings all kv heads
    of the page, into one half of a double buffer, while the other half is
    computed: the next block's copies, or the first block of the next slot
    that has live tokens, fly during this block's matmuls. The work is
    ``ceil(live pages / block_pages)`` blocks a slot; a slot with no live
    tokens costs no copy and no matmul, and its output rows are zeros.

    The mathematics is ``_decode_kernel_body``'s: fp32 scores, fp32 online
    softmax and accumulator, probabilities cast to the value dtype before
    PV, validity ``kv position <= row position`` (bounded by the slot's
    live length, beyond which no page was copied), quantized pages
    dequantized in-register by ``utils.quantization.dequantize_kv``.

    ``window`` (a window layer): a row at position p sees kv positions
    ``p - window < c <= p`` only, and the walk starts at the page that
    holds the first position any of the slot's rows sees, so it visits at
    most ``window_span_pages`` pages whatever the context; the pages
    before it may have been given back. ``has_sink``: a further operand
    [KVH, G, 128] holds each row's learned scalar, which starts the running
    maximum with a sum of one and no value. ``value_scale`` multiplies the
    output. The key pages may be wider than the value pages.

    The arena is the layers' stack ``[L, num_pages, KVH, page, D]`` and
    ``layer_ref[0]`` the layer this call reads: a page is ``at[layer,
    page]``. ``write`` (one query row a slot, unquantized pages): two
    further operands hold each slot's new key and value row, and the stack
    is this call's output as well as its input (aliased). Once the block
    with the page of the slot's position is in VMEM, the row goes into it
    at ``position % page`` and the whole page goes back to the arena while
    the block is attended: the step needs no scatter, so the stack is never
    sliced, re-laid out or copied. A slot of live length 0 writes nothing;
    a slot that writes has a live length past its position.

    ``latent`` (latent attention read absorbed, ``paged_latent_attention``):
    there are no value pages, no value buffer and no new value row. A page
    holds one entry a token that every query head shares (one kv head), and
    its first ``latent`` lanes are the value too: a block is copied once
    and attended as keys (all its lanes) and as values (those lanes).

    ``gathered`` (``_decode_rows_gathered``: several kv heads whose folded
    query rows are fewer than a sublane tile): a head's score rows would
    fill an eighth or a half of the tiles its softmax works on, once a head
    a block. The heads' score rows go into one ``[KVH x G, block]`` tile
    instead, the block's softmax runs once over all of them, and each head
    takes its probabilities' rows back out for its PV product: the same
    numbers in the same order, row for row. The running maximum, sum and
    accumulator, the sink and the output are ``[KVH x G, .]`` then."""
    if has_sink:
        sink_ref, refs = refs[0], refs[1:]
    if gathered:
        *refs, s_scr, o_scr = refs
    vnew_ref = v_hbm = vbuf = None
    if latent:
        ks_hbm = vs_hbm = ksbuf = vsbuf = wsems = None
        if write:
            knew_ref, (_, o_ref, k_hbm, kbuf, sems, state, acc, m_scr, l_scr, wsems) = refs[0], refs[1:]
        else:
            k_hbm, o_ref, kbuf, sems, state, acc, m_scr, l_scr = refs
    elif write:
        knew_ref, vnew_ref, refs = refs[0], refs[1], refs[2:]
        # the stack as this call's output: the same buffer on the chip,
        # and the one that holds the rows written so far when interpreted
        (_, _, o_ref, k_hbm, v_hbm,
         kbuf, vbuf, sems, state, acc, m_scr, l_scr, wsems) = refs
        ks_hbm = vs_hbm = ksbuf = vsbuf = None
    elif quant_bits:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         kbuf, vbuf, ksbuf, vsbuf, sems, state, acc, m_scr, l_scr) = refs
    else:
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, state, acc, m_scr, l_scr = refs
        ks_hbm = vs_hbm = ksbuf = vsbuf = None
    b, nslots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    kvh, ps = k_hbm.shape[2], k_hbm.shape[3]
    both = lambda pairs: list(pairs[:1] if latent else pairs)  # keys and values, or the one entry
    g = group * sq
    bk = block_pages * ps  # kv positions a block spans
    live = len_ref[b]

    def first_page(slot):
        """Table entry a slot's walk starts at: 0, or the page that holds
        the first position its earliest row sees through the window."""
        if window is None:
            return 0
        return jnp.maximum(len_ref[slot] - sq - window + 1, 0) // ps

    def walk_pages(slot):
        return (len_ref[slot] + ps - 1) // ps - first_page(slot)

    p0 = first_page(b)
    n_pages = walk_pages(b)
    n_blocks = (n_pages + block_pages - 1) // block_pages

    def page_copies(slot, blk, half, j):
        page = table_ref[slot, first_page(slot) + blk * block_pages + j]
        pairs = both([(k_hbm, kbuf, 0), (v_hbm, vbuf, 1)])
        if quant_bits:
            pairs += [(ks_hbm, ksbuf, 0), (vs_hbm, vsbuf, 1)]
        return [
            pltpu.make_async_copy(src.at[layer, page], dst.at[half, j], sems.at[half, which])
            for src, dst, which in pairs
        ]

    def for_each_live_page(slot, blk, half, act):
        count = jnp.minimum(block_pages, walk_pages(slot) - blk * block_pages)

        def one(j, _):
            for copy in page_copies(slot, blk, half, j):
                act(copy)
            return _

        jax.lax.fori_loop(0, count, one, None)

    def new_row_page(blk, half):
        """Whether block ``blk`` holds the page of the slot's position,
        where in the block, and that page's copies back to the arena (all
        kv heads of the page, keys and values)."""
        entry = pos_ref[b, 0] // ps
        j = entry - p0 - blk * block_pages
        page = table_ref[b, entry]
        back = [
            pltpu.make_async_copy(buf.at[half, j], dst.at[layer, page], wsems.at[which])
            for buf, dst, which in both(((kbuf, k_hbm, 0), (vbuf, v_hbm, 1)))
        ]
        return (j >= 0) & (j < block_pages), j, back

    def start(slot, blk, half):
        for_each_live_page(slot, blk, half, lambda copy: copy.start())

    def wait(slot, blk, half):
        for_each_live_page(slot, blk, half, lambda copy: copy.wait())

    @pl.when(b == 0)
    def _first():
        # state: [half the next block lands in, 1 if this slot's first
        # block was started by the slot before it]. The buffers start as
        # zeros: pages past a slot's frontier are never copied, and what
        # they leave in a block's tail is masked to probability zero,
        # which only holds against finite values.
        state[0] = 0
        state[1] = 0
        for buf in (kbuf, vbuf, ksbuf, vsbuf):
            if buf is not None:
                buf[...] = jnp.zeros_like(buf)

    @pl.when(n_blocks == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _walk():
        first_half = state[0]

        @pl.when(state[1] == 0)
        def _():
            start(b, 0, first_half)

        if has_sink:
            m_scr[...] = sink_ref[...]
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

        def block(ib, half):
            other = 1 - half

            @pl.when(ib + 1 < n_blocks)
            def _():
                start(b, ib + 1, other)

            @pl.when(ib + 1 == n_blocks)
            def _():
                nxt = jax.lax.fori_loop(
                    b + 1, nslots,
                    lambda s_, found: jnp.where(
                        (found == nslots) & (len_ref[s_] > 0), s_, found),
                    nslots,
                )

                @pl.when(nxt < nslots)
                def _():
                    start(nxt, 0, other)

                state[1] = (nxt < nslots).astype(jnp.int32)

            wait(b, ib, half)
            if write:
                here, j_new, back = new_row_page(ib, half)

                @pl.when(here)
                def _():
                    # the new rows into their page (a select over the page:
                    # no store at a row that is not a tile's first), and
                    # the page on its way back while the block is attended
                    off = pos_ref[b, 0] % ps
                    for buf, new_ref in both(((kbuf, knew_ref), (vbuf, vnew_ref))):
                        page = buf[half, j_new]  # [KVH, page, D]
                        row = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
                        new = jnp.broadcast_to(new_ref[0], page.shape)
                        buf[half, j_new] = jnp.where(row == off, new, page)
                    for copy in back:
                        copy.start()
            # row r of a fold is query token r % sq, of one head's rows and
            # of all heads' rows alike (a head has group x sq of them)
            rows = kvh * g if gathered else g
            kvpos = (p0 * ps + ib * bk
                     + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1))
            # a row sees kv position c iff c <= its own position, and never
            # past the live length: beyond it no page was copied
            rowpos = _fold_row_positions(pos_ref, b, sq, (rows, bk), bound=live - 1)
            valid = kvpos <= rowpos
            if window is not None:
                valid = valid & (rowpos - kvpos < window)

            def load(buf, sbuf, h_):
                x = buf[half, :, h_]  # [block_pages, ps, pd]
                if not quant_bits:
                    return x.reshape(bk, x.shape[-1])
                from ..utils.quantization import dequantize_kv

                # widen before the pages merge into rows: a page is a
                # whole number of 32-bit sublane tiles, not of 8-bit ones
                x = x.astype(jnp.int32).reshape(bk, x.shape[-1])
                return dequantize_kv(
                    x, sbuf[half, :, h_].reshape(bk, 1), quant_bits, out_dtype)

            def scores(h_, k):
                q = q_ref[0, h_]  # [G, D]: the kv head's query group x Sq rows
                return jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale

            def head(h_):
                k = load(kbuf, ksbuf, h_)
                v = k[:, :latent] if latent else load(vbuf, vsbuf, h_)
                s = jnp.where(valid, scores(h_, k), NEG_INF)
                _online_softmax_step(s, v, m_scr.at[h_], l_scr.at[h_], acc.at[h_])

            # eight heads at a time as straight-line code: their matmuls
            # and softmaxes then overlap (1.2-2.3x on the chip at 8 kv
            # heads against a loop over single heads; PR 43's sweep at 32
            # kv heads of one row: 16 / 32 together read 45.0 / 49.6% of
            # the bytes' bound against 43.9 in eights). Gathered, all of
            # them: a head's rows lie at an offset into the shared tile
            # that Mosaic has to know when it compiles (the same sweep:
            # 88.2% with 32 heads together, 86.8 in eights at offsets of
            # whole tiles)
            together = kvh if gathered else next(c for c in (8, 4, 2, 1) if kvh % c == 0)

            def for_each_head(one):
                def heads(i, _):
                    for j in range(together):
                        one(i * together + j)
                    return _

                if together == kvh:  # no loop at all: what the chip sweep timed
                    heads(0, None)
                else:
                    jax.lax.fori_loop(0, kvh // together, heads, None)

            if gathered:  # several kv heads: never the latent mode's one
                def mine(h_):  # the head's rows of the tile every head shares
                    return pl.ds(h_ * g, g)

                def put_scores(h_):
                    s_scr[mine(h_), :] = scores(h_, load(kbuf, ksbuf, h_))

                def put_values(h_):
                    v = load(vbuf, vsbuf, h_)
                    o_scr[mine(h_), :] = jax.lax.dot_general(
                        s_scr[mine(h_), :].astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )

                for_each_head(put_scores)
                p, alpha = _online_softmax_probs(
                    jnp.where(valid, s_scr[...], NEG_INF), m_scr, l_scr)
                s_scr[...] = p
                for_each_head(put_values)
                acc[...] = acc[...] * alpha + o_scr[...]
            else:
                for_each_head(head)
            if write:
                # the page is back before its half of the buffer is filled again
                @pl.when(here)
                def _():
                    for copy in back:
                        copy.wait()
            return other

        state[0] = jax.lax.fori_loop(0, n_blocks, block, first_half)
        # a row's own position is valid for it, so the sum is never zero
        out = acc[...] / l_scr[...][..., :1]
        if value_scale != 1.0:
            out = out * value_scale
        o_ref[0] = out.astype(o_ref.dtype)


def _paged_decode_kernel_call(q, k_pages, v_pages, page_table, pos, lengths,
                              sm_scale, interpret, k_scale=None,
                              v_scale=None, quant_bits=0, window=None,
                              sink=None, value_scale=1.0, layer=0,
                              k_new=None, v_new=None, latent=0):
    """``k_pages`` / ``v_pages`` (and the scale pages) are the layers' stack
    ``[L, num_pages, KVH, page, D]`` and ``layer`` the one to read. With
    ``k_new`` / ``v_new`` ``[B, KVH, 1, D]`` the call also writes each
    slot's new row (the kernel's ``write``) and returns ``(out, k_pages,
    v_pages)``, the stacks updated in place. ``latent``: ``v_pages`` and
    ``v_new`` are None, the values are the first ``latent`` lanes of the
    one stack, the custom call is named ``mla_attn`` and a write returns
    ``(out, k_pages)``."""
    b, h, sq, d = q.shape
    _, _, kvh, ps, pd = k_pages.shape  # pd: payload width (d, or d/2 packed int4)
    pdv = latent or v_pages.shape[-1]
    dv = 2 * pdv if quant_bits == 4 else pdv  # the output's width
    group = h // kvh
    g = group * sq
    write = k_new is not None
    if write and (sq != 1 or quant_bits):
        raise ValueError(
            "the paged decode kernel writes one unquantized row a slot; "
            f"got {sq} query rows, int{quant_bits} pages")
    n = _paged_decode_block_pages(
        kvh, ps, pd, k_pages.dtype, quant_bits, page_table.shape[1],
        pdv=None if pdv == pd else pdv,
        window_pages=None if window is None else window_span_pages(window, ps, sq))
    q_r = _fold_q_heads(q, kvh)
    gathered = _decode_rows_gathered(kvh, g)
    # the rows the kernel keeps its softmax state, its accumulator and its
    # output by: a tile a kv head, or every head's rows in one
    folded = (kvh * g,) if gathered else (kvh, g)
    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=sm_scale, sq=sq, group=group,
        block_pages=n, quant_bits=quant_bits, out_dtype=q.dtype,
        window=window, has_sink=sink is not None, value_scale=value_scale,
        write=write, latent=latent, gathered=gathered,
    )

    def per_slot(*block):
        return pl.BlockSpec((1,) + block, lambda b_, ln, po, tb, ly: (b_,) + (0,) * len(block))

    arena = pl.BlockSpec(memory_space=pl.ANY)
    in_specs, operands = [per_slot(kvh, g, d)], [q_r]
    if sink is not None:
        # row r of a kv head's fold is query head r // sq of its group
        rows = jnp.repeat(sink.astype(jnp.float32).reshape(kvh, group), sq, axis=1)
        operands.append(jnp.broadcast_to(rows.reshape(folded)[..., None], (*folded, 128)))
        in_specs.append(pl.BlockSpec((*folded, 128), lambda b_, ln, po, tb, ly: (0,) * (len(folded) + 1)))
    arenas = [k_pages] if latent else [k_pages, v_pages]
    if write:
        news = [k_new] if latent else [k_new, v_new]
        operands += [x.astype(a.dtype) for x, a in zip(news, arenas)]
        in_specs += [per_slot(kvh, 1, a.shape[-1]) for a in arenas]
    scalars = (lengths.astype(jnp.int32), pos, page_table.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1))
    first_arena = len(scalars) + len(operands)
    operands += arenas
    buffers = [pltpu.VMEM((2, n, kvh, ps, a.shape[-1]), a.dtype) for a in arenas]
    if quant_bits:
        # per-(page, kv-head, token) fp32 scales ride the same walk
        operands += [k_scale, v_scale]
        buffers += [pltpu.VMEM((2, n, kvh, ps, 1), jnp.float32)] * 2
    in_specs += [arena] * (4 if quant_bits else len(arenas))
    out_specs = per_slot(*folded, dv)
    out_shape = jax.ShapeDtypeStruct((b, *folded, dv), q.dtype)
    scratch = buffers + [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((2,), jnp.int32),
        _vmem((*folded, dv)), _vmem((*folded, 128)), _vmem((*folded, 128)),
    ]
    aliases = {}
    if write:
        out_specs = [out_specs] + [arena] * len(arenas)
        out_shape = [out_shape] + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arenas]
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
        aliases = {first_arena + i: 1 + i for i in range(len(arenas))}
    if gathered:
        # a block's scores, then its probabilities, and its PV products, every head's rows
        scratch += [_vmem((kvh * g, n * ps)), _vmem((kvh * g, dv))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    # the slots run in order: each starts the next one's first copies
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # its own name in the device trace: its roofline is not attn's
        **({"name": "mla_attn"} if latent else {}),
        **_grid_params(interpret, ("arbitrary",)),
    )(*scalars, *operands)
    if write:
        out, *arenas = out
        return (out.reshape(b, h, sq, dv), *arenas)
    return out.reshape(b, h, sq, dv)


def _dense_decode_kernel_call(q, k, v, pos, sm_scale, bk, interpret,
                              k_scale=None, v_scale=None, quant_bits=0):
    b, h, sq, d = q.shape
    kvh, length, pd = k.shape[1], k.shape[2], k.shape[3]
    group = h // kvh
    g = group * sq
    q_r = _fold_q_heads(q, kvh)
    maxblk = (jnp.max(pos, axis=1) // bk).astype(jnp.int32)
    entry = _dense_quant_kernel_entry if quant_bits else _decode_kernel_body
    kernel = functools.partial(
        entry, sm_scale=sm_scale, bk=bk, sq=sq, group=group,
        quant_bits=quant_bits, out_dtype=q.dtype,
    )

    def _kv_spec(width):
        return pl.BlockSpec(
            (1, 1, bk, width),
            lambda b_, h_, ib, mb, po: (b_, h_, jnp.minimum(ib, mb[b_]), 0),
        )

    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b_, h_, ib, mb, po: (b_, h_, 0, 0)),
        _kv_spec(pd),
        _kv_spec(pd),
    ]
    operands = [q_r, k, v]
    if quant_bits:
        in_specs += [_kv_spec(1), _kv_spec(1)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, length // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, ib, mb, po: (b_, h_, 0, 0)),
        scratch_shapes=[_vmem((g, d)), _vmem((g, 128)), _vmem((g, 128))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        **_decode_grid_params(interpret),
    )(maxblk, pos, *operands)
    return out.reshape(b, h, sq, d)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: jax.Array,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    block_kv: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    kv_quant_bits: int = 0,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    value_scale: float = 1.0,
) -> jax.Array:
    """Masked KV-cache decode attention with per-row validity.

    q: [B, H, Sq, D]; k/v: [B, KVH, L, D] — the full (static-length) cache
    arena, already containing the query rows' own K/V. ``q_positions`` is
    the GLOBAL position of each query row: shape [Sq] (shared across the
    batch — the single-stream decode/chunked-prefill case) or [B, Sq]
    (per-slot positions — the continuous-batching case, where every batch
    row is an independent request at its own cache depth). A query attends
    cache slot c iff ``c <= its position``, so per-slot cache lengths are
    respected and slots beyond a request's frontier (stale garbage from a
    previous occupant, padding from a bucketed prefill chunk) contribute
    exactly zero probability.

    Dispatch: at decode widths (Sq <= 16) the length-aware pallas kernel
    reads only the live kv blocks (HBM traffic ∝ live tokens, not L) on
    TPU — or through the interpreter under ``impl='interpret'`` — per
    :func:`resolve_decode_kernel` (``impl``, default "paged" with a warn-once dense fallback off-TPU). Prefill-size
    chunks and the ``dense`` mode run the masked-dense XLA path, which
    stays the bit-exactness reference. ``block_kv`` tunes the kernel's kv
    block (must divide L; default: largest of 512..16 that does).

    ``kv_quant_bits`` (8/4, with ``k_scale``/``v_scale`` [B, KVH, L, 1]
    fp32): k/v hold int8 payloads (int4 packed two-per-byte along D) — the
    kernel path dequantizes IN-REGISTER after the quantized HBM read; the
    masked-dense path runs the reference ``dequantize_kv`` first and stays
    the exactness oracle.

    ``window`` / ``sink`` / ``value_scale`` and a value width other than
    the key width are :func:`mha_reference`'s; the dense-arena kernel has
    none of them, so a layer that states one reads masked-dense here (the
    paged kernel, :func:`paged_decode_attention`, has them all).
    """
    mode = resolve_decode_kernel(impl)
    sq, d = q.shape[2], q.shape[3]
    if kv_quant_bits and (k_scale is None or v_scale is None):
        raise ValueError("kv_quant_bits needs k_scale and v_scale")
    if (window is not None or sink is not None or value_scale != 1.0
            or v.shape[-1] != k.shape[-1]):
        mode = "dense"
    if mode != "dense":
        bk = _pick_decode_block(k.shape[2], block_kv, mode == "interpret")
        if block_kv and bk and bk != int(block_kv):
            _warn_once(
                f"block_kv {block_kv}/{k.shape[2]}",
                "block_kv %s does not divide the cache length "
                "%s; the dense-arena decode kernel is using block %s "
                "instead — pick a divisor to make it effective.",
                block_kv, k.shape[2], bk,
            )
        use, interpret = _decode_kernel_gate(mode, sq, d, bk, kv_quant_bits)
        if use:
            scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
            pos = _positions_2d(q_positions, q.shape[0])
            return _dense_decode_kernel_call(
                q, k, v, pos, scale, bk, interpret,
                k_scale=k_scale, v_scale=v_scale, quant_bits=kv_quant_bits,
            )
    if kv_quant_bits:
        from ..utils.quantization import dequantize_kv

        k = dequantize_kv(k, k_scale, kv_quant_bits, q.dtype)
        v = dequantize_kv(v, v_scale, kv_quant_bits, q.dtype)
    kv_pos = jnp.arange(k.shape[2])
    seen = kv_pos <= q_positions[..., None]  # [Sq, L] or [B, Sq, L]
    if window is not None:
        seen = seen & (q_positions[..., None] - kv_pos < window)
    bias = jnp.where(seen, 0.0, NEG_INF)
    # [Sq] shared positions -> [1, 1, Sq, L]; [B, Sq] per-slot -> [B, 1, Sq, L]
    bias = bias[None, None] if q_positions.ndim == 1 else bias[:, None]
    return mha_reference(q, k, v, causal=False, sm_scale=sm_scale, bias=bias,
                         sink=sink, value_scale=value_scale)


def gather_kv_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize per-slot dense K (or V) from a paged arena.

    ``pages``: [num_pages, KVH, page_size, D] physical pages; ``page_table``:
    [B, P] int32 page ids per slot (row p of the result's length axis is
    global position p: the table is position-ordered, so ``page_table[b, c]``
    holds positions ``[c*page_size, (c+1)*page_size)``). Returns
    [B, KVH, P*page_size, D]. Duplicate table entries (the parking page
    padding unallocated tail entries) are fine — their rows sit beyond the
    slot's frontier and the decode mask zeroes them.
    """
    g = pages[page_table]                      # [B, P, KVH, page_size, D]
    g = jnp.swapaxes(g, 1, 2)                  # [B, KVH, P, page_size, D]
    b, kvh, p, ps, d = g.shape
    return g.reshape(b, kvh, p * ps, d)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    *,
    page_table: jax.Array,
    q_positions: jax.Array,
    kv_lengths: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    kv_quant_bits: int = 0,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    value_scale: float = 1.0,
    layer: Optional[jax.Array] = None,
    k_new: Optional[jax.Array] = None,
    v_new: Optional[jax.Array] = None,
):
    """Decode attention reading K/V through a per-slot page table.

    q: [B, H, Sq, D]; k_pages/v_pages: [num_pages, KVH, page_size, D];
    ``page_table`` [B, P] int32; ``q_positions`` [B, Sq] global positions.
    ``kv_lengths`` [B] int32 is each slot's count of live tokens, the bound
    of the kernel's walk (absent: the slot's last row position + 1). A slot
    given 0 is not walked at all and its output rows are zeros: the serving
    engine's way to say "inactive" for a slot whose parked write position
    would otherwise read as a request at the end of the cache. The mask is
    ``kv position <= row position`` either way, and the dense path ignores
    the lengths: an inactive slot's rows are discarded by the caller.

    On TPU (or under ``impl='interpret'``) the pallas paged kernel walks
    each slot's live pages DIRECTLY from the physical arena — the HBM read
    per step is the slot's live tokens (page-rounded), not its whole
    ``P * page_size`` reservation, which is the decode-bandwidth lever at
    high occupancy with mixed lengths. Otherwise (``impl='dense'`` / no
    TPU backend — warn-once) the
    gather maps each slot's pages back into position order and the read is
    exactly :func:`decode_attention`'s masked-dense path: the CPU-sim
    fallback and the bit-exactness reference the kernel is asserted
    against (tests/test_decode_kernel.py).

    ``kv_quant_bits`` (8/4, with ``k_scale``/``v_scale``
    [num_pages, KVH, page_size, 1] fp32 — a small parallel scales arena
    beside the pages): the pages hold int8 payloads and the kernel
    dequantizes in-register after the quantized HBM read, so the
    live-token bandwidth win compounds with the 2-4x byte shrink. The
    gather fallback dequantizes with the reference ``dequantize_kv`` —
    identical quantized inputs produce the oracle's exact values.

    The value pages may be narrower than the key pages ([.., Dv] against
    [.., D]; the output is [B, H, Sq, Dv]). ``window``: a row at position
    p sees positions ``p - window < c <= p``, and the kernel's walk starts
    at the page that holds the first of them, so the table's entries
    before it are never read (the engine gives those pages back).
    ``sink`` [H] and ``value_scale`` are :func:`mha_reference`'s.

    ``layer`` (with ``k_new`` / ``v_new`` [B, KVH, 1, D], the step's new
    rows, not yet in the pages): the pages are the layers' stack
    ``[L, num_pages, KVH, page_size, D]`` and the kernel puts each live
    slot's row at its position itself; the result is ``(out, k_pages,
    v_pages)``, the stacks updated in place (see ``_paged_decode_kernel``).
    That is the kernel's alone: a caller asks :func:`decode_kernel_active`
    first and keeps its own scatter where the dense path is taken.
    """
    mode = resolve_decode_kernel(impl)
    if kv_quant_bits and (k_scale is None or v_scale is None):
        raise ValueError("kv_quant_bits needs k_scale and v_scale")
    extras = {"window": window, "sink": sink, "value_scale": value_scale}
    if mode != "dense":
        sq, d = q.shape[2], q.shape[3]
        use, interpret = _decode_kernel_gate(
            mode, sq, d, k_pages.shape[-2], kv_quant_bits, paged=True,
            dv=v_pages.shape[-1] * (2 if kv_quant_bits == 4 else 1),
        )
        if use:
            scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
            pos = _positions_2d(q_positions, q.shape[0])
            if kv_lengths is None:
                kv_lengths = jnp.max(pos, axis=1) + 1
            pages = (k_pages, v_pages, k_scale, v_scale)
            if layer is None:  # one layer's pages: a stack of one
                pages, layer = [None if x is None else x[None] for x in pages], 0
            k_pages, v_pages, k_scale, v_scale = pages
            return _paged_decode_kernel_call(
                q, k_pages, v_pages, page_table, pos, kv_lengths, scale, interpret,
                k_scale=k_scale, v_scale=v_scale, quant_bits=kv_quant_bits,
                layer=layer, k_new=k_new, v_new=v_new, **extras,
            )
    if layer is not None:
        raise ValueError(
            "paged_decode_attention over the layers' stack is the kernel's "
            "path; this dispatch resolves to the dense read "
            "(decode_kernel_active says so beforehand)")
    k_full = gather_kv_pages(k_pages, page_table)
    v_full = gather_kv_pages(v_pages, page_table)
    if kv_quant_bits:
        return decode_attention(
            q, k_full, v_full, q_positions=q_positions, sm_scale=sm_scale,
            impl="dense",
            k_scale=gather_kv_pages(k_scale, page_table),
            v_scale=gather_kv_pages(v_scale, page_table),
            kv_quant_bits=kv_quant_bits, **extras,
        )
    return decode_attention(
        q, k_full, v_full, q_positions=q_positions, sm_scale=sm_scale,
        impl="dense", **extras,
    )


def paged_latent_attention(
    q: jax.Array,
    pages: jax.Array,
    *,
    page_table: jax.Array,
    q_positions: jax.Array,
    latent: int,
    sm_scale: float,
    kv_lengths: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    layer: Optional[jax.Array] = None,
    new: Optional[jax.Array] = None,
):
    """Latent attention read **absorbed** through a per-slot page table: a
    decode step of one model with ``kv_lora_rank``.

    ``pages`` [num_pages, 1, page_size, W] hold one entry a token that all
    query heads share, ``[latent | rotated key | zero lanes]`` (W the stored
    width, :func:`cache_entry_widths`), and its first ``latent`` lanes are
    the value as well: there is no second leaf and no second read. ``q``
    [B, H, Sq, W] are the queries in that layout (each head's unrotated
    part already multiplied by its key up-projection, its rotated part,
    zeros), so a score is one product over W; the result [B, H, Sq, latent]
    is the softmax-weighted latents, which the caller multiplies by each
    head's value up-projection. ``sm_scale`` is stated: it is that of the
    expanded head width (and YaRN's), not ``W^-1/2``.

    The kernel is :func:`paged_decode_attention`'s in its ``latent`` mode
    (one kv head, 64 query heads a page: 121 FLOP a byte at the published
    widths), named ``mla_attn`` in the device trace; the walk, the mask,
    ``kv_lengths`` and the dense fallback (a gather of each slot's pages
    and :func:`decode_attention`'s masked-dense read) are its own.
    ``layer`` with ``new`` [B, 1, 1, W]: the pages are the layers' stack and
    the kernel writes each live slot's new entry itself; the result is
    ``(out, pages)``."""
    mode = resolve_decode_kernel(impl)
    if mode != "dense":
        use, interpret = _decode_kernel_gate(
            mode, q.shape[2], pages.shape[-1], pages.shape[-2], 0, paged=True, dv=latent)
        if use:
            pos = _positions_2d(q_positions, q.shape[0])
            if kv_lengths is None:
                kv_lengths = jnp.max(pos, axis=1) + 1
            if layer is None:  # one layer's pages: a stack of one
                pages, layer = pages[None], 0
            return _paged_decode_kernel_call(
                q, pages, None, page_table, pos, kv_lengths, sm_scale, interpret,
                layer=layer, k_new=new, latent=latent)
    if layer is not None:
        raise ValueError(
            "paged_latent_attention over the layers' stack is the kernel's path; this "
            "dispatch resolves to the dense read (decode_kernel_active says so beforehand)")
    entries = gather_kv_pages(pages, page_table)
    return decode_attention(q, entries, entries[..., :latent], q_positions=q_positions,
                            sm_scale=sm_scale, impl="dense")


def ragged_latent_attention(
    q: jax.Array,
    new: jax.Array,
    pages: jax.Array,
    *,
    page_table: jax.Array,
    row_slot: jax.Array,
    row_pos: jax.Array,
    slot_hist: jax.Array,
    latent: int,
    sm_scale: float,
    impl: Optional[str] = None,
    token_block: Optional[int] = None,
    layer: Optional[jax.Array] = None,
):
    """Latent attention read **absorbed** for a packed ragged prefill:
    :func:`ragged_prefill_attention`'s contract for the rows, the table and
    the histories, over entries as :func:`paged_latent_attention` describes
    them. ``q`` [1, H, CAP, W], ``new`` [1, 1, CAP, W] the pack's own
    entries, ``pages`` [num_pages, 1, page_size, W]. A row attends its
    slot's cached entries and the pack's rows before it, both as they are
    stored: no cached latent is up-projected and no key or value of a head
    is made. Returns ``(out [1, H, CAP, latent], entries [CAP, 1, W])`` for
    the caller's scatter, or with ``layer`` (the pages the layers' stack)
    ``(out, pages)``, the pack's entries written by the kernel. The kernel
    is the ragged prefill kernel in its ``latent`` mode, named
    ``mla_prefill_attn`` in the device trace; the dense reference is
    ``_ragged_prefill_reference`` with the values cut from the entries."""
    mode = resolve_prefill_kernel(impl)
    b, h, cap, d = q.shape
    if b != 1:
        raise ValueError(f"packed ragged prefill takes batch 1, got {b}")
    bt = int(token_block or _PREFILL_TOKEN_BLOCK)
    if cap % bt:
        raise ValueError(f"packed capacity {cap} must be a multiple of the token block {bt}")
    row_slot = jnp.asarray(row_slot, jnp.int32)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    slot_hist = jnp.asarray(slot_hist, jnp.int32)
    if mode != "dense":
        use, interpret = _prefill_kernel_gate(mode, pages.shape[-1], pages.shape[-2], bt, 0, dv=latent)
        if use:
            return _ragged_prefill_kernel_call(
                q, new, None, pages, None, page_table, row_slot, row_pos, slot_hist,
                sm_scale, bt, interpret, layer=layer, latent=latent)
    if layer is not None:
        raise ValueError(
            "ragged_latent_attention over the layers' stack is the kernel's path; this "
            "dispatch resolves to the dense reference (prefill_writes_pages says so beforehand)")
    out, payload, *_ = _ragged_prefill_reference(
        q, new, new[..., :latent], pages, pages[..., :latent], page_table, row_slot, row_pos,
        slot_hist, sm_scale)
    return out, payload


# ---------------------------------------------------------------------------
# pallas ragged prefill kernel over the paged arena (ROADMAP item 3)
#
# The prefill counterpart of the paged decode kernel above: ONE dispatch
# packs the fresh tails of every pending admission into a fixed token
# capacity (rows are (token, query-head-group) pairs; padding is only up
# to the token-block granule), and the kv sweep of a token block is
#
#   [arena pages first .. ceil(hist/page)) -> packed fresh blocks first .. i]
#
# with flash online softmax across both phases. The layer's pages stay in
# HBM; a grid step is one token block, and inside it the block's slot's
# live table entries are walked in blocks of many pages, each page (all its
# kv heads) brought by one asynchronous copy into one half of a double
# buffer while the other half is attended, as the decode kernel does. So
# the work is the live pages: a padding block (slot -1) or a slot with no
# history copies nothing, a window layer starts at the first page its
# first row sees, and the fresh phase visits only the packed blocks of the
# block's own slot at or before it. Prefix-aware skipping is structural:
# positions already served by a prefix-cache / tier hit are never
# re-attended as QUERIES (only the fresh tail packs rows).
# The write is the kernel's too where the arena is the layers' stack
# carried through the layer scan (``models/decoder.arena_in_place``): a
# token block's fresh rows go into the slot's pages through the table and
# the pages back to the arena, the stack aliased to the output. Else (a
# quantized cache, pages of no whole lanes) the caller scatters, and
# quantize-on-write is fused: the kernel quantizes each fresh K/V block
# in-register (the exact ``utils.quantization.quantize_kv`` op
# sequence), emits payload+scale outputs for the caller's single arena
# scatter, and attends the tail over the DEQUANTIZED values — the same
# read the cache serves later, so packed prefill stays bit-compatible
# with the chunked dense oracle.
# ---------------------------------------------------------------------------

_PREFILL_KERNEL_MODES = ("ragged", "dense", "interpret")
# default q token block: one sublane tile; the packer pads each tail to
# this granule (vs a whole prefill bucket on the chunked path)
_PREFILL_TOKEN_BLOCK = 8
# the tallest block a serving engine packs with: a grid step is one token
# block, and a taller one shares each page it walks among more rows. From
# the chip sweep of PR 25 at the serving cells' shape (8 -> 16 -> 32 -> 64
# rows cut the chat cell's time to first token 4,455 -> 1,416 ms under the
# grid form that stood then); not swept again under the walk.
_PREFILL_TOKEN_BLOCK_MAX = 64
# VMEM the prefill kernel may spend on a block of its walk: the K/V page
# buffers (two halves each, scale pages included) and the two fp32 score
# tiles a head's rows take over the block's positions. From the chip sweep
# of PR 35 at the serving cells' shapes (PERF.md section 6): blocks of 32
# and 64 pages ran alike, 16 up to 1.6 times slower over a long history.
# The kv heads of a step are a loop: as straight-line code they ran 10-15%
# faster over a long history (1% of a pack) and compiled 3-5 times longer.
_PREFILL_VMEM_BUDGET = 8 * 1024 * 1024
_PREFILL_MAX_BLOCK_PAGES = 32
# position of a kv row that no query row may see (every real one is less)
_UNSEEN = 2 ** 30
# latent attention's packed kernel folds a token block's query heads into
# groups of at most this many rows (tokens x heads of the group), each
# attended against the one entry a token: 64 heads x 64 rows in one block
# would be 4,096 rows, a 8 MB accumulator and 8 MB of scores a block of the walk
_LATENT_GROUP_ROWS = 512


def prefill_token_block(capacities) -> int:
    """Token block of a packed ragged dispatch compiled at these row
    ``capacities``: as tall as the smallest allows (a taller block would
    only pad it) while the largest still holds four, so that several
    tails pack into one dispatch; in sublane tiles, at most
    ``_PREFILL_TOKEN_BLOCK_MAX``. A slot's tail pads to this granule."""
    rows = min(min(capacities), max(capacities) // 4, _PREFILL_TOKEN_BLOCK_MAX)
    return max(_PREFILL_TOKEN_BLOCK, rows // _PREFILL_TOKEN_BLOCK * _PREFILL_TOKEN_BLOCK)


def resolve_prefill_kernel(impl: Optional[str] = None) -> str:
    """Resolve the prefill-attention implementation choice: the explicit
    ``impl`` (``DecoderConfig.prefill_kernel``), else ``"ragged"`` (the
    packed pallas kernel, with a warn-once dense fallback off-TPU).
    ``"interpret"`` runs the same kernel through the pallas interpreter —
    the CPU test/CI mode, so tier-1 asserts the identical kernel."""
    mode = impl or "ragged"
    if mode not in _PREFILL_KERNEL_MODES:
        raise ValueError(
            f"prefill_kernel must be one of "
            f"{_PREFILL_KERNEL_MODES}, got {mode!r}"
        )
    return mode


def _warn_prefill_fallback(reason: str):
    """Warn-once per distinct reason: the ragged prefill kernel was
    requested (or defaulted) but this process runs the packed dispatch on
    its dense reference, which gathers each slot's whole cache."""
    _warn_once(
        "prefill:" + reason,
        "ragged prefill kernel unavailable (%s); the packed prefill "
        "dispatch runs its dense reference, which gathers each slot's "
        "whole cache reservation. Set DecoderConfig.prefill_kernel="
        "'dense' to silence, or 'interpret' to run the kernel through "
        "the pallas interpreter.",
        reason,
    )


def _prefill_kernel_gate(mode: str, d: int, ps: int, bt: int,
                         quant_bits: int = 0, dv: Optional[int] = None):
    """(use_kernel, interpret) for one ragged prefill dispatch. Compiled,
    the kernel takes key and value widths (``d``, ``dv``; absent: the
    same) that are 64-multiples, page size and token block 8-multiples
    (sublane tiles), and an int4 payload (``d // 2`` wide) that is itself
    a 64-multiple: every KV storage and width the grid form of before
    PR 35 took (tests/test_tpu_compile.py compiles each by name). It
    copies whole pages out of the arena in HBM as the paged decode kernel
    does, which Mosaic takes in whole lanes only, so a page narrower than
    a 128-multiple and the scale pages reach it as lane-dense views made
    before the call (``_ragged_prefill_kernel_call``)."""
    if mode == "dense":
        return False, False
    if ps <= 0 or bt <= 0:
        _warn_prefill_fallback("no valid page/token block size")
        return False, False
    if mode == "interpret":
        return True, True
    if jax.default_backend() != "tpu":
        _warn_prefill_fallback(f"no TPU backend ({jax.default_backend()} process)")
        return False, False
    dv = d if dv is None else dv
    if d % 64 != 0 or dv % 64 != 0 or ps % 8 != 0 or bt % 8 != 0:
        _warn_prefill_fallback(
            f"shape gate: head_dim {d}"
            + (f" (values {dv})" if dv != d else "")
            + f" must be a 64-multiple and the page size {ps} / token "
            f"block {bt} 8-multiples for the compiled kernel; the packed "
            "dispatch runs its dense reference"
        )
        return False, False
    if quant_bits == 4 and ((d // 2) % 64 != 0 or (dv // 2) % 64 != 0):
        _warn_prefill_fallback(
            f"shape gate: int4 KV packs the payload to head_dim/2 = "
            f"{d // 2}, which must itself be a 64-multiple for the "
            "compiled kernel (head_dim a 128-multiple); the packed "
            "dispatch runs its dense reference"
        )
        return False, False
    return True, False


def prefill_kernel_active(config) -> bool:
    """Would a packed ragged prefill dispatch on a model with this config
    run the pallas kernel in this process? The serving engine's
    ``serving/prefill_kernel_active`` gauge and per-request record read it
    — it must mirror :func:`ragged_prefill_attention`'s gate exactly."""
    page_size = getattr(config, "kv_page_size", None)
    if not page_size:
        return False
    mode = resolve_prefill_kernel(getattr(config, "prefill_kernel", None))
    if mode == "dense":
        return False
    bt = int(getattr(config, "prefill_kernel_block", None)
             or _PREFILL_TOKEN_BLOCK)
    quant_bits = {"int8": 8, "int4": 4}.get(
        getattr(config, "kv_cache_dtype", "bf16"), 0
    )
    _, lanes, dv = cache_entry_widths(config)
    use, _ = _prefill_kernel_gate(mode, lanes, int(page_size), bt, quant_bits, dv=dv)
    return use


def prefill_writes_pages(config) -> bool:
    """Would a packed ragged prefill dispatch on a model with this config
    write the pack's rows into the arena's pages inside the kernel (given
    the layers' stack and a layer index)? Where the kernel runs
    (:func:`prefill_kernel_active`) over unquantized pages (a quantized
    pack returns payloads and scales for the caller's scatter) whose widths
    are whole lanes as stored (a narrower page reaches the compiled kernel
    padded, by a copy). ``models/decoder.arena_in_place`` asks."""
    if not prefill_kernel_active(config):
        return False
    if getattr(config, "kv_cache_dtype", "bf16") in ("int8", "int4"):
        return False
    if resolve_prefill_kernel(getattr(config, "prefill_kernel", None)) == "interpret":
        return True
    _, lanes, dv = cache_entry_widths(config)
    return lanes % 128 == 0 and dv % 128 == 0


def _quantize_block(x, bits):
    """In-register quantize-on-write on one [rows, D] block through the
    SAME functions the jitted cache writes call
    (``utils.quantization.quantize_kv_values`` / ``kv_payload``), so
    the bytes are identical by construction. Returns (payload int8
    [rows, D or D/2], scale fp32 [rows, 1], deq fp32 [rows, D] — exactly
    what ``dequantize_kv`` hands a reader, so the tail attends the same
    values the cache serves later)."""
    from ..utils.quantization import kv_payload, quantize_kv_values

    qf, scale = quantize_kv_values(x, bits)
    return kv_payload(qf, bits), scale, qf * scale


def _prefill_block_pages(kvh: int, ps: int, pd: int, dtype, quant_bits: int,
                         table_len: int, rows: int,
                         pdv: Optional[int] = None,
                         window_pages: Optional[int] = None) -> int:
    """Pages a block of the ragged prefill walk holds: what
    :func:`_paged_decode_block_pages` reckons from the pages' shapes, with
    the prefill kernel's budget and the fp32 scores of the token block's
    ``rows`` folded rows (two tiles a page) counted beside the buffers. A
    quantized page's scales are one lane-dense fp32 row here
    (``_ragged_prefill_kernel_call``): K's and V's, in both halves, and
    the ``ps`` rows one is broadcast over while a head picks its column."""
    extra = 2 * rows * ps * 4
    if quant_bits:
        lanes = -(-kvh * ps // 128) * 128
        extra += 2 * (2 * _vmem_tile_bytes(1, lanes, jnp.float32) + ps * lanes * 4)
    return _paged_decode_block_pages(
        kvh, ps, pd, dtype, 0, table_len, pdv=pdv,
        window_pages=window_pages, budget=_PREFILL_VMEM_BUDGET,
        most=_PREFILL_MAX_BLOCK_PAGES, page_extra=extra)


def prefill_walk_pages(hist: int, first_pos: int, ps: int,
                       window: Optional[int] = None) -> int:
    """Live pages the arena walk of one token block visits: the table
    entries from the block's first (0, or the page that holds the first
    position a row at ``first_pos``, the block's first row, sees through
    the window) up to ``ceil(hist / ps)``. The kernel's own count, on the
    host: what the serving engine sums into ``pages_walked``."""
    lo = 0 if window is None else max(first_pos - window + 1, 0) // ps
    return max(-(-hist // ps) - lo, 0)


def _ragged_prefill_kernel(bslot_ref, bhist_ref, tbl_ref, blo_ref, bfirst_ref,
                           bpos_ref, blive_ref, layer_ref,
                           q_ref, *refs, sm_scale, bt, block_pages,
                           key_lanes, value_lanes, quant_bits=0,
                           out_dtype=None, window=None, has_sink=False,
                           value_scale=1.0, write=False, latent=0):
    """One token block a grid step: ``bt`` packed rows of one slot, folded
    with their query-head group into ``[KVH, bt*group, D]``.

    Arena phase: a loop over blocks of ``block_pages`` consecutive table
    entries of the block's slot, from entry ``blo_ref[i]`` (0, or a window
    layer's first page: the pages before it may have been given back and
    are never read) up to ``ceil(hist / page)``. Every live page of a
    block comes from the arena (left in HBM) by one asynchronous copy that
    brings all its kv heads, into one half of a double buffer, while the
    other half is attended head by head. A padding block (slot -1, history
    0 in ``bhist_ref``) and a slot with no history copy nothing.

    Fresh phase: the packed blocks ``bfirst_ref[i] .. i`` (the first block
    of the slot's rows through this one; a window layer leaves out those
    wholly behind its first row's window), one at a time from the packed
    K/V, which sits in VMEM whole. Fresh K/V is quantized in-register
    (quantize-on-write): the payload and scale of block ``i`` are this
    step's outputs, and the tail attends the dequantized form, keeping
    bit-compatibility with the dense oracle that reads the cache back.

    The mathematics is the dense reference's: fp32 scores, fp32 online
    softmax and accumulator, probabilities cast to the value dtype before
    PV; a row at position p sees arena positions ``c < hist, c <= p`` and
    fresh rows of its slot at positions ``0 <= c <= p`` (a window layer:
    ``p - c < window`` besides); a row that sees nothing (a pad row) is
    exactly zero. ``has_sink``: an operand [KVH, bt*group, 1] holds each
    row's learned scalar, which starts the running maximum with a sum of
    one and no value. ``value_scale`` multiplies the output. The key pages
    may be wider than the value pages; quantized pages walk their scale
    pages with them (a page's scales as one lane-dense row, (kv head,
    token) the lanes) and are dequantized in-register by
    ``utils.quantization.dequantize_kv``. Pages come in whole lanes:
    ``key_lanes`` / ``value_lanes`` are the widths stored, which a
    zero-padded page (a 64-wide head, an int4 payload) is read at.

    The arena is the layers' stack ``[L, num_pages, KVH, page, D]`` and
    ``layer_ref[0]`` the layer this call reads: a page is ``at[layer,
    page]``. ``write`` (unquantized pages): the stack is this call's output
    as well as its input (aliased), and the step puts its block's fresh
    keys and values, which it holds in VMEM, into the slot's pages itself:
    the ``blive_ref[i]`` live rows from position ``bpos_ref[i]`` on lie in
    at most ``(bt + page - 2) // page + 1`` consecutive table entries. A
    page the rows do not cover whole (the block starts mid-page, or the tail
    ends in it) is read first, while the arena is walked; after the walk the
    rows go into their pages (moved to their place in the page by a
    one-hot product, which is exact, and a select over the page) and the
    pages go back to the arena while the fresh phase runs; the step ends
    when they have arrived, so the next block of the slot, which may share
    this block's last page, reads what was written. Only pages that hold a
    live row are touched: a padding block and a tail's pad rows (position
    -1) write nothing. The pack needs no scatter, so the stack is never
    sliced, re-laid out or copied (``models/decoder.arena_in_place``).

    ``latent`` (latent attention read absorbed,
    ``ragged_latent_attention``): there are no value pages, buffers or
    fresh values. A page holds one entry a token that every query head
    shares, and its first ``latent`` lanes are the value too; the query
    heads come folded in ``q_ref.shape[1]`` groups of heads, each attended
    against that one entry as the kv heads are here, so that a group's
    rows and accumulator stay of a size the vector memory holds."""
    if has_sink:
        sink_ref, refs = refs[0], refs[1:]
    vn_ref = v_hbm = vbuf = wvbuf = ks_hbm = vs_hbm = ksbuf = vsbuf = None
    if latent:
        kn_ref, qpos_ref, kvpos_ref, k_hbm = refs[:4]
        if write:  # the stack as this call's output, as below
            o_ref, k_hbm, kbuf, sems, acc, m_scr, l_scr, wkbuf, wsems = refs[4:]
        else:
            o_ref, kbuf, sems, acc, m_scr, l_scr = refs[4:]
    else:
        kn_ref, vn_ref, qpos_ref, kvpos_ref, k_hbm, v_hbm = refs[:6]
        if write:
            # the stack as this call's output: the same buffer on the chip,
            # and the one that holds the rows written so far when interpreted
            (o_ref, k_hbm, v_hbm, kbuf, vbuf, sems, acc, m_scr, l_scr,
             wkbuf, wvbuf, wsems) = refs[6:]
        elif quant_bits:
            (ks_hbm, vs_hbm, o_ref, kq_ref, kso_ref, vq_ref, vso_ref,
             kbuf, vbuf, ksbuf, vsbuf, sems, acc, m_scr, l_scr) = refs[6:]
        else:
            o_ref, kbuf, vbuf, sems, acc, m_scr, l_scr = refs[6:]
    i = pl.program_id(0)
    layer = layer_ref[0]
    kvh, ps = k_hbm.shape[2], k_hbm.shape[3]
    # the blocks of folded query rows a step attends one after another: a
    # kv head's each, or (latent) groups of heads against the one entry
    qh = q_ref.shape[1]
    kv_of = (lambda h_: 0) if latent else (lambda h_: h_)
    both = lambda pairs: list(pairs[:1] if latent else pairs)
    bk = block_pages * ps  # kv positions a block of the walk spans
    slot = bslot_ref[i]
    row = jnp.maximum(slot, 0)
    hist = bhist_ref[i]
    lo = blo_ref[i]
    n_pages = jnp.maximum((hist + ps - 1) // ps - lo, 0)
    n_blocks = (n_pages + block_pages - 1) // block_pages
    # per-row (token, head-group) query positions, expanded per folded
    # row by the caller: a (bt, group) -> (bt*group, 1) reshape in here
    # is a shape cast Mosaic cannot lay out
    rowpos = qpos_ref[0]  # [bt*group, 1]

    def page_copies(blk, half, j):
        page = tbl_ref[row, lo + blk * block_pages + j]
        pairs = both([(k_hbm, kbuf, 0), (v_hbm, vbuf, 1)])
        if quant_bits:
            pairs += [(ks_hbm, ksbuf, 0), (vs_hbm, vsbuf, 1)]
        return [
            pltpu.make_async_copy(src.at[layer, page], dst.at[half, j], sems.at[half, which])
            for src, dst, which in pairs
        ]

    def for_each_live_page(blk, half, act):
        count = jnp.minimum(block_pages, n_pages - blk * block_pages)

        def one(j, _):
            for copy in page_copies(blk, half, j):
                act(copy)
            return _

        jax.lax.fori_loop(0, count, one, None)

    def start(blk, half):
        for_each_live_page(blk, half, lambda copy: copy.start())

    def wait(blk, half):
        for_each_live_page(blk, half, lambda copy: copy.wait())

    @pl.when(i == 0)
    def _first():
        # the buffers start as zeros: pages past a slot's frontier are
        # never copied, and what they leave in a block's tail is masked to
        # probability zero, which only holds against finite values
        for buf in (kbuf, vbuf, ksbuf, vsbuf):
            if buf is not None:
                buf[...] = jnp.zeros_like(buf)

    @pl.when(n_blocks > 0)
    def _():
        start(0, 0)

    if write:
        # this block's live rows as rows of its first touched page on:
        # [w_lo, w_hi) of the w_pages * ps rows the touched pages hold
        w_pages = wkbuf.shape[0]
        entry0 = bpos_ref[i] // ps
        w_lo = bpos_ref[i] - entry0 * ps
        w_hi = w_lo + blive_ref[i]
        n_written = jnp.where(blive_ref[i] > 0, (w_hi + ps - 1) // ps, 0)

        def for_each_written_page(act, back):
            """``act(copy)`` for the copies, keys and values, of every
            touched page: ``back`` to the arena, or in from it, and then
            of the pages alone that the rows do not cover whole."""
            for j in range(w_pages):
                touched = j < n_written
                if not back:
                    touched &= (w_lo > j * ps) | (w_hi < (j + 1) * ps)

                @pl.when(touched)
                def _():
                    page = tbl_ref[row, entry0 + j]
                    for hbm, buf, which in both(((k_hbm, wkbuf, 0), (v_hbm, wvbuf, 1))):
                        there, here = hbm.at[layer, page], buf.at[j]
                        src, dst = (here, there) if back else (there, here)
                        act(pltpu.make_async_copy(src, dst, wsems.at[j, which]))

        for_each_written_page(lambda copy: copy.start(), back=False)

    if has_sink:
        # the learned scalar joins the denominator and carries no value
        m_scr[...] = jnp.broadcast_to(sink_ref[...], m_scr.shape)
        l_scr[...] = jnp.ones_like(l_scr)
    else:
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
    acc[...] = jnp.zeros_like(acc)

    def attend(h_, k, v, kvpos):
        """Fold kv rows ``k`` / ``v`` at positions ``kvpos`` [1, rows]
        (``_UNSEEN`` where no row may see them) into head ``h_``."""
        s = jax.lax.dot_general(
            q_ref[0, h_], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        valid = kvpos <= rowpos  # never true of a pad row (position -1)
        if window is not None:
            valid = valid & (rowpos - kvpos < window)
        s = jnp.where(valid, s, NEG_INF)
        _online_softmax_step(s, v, m_scr.at[h_], l_scr.at[h_], acc.at[h_], valid=valid)

    def each_head(fn, count=None):
        def one(h_, _):
            fn(h_)
            return _

        jax.lax.fori_loop(0, qh if count is None else count, one, None)

    def block(ib, half):
        @pl.when(ib + 1 < n_blocks)
        def _():
            start(ib + 1, 1 - half)

        wait(ib, half)
        kvp = ((lo + ib * block_pages) * ps
               + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
        # only the slot's live prefix: stale arena rows past the frontier,
        # and the block's tail no page was copied into, never score
        kvp = jnp.where(kvp < hist, kvp, _UNSEEN)

        def scale_column(sbuf, h_):
            # a page's scales are one lane-dense row, (kv head, token) the
            # lanes: each kv row picks its own lane, exactly (the other
            # lanes add zeros), which stands them up as the column
            # dequantize_kv takes
            rows = sbuf[half]  # [block_pages, 1, lanes]
            lanes = rows.shape[-1]
            rows = jnp.broadcast_to(rows, (block_pages, ps, lanes)).reshape(bk, lanes)
            lane = jax.lax.broadcasted_iota(jnp.int32, (bk, lanes), 1)
            token = jax.lax.broadcasted_iota(jnp.int32, (bk, lanes), 0) % ps
            return jnp.sum(jnp.where(lane == h_ * ps + token, rows, 0.0),
                           axis=1, keepdims=True)

        def load(buf, sbuf, h_, lanes):
            x = buf[half, :, h_]  # [block_pages, ps, whole lanes]
            if quant_bits:
                # widen before the pages merge into rows: a page is a
                # whole number of 32-bit sublane tiles, not of 8-bit ones
                x = x.astype(jnp.int32)
            x = x.reshape(bk, x.shape[-1])
            if lanes != x.shape[-1]:
                x = x[:, :lanes]  # a zero-padded page, at the width stored
            if not quant_bits:
                return x
            from ..utils.quantization import dequantize_kv

            return dequantize_kv(x, scale_column(sbuf, h_), quant_bits, out_dtype)

        def one_head(h_):
            k = load(kbuf, ksbuf, kv_of(h_), key_lanes)
            attend(h_, k, k[:, :latent] if latent else load(vbuf, vsbuf, h_, value_lanes), kvp)

        each_head(one_head)
        return 1 - half

    jax.lax.fori_loop(0, n_blocks, block, 0)

    if write:
        for_each_written_page(lambda copy: copy.wait(), back=False)
        # row r of the touched pages is the block's row r - w_lo: a one-hot
        # product puts it there exactly (one term a row, the rest zeros)
        at = jax.lax.broadcasted_iota(jnp.int32, (w_pages * ps, bt), 0)
        take = jax.lax.broadcasted_iota(jnp.int32, (w_pages * ps, bt), 1)
        pick = at - w_lo == take
        exact = jax.lax.Precision.HIGHEST if kn_ref.dtype == jnp.float32 else None

        def put_rows(h_):
            for new_ref, buf in both(((kn_ref, wkbuf), (vn_ref, wvbuf))):
                moved = jax.lax.dot_general(
                    pick.astype(new_ref.dtype), new_ref[h_, i],
                    (((1,), (0,)), ((), ())), precision=exact,
                    preferred_element_type=jnp.float32).astype(buf.dtype)
                for j in range(w_pages):
                    page = buf[j, h_]  # [page, D]
                    r = j * ps + jax.lax.broadcasted_iota(jnp.int32, page.shape, 0)
                    buf[j, h_] = jnp.where(
                        (r >= w_lo) & (r < w_hi), moved[j * ps:(j + 1) * ps], page)

        each_head(put_rows, kvh)
        # on their way back while the fresh phase runs
        for_each_written_page(lambda copy: copy.start(), back=True)

    def fresh_kv(h_, jf):
        if latent:
            kn = kn_ref[0, jf]  # [bt, D]: the entry, its first lanes the value
            return kn, kn[:, :latent], None
        kn, vn = kn_ref[h_, jf], vn_ref[h_, jf]  # [bt, D], [bt, Dv]
        if not quant_bits:
            return kn, vn, None
        kp, ksv, kdq = _quantize_block(kn, quant_bits)
        vp, vsv, vdq = _quantize_block(vn, quant_bits)
        return kdq.astype(out_dtype), vdq.astype(out_dtype), (kp, ksv, vp, vsv)

    if quant_bits:
        def payloads(h_):
            kp, ksv, vp, vsv = fresh_kv(h_, i)[2]
            kq_ref[h_, 0] = kp
            kso_ref[h_, 0] = ksv
            vq_ref[h_, 0] = vp
            vso_ref[h_, 0] = vsv

        each_head(payloads)

    # packed tails are position-ordered per slot, so blocks of the same
    # slot after this one are entirely above the causal frontier, and
    # those of other slots are never visited
    jf0 = bfirst_ref[i]
    if window is not None:
        # block jf's last position is (i - jf) * bt - bt + 1 behind this
        # block's first row, which sees window - 1 positions behind itself
        jf0 = jnp.maximum(jf0, i - (window + bt - 2) // bt)

    def fresh(jf, carry):
        kvq = kvpos_ref[jf]  # [1, bt] fresh positions, -1 on a pad row
        kvq = jnp.where(kvq >= 0, kvq, _UNSEEN)
        each_head(lambda h_: attend(h_, *fresh_kv(h_, jf)[:2], kvq))
        return carry

    jax.lax.fori_loop(jf0, jnp.where(slot >= 0, i + 1, jf0), fresh, None)

    def out(h_):
        l = l_scr[h_][:, :1]
        o = acc[h_] / jnp.where(l == 0.0, 1.0, l)
        if value_scale != 1.0:
            o = o * value_scale
        o_ref[0, h_] = o.astype(o_ref.dtype)

    each_head(out)
    if write:
        # the pages are back before the slot's next block reads them
        for_each_written_page(lambda copy: copy.wait(), back=True)


def _whole_lanes(x):
    """``x`` with its last dimension zero-padded to a 128-multiple: Mosaic
    copies a page out of HBM only in whole lanes
    (``_decode_kernel_gate``). A copy of the array where it pads."""
    pad = -x.shape[-1] % 128
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _ragged_prefill_kernel_call(q, k_new, v_new, k_pages, v_pages, page_table,
                                row_slot, row_pos, slot_hist, sm_scale, bt,
                                interpret, k_scale=None, v_scale=None,
                                quant_bits=0, window=None, sink=None,
                                value_scale=1.0, layer=None, latent=0):
    """``latent``: ``v_new`` and ``v_pages`` are None, the values are the
    first ``latent`` lanes of the one arena and of ``k_new``, the query
    heads are attended in groups (``_LATENT_GROUP_ROWS``), the custom call
    is named ``mla_prefill_attn`` and a write returns ``(out, k_pages)``.

    ``layer`` absent: ``k_pages`` / ``v_pages`` (and the scale pages) are
    one layer's pages ``[num_pages, KVH, page, D]`` and the result is
    ``(out, k_payload, k_scale, v_payload, v_scale)`` for the caller's
    scatter. With ``layer`` they are the layers' stack ``[L, num_pages, KVH,
    page, D]``, the call also writes the pack's rows into the slots' pages
    (the kernel's ``write``) and returns ``(out, k_pages, v_pages)``, the
    stacks updated in place."""
    _, h, cap, d = q.shape
    write = layer is not None
    if not write:  # one layer's pages: a stack of one
        k_pages, v_pages, k_scale, v_scale = (
            None if x is None else x[None] for x in (k_pages, v_pages, k_scale, v_scale))
        layer = 0
    _, _, kvh, ps, key_lanes = k_pages.shape
    dv, value_lanes = (latent, latent) if latent else (v_new.shape[-1], v_pages.shape[-1])
    if write and (quant_bits or (not interpret and (key_lanes % 128 or value_lanes % 128))):
        raise ValueError(
            "the ragged prefill kernel writes unquantized pages of whole lanes; "
            f"got int{quant_bits} pages {key_lanes} / {value_lanes} wide "
            "(prefill_writes_pages says so beforehand)")
    # the kernel copies whole pages out of HBM, which Mosaic takes in whole
    # lanes only: a narrower arena (a 64-wide head, an int4 payload) goes
    # in padded, by a copy of the layer's pages, and the kernel reads the
    # lanes that are stored. Pages a model stores whole (128; 192-wide
    # keys at 256, ``paged_key_lanes``) go in as they are, and so does the
    # stack the kernel writes (interpreted, any width is whole).
    if not write:
        k_pages = _whole_lanes(k_pages)
        v_pages = None if latent else _whole_lanes(v_pages)
    pd = k_pages.shape[-1]
    pdv = pd if latent else v_pages.shape[-1]
    # the blocks the query heads are folded into: a kv head's group each, or
    # (latent) groups of heads small enough that a block's rows, scores and
    # accumulator stay in vector memory, all against the one entry a token
    qh = kvh
    if latent:
        qh = next(n_ for n_ in range(1, h + 1) if h % n_ == 0 and bt * h // n_ <= _LATENT_GROUP_ROWS or n_ == h)
    group = h // qh
    ntb = cap // bt
    g = bt * group
    n = _prefill_block_pages(
        kvh, ps, pd, k_pages.dtype, quant_bits, page_table.shape[1], g,
        pdv=None if pdv == pd else pdv,
        # the window - 1 positions before a block's first row
        window_pages=None if window is None else window_span_pages(window - 1, ps))
    # fold: per token block, one [bt*group, D] block a kv head, rows
    # ordered (token, group member) — same convention as _fold_q_heads
    q_r = (q[0].reshape(qh, group, ntb, bt, d)
           .transpose(2, 0, 3, 1, 4).reshape(ntb, qh, g, d))
    kn_r = k_new[0].reshape(kvh, ntb, bt, k_new.shape[-1])
    vn_r = None if latent else v_new[0].reshape(kvh, ntb, bt, dv)
    blk_slot = row_slot.reshape(ntb, bt)[:, 0].astype(jnp.int32)
    blk_hist = jnp.where(
        blk_slot >= 0, slot_hist[jnp.maximum(blk_slot, 0)], 0
    ).astype(jnp.int32)
    first_pos = row_pos.reshape(ntb, bt)[:, 0].astype(jnp.int32)
    # table entry a block's arena walk starts at: a window layer's is the
    # page of the first position its first row sees
    blk_lo = (jnp.maximum(first_pos - window + 1, 0) // ps if window is not None
              else jnp.zeros_like(first_pos))
    # first packed block of each block's slot (a slot's rows are contiguous)
    idx = jnp.arange(ntb, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), blk_slot[1:] != blk_slot[:-1]])
    blk_first = jax.lax.cummax(jnp.where(starts, idx, 0))
    pos_in = row_pos.reshape(ntb, 1, bt).astype(jnp.int32)
    # row r of a folded q block is token r // group
    pos_rows = jnp.repeat(row_pos.astype(jnp.int32), group).reshape(ntb, g, 1)
    # what a block writes: its live rows (a tail's pads come last), from
    # its first row's position on
    blk_live = jnp.sum(row_pos.reshape(ntb, bt) >= 0, axis=1).astype(jnp.int32)
    prefetch = [blk_slot, blk_hist, page_table.astype(jnp.int32), blk_lo, blk_first,
                jnp.maximum(first_pos, 0), blk_live, jnp.asarray(layer, jnp.int32).reshape(1)]

    kernel = functools.partial(
        _ragged_prefill_kernel, sm_scale=sm_scale, bt=bt, block_pages=n,
        key_lanes=key_lanes, value_lanes=value_lanes, quant_bits=quant_bits,
        out_dtype=q.dtype, window=window, has_sink=sink is not None, value_scale=value_scale,
        write=write, latent=latent,
    )

    def per_block(*block):
        return pl.BlockSpec((1,) + block, lambda i, *_: (i,) + (0,) * len(block))

    def whole(x):
        return pl.BlockSpec(x.shape, lambda i, *_: (0,) * x.ndim)

    def fresh_out(width):
        # block i of the packed rows, every kv head: this step's payloads
        return pl.BlockSpec((kvh, 1, bt, width), lambda i, *_: (0, i, 0, 0))

    arena = pl.BlockSpec(memory_space=pl.ANY)
    in_specs, operands = [per_block(qh, g, d)], [q_r]
    if sink is not None:
        # row r of a folded q block is group member r % group of its kv head
        rows = jnp.tile(sink.astype(jnp.float32).reshape(kvh, 1, group), (1, bt, 1))
        operands.append(rows.reshape(kvh, g, 1))
        in_specs.append(whole(operands[-1]))
    arenas = [k_pages] if latent else [k_pages, v_pages]
    fresh_rows = [kn_r] if latent else [kn_r, vn_r]
    operands += [*fresh_rows, pos_rows, pos_in]
    first_arena = len(prefetch) + len(operands)
    operands += arenas
    in_specs += [*map(whole, fresh_rows), per_block(g, 1), whole(pos_in)] + [arena] * len(arenas)
    buffers = [pltpu.VMEM((2, n, kvh, ps, a.shape[-1]), a.dtype) for a in arenas]
    out_specs = [per_block(qh, g, dv)]
    out_shape = [jax.ShapeDtypeStruct((ntb, qh, g, dv), q.dtype)]
    if quant_bits:
        # per-(page, kv-head, token) fp32 scales ride the same walk, a
        # page's as one lane-dense row (a view made here: the scale pages
        # end in a dimension of 1 as stored, which is no whole lane)
        scales = [_whole_lanes(x.reshape(x.shape[:2] + (1, kvh * ps)))
                  for x in (k_scale, v_scale)]
        operands += scales
        in_specs += [arena, arena]
        buffers += [pltpu.VMEM((2, n) + scales[0].shape[2:], jnp.float32)] * 2
        for width, dt in ((key_lanes, jnp.int8), (1, jnp.float32),
                          (value_lanes, jnp.int8), (1, jnp.float32)):
            out_specs.append(fresh_out(width))
            out_shape.append(jax.ShapeDtypeStruct((kvh, ntb, bt, width), dt))
    scratch = buffers + [
        pltpu.SemaphoreType.DMA((2, 2)),
        _vmem((qh, g, dv)), _vmem((qh, g, 128)), _vmem((qh, g, 128)),
    ]
    aliases = {}
    if write:
        # the pages a block's rows touch, staged: bt rows from anywhere in a page
        w_pages = (bt + ps - 2) // ps + 1
        out_specs += [arena] * len(arenas)
        out_shape += [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arenas]
        scratch += [pltpu.VMEM((w_pages, kvh, ps, a.shape[-1]), a.dtype) for a in arenas]
        scratch.append(pltpu.SemaphoreType.DMA((w_pages, 2)))
        aliases = {first_arena + j: 1 + j for j in range(len(arenas))}
    vmem_limit = None
    if latent:
        # a token block's queries and outputs (both double-buffered), its
        # float32 accumulator, maximum and sum, and the scores of a group
        # over a block of the walk, which pass the default scoped limit
        need = (2 * qh * g * (d + dv) * q.dtype.itemsize + qh * g * (dv + 256) * 4
                + 2 * n * ps * pd * k_pages.dtype.itemsize + 4 * g * n * ps * 4)
        vmem_limit = min(max(2 * need, 32 * 2**20), 100 * 2**20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(ntb,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        # the latent form under a name of its own in the device trace
        name="mla_prefill_attn" if latent else "ragged_prefill_attn",
        # the first step zeroes the page buffers for those after it, and a
        # slot's blocks write its pages in order
        **_grid_params(interpret, ("arbitrary",), vmem_limit_bytes=vmem_limit),
    )(*prefetch, *operands)
    o = outs[0]  # out_shape is a list, so pallas returns a list
    out = (o.reshape(ntb, qh, bt, group, dv)
           .transpose(1, 3, 0, 2, 4).reshape(1, h, cap, dv))
    if write:
        return (out, *outs[1:])
    if latent:
        return out, jnp.swapaxes(k_new[0], 0, 1)
    if quant_bits:
        k_pay = jnp.swapaxes(outs[1].reshape(kvh, cap, key_lanes), 0, 1)
        k_scl = jnp.swapaxes(outs[2].reshape(kvh, cap, 1), 0, 1)
        v_pay = jnp.swapaxes(outs[3].reshape(kvh, cap, value_lanes), 0, 1)
        v_scl = jnp.swapaxes(outs[4].reshape(kvh, cap, 1), 0, 1)
    else:
        k_pay = jnp.swapaxes(k_new[0], 0, 1)
        v_pay = jnp.swapaxes(v_new[0], 0, 1)
        k_scl = v_scl = None
    return out, k_pay, k_scl, v_pay, v_scl


def _ragged_prefill_reference(q, k_new, v_new, k_pages, v_pages, page_table,
                              row_slot, row_pos, slot_hist, scale,
                              k_scale=None, v_scale=None, quant_bits=0,
                              window=None, sink=None, value_scale=1.0):
    """Chunked-dense-oracle math for a packed ragged dispatch: per-row
    gathered arena context + packed fresh kv, masked exactly as the
    kernel masks, through the reference op sequence (``quantize_kv`` /
    ``dequantize_kv`` / fp32 softmax). The fallback path and the
    bit-exactness reference the kernel is asserted against."""
    from ..utils.quantization import dequantize_kv, quantize_kv

    _, h, cap, d = q.shape
    kvh = k_pages.shape[1]
    group = h // kvh
    kn_t = jnp.swapaxes(k_new[0], 0, 1)  # [CAP, KVH, D]
    vn_t = jnp.swapaxes(v_new[0], 0, 1)
    if quant_bits:
        k_pay, k_scl = quantize_kv(kn_t, quant_bits)
        v_pay, v_scl = quantize_kv(vn_t, quant_bits)
        k_fresh = dequantize_kv(k_pay, k_scl, quant_bits, q.dtype)
        v_fresh = dequantize_kv(v_pay, v_scl, quant_bits, q.dtype)
    else:
        k_pay, v_pay = kn_t, vn_t
        k_scl = v_scl = None
        k_fresh, v_fresh = kn_t, vn_t
    k_ctx = gather_kv_pages(k_pages, page_table)  # [S, KVH, L, pd]
    v_ctx = gather_kv_pages(v_pages, page_table)
    if quant_bits:
        k_ctx = dequantize_kv(
            k_ctx, gather_kv_pages(k_scale, page_table), quant_bits, q.dtype)
        v_ctx = dequantize_kv(
            v_ctx, gather_kv_pages(v_scale, page_table), quant_bits, q.dtype)
    sl = jnp.maximum(row_slot, 0)
    k_row = k_ctx[sl]  # [CAP, KVH, L, D] — per-row slot context
    v_row = v_ctx[sl]
    qg = q[0].reshape(kvh, group, cap, d)
    s_ctx = jnp.einsum(
        "kgrd,rkld->kgrl", qg, k_row, preferred_element_type=jnp.float32
    ) * scale
    length = k_row.shape[2]
    lpos = jnp.arange(length)
    hist_r = jnp.where(row_slot >= 0, slot_hist[sl], 0)
    valid_ctx = ((lpos[None, :] < hist_r[:, None])
                 & (lpos[None, :] <= row_pos[:, None]))
    if window is not None:
        valid_ctx = valid_ctx & (row_pos[:, None] - lpos[None, :] < window)
    s_ctx = jnp.where(valid_ctx[None, None], s_ctx, NEG_INF)
    kf = jnp.swapaxes(k_fresh, 0, 1)  # [KVH, CAP, D]
    vf = jnp.swapaxes(v_fresh, 0, 1)
    s_new = jnp.einsum(
        "kgrd,kcd->kgrc", qg, kf, preferred_element_type=jnp.float32
    ) * scale
    valid_new = ((row_slot[None, :] == row_slot[:, None])
                 & (row_slot[:, None] >= 0)
                 & (row_pos[None, :] <= row_pos[:, None])
                 & (row_pos[None, :] >= 0))
    if window is not None:
        valid_new = valid_new & (row_pos[:, None] - row_pos[None, :] < window)
    s_new = jnp.where(valid_new[None, None], s_new, NEG_INF)
    s = jnp.concatenate([s_ctx, s_new], axis=-1)
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(kvh, group, 1, 1), s.shape[:-1] + (1,))
        s = jnp.concatenate([s, col], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    out = (jnp.einsum("kgrl,rkld->kgrd", p[..., :length].astype(v_row.dtype), v_row)
           + jnp.einsum("kgrc,kcd->kgrd", p[..., length:length + cap].astype(vf.dtype), vf))
    if value_scale != 1.0:
        out = out * value_scale
    # pad rows are fully masked: softmax degenerates to uniform — force
    # the kernel's exact 0 output (safe_l semantics) instead
    row_ok = (row_slot >= 0) & (row_pos >= 0)
    out = jnp.where(row_ok[None, None, :, None], out, 0.0)
    out = out.reshape(h, cap, v_new.shape[-1])[None].astype(q.dtype)
    return out, k_pay, k_scl, v_pay, v_scl


def ragged_prefill_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    *,
    page_table: jax.Array,
    row_slot: jax.Array,
    row_pos: jax.Array,
    slot_hist: jax.Array,
    sm_scale: Optional[float] = None,
    impl: Optional[str] = None,
    token_block: Optional[int] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    kv_quant_bits: int = 0,
    window: Optional[int] = None,
    sink: Optional[jax.Array] = None,
    value_scale: float = 1.0,
    layer: Optional[jax.Array] = None,
):
    """Packed ragged prefill attention over the paged KV arena, with
    quantize-on-write fused.

    q/k_new/v_new: [1, H|KVH, CAP, D] — the packed fresh tails of every
    admission in this dispatch (post-RoPE), CAP a fixed compile-time
    capacity. ``row_slot``/``row_pos`` [CAP] int32 map each packed row to
    its (slot, absolute position); -1 marks padding (only up to the
    token-block granule). Rows of one slot must be contiguous,
    position-ordered, and token-block aligned — the packer's contract,
    which the kernel leans on: a token block attends the packed blocks from
    the first of its slot's run (where the slot id last changed) through
    itself, so rows of one slot in two runs would not see each other.
    ``slot_hist`` [S] int32 is each slot's live prefix length (tokens
    already in the arena: a prefix-cache/tier hit plus earlier packed
    dispatches of a long tail); the kernel walks exactly
    ``ceil(hist/page_size)`` arena pages per token block, in blocks of
    many pages copied out of HBM (``_ragged_prefill_kernel``), and never
    re-attends served positions as queries — the prefix-aware skip.

    Returns ``(out [1, H, CAP, D], k_payload, k_scale, v_payload,
    v_scale)`` — payloads token-major [CAP, KVH, pd] ready for one arena
    scatter (scales None unquantized; payloads then pass through k_new/
    v_new). Dispatch mirrors the decode kernel's:
    :func:`resolve_prefill_kernel` (``impl``, default "ragged" with a
    warn-once dense fallback off-TPU, "interpret" for CPU tests); the
    dense reference stays the bit-exactness oracle.

    The values (``v_new``, ``v_pages``) may be narrower than the keys; the
    output has their width. ``window``: a row at position p sees positions
    ``p - window < c <= p`` of its slot, in the arena and among the packed
    rows; the kernel's arena walk then starts at the first page the token
    block's earliest row sees and spans at most ``window_span_pages``, so
    the pages behind the window (which may have been given back) are never
    read. ``sink`` [H] and ``value_scale`` are :func:`mha_reference`'s.

    ``layer``: the pages are the layers' stack ``[L, num_pages, KVH,
    page_size, D]`` and the kernel puts the pack's rows into the slots'
    pages itself, through the table; the result is ``(out, k_pages,
    v_pages)``, the stacks updated in place (see
    ``_ragged_prefill_kernel``). That is the kernel's alone, for
    unquantized pages of whole lanes: a caller asks
    :func:`prefill_writes_pages` first and keeps its own scatter elsewhere."""
    mode = resolve_prefill_kernel(impl)
    b, h, cap, d = q.shape
    if b != 1:
        raise ValueError(f"packed ragged prefill takes batch 1, got {b}")
    bt = int(token_block or _PREFILL_TOKEN_BLOCK)
    if cap % bt:
        raise ValueError(
            f"packed capacity {cap} must be a multiple of the token "
            f"block {bt}"
        )
    if kv_quant_bits and (k_scale is None or v_scale is None):
        raise ValueError("kv_quant_bits needs k_scale and v_scale")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    row_slot = jnp.asarray(row_slot, jnp.int32)
    row_pos = jnp.asarray(row_pos, jnp.int32)
    slot_hist = jnp.asarray(slot_hist, jnp.int32)
    if mode != "dense":
        use, interpret = _prefill_kernel_gate(
            mode, k_pages.shape[-1] * (2 if kv_quant_bits == 4 else 1),
            k_pages.shape[-2], bt, kv_quant_bits,
            dv=v_pages.shape[-1] * (2 if kv_quant_bits == 4 else 1),
        )
        if use:
            return _ragged_prefill_kernel_call(
                q, k_new, v_new, k_pages, v_pages, page_table, row_slot,
                row_pos, slot_hist, scale, bt, interpret,
                k_scale=k_scale, v_scale=v_scale, quant_bits=kv_quant_bits,
                window=window, sink=sink, value_scale=value_scale, layer=layer,
            )
    if layer is not None:
        raise ValueError(
            "ragged_prefill_attention over the layers' stack is the kernel's "
            "path; this dispatch resolves to the dense reference "
            "(prefill_writes_pages says so beforehand)")
    return _ragged_prefill_reference(
        q, k_new, v_new, k_pages, v_pages, page_table, row_slot, row_pos,
        slot_hist, scale, k_scale=k_scale, v_scale=v_scale,
        quant_bits=kv_quant_bits, window=window, sink=sink,
        value_scale=value_scale,
    )


def flash_kernel_engaged(q, k, *, impl: str = "auto", bias=None, interpret: bool = False) -> bool:
    """Whether ``dot_product_attention`` takes the pallas kernel for these
    operands. Depends on the sequence lengths and head_dim only, so it
    answers the same per shard of the batch and head axes — a caller that
    must wrap the kernel for its mesh (``parallel.context``) asks here."""
    if impl == "xla" or bias is not None:
        return False
    if impl == "flash":
        return True
    blocks_ok = (
        _pick_block(q.shape[2], 1024) and _pick_block(k.shape[2], 1024) and q.shape[-1] % 128 == 0
    )
    return bool((jax.default_backend() == "tpu" or interpret) and blocks_ok)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Attention dispatcher: pallas flash kernel on TPU when shapes allow,
    XLA reference otherwise. Layout [B, H, S, D]. ``impl`` ∈
    {"auto", "flash", "xla"}.

    Padding should arrive as ``kv_mask`` and packed sequences as
    ``segment_ids`` — both stay on the flash path. An arbitrary additive
    ``bias`` falls back to XLA (the kernel implements masks, not biases).

    Mesh-free: the XLA reference partitions like any other op, but the SPMD
    partitioner cannot split the kernel, so a program partitioned over a
    multi-device mesh calls ``parallel.context.dot_product_attention_sharded``."""
    if impl == "flash" and bias is not None:
        raise ValueError("flash impl does not support arbitrary bias; use kv_mask/segment_ids or impl='xla'")

    def _fold_masks_into_bias(bias):
        # Masks must survive on every path — the XLA fallback honors them by
        # folding into the additive bias (padding keys get -inf logits).
        if kv_mask is None and q_segment_ids is None:
            return bias
        bias_parts = [] if bias is None else [bias]
        if kv_mask is not None:
            bias_parts.append(jnp.where(kv_mask[:, None, None, :] != 0, 0.0, NEG_INF))
        if q_segment_ids is not None:
            same = q_segment_ids[:, None, :, None] == kv_segment_ids[:, None, None, :]
            bias_parts.append(jnp.where(same, 0.0, NEG_INF))
        return sum(bias_parts)

    if flash_kernel_engaged(q, k, impl=impl, bias=bias, interpret=interpret):
        return flash_attention(
            q, k, v, causal=causal, sm_scale=sm_scale,
            kv_mask=kv_mask, q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            interpret=interpret or jax.default_backend() != "tpu",
        )
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale, bias=_fold_masks_into_bias(bias))
