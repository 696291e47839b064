"""Context parallelism: ring attention over the "sequence" mesh axis.

The reference has NO long-context support (SURVEY §5: no ring attention,
no Ulysses, no context parallel anywhere in src/ — only a Megatron
sequence_parallelism flag passthrough). This is new capability, designed
for TPU: sequence shards live on different chips, K/V blocks rotate around
the ring via `lax.ppermute` over ICI while each chip computes its local
attention block, and partial results merge with logsumexp weights
(online-softmax across devices). Communication is O(S·D) per step and
overlaps with compute; the O(S²) score matrix never exists globally.

The ring is unrolled in Python (ring size = mesh axis degree, static at
trace time), so reverse-mode AD works through it out of the box — the
backward pass runs the rotation in reverse automatically.

Used by models/decoder.py when `ShardingConfig.sequence_parallel > 1`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..ops.attention import NEG_INF, dot_product_attention, flash_kernel_engaged


def _local_attn_with_lse(q, k, v, bias, sm_scale):
    """Softmax attention on local blocks, returning (normalized out, lse).
    q [B,H,Sq,D], k/v [B,KVH,Skv,D] (KVH divides H — grouped einsum, so GQA
    k/v stay unexpanded and the ring rotates the small tensors), bias
    [Sq,Skv] additive.

    NOTE: materializes the [Sq_local, Skv_local] fp32 score block — fine up
    to ~8k tokens/shard; the flash-kernel inner step (ring-level custom_vjp)
    is tracked as a follow-up for the extreme-context regime."""
    b, h, sq, d = q.shape
    kvh = k.shape[1]
    g = h // kvh  # 1 for MHA — the grouped path covers both cases
    qg = q.reshape(b, kvh, g, sq, d)
    s = jnp.einsum("bkgqd,bkcd->bkgqc", qg, k, preferred_element_type=jnp.float32) * sm_scale
    s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqc,bkcd->bkgqd", (p / l).astype(v.dtype), v).astype(jnp.float32)
    return o.reshape(b, h, sq, d), (m + jnp.log(l)).reshape(b, h, sq)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Per-shard body (call under shard_map). q/k/v: local shards
    [B, H, S/n, D]; sequence order is the mesh axis order.

    ``impl``: "flash" uses the pallas kernel as the inner step (VMEM-resident
    scores, a ring-level custom VJP runs a reverse ring of dq/dkv kernels);
    "dense" materializes the local [Sq, Skv] fp32 block (any shape);
    "auto" picks flash when the local shapes tile (128-multiples)."""
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s_local, d = q.shape[2], q.shape[3]
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        impl = "flash" if (s_local % 128 == 0 and d % 128 == 0 and (on_tpu or interpret)) else "dense"
    if impl == "flash":
        return _ring_flash(q, k, v, axis_name, axis_size, causal, sm_scale, interpret)
    return _ring_dense(q, k, v, axis_name, axis_size, causal, sm_scale)


def _ring_dense(q, k, v, axis_name, axis_size, causal, sm_scale):
    n = axis_size
    i = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    dtype = q.dtype

    q_pos = i * s_local + jnp.arange(s_local)  # global positions of my queries

    o_acc = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    lse_acc = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    k_cur, v_cur = k, v
    fwd_perm = [(p_, (p_ + 1) % n) for p_ in range(n)]

    for r in range(n):
        j = (i - r) % n  # which sequence chunk I hold this step
        if causal:
            kv_pos = j * s_local + jnp.arange(s_local)
            bias = jnp.where(q_pos[:, None] >= kv_pos[None, :], 0.0, NEG_INF)
        else:
            bias = jnp.zeros((s_local, s_local), jnp.float32)
        o_r, lse_r = _local_attn_with_lse(q, k_cur, v_cur, bias, sm_scale)
        new_lse = jnp.logaddexp(lse_acc, lse_r)
        w_old = jnp.exp(lse_acc - new_lse)[..., None]
        w_new = jnp.exp(lse_r - new_lse)[..., None]
        o_acc = o_acc * w_old + o_r * w_new
        lse_acc = new_lse
        if r != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, fwd_perm)

    return o_acc.astype(dtype)


# ---------------------------------------------------------------------------
# flash inner step: the pallas kernel per ring hop + ring-level custom VJP
# ---------------------------------------------------------------------------
#
# Per hop r, the chunk I hold is j = (i - r) % n — traced, so the causal
# structure is a 3-way lax.switch: j < i full block, j == i causal block,
# j > i contributes nothing (the kernel call is skipped entirely, unlike the
# dense path which burns FLOPs on a fully masked block).
#
# The backward runs the ring again: with the GLOBAL lse and delta, the
# per-block flash backward contributions (p = exp(s - lse)) sum exactly, so
# dq accumulates locally while dk/dv accumulate on buffers that travel WITH
# k/v — after n hops they land back on the chunk's owner.


def _hop_cases(q, k_cur, v_cur, sm_scale, fwd=True, out=None, lse=None, do=None, interpret=False):
    from ..ops.attention import flash_attention_bwd, flash_attention_with_lse

    if fwd:
        def full(_):
            return flash_attention_with_lse(q, k_cur, v_cur, causal=False, sm_scale=sm_scale, interpret=interpret)

        def diag(_):
            return flash_attention_with_lse(q, k_cur, v_cur, causal=True, sm_scale=sm_scale, interpret=interpret)

        def skip(_):
            return (
                jnp.zeros(q.shape[:3] + (v_cur.shape[-1],), q.dtype),
                jnp.full(q.shape[:3], NEG_INF, jnp.float32),
            )

        return full, diag, skip

    def full_b(_):
        return flash_attention_bwd(q, k_cur, v_cur, out, lse, do, causal=False, sm_scale=sm_scale, interpret=interpret)

    def diag_b(_):
        return flash_attention_bwd(q, k_cur, v_cur, out, lse, do, causal=True, sm_scale=sm_scale, interpret=interpret)

    def skip_b(_):
        return jnp.zeros_like(q), jnp.zeros_like(k_cur), jnp.zeros_like(v_cur)

    return full_b, diag_b, skip_b


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, axis_size, causal, sm_scale, interpret):
    out, _ = _ring_flash_fwd_loop(q, k, v, axis_name, axis_size, causal, sm_scale, interpret)
    return out


def _case_index(j, i, causal):
    # 0 = full block, 1 = causal diagonal block, 2 = skip
    if not causal:
        return jnp.int32(0)
    return jnp.where(j == i, 1, jnp.where(j < i, 0, 2)).astype(jnp.int32)


def _ring_flash_fwd_loop(q, k, v, axis_name, axis_size, causal, sm_scale, interpret):
    n = axis_size
    i = jax.lax.axis_index(axis_name)
    dtype = q.dtype
    o_acc = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    lse_acc = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    k_cur, v_cur = k, v
    fwd_perm = [(p_, (p_ + 1) % n) for p_ in range(n)]
    for r in range(n):
        j = (i - r) % n
        full, diag, skip = _hop_cases(q, k_cur, v_cur, sm_scale, fwd=True, interpret=interpret)
        o_r, lse_r = jax.lax.switch(_case_index(j, i, causal), [full, diag, skip], ())
        new_lse = jnp.logaddexp(lse_acc, lse_r)
        w_old = jnp.exp(lse_acc - new_lse)[..., None]
        w_new = jnp.exp(lse_r - new_lse)[..., None]
        o_acc = o_acc * w_old + o_r.astype(jnp.float32) * w_new
        lse_acc = new_lse
        if r != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, fwd_perm)
    return o_acc.astype(dtype), lse_acc


def _ring_flash_vjp_fwd(q, k, v, axis_name, axis_size, causal, sm_scale, interpret):
    out, lse = _ring_flash_fwd_loop(q, k, v, axis_name, axis_size, causal, sm_scale, interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, axis_size, causal, sm_scale, interpret, res, do):
    q, k, v, out, lse = res
    n = axis_size
    i = jax.lax.axis_index(axis_name)
    fwd_perm = [(p_, (p_ + 1) % n) for p_ in range(n)]
    dq_acc = jnp.zeros(q.shape, jnp.float32)
    dk_cur = jnp.zeros(k.shape, jnp.float32)
    dv_cur = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    for r in range(n):
        j = (i - r) % n
        full_b, diag_b, skip_b = _hop_cases(
            q, k_cur, v_cur, sm_scale, fwd=False, out=out, lse=lse, do=do, interpret=interpret
        )
        dq_r, dk_r, dv_r = jax.lax.switch(_case_index(j, i, causal), [full_b, diag_b, skip_b], ())
        dq_acc = dq_acc + dq_r.astype(jnp.float32)
        dk_cur = dk_cur + dk_r.astype(jnp.float32)
        dv_cur = dv_cur + dv_r.astype(jnp.float32)
        # rotate after EVERY hop (n total): the k/dk buffers complete the
        # full cycle and land back on the chunk owner
        k_cur = jax.lax.ppermute(k_cur, axis_name, fwd_perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, fwd_perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, fwd_perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, fwd_perm)
    return dq_acc.astype(q.dtype), dk_cur.astype(k.dtype), dv_cur.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def attention_partition_specs(
    mesh: Mesh, q, k, seq_axis: Optional[str] = None, manual: frozenset = frozenset()
):
    """(q_spec, kv_spec, row_spec) for attention over q [B, H, S, D] and
    k/v [B, KVH, S, D] on ``mesh``: batch over the data axes that divide it,
    heads over "tensor", the sequence over ``seq_axis`` (None = whole).
    ``row_spec`` is for [B, S] companions (padding mask, segment ids).
    Axes in ``manual`` are left out: an enclosing shard_map has already
    split the operands over them."""
    size = lambda a: 1 if a in manual else mesh.shape.get(a, 1)

    def _batch_axes(dim: int) -> tuple:
        kept, prod = [], 1
        for a in ("replica", "data", "fsdp"):
            sz = size(a)
            if sz > 1 and dim % (prod * sz) == 0:
                kept.append(a)
                prod *= sz
        return tuple(kept)

    # Head sharding: q and kv must shard consistently or the GQA grouping
    # silently changes. Shard both over "tensor" iff both divide; the MQA
    # special case (kv_heads=1 replicated, q heads sharded) is also exact
    # because every q head maps to the single kv head.
    tp = size("tensor")
    h, kvh = q.shape[1], k.shape[1]
    if tp > 1 and h % tp == 0 and kvh % tp == 0:
        q_head, kv_head = "tensor", "tensor"
    elif tp > 1 and h % tp == 0 and kvh == 1:
        q_head, kv_head = "tensor", None
    else:
        q_head, kv_head = None, None

    qb = _batch_axes(q.shape[0]) or None
    return (
        P(qb, q_head, seq_axis, None),
        P(qb, kv_head, seq_axis, None),
        P(qb, seq_axis),
    )


_ROW_OPERANDS = ("kv_mask", "q_segment_ids", "kv_segment_ids")


def dot_product_attention_sharded(q, k, v, mesh: Optional[Mesh], **kwargs):
    """``dot_product_attention`` for a program partitioned over ``mesh``.
    The XLA reference partitions like any other op and is called as is. The
    kernel runs per shard of the batch and head axes: attention is
    independent per (batch row, head), so this is exact — and it is what a
    Mosaic kernel needs, the SPMD partitioner cannot split a
    ``tpu_custom_call`` ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map").

    Inside an enclosing shard_map (the compressed-replica train step,
    LocalSGD) only the axes that are still automatic are mapped, over the
    context mesh; where every axis is manual already the kernel is called
    bare."""
    context_mesh = jax.sharding.get_abstract_mesh()
    manual = frozenset(context_mesh.manual_axes)
    auto = frozenset(mesh.axis_names) - manual if mesh is not None and mesh.size > 1 else ()
    if not auto or not flash_kernel_engaged(
        q, k, impl=kwargs.get("impl", "auto"), bias=kwargs.get("bias"),
        interpret=kwargs.get("interpret", False),
    ):
        return dot_product_attention(q, k, v, **kwargs)
    # the [B, S] companions are split with the batch; the rest is static
    rows = tuple(kwargs.pop(name, None) for name in _ROW_OPERANDS)
    q_spec, kv_spec, row_spec = attention_partition_specs(mesh, q, k, manual=manual)
    return shard_map(
        lambda q, k, v, *rows: dot_product_attention(
            q, k, v, **dict(zip(_ROW_OPERANDS, rows)), **kwargs
        ),
        mesh=context_mesh if manual else mesh,
        in_specs=(q_spec, kv_spec, kv_spec, *(None if r is None else row_spec for r in rows)),
        out_specs=q_spec,
        axis_names=auto,
        check_vma=False,
    )(q, k, v, *rows)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    seq_axis: str = "sequence",
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Global-view entry: q [B, H, S, D] (any resharding handled by jit),
    sequence sharded over ``seq_axis``, heads over "tensor", batch over the
    data axes. Falls back to plain attention when the axis is trivial."""
    n = mesh.shape.get(seq_axis, 1)
    if n == 1 or q.shape[2] % n or k.shape[2] % n:
        # trivial axis, or sequence not divisible by the ring: dense fallback
        return dot_product_attention(q, k, v, causal=causal, sm_scale=sm_scale)

    q_spec, kv_spec, _ = attention_partition_specs(mesh, q, k, seq_axis=seq_axis)
    fn = shard_map(
        partial(
            ring_attention,
            axis_name=seq_axis,
            axis_size=n,
            causal=causal,
            sm_scale=sm_scale,
            impl=impl,
            interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v)
