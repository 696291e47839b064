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

import contextlib
import contextvars
import math
from functools import partial, reduce
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


# ---------------------------------------------------------------------------
# tensor-parallel products that exchange their rows while they multiply
# ---------------------------------------------------------------------------
#
# Megatron's layout gives every block two all-reduces over "tensor" in the
# forward pass (after wo, after w_down) and two in the backward (the input
# gradients of wq/wk/wv and of w_gate/w_up). The next operation needs the sum,
# so nothing can run beside them. Here the residual stream is carried
# sequence-sharded over "tensor" instead, and the products do the exchange
# themselves, a ring of n - 1 `ppermute` hops cut so that every hop has a
# product to hide behind:
#
#   gather_einsum   (column-parallel: wq/wk/wv) multiplies the rows a chip
#                   holds while the neighbour's rows arrive;
#   einsum_scatter  (row-parallel: wo) multiplies the neighbour's rows first
#                   and sends that partial sum while it multiplies its own;
#   mlp_exchange    (w_gate/w_up, then w_down) is the two as one ring: a
#                   chunk's hidden rows go from the one product to the other
#                   and are never put together (copying the gathered outputs
#                   into place cost more than the exchange it hid).
#
# Each is the other's transpose, and its custom VJP says so: the backward pass
# of one runs the other's ring (JAX's own transposition of the forward ring
# pads every slice into zeros of the whole and adds them up). The partial sums
# travel in the activations' dtype, as the all-reduce's did: with two chips
# the sum is the same two numbers.

TENSOR_AXIS = "tensor"

_exchange_log: contextvars.ContextVar = contextvars.ContextVar("tp_exchange_log", default=None)


@contextlib.contextmanager
def record_exchanged_products():
    """Collects, while a program is traced inside it, the einsum of every
    product that took the exchange path (a set: a scanned, recomputed body is
    traced more than once). The train step keeps what its trace gave
    (``step._audit_tp_products``): four a decoder block, none on one chip."""
    log = set()
    token = _exchange_log.set(log)
    try:
        yield log
    finally:
        _exchange_log.reset(token)


def tp_exchange_size(mesh: Optional[Mesh], seq_len: int, sharded_dims=(), *, use_cache: bool = False,
                     use_fp8: bool = False) -> int:
    """The size of the "tensor" axis where a block's tensor-parallel products
    take the exchange path (:func:`gather_einsum`, :func:`einsum_scatter`),
    else 0: the product and its constraint are GSPMD's. Decided from what the
    trace can observe: a "tensor" axis above 1 that no enclosing shard_map has
    made manual, no "sequence" axis in use (ring attention owns the rows
    then), a module that neither caches nor multiplies in fp8, and a sequence
    and ``sharded_dims`` (heads, kv heads, the MLP's width) the axis divides."""
    if mesh is None or use_cache or use_fp8:
        return 0
    n = mesh.shape.get(TENSOR_AXIS, 1)
    if n == 1 or mesh.shape.get("sequence", 1) > 1:
        return 0
    if TENSOR_AXIS in jax.sharding.get_abstract_mesh().manual_axes:
        return 0
    if seq_len % n or any(d % n for d in sharded_dims):
        return 0
    return n


def _terms(subscripts: str):
    """(x's term, the weight's term, the output's term) of ``"x,w->o"``."""
    terms, out_term = subscripts.split("->")
    return (*terms.split(","), out_term)


def _cotangent_einsums(subscripts: str):
    """The two products that transpose ``einsum("x,w->o")``: the output's
    cotangent with the weight gives x's, x with the output's cotangent the
    weight's. (Every letter of these products stands in two terms.)"""
    x_term, w_term, out_term = _terms(subscripts)
    return f"{out_term},{w_term}->{x_term}", f"{x_term},{out_term}->{w_term}"


def _ring(n: int):
    return [(p, (p + 1) % n) for p in range(n)]


def _hop(x, n: int):
    with jax.named_scope("tp_exchange"):
        return jax.lax.ppermute(x, TENSOR_AXIS, _ring(n))


def _rows_of(offset, n: int):
    """The chip whose rows a ring step works on: ``offset`` places up the ring."""
    return (jax.lax.axis_index(TENSOR_AXIS) - offset) % n


def _gather_ring(x, n: int, products):
    """The ring of a gathered product, on one chip: ``products(r, x_r)`` for
    the rows in hand after ``r`` hops, chip ``_rows_of(r)``'s, while the next
    chip's travel. Returns the rows of every hop, own first."""
    held = [x]
    for r in range(n):
        if r != n - 1:
            held.append(_hop(held[r], n))
        products(r, held[r])
    return held


def _scatter_ring(n: int, product):
    """The ring of a scattered product, on one chip: ``product(r)`` is this
    chip's partial sum for chip ``_rows_of(r + 1)``'s rows (those a gather
    ring holds after ``(r + 1) % n`` hops): the next chip's first, and the
    running sum travels on while the next is multiplied; its own come last."""
    acc = None
    for r in range(n):
        arriving = None if acc is None else _hop(acc, n)
        acc = product(r)
        if arriving is not None:
            # (the barrier keeps the sum out of the product's fusion, which
            # would make the product wait for the hop it is there to hide)
            acc, arriving = jax.lax.optimization_barrier((acc, arriving))
            acc = acc + arriving
    return acc


def _into_rows(whole, part, chip, axis: int, n: int):
    """``part`` written as chip ``chip``'s rows of ``whole`` (made on the
    first write, uninitialised: every row is written once)."""
    rows = part.shape[axis]
    if whole is None:
        whole = jax.lax.empty(part.shape[:axis] + (n * rows,) + part.shape[axis + 1:], part.dtype)
    return jax.lax.dynamic_update_slice_in_dim(whole, part, chip * rows, axis)


def _rows_from(whole, chip, axis: int, n: int):
    rows = whole.shape[axis] // n
    return jax.lax.dynamic_slice_in_dim(whole, chip * rows, rows, axis)


def _add_product(acc, subscripts, a, b):
    """A weight's gradient, summed over the hops in the weight's dtype: each
    hop's rows give one rounded partial product, as each fsdp shard's rows do
    before GSPMD reduces them (a float32 sum would have to be reduced over
    "fsdp" in float32, at twice the bytes)."""
    g = jnp.einsum(subscripts, a, b)
    return g if acc is None else acc + g


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gather_products(subscripts, n, x, ws):
    return _gather_products_fwd(subscripts, n, x, ws)[0]


def _gather_products_fwd(subscripts, n, x, ws):
    out_rows = _terms(subscripts)[2].index("s")
    outs = [None] * len(ws)

    def products(r, x_r):
        for k, w in enumerate(ws):
            outs[k] = _into_rows(outs[k], jnp.einsum(subscripts, x_r, w), _rows_of(r, n), out_rows, n)

    held = _gather_ring(x, n, products)
    return tuple(outs), (held, ws)


def _gather_products_bwd(subscripts, n, res, d_outs):
    # the transpose of a gathered product is a scattered one: x's cotangent
    # is summed around the ring, the weights' over the rows of every hop
    held, ws = res
    out_rows = _terms(subscripts)[2].index("s")
    to_x, to_w = _cotangent_einsums(subscripts)
    d_ws = [None] * len(ws)

    def product(r):
        d_rows = [_rows_from(d, _rows_of(r + 1, n), out_rows, n) for d in d_outs]
        for k, d in enumerate(d_rows):
            d_ws[k] = _add_product(d_ws[k], to_w, held[(r + 1) % n], d)
        return reduce(jnp.add, [jnp.einsum(to_x, d, w) for d, w in zip(d_rows, ws)])

    d_x = _scatter_ring(n, product)
    return d_x, tuple(d_ws)


_gather_products.defvjp(_gather_products_fwd, _gather_products_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scatter_product(subscripts, n, x, w):
    return _scatter_product_fwd(subscripts, n, x, w)[0]


def _scatter_product_fwd(subscripts, n, x, w):
    x_rows = _terms(subscripts)[0].index("s")
    out = _scatter_ring(n, lambda r: jnp.einsum(subscripts, _rows_from(x, _rows_of(r + 1, n), x_rows, n), w))
    return out, (x, w)


def _scatter_product_bwd(subscripts, n, res, d_out):
    # the transpose of a scattered product is a gathered one
    x, w = res
    x_rows = _terms(subscripts)[0].index("s")
    to_x, to_w = _cotangent_einsums(subscripts)
    grads = [None, None]

    def products(r, d_r):
        grads[0] = _into_rows(grads[0], jnp.einsum(to_x, d_r, w), _rows_of(r, n), x_rows, n)
        grads[1] = _add_product(grads[1], to_w, _rows_from(x, _rows_of(r, n), x_rows, n), d_r)

    _gather_ring(d_out, n, products)
    return tuple(grads)


_scatter_product.defvjp(_scatter_product_fwd, _scatter_product_bwd)

_MLP_IN, _MLP_OUT = "bse,em->bsm", "bsm,me->bse"


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mlp_products(act, n, x, w_in, w_down):
    return _mlp_products_fwd(act, n, x, w_in, w_down)[0]


def _mlp_products_fwd(act, n, x, w_in, w_down):
    # both rings over the same rows: what hop r of the gather multiplied is
    # what hop r - 1 of the scatter sends on, and no row leaves its chunk
    pre = []
    held = _gather_ring(x, n, lambda r, x_r: pre.append([jnp.einsum(_MLP_IN, x_r, w) for w in w_in]))
    out = _scatter_ring(n, lambda r: jnp.einsum(_MLP_OUT, act(*pre[(r + 1) % n]), w_down))
    return out, (held, pre, w_in, w_down)


def _mlp_products_bwd(act, n, res, d_out):
    held, pre, w_in, w_down = res
    (in_to_x, in_to_w), (out_to_x, out_to_w) = _cotangent_einsums(_MLP_IN), _cotangent_einsums(_MLP_OUT)
    d_pre, d_w_in, d_w_down = [None] * n, [None] * len(w_in), [None]

    def products(r, d_r):
        hidden, act_vjp = jax.vjp(act, *pre[r])
        d_w_down[0] = _add_product(d_w_down[0], out_to_w, hidden, d_r)
        d_pre[r] = act_vjp(jnp.einsum(out_to_x, d_r, w_down))
        for k, d in enumerate(d_pre[r]):
            d_w_in[k] = _add_product(d_w_in[k], in_to_w, held[r], d)

    _gather_ring(d_out, n, products)
    d_x = _scatter_ring(
        n, lambda r: reduce(jnp.add, [jnp.einsum(in_to_x, d, w) for d, w in zip(d_pre[(r + 1) % n], w_in)]))
    return d_x, tuple(d_w_in), d_w_down[0]


_mlp_products.defvjp(_mlp_products_fwd, _mlp_products_bwd)


def _over_tensor(products: tuple, body, mesh: Mesh, in_specs, out_specs):
    """``body`` mapped over "tensor" alone: every other axis stays automatic
    (the weights' fsdp gathers are GSPMD's), and inside an enclosing
    shard_map over other axes the context mesh is the one to map over.
    ``products``: the einsums it exchanges, for :func:`record_exchanged_products`."""
    log = _exchange_log.get()
    if log is not None:
        log.update(products)
    context_mesh = jax.sharding.get_abstract_mesh()
    # (jitted: a shard_map that leaves axes automatic cannot run op by op, as
    # a flax ``init`` outside jit would; inside a jit this one is inlined)
    return jax.jit(shard_map(
        body, mesh=context_mesh if context_mesh.manual_axes else mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=frozenset({TENSOR_AXIS}), check_vma=False))


def _spec(term: str, letters: str) -> P:
    return P(*(TENSOR_AXIS if c in letters else None for c in term))


def gather_einsum(subscripts: str, x, weights, mesh: Mesh, *, shard: str):
    """``[einsum(subscripts, x, w) for w in weights]`` where ``x``'s rows
    (letter "s") are sharded over "tensor" and every weight's letter ``shard``
    is: the outputs hold every row and their ``shard`` letter stays sharded.
    In place of an all-gather of ``x`` before the products, each chip
    multiplies the rows it holds while a `ppermute` brings the next chip's."""
    n = mesh.shape[TENSOR_AXIS]
    x_term, w_term, out_term = _terms(subscripts)
    return _over_tensor(
        (subscripts,), lambda x, *ws: _gather_products(subscripts, n, x, ws), mesh,
        (_spec(x_term, "s"),) + (_spec(w_term, shard),) * len(weights),
        (_spec(out_term, shard),) * len(weights))(x, *weights)


def einsum_scatter(subscripts: str, x, w, mesh: Mesh, *, shard: str):
    """``einsum(subscripts, x, w)`` where the contracted letter ``shard`` of
    both operands is sharded over "tensor" and ``x`` holds every row: the
    output's rows (letter "s") come out sharded over "tensor", each chip's
    the sum of every chip's partial product for them. In place of an
    all-reduce after the product, each chip multiplies the rows of the chip
    farthest down the ring first and passes the running sum on while it
    multiplies the next; its own rows come last."""
    n = mesh.shape[TENSOR_AXIS]
    x_term, w_term, out_term = _terms(subscripts)
    return _over_tensor(
        (subscripts,), lambda x, w: _scatter_product(subscripts, n, x, w), mesh,
        (_spec(x_term, shard), _spec(w_term, shard)), _spec(out_term, "s"))(x, w)


def mlp_exchange(x, w_in, w_down, act, mesh: Mesh):
    """``act(*[x @ w for w in w_in]) @ w_down`` (a gated or a two-matrix MLP)
    where ``x``'s rows are sharded over "tensor", every matrix's MLP width is,
    and the output's rows come out sharded like ``x``'s: a gathered product
    handing its rows, chunk by chunk, to a scattered one. Each chip multiplies
    the rows it holds while the next chip's arrive, and sends the next chip's
    partial sum on while it multiplies its own into ``w_down``; the hidden
    rows are never put together, so nothing is copied into place. ``act``: a
    module-level function of the products in ``w_in``'s order."""
    n = mesh.shape[TENSOR_AXIS]
    (x_term, in_term, _), (_, down_term, _) = _terms(_MLP_IN), _terms(_MLP_OUT)
    rows = _spec(x_term, "s")
    return _over_tensor(
        (_MLP_IN, _MLP_OUT), lambda x, w_down, *w_in: _mlp_products(act, n, x, w_in, w_down), mesh,
        (rows, _spec(down_term, "m")) + (_spec(in_term, "m"),) * len(w_in), rows)(x, w_down, *w_in)
