"""The selective state-space recurrence (Mamba-1) for the serving programs.

One token of one sequence advances a state ``S`` in R^(N x D) (``N`` =
``d_state``, ``D`` = ``d_inner``; laid out ``[N, D]`` so that the wide
dimension lies on lanes: Mosaic copies whole lanes only):

    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * u_t) (x) B_t
    y_t = S_t^T C_t + D_skip * u_t

with ``u_t``, ``dt_t`` in R^D (the convolved input and the step, after its
softplus), ``B_t``, ``C_t`` in R^N, ``A`` in R^(N x D) negative. Everything
is float32: the recurrence compounds its rounding over a request's length.

Both serving programs hand the recurrence over as **blocks**: ``u``, ``dt``
``[blocks, rows, D]`` and ``b``, ``c`` ``[blocks, rows, N]``, each block the
rows of one slot in order, with three descriptors a block:

- ``block_slot`` the slot whose state the block advances (-1: no slot, a
  pack's trailing padding);
- ``block_rows`` how many of its rows are real (the rest advance nothing);
- ``block_fresh`` 1 where the slot's state is zeroed before the first row (a
  request's first chunk: the reset costs no dispatch of its own).

A packed prefill is the pack's token blocks (consecutive blocks of one slot
continue each other); a decode step is one block of one row a slot, with 0
rows for a slot that is not live. The states of all layers of a scanned
stack are one array ``[layers, slots, N, D]`` and the call names its layer,
so that the stack rides the layer scan whole and is updated in place
(``input_output_aliases``): no layer's state is sliced out or put back.

:func:`selective_scan_reference` is the ``jax.numpy`` form (differentiable;
what runs off the chip and in the plain forward pass), and
:func:`ssm_scan` the dispatch onto the one pallas kernel,
``pallas_call(name="ssm_scan")``: a grid step is one block; the slot's state
comes out of HBM into VMEM through a block spec indexed by the block's
slot, so the next block's state is on its way while this one's rows are
walked, and goes back when the slot changes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import pl, pltpu

_KERNEL_MODES = ("scan", "reference", "interpret")
_LANE_CHUNK = 512            # lanes of the state walked at a time: 8 vregs a value at N = 16
_VMEM_LIMIT = 48 * 2 ** 20   # a 64-row block at D = 5120 holds about 12 MB, double-buffered


def resolve_ssm_kernel(impl: Optional[str] = None) -> str:
    """``impl`` (``DecoderConfig.ssm_kernel``), else the kernel on a TPU and
    the ``jax.numpy`` reference elsewhere. "interpret" runs the same kernel
    through the pallas interpreter, for the tests on the CPU."""
    mode = impl or ("scan" if jax.default_backend() == "tpu" else "reference")
    if mode not in _KERNEL_MODES:
        raise ValueError(f"ssm_kernel must be one of {_KERNEL_MODES}, got {mode!r}")
    return mode


def ssm_kernel_active(config) -> bool:
    """Would the serving programs of a model with this config run the
    ``ssm_scan`` kernel in this process (the ``serving/ssm_kernel_active``
    gauge)? It mirrors :func:`ssm_scan`'s dispatch."""
    return resolve_ssm_kernel(getattr(config, "ssm_kernel", None)) != "reference"


def selective_scan_reference(u, dt, b, c, a, d_skip, state, *, block_slot, block_rows,
                             block_fresh, layer=0):
    """The recurrence in ``jax.numpy``: blocks in order, rows in order.
    ``u``, ``dt`` [blocks, rows, D]; ``b``, ``c`` [blocks, rows, N]; ``a``
    [N, D]; ``d_skip`` [D]; ``state`` [layers, slots, N, D] float32. Returns
    ``(y [blocks, rows, D] float32, state)``."""
    f32 = jnp.float32
    a, d_skip = a.astype(f32), d_skip.astype(f32)
    rows = u.shape[1]
    stack = state[layer]

    def block(stack, xs):
        u_b, dt_b, b_b, c_b, slot, n, fresh = xs
        at = jnp.maximum(slot, 0)
        before = stack[at]

        def row(s, r):
            u_t, dt_t, b_t, c_t, i = r
            dt_t = jnp.where(i < n, dt_t, 0.0)  # dt = 0: the state stays as it is
            s = jnp.exp(dt_t[None, :] * a) * s + (dt_t * u_t)[None, :] * b_t[:, None]
            return s, jnp.sum(s * c_t[:, None], axis=0) + d_skip * u_t

        s, y = jax.lax.scan(row, jnp.where(fresh > 0, 0.0, before),
                            (u_b.astype(f32), dt_b.astype(f32), b_b.astype(f32), c_b.astype(f32),
                             jnp.arange(rows)))
        # a block of no slot or no rows advances nothing (a fresh one still zeroes)
        keep = (slot < 0) | ((n <= 0) & (fresh <= 0))
        return stack.at[at].set(jnp.where(keep, before, s)), y

    stack, y = jax.lax.scan(block, stack, (u, dt, b, c, block_slot, block_rows, block_fresh))
    return y, state.at[layer].set(stack)


def _ssm_scan_kernel(layer_ref, slot_ref, rows_ref, fresh_ref, cont_ref,
                     u_ref, dt_ref, b_ref, c_ref, a_ref, dskip_ref, s_in_ref,
                     y_ref, s_out_ref, *, group: int, chunk: int):
    del layer_ref, slot_ref  # the block specs read them
    f32 = jnp.float32
    j = pl.program_id(0)
    rows, width = u_ref.shape[1], u_ref.shape[2]
    n = rows_ref[j]

    # the slot's state stays in the output block while consecutive blocks
    # continue one slot; the first of them takes it from HBM, or from zero
    @pl.when(cont_ref[j] == 0)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(fresh_ref[j] == 1)
    def _():
        s_out_ref[...] = jnp.zeros(s_out_ref.shape, f32)

    @pl.when(n < rows)
    def _():  # rows no group reaches are padding: their y is never read, but is no garbage either
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def walk(base):
        for c0 in range(0, width, chunk):
            lanes = slice(c0, c0 + chunk)
            a, d_skip = a_ref[:, lanes], dskip_ref[:, lanes]
            u_g = u_ref[0, pl.ds(base, group), lanes].astype(f32)
            dt_g = dt_ref[0, pl.ds(base, group), lanes].astype(f32)
            s = s_out_ref[0, 0, :, lanes]
            ys = []
            for i in range(group):
                dt_i = jnp.where(base + i < n, dt_g[i:i + 1], 0.0)  # a padding row advances nothing
                u_i = u_g[i:i + 1]
                s = jnp.exp(dt_i * a) * s + (dt_i * u_i) * b_ref[0, base + i]
                ys.append(jnp.sum(s * c_ref[0, base + i], axis=0, keepdims=True) + d_skip * u_i)
            s_out_ref[0, 0, :, lanes] = s
            y_ref[0, pl.ds(base, group), lanes] = (
                jnp.concatenate(ys, axis=0) if group > 1 else ys[0]).astype(y_ref.dtype)

    if rows == group:  # one group (a decode step's one row): no loop, and no dynamic row index
        pl.when(n > 0)(lambda: walk(0))
    else:
        def body(g, carry):
            walk(pl.multiple_of(g * group, group))
            return carry

        jax.lax.fori_loop(0, (n + group - 1) // group, body, 0)


def _block_descriptors(block_slot, block_rows, block_fresh):
    """What the kernel's grid reads a block: the slot whose state its block
    specs fetch (a block of no slot stays on the one before it, so nothing
    moves), its rows, its reset, and whether it continues the block before
    it (the state is then already in VMEM)."""
    i32 = jnp.int32
    slot = block_slot.astype(i32)
    nb = slot.shape[0]
    last = jax.lax.cummax(jnp.where(slot >= 0, jnp.arange(nb, dtype=i32), -1))
    at = jnp.where(last >= 0, slot[jnp.maximum(last, 0)], 0)
    rows = jnp.where(slot >= 0, block_rows.astype(i32), 0)
    fresh = jnp.where(slot >= 0, block_fresh.astype(i32), 0)
    cont = jnp.concatenate([jnp.zeros((1,), i32), (at[1:] == at[:-1]).astype(i32)])
    return at, rows, fresh, cont


def _ssm_scan_call(u, dt, b, c, a, d_skip, state, block_slot, block_rows, block_fresh, layer,
                   interpret: bool):
    f32 = jnp.float32
    nb, rows, width = u.shape
    n = a.shape[0]
    group = 8 if rows % 8 == 0 else 1
    if group == 1 and rows != 1:
        raise ValueError(f"ssm_scan walks blocks of 1 row or of a multiple of 8, got {rows}")
    chunk = _LANE_CHUNK if width % _LANE_CHUNK == 0 else width
    at, n_rows, fresh, cont = _block_descriptors(block_slot, block_rows, block_fresh)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), at, n_rows, fresh, cont)

    def a_block(j, *_):
        return (j, 0, 0)

    def a_row(j, *_):
        return (j, 0, 0, 0)

    def whole(j, *_):
        return (0, 0)

    def of_slot(j, ly, sl, *_):
        return (ly[0], sl[j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, rows, width), a_block),          # u
        pl.BlockSpec((1, rows, width), a_block),          # dt
        pl.BlockSpec((1, rows, n, 1), a_row),             # b: a column a row, to broadcast along lanes
        pl.BlockSpec((1, rows, n, 1), a_row),             # c
        pl.BlockSpec((n, width), whole),                  # a
        pl.BlockSpec((1, width), whole),                  # d_skip
        pl.BlockSpec((1, 1, n, width), of_slot),          # the layers' states, this block's slot
    ]
    out_specs = [pl.BlockSpec((1, rows, width), a_block), pl.BlockSpec((1, 1, n, width), of_slot)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(nb,), in_specs=in_specs, out_specs=out_specs)
    # blocks in order: consecutive blocks of a slot hand its state on
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)}
    operands = (u, dt, b.astype(f32)[..., None], c.astype(f32)[..., None], a.astype(f32),
                d_skip.astype(f32).reshape(1, width), state)
    y, state = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, group=group, chunk=chunk),
        grid_spec=grid_spec, name="ssm_scan", interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((nb, rows, width), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(scalars) + len(operands) - 1: 1},
        **params,
    )(*scalars, *operands)
    return y, state


def ssm_scan(u, dt, b, c, a, d_skip, state, *, block_slot, block_rows, block_fresh, layer=0,
             impl: Optional[str] = None):
    """The recurrence over blocks (the module's docstring): returns ``(y
    [blocks, rows, D] float32, state)``, the stack of states with this
    ``layer``'s slots advanced. ``impl``: :func:`resolve_ssm_kernel`."""
    mode = resolve_ssm_kernel(impl)
    if state.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is float32, got {state.dtype}")
    if mode == "reference":
        return selective_scan_reference(u, dt, b, c, a, d_skip, state, block_slot=block_slot,
                                        block_rows=block_rows, block_fresh=block_fresh, layer=layer)
    return _ssm_scan_call(u, dt, b, c, a, d_skip, state, block_slot, block_rows, block_fresh, layer,
                          mode == "interpret")


# -- the recurrence with heads (Mamba-2) ---------------------------------------
#
# One token advances a state ``S`` in R^(H x P x N) (``H`` heads of ``P``
# channels, ``N`` = ``d_state``):
#
#     S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
#     y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
#
# ``A`` and ``dt`` are one scalar a head and ``B``, ``C`` one vector a group of
# heads. Per channel ``d = h P + p`` that is Mamba-1's update with a decay that
# does not depend on ``n`` and with ``B``, ``C`` chosen by the channel's group,
# so the callers hand the per-head quantities over **a channel** (``dt``, ``a``,
# ``d_skip`` repeated ``P`` times: a few KB a row beside a state of megabytes)
# and the blocks' descriptors are the ones above. The state is laid out
# ``[layers, slots, D / lane, N, lane]``: the channels in chunks of ``lane``
# (128 where a group's channels are whole lanes; a chunk then lies in one
# group), ``N`` on sublanes, so that a grid step walks its slot's state chunk
# by chunk, 16 vector registers at a time at N = 128, with B and C down the
# sublanes, the same in every lane, and the read a sum over sublanes. Rows,
# B and C keep the layout they are computed in (a chunk is an index, not a
# copy).


def ssd_state_shape(channels: int, groups: int, n: int) -> tuple:
    """``(chunks, n, lane)``: a slot's state in one layer of ``channels``
    (heads x head_dim) channels in ``groups`` groups of B and C."""
    per_group = channels // groups
    lane = 128 if per_group % 128 == 0 else per_group
    return channels // lane, n, lane


def ssd_scan_reference(u, dt, b, c, a, d_skip, state, *, block_slot, block_rows, block_fresh,
                       layer=0):
    """The recurrence in ``jax.numpy``: blocks in order, rows in order.
    ``u``, ``dt`` [blocks, rows, D] (the step a channel); ``b``, ``c``
    [blocks, rows, G, N]; ``a``, ``d_skip`` [D]; ``state`` [layers, slots,
    D / lane, N, lane] float32. Returns ``(y [blocks, rows, D] float32,
    state)``."""
    f32 = jnp.float32
    _, _, chunks, _, lane = state.shape
    rows, width = u.shape[1], u.shape[2]
    per = chunks // b.shape[2]  # chunks a group
    a_c = a.astype(f32).reshape(chunks, 1, lane)
    skip_c = d_skip.astype(f32).reshape(chunks, lane)
    stack = state[layer]

    def block(stack, xs):
        u_b, dt_b, b_b, c_b, slot, n, fresh = xs
        at = jnp.maximum(slot, 0)
        before = stack[at]

        def row(s, r):
            u_t, dt_t, b_t, c_t, i = r
            dt_t = jnp.where(i < n, dt_t, 0.0).reshape(chunks, 1, lane)  # dt = 0: the state stays
            u_t = u_t.reshape(chunks, 1, lane)
            b_t = jnp.repeat(b_t, per, axis=0)[:, :, None]               # [chunks, N, 1]
            c_t = jnp.repeat(c_t, per, axis=0)[:, :, None]
            s = jnp.exp(dt_t * a_c) * s + (dt_t * u_t) * b_t
            return s, (jnp.sum(s * c_t, axis=1) + skip_c * u_t[:, 0]).reshape(width)

        s, y = jax.lax.scan(row, jnp.where(fresh > 0, 0.0, before),
                            (u_b.astype(f32), dt_b.astype(f32), b_b.astype(f32), c_b.astype(f32),
                             jnp.arange(rows)))
        keep = (slot < 0) | ((n <= 0) & (fresh <= 0))
        return stack.at[at].set(jnp.where(keep, before, s)), y

    stack, y = jax.lax.scan(block, stack, (u, dt, b, c, block_slot, block_rows, block_fresh))
    return y, state.at[layer].set(stack)


_SSD_VMEM = 64 * 2 ** 20  # a slot's 4 MB state in and out, double-buffered, beside a 64-row block's rows


def _ssd_scan_kernel(layer_ref, slot_ref, rows_ref, fresh_ref, cont_ref,
                     u_ref, dt_ref, b_ref, c_ref, a_ref, dskip_ref, s_in_ref,
                     y_ref, s_out_ref, bb, cc, *, group: int):
    del layer_ref, slot_ref  # the block specs read them
    f32 = jnp.float32
    j = pl.program_id(0)
    rows, chunks, lane = u_ref.shape[1:]
    groups, n_state = b_ref.shape[2:]
    per = chunks // groups
    n = rows_ref[j]

    # as ssm_scan: the state stays in the output block while consecutive
    # blocks continue one slot
    @pl.when(cont_ref[j] == 0)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(fresh_ref[j] == 1)
    def _():
        s_out_ref[...] = jnp.zeros(s_out_ref.shape, f32)

    @pl.when(n < rows)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def walk(base):  # ``group`` rows from row ``base``, over every chunk of the state
        def of_group(g, carry):
            # a row's B and C are vectors along the lanes; the state wants
            # them down the sublanes, the same in every lane: the row spread
            # over a square tile and turned (once a group of heads, for all
            # its chunks)
            for i in range(group):
                for ref, tile in ((b_ref, bb), (c_ref, cc)):
                    tile[i] = jnp.broadcast_to(ref[0, base + i, pl.ds(g, 1), :], (lane, n_state)).T

            def of_chunk(k, carry):
                ch = g * per + k
                a, d_skip = a_ref[pl.ds(ch, 1), :], dskip_ref[pl.ds(ch, 1), :]    # [1, lane]
                s = s_out_ref[0, 0, ch]                                            # [N, lane]
                for i in range(group):
                    u_i = u_ref[0, base + i, pl.ds(ch, 1), :]
                    dt_i = jnp.where(base + i < n, dt_ref[0, base + i, pl.ds(ch, 1), :], 0.0)  # padding: no advance
                    s = jnp.exp(dt_i * a) * s + (dt_i * u_i) * bb[i]
                    y_ref[0, base + i, pl.ds(ch, 1), :] = (
                        jnp.sum(s * cc[i], axis=0, keepdims=True) + d_skip * u_i).astype(y_ref.dtype)
                s_out_ref[0, 0, ch] = s
                return carry

            return jax.lax.fori_loop(0, per, of_chunk, carry)

        jax.lax.fori_loop(0, groups, of_group, 0)

    if rows == group:  # one group (a decode step's one row): no loop
        pl.when(n > 0)(lambda: walk(0))
    else:  # (a row is a leading index of its blocks: any base will do)
        def body(r, carry):
            walk(r * group)
            return carry

        jax.lax.fori_loop(0, (n + group - 1) // group, body, 0)


def _ssd_scan_call(u, dt, b, c, a, d_skip, state, block_slot, block_rows, block_fresh, layer,
                   interpret: bool):
    f32 = jnp.float32
    nb, rows, width = u.shape
    _, _, chunks, n, lane = state.shape
    groups = b.shape[2]
    group = 8 if rows % 8 == 0 else 1
    if group == 1 and rows != 1:
        raise ValueError(f"ssd_scan walks blocks of 1 row or of a multiple of 8, got {rows}")
    if chunks * lane != width or chunks % groups:
        raise ValueError(f"the state {state.shape} does not lay out {width} channels in {groups} groups")
    at, n_rows, fresh, cont = _block_descriptors(block_slot, block_rows, block_fresh)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), at, n_rows, fresh, cont)
    by_chunk = lambda v: v.astype(f32).reshape(*v.shape[:-1], chunks, lane)  # a chunk by an index, no copy

    def a_block(j, *_):
        return (j, 0, 0, 0)

    def whole(j, *_):
        return (0, 0)

    def of_slot(j, ly, sl, *_):
        return (ly[0], sl[j], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, rows, chunks, lane), a_block),    # u
        pl.BlockSpec((1, rows, chunks, lane), a_block),    # dt, a channel
        pl.BlockSpec((1, rows, groups, n), a_block),       # b
        pl.BlockSpec((1, rows, groups, n), a_block),       # c
        pl.BlockSpec((chunks, lane), whole),               # a, a channel
        pl.BlockSpec((chunks, lane), whole),               # d_skip
        pl.BlockSpec((1, 1, chunks, n, lane), of_slot),    # the layers' states, this block's slot
    ]
    out_specs = [pl.BlockSpec((1, rows, chunks, lane), a_block),
                 pl.BlockSpec((1, 1, chunks, n, lane), of_slot)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(nb,), in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((group, n, lane), f32), pltpu.VMEM((group, n, lane), f32)])
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_SSD_VMEM)}
    operands = (by_chunk(u), by_chunk(dt), b.astype(f32), c.astype(f32), by_chunk(a), by_chunk(d_skip), state)
    y, state = pl.pallas_call(
        functools.partial(_ssd_scan_kernel, group=group),
        grid_spec=grid_spec, name="ssd_scan", interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((nb, rows, chunks, lane), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(scalars) + len(operands) - 1: 1},
        **params,
    )(*scalars, *operands)
    return y.reshape(nb, rows, width), state


def ssd_scan(u, dt, b, c, a, d_skip, state, *, block_slot, block_rows, block_fresh, layer=0,
             impl: Optional[str] = None):
    """The recurrence with heads over blocks (the comment above): ``u``,
    ``dt`` [blocks, rows, D] with the step a channel, ``b``, ``c`` [blocks,
    rows, G, N], ``a``, ``d_skip`` [D], ``state`` [layers, slots,
    *ssd_state_shape] float32. Returns ``(y [blocks, rows, D] float32,
    state)`` with this ``layer``'s slots advanced. ``impl``:
    :func:`resolve_ssm_kernel`; the kernel is ``pallas_call(name="ssd_scan")``."""
    mode = resolve_ssm_kernel(impl)
    if state.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is float32, got {state.dtype}")
    if mode == "reference":
        return ssd_scan_reference(u, dt, b, c, a, d_skip, state, block_slot=block_slot,
                                  block_rows=block_rows, block_fresh=block_fresh, layer=layer)
    return _ssd_scan_call(u, dt, b, c, a, d_skip, state, block_slot, block_rows, block_fresh, layer,
                          mode == "interpret")


# -- the gated delta rule (Gated DeltaNet) -------------------------------------
#
# One token advances a state ``S`` in R^(Hv x dk x dv) (``Hv`` value heads; a
# key head serves ``Hv / Hk`` of them; ``dk``, ``dv`` the keys' and the values'
# head widths), a value head:
#
#     S <- exp(g_t) S;  r = S^T k_t;  S <- S + k_t (x) (beta_t (v_t - r));  o_t = S^T q_t
#
# ``g_t`` (negative) and ``beta_t`` are one scalar a value head a token; ``q``
# and ``k`` reach the recurrence normed and scaled. Unlike both recurrences
# above the step is not a decay and an add: ``r`` reads the whole state before
# the write. The state is laid out ``[layers, slots, Hv, dk, dv]``, the values'
# width on lanes and the keys' down the sublanes, so that the read and the
# output are sums over sublanes and the write a product with ``k`` down the
# sublanes, the same in every lane (the turned tile of ``ssd_scan``, made once a
# key head for the value heads it serves). The blocks' descriptors are the
# ones above. Two forms give the same numbers, and the one kernel
# (``pallas_call(name="gdn_scan")``, :func:`gdn_scan`) runs each where it is
# the shorter, chosen by the block's shape:
#
# - **the row walk** (:func:`gdn_scan_reference`, and the kernel for a block of
#   one row, a decode step's slot): four passes over a head's state a row, on
#   the vector unit, at the pace of the state's bytes;
# - **the chunked form** (the kernel for a block of more rows, a pack's token
#   block; :func:`gdn_chunked` is its ``jax.numpy`` mirror, the tests' second
#   derivation of the rule and the form a training step would differentiate):
#   ``C`` rows at a time, the slot's state in VMEM throughout. With ``G`` the
#   running sum of ``g`` within the chunk, ``A[t, j] = beta_t exp(G_t - G_j)
#   k_t . k_j`` below the diagonal, the rows' updates solve ``(I + A) U = beta
#   (V - exp(G) K S_0)``, a unit lower triangular system; then ``O = exp(G) Q
#   S_0 + tril(exp(G_t - G_j) Q K^T) U`` and ``S_C = exp(G_C) S_0 + (exp(G_C -
#   G) K)^T U``: products for the matrix unit, float32 at the highest
#   precision, and forward substitution. The kernel takes a block's live rows
#   as one chunk (the least of 8, 16, 32 rows or the block's that holds them)
#   and solves the system 32 rows at a time. Every sum of ``g``
#   that is exponentiated (``G_t - G_j`` for ``t >= j``, ``G_t``, ``G_C -
#   G_t``) is taken as a sum of its non-positive terms, never as a difference
#   of running sums: ``g`` of -50 a row is in the configuration's range, and a
#   ratio between two near rows must not inherit the rounding of a sum of
#   hundreds. Nothing overflows and nothing is divided by a decay.


def gdn_scan_reference(q, k, v, decay, beta, state, *, block_slot, block_rows, block_fresh, layer=0):
    """The delta rule in ``jax.numpy``: blocks in order, rows in order. ``q``,
    ``k`` [blocks, rows, Hk, dk]; ``v`` [blocks, rows, Hv, dv]; ``decay`` (=
    ``exp(g)``) and ``beta`` [blocks, rows, Hv]; ``state`` [layers, slots, Hv,
    dk, dv] float32. Returns ``(o [blocks, rows, Hv, dv] float32, state)``."""
    f32 = jnp.float32
    rows = q.shape[1]
    per = v.shape[2] // k.shape[2]  # value heads a key head
    stack = state[layer]

    def block(stack, xs):
        q_b, k_b, v_b, d_b, b_b, slot, n, fresh = xs
        at = jnp.maximum(slot, 0)
        before = stack[at]

        def row(s, r):
            q_t, k_t, v_t, d_t, b_t, i = r
            live = i < n  # a padding row advances nothing
            d_t, b_t = jnp.where(live, d_t, 1.0), jnp.where(live, b_t, 0.0)
            q_t, k_t = jnp.repeat(q_t, per, axis=0), jnp.repeat(k_t, per, axis=0)   # [Hv, dk]
            s = d_t[:, None, None] * s
            read = jnp.sum(s * k_t[:, :, None], axis=1)                              # [Hv, dv]
            s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
            return s, jnp.sum(s * q_t[:, :, None], axis=1)

        s, o = jax.lax.scan(row, jnp.where(fresh > 0, 0.0, before),
                            (q_b.astype(f32), k_b.astype(f32), v_b.astype(f32), d_b.astype(f32),
                             b_b.astype(f32), jnp.arange(rows)))
        keep = (slot < 0) | ((n <= 0) & (fresh <= 0))
        return stack.at[at].set(jnp.where(keep, before, s)), o

    stack, o = jax.lax.scan(block, stack, (q, k, v, decay, beta, block_slot, block_rows, block_fresh))
    return o, state.at[layer].set(stack)


def gdn_chunked(q, k, v, g, beta, state, *, block_slot, block_rows, block_fresh, layer=0, chunk: int = 64):
    """The chunked form (the comment above) in ``jax.numpy``: the arguments of
    :func:`gdn_scan_reference` but ``g`` itself, not its exponential (a chunk
    works with its running sums). A block is walked in chunks of ``chunk`` rows;
    where ``chunk`` does not divide the block's rows the block is padded with
    rows that advance nothing. Returns ``(o, state)`` alike."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    nb, rows, hk, dk = q.shape
    hv = v.shape[2]
    per = hv // hk
    c = min(chunk, rows)
    padded = -(-rows // c) * c
    stack = state[layer]
    lower = jnp.tril(jnp.ones((c, c), bool), -1)
    upto = jnp.tril(jnp.ones((c, c), bool))

    def by_chunks(x):  # [rows, heads, ...] -> [chunks, Hv, c, ...], a key head's rows for each value head it serves
        x = jnp.pad(x.astype(f32), ((0, padded - rows),) + ((0, 0),) * (x.ndim - 1))
        if x.shape[1] == hk and per > 1:
            x = jnp.repeat(x, per, axis=1)
        x = x.reshape(padded // c, c, *x.shape[1:])
        return jnp.moveaxis(x, 2, 1)

    def block(stack, xs):
        q_b, k_b, v_b, g_b, b_b, slot, n, fresh = xs
        at = jnp.maximum(slot, 0)
        before = stack[at]
        live = jnp.arange(rows) < n
        g_b = jnp.where(live[:, None], g_b, 0.0)   # decay 1 and beta 0: the state stays as it is
        b_b = jnp.where(live[:, None], b_b, 0.0)

        def one(s, xs):
            qc, kc, vc, gc, bc = xs                                   # [Hv, c, dk | dv], [Hv, c]
            # sums of g between rows, each a sum of non-positive terms and none a difference of running sums: the
            # ratio between near rows keeps its digits however far the state has decayed before them
            g_upto = jnp.where(upto, gc[:, None, :], 0.0)                                      # [Hv, t, i]: g_i, i <= t
            ratio = jnp.einsum("hti,ij->htj", g_upto, lower.astype(f32), precision=hi)         # G_t - G_j, t >= j
            run = jnp.sum(g_upto, axis=-1)                                                     # G
            left = jnp.sum(jnp.where(upto, 0.0, gc[:, None, :]), axis=-1)                      # G_C - G
            kk = jnp.einsum("htd,hjd->htj", kc, kc, precision=hi)
            a = jnp.where(lower, bc[:, :, None] * jnp.exp(ratio) * kk, 0.0)
            grown = jnp.exp(run)[:, :, None]
            rhs = bc[:, :, None] * (vc - grown * jnp.einsum("htd,hde->hte", kc, s, precision=hi))
            u = jax.scipy.linalg.solve_triangular(a + jnp.eye(c, dtype=f32), rhs, lower=True, unit_diagonal=True)
            qk = jnp.einsum("htd,hjd->htj", qc, kc, precision=hi)
            qk = jnp.where(upto, jnp.exp(ratio) * qk, 0.0)
            o = grown * jnp.einsum("htd,hde->hte", qc, s, precision=hi) \
                + jnp.einsum("htj,hje->hte", qk, u, precision=hi)
            to_end = jnp.exp(left)[:, :, None] * kc                    # exp(G_C - G) K
            s = jnp.exp(run[:, -1])[:, None, None] * s + jnp.einsum("htd,hte->hde", to_end, u, precision=hi)
            return s, o

        s, o = jax.lax.scan(one, jnp.where(fresh > 0, 0.0, before),
                            tuple(by_chunks(x) for x in (q_b, k_b, v_b, g_b, b_b)))
        o = jnp.moveaxis(o, 1, 2).reshape(padded, hv, -1)[:rows]
        keep = (slot < 0) | ((n <= 0) & (fresh <= 0))
        return stack.at[at].set(jnp.where(keep, before, s)), o

    stack, o = jax.lax.scan(block, stack, (q, k, v, g, beta, block_slot, block_rows, block_fresh))
    return o, state.at[layer].set(stack)


_GDN_VMEM = 48 * 2 ** 20  # a slot's 2 MB state in and out, double-buffered, beside a 64-row block's rows
# rows of a chunk's triangular system solved by substitution at a time, the blocks of rows above them through a
# product (on the chip a 64-row chunk takes 7% longer at 16 and half as long again at 64: PERF.md section 6, PR 49)
_GDN_SOLVE = 32
_GDN_CHUNKS = (8, 16, 32)  # a block of fewer live rows than it holds is one chunk of the least of these that holds them


def _gdn_block_start(j, fresh_ref, cont_ref, s_in_ref, s_out_ref):
    # as ssm_scan: the state stays in the output block while consecutive
    # blocks continue one slot
    @pl.when(cont_ref[j] == 0)
    def _():
        s_out_ref[...] = s_in_ref[...]

    @pl.when(fresh_ref[j] == 1)
    def _():
        s_out_ref[...] = jnp.zeros(s_out_ref.shape, jnp.float32)


def _gdn_step_kernel(layer_ref, slot_ref, rows_ref, fresh_ref, cont_ref,
                     q_ref, k_ref, v_ref, d_ref, b_ref, s_in_ref, o_ref, s_out_ref):
    """A block of one row (a decode step's slot): the row walk."""
    del layer_ref, slot_ref  # the block specs read them
    j = pl.program_id(0)
    hk, dk = q_ref.shape[2:]
    hv, dv = v_ref.shape[2:]
    per = hv // hk
    _gdn_block_start(j, fresh_ref, cont_ref, s_in_ref, s_out_ref)

    @pl.when(rows_ref[j] < 1)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(rows_ref[j] > 0)
    def _():
        def of_key_head(g, carry):
            # the row's key and query are vectors along the lanes; the state
            # wants them down the sublanes, the same in every lane: the row
            # spread over a tile and turned, once for the value heads it serves
            kk, qq = (jnp.broadcast_to(ref[0, 0, pl.ds(g, 1), :], (dv, dk)).T for ref in (k_ref, q_ref))
            for p in range(per):
                h = g * per + p
                s = d_ref[0, 0, pl.ds(h, 1), :] * s_out_ref[0, 0, h]                   # [dk, dv]
                read = jnp.sum(s * kk, axis=0, keepdims=True)
                s = s + kk * (b_ref[0, 0, pl.ds(h, 1), :] * (v_ref[0, 0, pl.ds(h, 1), :] - read))
                o_ref[0, 0, pl.ds(h, 1), :] = jnp.sum(s * qq, axis=0, keepdims=True).astype(o_ref.dtype)
                s_out_ref[0, 0, h] = s
            return carry

        jax.lax.fori_loop(0, hk, of_key_head, 0)


def _gdn_chunk_kernel(layer_ref, slot_ref, rows_ref, fresh_ref, cont_ref,
                      q_ref, k_ref, v_ref, gb_ref, s_in_ref, o_ref, s_out_ref, u_ref):
    """A block of several rows of one slot (a pack's token block): the chunked
    form, a value head at a time, the slot's state in VMEM throughout. The
    rows are read as they are computed, ``[rows x heads, width]`` (a head's
    rows are every ``heads``-th); ``g`` and ``beta`` come as two rows a value
    head, ``[Hv, 2, rows]``."""
    del layer_ref, slot_ref  # the block specs read them
    f32 = jnp.float32
    j = pl.program_id(0)
    hv, _, rows = gb_ref.shape[1:]
    dk, dv = s_in_ref.shape[3:]
    hk = q_ref.shape[1] // rows
    per = hv // hk
    n = rows_ref[j]
    _gdn_block_start(j, fresh_ref, cont_ref, s_in_ref, s_out_ref)

    @pl.when(n < rows)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    dot = functools.partial(jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST, preferred_element_type=f32)
    mm = lambda x, y: dot(x, y, (((1,), (0,)), ((), ())))
    nt, tn = (((1,), (1,)), ((), ())), (((0,), (0,)), ((), ()))  # x y^T, x^T y

    def chunk(c):  # the block's first ``c`` rows, which hold its live ones
        t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)       # a row of the chunk, down the sublanes
        i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)       # and along the lanes
        upto, below, live = i <= t, i < t, i[:1] < n
        after = below.astype(f32)                                 # [i, j]: row i comes after row j
        live_rows = t[:, :1] < n
        rows_of = lambda ref, head, heads: ref[0, pl.ds(head, c, stride=heads), :]

        def of_key_head(g, carry):
            k, q = rows_of(k_ref, g, hk), rows_of(q_ref, g, hk)                       # [c, dk]
            kq = jnp.concatenate([k, q], axis=0)
            kk, qk = dot(k, k, nt), dot(q, k, nt)                                     # [c, c]: k_t . k_j, q_t . k_j
            for p in range(per):
                h = g * per + p
                s = s_out_ref[0, 0, h]                                                # [dk, dv]
                g_row = jnp.where(live, gb_ref[0, h, 0:1, :c], 0.0)                   # [1, c]; a padding row: g 0
                b_row = jnp.where(live, gb_ref[0, h, 1:2, :c], 0.0)                   # and beta 0
                beta = jnp.sum(jnp.where(i == t, b_row, 0.0), axis=1, keepdims=True)  # [c, 1]: the row, turned
                # sums of g between rows, each of non-positive terms (the comment above)
                g_upto = jnp.where(upto, g_row, 0.0)
                decay = jnp.exp(mm(g_upto, after))                                    # exp(G_t - G_j), t >= j
                grown = jnp.exp(jnp.sum(g_upto, axis=1, keepdims=True))               # exp(G_t)
                to_end = jnp.exp(jnp.sum(jnp.where(i > t, g_row, 0.0), axis=1, keepdims=True))  # exp(G_C - G_t)
                from_s = mm(kq, s)                                                    # [2c, dv]: K S_0, Q S_0
                a = jnp.where(below, beta * decay * kk, 0.0)
                rhs = beta * (rows_of(v_ref, h, hv) - grown * from_s[:c])
                # (I + A) U = rhs, a block of rows at a time: the rows above
                # through one product, the block's own by substitution
                u_ref[:c] = jnp.zeros((c, dv), f32)
                for lo in range(0, c, _GDN_SOLVE):
                    hi = min(lo + _GDN_SOLVE, c)
                    r = rhs[lo:hi] - mm(a[lo:hi], u_ref[:c]) if lo else rhs[lo:hi]
                    own = a[lo:hi, lo:hi]
                    for x in range(hi - lo - 1):
                        r = r - own[:, x:x + 1] * r[x:x + 1]
                    u_ref[lo:hi] = r
                u = u_ref[:c]
                o = grown * from_s[c:] + mm(jnp.where(upto, decay * qk, 0.0), u)
                o_ref[0, pl.ds(h, c, stride=hv), :] = jnp.where(live_rows, o, 0.0).astype(o_ref.dtype)
                # (the chunk's whole decay as a scalar reduction: Mosaic spreads no [1, 1] slice over a tile)
                s_out_ref[0, 0, h] = jnp.exp(jnp.sum(g_row)) * s + dot(to_end * k, u, tn)
            return carry

        jax.lax.fori_loop(0, hk, of_key_head, 0)

    sizes = [*(c for c in _GDN_CHUNKS if c < rows), rows]
    for fewer, c in zip([0, *sizes], sizes):
        pl.when((n > fewer) & (n <= c))(functools.partial(chunk, c))


def _gdn_scan_call(q, k, v, g, beta, state, block_slot, block_rows, block_fresh, layer, interpret: bool):
    """The kernel, its form by the block's shape: a block of one row walks
    it, a block of more takes its live rows as one chunk."""
    f32 = jnp.float32
    nb, rows, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if rows != 1 and rows % 8:
        raise ValueError(f"gdn_scan takes blocks of 1 row or of a multiple of 8, got {rows}")
    if state.shape[2:] != (hv, dk, dv) or hv % hk:
        raise ValueError(f"the state {state.shape} is not [layers, slots, {hv}, {dk}, {dv}]")
    at, n_rows, fresh, cont = _block_descriptors(block_slot, block_rows, block_fresh)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), at, n_rows, fresh, cont)
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if rows == 1:
        kernel, scratch = _gdn_step_kernel, []
        a_channel = lambda x: jnp.broadcast_to(x[..., None], (nb, 1, hv, dv))  # a head's scalar on its lanes
        operands = (q, k, v, a_channel(jnp.exp(g)), a_channel(beta))
    else:
        kernel, scratch = _gdn_chunk_kernel, [pltpu.VMEM((rows, dv), f32)]
        by_row = lambda x: x.reshape(nb, -1, x.shape[-1])  # [nb, rows x heads, width]: no copy
        operands = (by_row(q), by_row(k), by_row(v), jnp.stack([g, beta], axis=-1).transpose(0, 2, 3, 1))
    a_block = lambda x: pl.BlockSpec((1, *x.shape[1:]), lambda j, *_: (j,) + (0,) * (x.ndim - 1))
    # the layers' states, this block's slot
    of_slot = pl.BlockSpec((1, 1, hv, dk, dv), lambda j, ly, sl, *_: (ly[0], sl[j], 0, 0, 0))
    o_shape = jax.ShapeDtypeStruct(operands[2].shape, f32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(nb,), in_specs=[*map(a_block, operands), of_slot],
        out_specs=[a_block(o_shape), of_slot], scratch_shapes=scratch)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",), vmem_limit_bytes=_GDN_VMEM)}
    o, state = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="gdn_scan", interpret=interpret,
        out_shape=[o_shape, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(scalars) + len(operands): 1},
        **params,
    )(*scalars, *operands, state)
    return o.reshape(nb, rows, hv, dv), state


def gdn_scan(q, k, v, g, beta, state, *, block_slot, block_rows, block_fresh, layer=0,
             impl: Optional[str] = None):
    """The gated delta rule over blocks (the comment above): ``q``, ``k``
    [blocks, rows, Hk, dk] normed and scaled, ``v`` [blocks, rows, Hv, dv],
    ``g`` and ``beta`` [blocks, rows, Hv], ``state`` [layers, slots, Hv, dk, dv]
    float32. Returns ``(o [blocks, rows, Hv, dv] float32, state)`` with this
    ``layer``'s slots advanced, by ``impl`` (:func:`resolve_ssm_kernel`; the
    kernel is ``pallas_call(name="gdn_scan")``, the row walk for blocks of one
    row and the chunked form for blocks of more)."""
    mode = resolve_ssm_kernel(impl)
    if state.dtype != jnp.float32:
        raise ValueError(f"the recurrent state is float32, got {state.dtype}")
    blocks = dict(block_slot=block_slot, block_rows=block_rows, block_fresh=block_fresh, layer=layer)
    if mode == "reference":
        return gdn_scan_reference(q, k, v, jnp.exp(g.astype(jnp.float32)), beta, state, **blocks)
    return _gdn_scan_call(q, k, v, g, beta, state, block_slot, block_rows, block_fresh, layer, mode == "interpret")
