"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542) in its causal chunked form: the history of a sequence is
kept exact inside the open window of ``window`` positions, and every window
that has closed is kept as one pooled key and value a chunk of ``chunk``
positions. Per kv head, with ``s`` the attention's own scale and ``mu``,
``phi`` two learned vectors of the head's width:

    kbar_c = sum_m softmax_m(s mu . k_m) k_m
    vbar_c = sum_m softmax_m(s (phi . k_m - |k_m|^2 / 2)) v_m

both softmaxes over the chunk's positions with float32 logits. A query at
position ``t`` attends, under one softmax, the summaries of the chunks of
every window before its own and the exact keys of its own window up to ``t``.

**Entry order.** In the serving cache a slot's list of entries is
``[summaries of closed windows][tokens of the open window]``: position ``t``
sits at entry ``(t // window) * (window // chunk) + t % window``
(:func:`entry_index`), every summary lies before every open token, and "entry
index <= the query's" is exactly the layer's mask. The paged decode kernel
and the ragged prefill kernel therefore walk that list as they walk a full
layer's, with positions and lengths counted in entries; what is new on the
device is the pooling of a filled page of the open window (a page is a
chunk) into one entry of a page the slot holds aside until the window closes
(``serving/pages.CacheKind``): in a decode step :func:`eva_pool_pages`, the
``eva_pool`` kernel in place on the carried stack; in a pack
:func:`eva_pool_reference`, a gather and a scatter on the layer's pages.

:func:`eva_attention` is the whole-sequence form without a cache (a forward
pass outside the serving engine, and the tests' oracle).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .attention import NEG_INF, _grid_params, pl, pltpu


def entry_index(pos, window: int, chunk: int):
    """Entry a position takes in a slot's list (and the entries a context of
    ``pos`` positions holds). Negative positions (a pack's padding rows)
    stay as they are. (``serving/pages.CacheKind.entries`` is the host's.)"""
    return jnp.where(pos >= 0, (pos // window) * (window // chunk) + pos % window, pos)


def pool_chunks(k, v, mu, phi, sm_scale: float):
    """``k`` [..., KVH, C, D], ``v`` [..., KVH, C, Dv] (a chunk's rotated keys
    and its values), ``mu`` / ``phi`` [KVH, D] -> ``(kbar [..., KVH, D], vbar
    [..., KVH, Dv])`` in float32. The one statement of the pooling: the
    kernel, its reference and the whole-sequence form all call it."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    mu32, phi32 = mu.astype(jnp.float32)[:, None, :], phi.astype(jnp.float32)[:, None, :]
    lk = sm_scale * jnp.sum(mu32 * k32, axis=-1)
    lv = sm_scale * (jnp.sum(phi32 * k32, axis=-1) - 0.5 * jnp.sum(k32 * k32, axis=-1))
    wk = jax.nn.softmax(lk, axis=-1)[..., None]
    wv = jax.nn.softmax(lv, axis=-1)[..., None]
    return jnp.sum(wk * k32, axis=-2), jnp.sum(wv * v32, axis=-2)


def eva_attention(q, k, v, mu, phi, *, window: int, chunk: int,
                  sm_scale: Optional[float] = None):
    """Whole-sequence causal EVA attention, no cache: q [B, H, S, D], k [B,
    KVH, S, D], v [B, KVH, S, Dv] (rotated), ``mu`` / ``phi`` [KVH, D]. A
    chunk is seen only once its whole window has closed, so a trailing
    incomplete chunk is never pooled. Plain ``jax.numpy``: [S, S + S / chunk]
    scores a head."""
    b, h, s, d = q.shape
    kvh = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    nc = s // chunk
    kc = jnp.swapaxes(k[:, :, :nc * chunk].reshape(b, kvh, nc, chunk, d), 1, 2)
    vc = jnp.swapaxes(v[:, :, :nc * chunk].reshape(b, kvh, nc, chunk, v.shape[-1]), 1, 2)
    kbar, vbar = pool_chunks(kc, vc, mu, phi, scale)  # [B, nc, KVH, D]
    kbar, vbar = jnp.swapaxes(kbar, 1, 2), jnp.swapaxes(vbar, 1, 2)
    pos = jnp.arange(s)
    local = (pos[None, :] <= pos[:, None]) & (pos[None, :] // window == pos[:, None] // window)
    remote = (jnp.arange(nc)[None, :] * chunk) // window < pos[:, None] // window
    seen = jnp.concatenate([remote, local], axis=1)  # [S, nc + S]
    keys = jnp.concatenate([kbar, k.astype(jnp.float32)], axis=2)
    vals = jnp.concatenate([vbar, v.astype(jnp.float32)], axis=2)
    qg = q.astype(jnp.float32).reshape(b, kvh, h // kvh, s, d)
    sc = jnp.einsum("bkgsd,bktd->bkgst", qg, keys) * scale
    p = jax.nn.softmax(jnp.where(seen[None, None, None], sc, NEG_INF), axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, vals)
    return out.reshape(b, h, s, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# pooling a filled page into one entry of another page, in the paged arena
# ---------------------------------------------------------------------------


def _eva_pool_kernel(src_ref, dst_ref, off_ref, layer_ref, mu_ref, phi_ref,
                     _k_in, _v_in, k_hbm, v_hbm, kbuf, vbuf, kdst, vdst, sems,
                     *, sm_scale):
    """One pooled page a grid step. ``src_ref[i]`` 0 (the parking page):
    nothing to pool, no copy. Else the page's keys and values (all kv heads)
    and the destination page come into VMEM, the pooled key and value go
    into the destination at row ``off_ref[i]`` (a select over the page: no
    store at a row that is not a tile's first, as the decode kernel writes
    its new row), and the destination goes back. Steps run in order and each
    waits for its own write, so two steps may fill rows of one page."""
    i = pl.program_id(0)
    layer, src, dst, off = layer_ref[0], src_ref[i], dst_ref[i], off_ref[i]

    @pl.when(src > 0)
    def _():
        reads = [
            pltpu.make_async_copy(k_hbm.at[layer, src], kbuf, sems.at[0]),
            pltpu.make_async_copy(v_hbm.at[layer, src], vbuf, sems.at[1]),
            pltpu.make_async_copy(k_hbm.at[layer, dst], kdst, sems.at[2]),
            pltpu.make_async_copy(v_hbm.at[layer, dst], vdst, sems.at[3]),
        ]
        for copy in reads:
            copy.start()
        for copy in reads:
            copy.wait()
        kbar, vbar = pool_chunks(kbuf[...], vbuf[...], mu_ref[...], phi_ref[...], sm_scale)
        for buf, new in ((kdst, kbar), (vdst, vbar)):
            page = buf[...]  # [KVH, page, D]
            row = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
            buf[...] = jnp.where(row == off, new[:, None, :].astype(page.dtype), page)
        writes = [
            pltpu.make_async_copy(kdst, k_hbm.at[layer, dst], sems.at[2]),
            pltpu.make_async_copy(vdst, v_hbm.at[layer, dst], sems.at[3]),
        ]
        for copy in writes:
            copy.start()
        for copy in writes:
            copy.wait()


def _eva_pool_kernel_call(k_pages, v_pages, mu, phi, src, dst, off, layer, sm_scale, interpret):
    _, _, kvh, ps, d = k_pages.shape
    dv = v_pages.shape[-1]
    scalars = (src.astype(jnp.int32), dst.astype(jnp.int32), off.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1))
    whole = lambda x: pl.BlockSpec(x.shape, lambda i, *_: (0,) * x.ndim)
    arena = pl.BlockSpec(memory_space=pl.ANY)
    mu32, phi32 = mu.astype(jnp.float32), phi.astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(src.shape[0],),
        in_specs=[whole(mu32), whole(phi32), arena, arena],
        out_specs=[arena, arena],
        scratch_shapes=[
            pltpu.VMEM((kvh, ps, d), k_pages.dtype), pltpu.VMEM((kvh, ps, dv), v_pages.dtype),
            pltpu.VMEM((kvh, ps, d), k_pages.dtype), pltpu.VMEM((kvh, ps, dv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    first_arena = len(scalars) + 2
    return pl.pallas_call(
        functools.partial(_eva_pool_kernel, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (k_pages, v_pages)],
        input_output_aliases={first_arena: 0, first_arena + 1: 1},
        name="eva_pool",
        **_grid_params(interpret, ("arbitrary",)),
    )(*scalars, mu32, phi32, k_pages, v_pages)


def eva_pool_reference(k_pages, v_pages, mu, phi, src, dst, off, sm_scale):
    """The kernel's mathematics as a gather and a scatter of XLA's over one
    layer's pages [num_pages, KVH, page, D]: a program's pooling where the
    arena is split by layer (``models/decoder.arena_in_place`` says no),
    and what the kernel is tested against. A step with ``src`` 0 writes the
    parking page's pooling into the parking page, which nothing reads."""
    kbar, vbar = pool_chunks(k_pages[src], v_pages[src], mu, phi, sm_scale)
    return (k_pages.at[dst, :, off].set(kbar.astype(k_pages.dtype)),
            v_pages.at[dst, :, off].set(vbar.astype(v_pages.dtype)))


def eva_pool_pages(k_pages, v_pages, mu, phi, src, dst, off, *, sm_scale: float, layer,
                   interpret: bool = False):
    """The ``eva_pool`` kernel in place on the layers' stack [L, num_pages,
    KVH, page, D] (the carried arena of a decode step or a pack, after the
    attention kernel's own writes of the layer): in layer ``layer``, page
    ``src[i]`` (a filled chunk of an open window) is pooled into row
    ``off[i]`` of page ``dst[i]``, for every ``i`` with ``src[i] > 0``.
    Returns the stacks, aliased to the inputs. One layer's pages on their own
    (a program where the arena is split by layer) take
    :func:`eva_pool_reference`: aliased to a slice of the split scan the
    kernel costs a copy of the layer's pages (PERF.md, PR 38)."""
    return _eva_pool_kernel_call(k_pages, v_pages, mu, phi, src, dst, off, layer, sm_scale, interpret)


def pool_plan(positions, live, slots, page_table, *, window: int, chunk: int, size: int):
    """Which pages a program's rows fill, from what the model is given:
    ``positions`` [R] (true positions), ``live`` [R] bool, ``slots`` [R] the
    rows' table rows. A row at the last position of a chunk fills its page;
    the page is pooled into entry ``(position % window) // chunk`` of the
    open window's summaries, which the slot holds aside in the table's last
    ``window / chunk / page`` columns until the window closes. Returns
    ``(src, dst, off)`` [size], parking (0) where there is nothing to pool;
    ``size`` must bound the rows that fill a page (every row of a decode
    step; a pack's rows / chunk, its blocks being chunk-multiples)."""
    ps = chunk  # a page is a chunk
    aside = window // chunk // ps
    fills = live & (positions % chunk == chunk - 1)
    (rows,) = jnp.nonzero(fills, size=size, fill_value=positions.shape[0])
    ok = rows < positions.shape[0]
    rows = jnp.minimum(rows, positions.shape[0] - 1)
    pos, slot = positions[rows], jnp.maximum(slots[rows], 0)
    j = (pos % window) // chunk
    src = page_table[slot, entry_index(pos, window, chunk) // ps]
    dst = page_table[slot, page_table.shape[1] - aside + j // ps]
    return jnp.where(ok, src, 0), jnp.where(ok, dst, 0), jnp.where(ok, j % ps, 0)
