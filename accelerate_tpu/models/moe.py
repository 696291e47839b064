"""Mixture-of-experts FFN: one routing for every caller, without a capacity
and without a dropped token.

The reference only passes MoE through to DeepSpeed
(/root/reference/src/accelerate/utils/dataclasses.py:978-984,
`transformer_moe_cls_names`); there is no in-repo MoE runtime. This one
routes by sorting:

- the router runs in float32 at the highest matmul precision over its
  ``moe_router_outputs`` outputs; the scores are a softmax over them or a
  sigmoid of each (``moe_scoring``); the top ``moe_top_k`` are chosen by
  score plus an optional learned selection bias, and the chosen scores,
  without the bias, are normalised to sum to one and multiplied by
  ``moe_routed_scale``. With a group stage (``moe_n_group`` > 1) the
  outputs lie in equal groups, a group scores the sum of its two best
  biased scores, and the choice is among the experts of the
  ``moe_topk_group`` best groups only;
- ``moe_shared_experts`` gated MLPs of the experts' width (one of their
  summed width, or of ``moe_shared_dim``) take every token, and their result
  is added to the routed one, behind a scalar gate of its own a token
  (``sigmoid(x w)``) where ``moe_shared_gate`` says so: a program that holds
  a share of the routed experts computes it whole;
- with ``moe_latent_dim`` (LatentMoE) the routed experts live in a narrower
  width: one projection down before the dispatch, the experts and the
  combine there, one projection back, both computed once whatever share is
  held (the way back is linear, so the shares' partial sums add up); the
  router and the shared expert read the full width;
- ``mlp_kind`` "relu2": an expert (and the shared one) is two matrices,
  ``W_down relu(W_up x)^2``, with no gate matrix (:func:`grouped_mlp` with
  ``wg`` None; the kernel ``moe_experts_relu2``, which multiplies an
  expert's own row tiles only: 22 experts a token make a thousand rows a
  decode step, which every expert cannot all multiply);
- every (token, chosen expert) pair whose expert is held here
  (``moe_experts_held = (first, count)``; all of them by default) is sorted
  by expert, the tokens' rows are gathered in that order, and the three
  expert matrices multiply them group by group (:func:`grouped_mlp`: a
  pallas kernel named ``moe_experts`` where the serving kernels run, every
  expert over every row up to 256 rows and over its own row tiles past that, and
  ``jax.lax.ragged_dot`` elsewhere, which differentiates); the rows come
  back weighted and are added to their tokens;
- shapes are static. The rows multiplied at once are :func:`expert_rows`:
  all ``tokens x k`` pairs where every expert is held (one pass, so the
  layer differentiates), and twice the expected pairs where a share is
  held. Pairs beyond that are not dropped: a ``while_loop`` multiplies
  further chunks of the same shape until every held pair is done, so skew
  costs time and never a token (the serving engine counts the chunks,
  ``expert_chunks`` on its dispatch spans: one a layer where nothing
  overflowed). A group stage makes the held share's load burstier (a token
  whose best groups leave out the held experts' group sends them nothing,
  another twice its share) at the same mean, which the factor of two holds
  at a pack's hundreds of rows and the floor of 16 rows at a decode step's
  few. Pairs on absent experts sort last and are
  never multiplied: what those experts would add is left out, and the
  weights stay normalised over all k chosen (the model-configs guide,
  section 4). On one chip the layer runs without its exchange.

Per-expert weights carry the logical axis ("expert", ...) and shard over
the mesh "expert" axis. The Switch load-balancing auxiliary loss is
computed from the router's scores as before.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.layers import swiglu, two_term_matmul, two_terms
from .configs import MOE_LOAD_COLLECTION as LOAD_COLLECTION
from .configs import DecoderConfig


def router_scores(logits: jax.Array, scoring: str) -> jax.Array:
    """float32 scores [tokens, outputs] from the router's logits."""
    logits = logits.astype(jnp.float32)
    return jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)


def top_k_routing(scores: jax.Array, top_k: int,
                  selection_bias: Optional[jax.Array] = None, *, n_group: int = 1,
                  topk_group: int = 1, routed_scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """(experts [tokens, k] int32, weights [tokens, k] float32): the top k
    of ``scores + selection_bias``; the weights are the chosen scores
    themselves, normalised over the k chosen and multiplied by
    ``routed_scale``. ``n_group`` > 1, the group stage: the outputs lie in
    ``n_group`` equal groups in order, a group scores the sum of its two
    largest biased scores, and experts outside the ``topk_group`` best
    groups cannot be chosen."""
    choose = scores if selection_bias is None else scores + selection_bias.astype(jnp.float32)
    if n_group > 1:
        tokens, outputs = choose.shape
        grouped = choose.reshape(tokens, n_group, outputs // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        keep = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        choose = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(tokens, outputs)
    _, idx = jax.lax.top_k(choose, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / jnp.maximum(jnp.sum(chosen, -1, keepdims=True), 1e-9)
    return idx.astype(jnp.int32), weights * routed_scale if routed_scale != 1.0 else weights


def load_balance_loss(scores: jax.Array, experts: jax.Array) -> jax.Array:
    """Switch auxiliary loss ``E * sum_e f_e * P_e`` over one group of
    tokens: f the share of tokens whose first choice is e, P the mean
    score of e. 1 at perfect balance."""
    n = scores.shape[-1]
    top1 = jax.nn.one_hot(experts[..., 0], n, dtype=jnp.float32)
    return n * jnp.sum(jnp.mean(top1, axis=-2) * jnp.mean(scores, axis=-2), axis=-1)


def expert_rows(tokens: int, top_k: int, held: int, outputs: int) -> int:
    """Rows the grouped product multiplies at once. Every pair where every
    expert is held; else twice the pairs expected on the held share (a
    multiple of 8, at least 16), which a balanced router fills half and
    skew overflows into further chunks of the same shape, never into a
    drop: :func:`expert_chunks` says how many ran."""
    pairs = tokens * top_k
    if held >= outputs:
        return pairs
    want = max(16, -(-2 * pairs * held // outputs))
    return min(pairs, -(-want // 8) * 8)


def expert_chunks(held_pairs: int, rows: int) -> int:
    """Chunks of ``rows`` rows :func:`routed_experts` multiplies for a layer
    whose held experts got ``held_pairs`` pairs: the loop's own count (one
    pass where nothing overflows, none where no pair is held; a layer that
    holds every expert has ``rows`` = all pairs and always makes its one)."""
    return -(-int(held_pairs) // int(rows))


def sort_pairs(experts: jax.Array, first: int, count: int,
               token_mask: Optional[jax.Array] = None):
    """Sort the (token, choice) pairs by held expert. Returns ``order``
    [pairs] (pair indices, held pairs first and by expert, absent last),
    ``sizes`` [count] (pairs on each held expert) and their sum. A pair of
    a masked token counts as absent."""
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < count)
    if token_mask is not None:
        held = held & jnp.repeat(token_mask.reshape(-1), experts.shape[-1])
    key = jnp.where(held, local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(jax.nn.one_hot(key, count + 1, dtype=jnp.int32), axis=0)[:count]
    return order, sizes, jnp.sum(sizes)


# -- the grouped product ------------------------------------------------------

_EXPERT_TILE = 256            # columns of the expert width a kernel step holds
_EXPERT_VMEM = 64 * 1024 * 1024


def _experts_kernel(live_ref, gmap_ref, start_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref,
                    o_ref, acc):
    """Grid (experts, tiles of the expert width). Step (g, t) multiplies
    every row by tile t of expert ``gmap[g]`` of layer ``layer[0]`` and
    keeps the rows that are that expert's. Experts without a row come last
    in ``gmap`` as repeats of the last live one, so their weights are
    neither fetched nor multiplied."""
    from jax.experimental import pallas as pl

    g, t, nt = pl.program_id(0), pl.program_id(1), pl.num_programs(1)

    @pl.when((g == 0) & (t == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < live_ref[0])
    def _multiply():
        @pl.when(t == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        acc[...] += jnp.dot(hidden, wd_ref[0, 0], preferred_element_type=jnp.float32)

        @pl.when(t == nt - 1)
        def _():
            e = gmap_ref[g]
            row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            mine = (row >= start_ref[e]) & (row < start_ref[e + 1])
            o_ref[...] += jnp.where(mine, acc[...], 0.0)


def _grid_order(sizes):
    """What both kernels' grids read of the pairs on each expert: how many
    experts have a row, the experts in grid order (those with a row first, in
    order; the rest repeat the last of them, so that nothing new is fetched
    for them) and each expert's first row."""
    count = sizes.shape[0]
    live = sizes > 0
    n_live = jnp.sum(live.astype(jnp.int32))
    ranked = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(jnp.int32)
    last = ranked[jnp.maximum(n_live - 1, 0)]
    gmap = jnp.where(jnp.arange(count) < n_live, ranked, last)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes).astype(jnp.int32)])
    return n_live, gmap, starts


# Rows up to which every expert multiplies every row (:func:`_experts_kernel_call`). Measured (``chip_smoke.py
# --phase gdn``; PERF.md section 6, PR 48, call 5; us a call, every row | own row tiles): at 64 experts of 2,048 ->
# 512 the two change places between 128 and 256 rows (64 rows 280 | 460, 128 rows 373 | 373, 256 rows 555 | 491, 320
# rows 694 | 520, 640 rows 1,453 | 559); at MiMo's 16 experts of 4,096 -> 2,048 (calls of 64-256 rows) and
# GigaChat's 8 of 7,168 -> 2,048 (16-128 rows) the own-tiles kernel does not compile at its width tile of 1,024
# (172-177 MB of the chip's 128 MB of VMEM), so 256 is the least that keeps their calls on the kernel that runs
# them, and no call of the narrow experts' cell has fewer than 320 rows.
_ALL_ROWS_MAX = 256


def _experts_kernel_call(xs, wg, wu, wd, sizes, layer, interpret: bool):
    """``wg``, ``wu`` [L, E, d, m] and ``wd`` [L, E, m, d] are the layers'
    stacks as the layer scan holds them, ``layer`` which of them to
    multiply by: the kernel fetches its tiles out of the stack, so no
    layer's experts are sliced out (805 MB a layer in the MiMo cell) before
    a call that reads a few of them. One layer's leaves are a stack of one
    (``w[None]``, layer 0: a bitcast).

    Up to ``_ALL_ROWS_MAX`` rows every expert multiplies every row and keeps
    its own (:func:`_experts_all_rows_call`): the weights' bytes bound that (6
    operations a weight byte a row against the chip's 240 a byte). Past it the
    products would bound it, 64 narrow experts over a step's 320 rows or a
    pack's 640 (ISSUE 48: half the device's time, my chip run), and the kernel
    walks each expert's own row tiles instead, as the two-matrix one does."""
    if xs.shape[0] > _ALL_ROWS_MAX:
        return _experts2_kernel_call(xs, wu, wd, sizes, layer, interpret, wg=wg)
    return _experts_all_rows_call(xs, wg, wu, wd, sizes, layer, interpret)


def _experts_all_rows_call(xs, wg, wu, wd, sizes, layer, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = xs.shape
    _, count, _, m = wg.shape
    tile = next(c for c in (_EXPERT_TILE, 128, m) if m % c == 0)
    n_live, gmap, starts = _grid_order(sizes)

    def w_in(g, t, lv, gm, st, ly):
        return (ly[0], gm[g], 0, jnp.where(g < lv[0], t, m // tile - 1))

    def w_out(g, t, lv, gm, st, ly):
        return (ly[0], gm[g], jnp.where(g < lv[0], t, m // tile - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(count, m // tile),
        in_specs=[
            pl.BlockSpec((rows, d), lambda g, t, *_: (0, 0)),
            pl.BlockSpec((1, 1, d, tile), w_in),
            pl.BlockSpec((1, 1, d, tile), w_in),
            pl.BlockSpec((1, 1, tile, d), w_out),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda g, t, *_: (0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
    )
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_EXPERT_VMEM)}
    return pl.pallas_call(
        _experts_kernel, grid_spec=grid_spec, name="moe_experts", interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32), **params,
    )(n_live.reshape(1), gmap, starts, jnp.asarray(layer, jnp.int32).reshape(1), xs, wg, wu, wd)


def _experts_own_tiles_kernel(live_ref, gmap_ref, start_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                              *, tm: int):
    """Grid (experts, tiles of the expert width), gated experts, many rows.
    Step (g, t) walks the row tiles of ``tm`` rows that hold rows of expert
    ``gmap[g]`` (the rows are sorted by expert, so they are consecutive),
    multiplies each by tile t of that expert of layer ``layer[0]`` and adds the
    expert's own rows of the product to the output, which stays in VMEM over
    the whole grid. Experts without a row come last in ``gmap`` as repeats of
    the last live one, as in :func:`_experts_kernel`."""
    from jax.experimental import pallas as pl

    g, t = pl.program_id(0), pl.program_id(1)

    @pl.when((g == 0) & (t == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < live_ref[0])
    def _multiply():
        e = gmap_ref[g]
        lo, hi = start_ref[e], start_ref[e + 1]
        w_gate, w_up, w_down = wg_ref[0, 0], wu_ref[0, 0], wd_ref[0, 0]

        def tile(r, carry):
            at = pl.ds(pl.multiple_of(r * tm, tm), tm)
            x = x_ref[at, :]
            gate = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
            up = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
            out = jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype), w_down, preferred_element_type=jnp.float32)
            row = r * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            o_ref[at, :] += jnp.where((row >= lo) & (row < hi), out, 0.0)
            return carry

        jax.lax.fori_loop(lo // tm, (hi + tm - 1) // tm, tile, 0)


def _experts2_kernel(live_ref, gmap_ref, start_ref, layer_ref, x_ref, wu_ref, wd_ref, o_ref, *, tm: int):
    """Grid (experts, tiles of the expert width), two-matrix experts
    (``relu(x W_up)^2 W_down``). Step (g, t) walks the row tiles of ``tm``
    rows that hold rows of expert ``gmap[g]`` (the rows are sorted by expert,
    so they are consecutive), multiplies each by tile t of that expert of
    layer ``layer[0]`` and adds the expert's own rows of the product to the
    output, which stays in VMEM over the whole grid. Experts without a row
    come last in ``gmap`` as repeats of the last live one, as in
    :func:`_experts_kernel`."""
    from jax.experimental import pallas as pl

    g, t = pl.program_id(0), pl.program_id(1)

    @pl.when((g == 0) & (t == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(g < live_ref[0])
    def _multiply():
        e = gmap_ref[g]
        lo, hi = start_ref[e], start_ref[e + 1]
        w_up, w_down = wu_ref[0, 0], wd_ref[0, 0]

        def product(v, w):
            """``v @ w`` in float32. Float32 rows against bfloat16 weights go
            in two terms (``ops/layers.two_term_matmul``, here as one tile of
            twice the rows: the matrix unit loads a tile's weights once either
            way, which is what a tile of 16 rows costs)."""
            if v.dtype != jnp.float32 or w.dtype != jnp.bfloat16:
                return jnp.dot(v.astype(w.dtype), w, preferred_element_type=jnp.float32)
            both = jnp.dot(jnp.concatenate(two_terms(v), axis=0).astype(w.dtype), w,
                           preferred_element_type=jnp.float32)
            return both[:tm] + both[tm:]

        def tile(r, carry):
            at = pl.ds(pl.multiple_of(r * tm, tm), tm)
            hidden = jnp.square(jnp.maximum(product(x_ref[at, :], w_up), 0.0))
            out = product(hidden.astype(x_ref.dtype), w_down)
            row = r * tm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            o_ref[at, :] += jnp.where((row >= lo) & (row < hi), out, 0.0)
            return carry

        jax.lax.fori_loop(lo // tm, (hi + tm - 1) // tm, tile, 0)


def _experts2_tiles(rows: int, count: int, m: int) -> tuple:
    """``(row tile, width tile)`` of the two-matrix kernel: the row tile is 16
    rows (a bfloat16 tile) or, where an expert expects more (``rows`` is twice
    the expected pairs), the power of two up to 128 that holds them; the width
    tile the widest multiple of 128 up to 1,024 that divides ``m`` (``m``
    itself where none does)."""
    tm = 16
    while tm < 128 and tm < rows // (2 * count):
        tm *= 2
    tile = next((c for c in range(1024, 0, -128) if m % c == 0), m)
    return tm, tile


def _experts2_kernel_call(xs, wu, wd, sizes, layer, interpret: bool, wg=None):
    """As :func:`_experts_kernel_call` for two-matrix experts: ``wu`` [L, E,
    d, m], ``wd`` [L, E, m, d], the layers' stacks, read where they are. With
    ``wg`` the experts are gated and many rows (:func:`_experts_own_tiles_kernel`,
    under the gated kernel's name): the same grid, row tiles and weight blocks."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = xs.shape
    _, count, _, m = wu.shape
    tm, tile = _experts2_tiles(rows, count, m)
    padded = -(-rows // tm) * tm
    if padded != rows:  # (expert_rows gives multiples of 8: a few zero rows no expert owns)
        xs = jnp.pad(xs, ((0, padded - rows), (0, 0)))
    n_live, gmap, starts = _grid_order(sizes)

    def w_in(g, t, lv, gm, st, ly):
        return (ly[0], gm[g], 0, jnp.where(g < lv[0], t, m // tile - 1))

    def w_out(g, t, lv, gm, st, ly):
        return (ly[0], gm[g], jnp.where(g < lv[0], t, m // tile - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(count, m // tile),
        in_specs=[
            pl.BlockSpec((padded, d), lambda g, t, *_: (0, 0)),
            *([pl.BlockSpec((1, 1, d, tile), w_in)] * (1 if wg is None else 2)),
            pl.BlockSpec((1, 1, tile, d), w_out),
        ],
        out_specs=pl.BlockSpec((padded, d), lambda g, t, *_: (0, 0)),
    )
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_EXPERT_VMEM)}
    kernel, name, weights = ((_experts2_kernel, "moe_experts_relu2", (wu, wd)) if wg is None
                             else (_experts_own_tiles_kernel, "moe_experts", (wg, wu, wd)))
    out = pl.pallas_call(
        functools.partial(kernel, tm=tm), grid_spec=grid_spec, name=name,
        interpret=interpret, out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32), **params,
    )(n_live.reshape(1), gmap, starts, jnp.asarray(layer, jnp.int32).reshape(1), xs, *weights)
    return out[:rows]


def grouped_mlp(xs: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                sizes: jax.Array, impl: str = "xla", layer=None) -> jax.Array:
    """``(silu(x Wg_e) * (x Wu_e)) Wd_e`` for rows ``xs`` [rows, d] sorted
    by expert, ``sizes`` [experts] rows each; rows past their sum come out
    as zeros. float32 [rows, d]. ``impl``: "xla" (``jax.lax.ragged_dot``),
    "pallas" or "interpret" (the ``moe_experts`` kernel). With ``layer``
    (the kernel only), the weights are the layers' stacks ([L, E, ...]) and
    it says which layer's experts multiply: the kernel reads them where they
    are. ``wg`` None: two-matrix experts, ``relu(x Wu_e)^2 Wd_e`` (the kernel
    ``moe_experts_relu2``)."""
    if impl != "xla":
        if layer is None:
            wg, wu, wd, layer = None if wg is None else wg[None], wu[None], wd[None], 0
        if wg is None:
            return _experts2_kernel_call(xs, wu, wd, sizes, layer, impl == "interpret")
        return _experts_kernel_call(xs, wg, wu, wd, sizes, layer, impl == "interpret")
    sizes = sizes.astype(jnp.int32)
    xs = xs.astype(wu.dtype)  # (float32 rows are the kernel's two terms; here they are one)
    up = jax.lax.ragged_dot(xs, wu, sizes, preferred_element_type=jnp.float32)
    if wg is None:
        hidden = jnp.square(jax.nn.relu(up)).astype(xs.dtype)
    else:
        gate = jax.lax.ragged_dot(xs, wg, sizes, preferred_element_type=jnp.float32)
        hidden = swiglu(gate, up).astype(xs.dtype)
    return jax.lax.ragged_dot(hidden, wd, sizes, preferred_element_type=jnp.float32)


def experts_impl(cfg, decode: bool) -> str:
    """Where the serving kernels run, so does the ``moe_experts`` kernel:
    compiled on a TPU, interpreted where ``decode_kernel`` says so. A
    program that is differentiated (training) takes ``ragged_dot``."""
    if not decode:
        return "xla"
    mode = getattr(cfg, "decode_kernel", None)
    if mode == "interpret":
        return "interpret"
    if mode == "dense" or jax.default_backend() != "tpu":
        return "xla"
    return "pallas"


def routed_experts(x, experts, weights, wg, wu, wd, *, first: int = 0,
                   outputs: Optional[int] = None, token_mask=None, impl: str = "xla", layer=None):
    """The held experts' part of the layer's result for tokens ``x``
    [tokens, d], and the pairs on each held expert [count]. ``layer``: as
    :func:`grouped_mlp` takes it."""
    tokens, d = x.shape
    k, count = experts.shape[-1], wu.shape[-3]
    order, sizes, n_held = sort_pairs(experts, first, count, token_mask)
    rows = expert_rows(tokens, k, count, outputs or count)
    pair_token = order // k
    pair_weight = weights.reshape(-1)[order]

    def chunk(c, y):
        lo = c * rows
        idx = lo + jnp.arange(rows)
        tok = jnp.take(pair_token, idx, mode="clip")
        ends = jnp.cumsum(sizes)
        here = jnp.clip(ends, lo, lo + rows) - jnp.clip(ends - sizes, lo, lo + rows)
        out = grouped_mlp(jnp.take(x, tok, axis=0), wg, wu, wd, here, impl, layer)
        w = jnp.where(idx < n_held, jnp.take(pair_weight, idx, mode="clip"), 0.0)
        return y.at[tok].add(out * w[:, None])

    y0 = jnp.zeros((tokens, d), jnp.float32)
    if rows >= tokens * k:
        y = chunk(0, y0)  # one pass holds every pair: no loop, so it differentiates
    else:
        _, y = jax.lax.while_loop(
            lambda cy: cy[0] * rows < n_held, lambda cy: (cy[0] + 1, chunk(cy[0], cy[1])),
            (jnp.int32(0), y0))
    return y, sizes


class MoeMLP(nn.Module):
    """Drop-in replacement for DecoderMLP returning (y, aux_loss).
    ``token_mask`` [b, s] bool marks the tokens that are real (a serving
    step's live slots, a packed prefill's rows); the others are routed
    nowhere. ``router_input``: what the router reads where it is not ``x``
    (the same activations before they were rounded to the layer's dtype).
    Where the ``moe_load`` collection is mutable, the pairs on each held
    expert are written to it. ``stack``: ``(w_gate, w_up, w_down, layer)``,
    the scanned run's stacks of the three expert leaves and this layer's
    index in them (``models/decoder.expert_stacks``); the layer then
    leaves its own slices of them unread, and the kernel reads the stack."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, token_mask=None, router_input=None,
                 stack=None) -> Tuple[jax.Array, jax.Array]:
        from .decoder import _constrain, _dense_init

        cfg = self.config
        E, k = cfg.moe_num_experts, cfg.moe_top_k
        R = cfg.moe_router_outputs or E
        first = cfg.moe_experts_held[0] if cfg.moe_experts_held else 0
        b, s, d = x.shape
        m = cfg.mlp_dim
        dt = cfg.dtype
        gated = cfg.mlp_kind != "relu2"
        dl = cfg.moe_latent_dim or d  # the width the routed experts take and return

        router_w = self.param(
            "router",
            nn.with_logical_partitioning(_dense_init(), ("embed", "router_experts")),
            (d, R),
        )
        bias = None
        if cfg.moe_selection_bias:
            bias = self.param(
                "selection_bias",
                nn.with_logical_partitioning(nn.initializers.zeros, ("router_experts",)),
                (R,), jnp.float32)
        if stack is not None:
            wg, wu, wd, layer = stack
        else:
            layer = None
            wg = self.param(
                "w_gate",
                nn.with_logical_partitioning(_dense_init(), ("expert", "embed", "mlp")),
                (E, dl, m),
            ).astype(dt) if gated else None
            wu = self.param(
                "w_up",
                nn.with_logical_partitioning(_dense_init(), ("expert", "embed", "mlp")),
                (E, dl, m),
            ).astype(dt)
            wd = self.param(
                "w_down",
                nn.with_logical_partitioning(_dense_init(), ("expert", "mlp", "embed")),
                (E, m, dl),
            ).astype(dt)

        flat = x.reshape(b * s, d)
        with jax.named_scope("moe_router"):
            routed = flat if router_input is None else router_input.reshape(b * s, d)
            logits = jnp.matmul(routed.astype(jnp.float32), router_w.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            scores = router_scores(logits, cfg.moe_scoring)
            experts, weights = top_k_routing(
                scores, k, bias, n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
                routed_scale=float(cfg.moe_routed_scale))
        # the auxiliary loss is a mean over the batch rows' own balance, as
        # the grouped routing before it had it
        aux_loss = jnp.mean(load_balance_loss(
            scores.reshape(b, s, R), experts.reshape(b, s, k)))
        into = flat.astype(dt)
        if cfg.moe_latent_dim:
            # the experts' own width: down before the dispatch, back after the
            # combine, once each whatever share of the experts is held. These
            # two and the two-matrix shared expert read the normed stream as it
            # is carried, in two terms (ops/layers.two_term_matmul): the next
            # layers' routers turn their rounding into other experts
            latent = [self.param(name, nn.with_logical_partitioning(_dense_init(), axes), shape).astype(dt)
                      for name, axes, shape in (("w_latent_in", ("embed", None), (d, dl)),
                                                ("w_latent_out", (None, "embed"), (dl, d)))]
            with jax.named_scope("moe_latent"):
                into = two_term_matmul(routed, latent[0])  # float32 rows: the experts' kernel takes them in two terms
        y, sizes = routed_experts(
            into, experts, weights, wg, wu, wd, first=first, outputs=R, token_mask=token_mask,
            impl=experts_impl(cfg, self.decode), layer=layer)
        if cfg.moe_latent_dim:
            with jax.named_scope("moe_latent"):
                y = two_term_matmul(y, latent[1])
        if self.is_mutable_collection(LOAD_COLLECTION):
            self.variable(LOAD_COLLECTION, "pairs", lambda: jnp.zeros((E,), jnp.int32)).value = sizes
        ms = cfg.shared_mlp_dim
        if ms:
            # the shared expert: every token, once, beside whatever share of
            # the routed experts is held
            names = (("shared_gate", ("embed", "mlp"), (d, ms)),) if gated else ()
            names += (("shared_up", ("embed", "mlp"), (d, ms)), ("shared_down", ("mlp", "embed"), (ms, d)))
            shared = [self.param(name, nn.with_logical_partitioning(_dense_init(), axes), shape).astype(dt)
                      for name, axes, shape in names]
            with jax.named_scope("moe_shared"):
                xs = flat.astype(dt)
                if gated:
                    hidden = swiglu(xs @ shared[0], xs @ shared[1])
                    own = jnp.matmul(hidden, shared[2], preferred_element_type=jnp.float32)
                else:
                    hidden = jnp.square(jax.nn.relu(two_term_matmul(routed, shared[0])))
                    own = two_term_matmul(hidden, shared[1])
                if cfg.moe_shared_gate:
                    # a scalar gate of the shared expert's own, a token: sigmoid(x w)
                    w_sg = self.param("shared_out_gate", nn.with_logical_partitioning(_dense_init(), ("embed", None)),
                                      (d, 1)).astype(dt)
                    own = own * jax.nn.sigmoid(jnp.matmul(xs, w_sg, preferred_element_type=jnp.float32))
                y = y + own
        if gated:
            y = y.astype(dt)  # (two-matrix experts hand the stream their float32 sum)
        y = y.reshape(b, s, d)
        return _constrain(y, ("batch", "seq", "embed"), self.mesh), aux_loss
