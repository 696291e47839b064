"""Slot-arena plumbing for the continuous-batching decode engine.

The arena IS the model's flax "cache" collection, created at batch =
``num_slots``: K/V leaves are ``[..., num_slots, KVH, max_cache_len, D]``
(a leading layer axis under ``scan_layers``). Each batch row is one
*slot* — an independent request at its own cache depth. Nothing here ever
changes a shape: admission writes a slot's prefix, eviction is a host-side
bookkeeping change, decode scatters one token per slot — so a live engine
triggers **zero recompiles** across admissions/evictions at any mix of
prompt lengths (asserted via the jax.monitoring compile counters,
``utils/compile_cache.compile_event_counters``).

Slot lifecycle note: a freed slot is reused WITHOUT clearing — the decode
attention path (``ops/attention.decode_attention``) masks every position
past a slot's frontier, and both prefill chunks and decode steps write a
position before it can be attended, so a previous occupant's stale K/V is
unreachable by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .pages import SLOT_LEAF_NAMES, leaf_name

# A slot leaf is found by its name (pages.SLOT_LEAF_NAMES: keys, values,
# their scales, a seq2seq decoder's cross keys and values), never by its
# rank: [B, KVH, L, D] with an optional leading layer axis from nn.scan.
# Everything else is a cache_index bookkeeping leaf.


def _slot_axis(leaf) -> int:
    return leaf.ndim - 4


def _map_slots(fn, arena, *rest, other=lambda leaf, *_: leaf):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf, *r: fn(leaf, *r) if leaf_name(path) in SLOT_LEAF_NAMES
        else other(leaf, *r), arena, *rest)


def init_arena(definition, params, num_slots: int, placer):
    """All-zeros cache arena shaped for ``num_slots`` concurrent requests.
    Shapes come from ``jax.eval_shape`` over the batched decode apply — no
    compile, no device compute, and automatically correct for any cache
    layout the model family uses (scan vs. unrolled layers, GQA, dtypes)."""

    def shape_fn(p):
        _, mutated = definition.apply(
            {"params": placer(p)},
            jnp.zeros((num_slots, 1), jnp.int32),
            positions=jnp.zeros((num_slots, 1), jnp.int32),
            use_cache=True,
            decode=True,
            cache_positions=jnp.zeros((num_slots,), jnp.int32),
            mutable=["cache"],
        )
        return mutated["cache"]

    shapes = jax.eval_shape(shape_fn, params)
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def arena_num_slots(arena) -> int:
    flat, _ = jax.tree_util.tree_flatten_with_path(arena)
    for path, leaf in flat:
        if leaf_name(path) in SLOT_LEAF_NAMES:
            return int(leaf.shape[_slot_axis(leaf)])
    raise ValueError("arena holds no K/V leaves")


def arena_nbytes(arena) -> int:
    return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(arena))


def slot_view(arena, slot, start):
    """Batch-1 cache tree for one slot (dynamic slice along the slot axis).
    ``cache_index`` leaves become ``start``, so the scalar-index decode
    path (the one chunked prefill rides) continues this slot exactly where
    its previous chunk stopped. Traced-friendly: ``slot``/``start`` may be
    tracers, keeping the caller's jit free of per-slot recompiles."""
    return _map_slots(
        lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=_slot_axis(leaf)),
        arena, other=lambda leaf: jnp.full(leaf.shape, start, leaf.dtype))


def write_slot(arena, slot_tree, slot):
    """Write a batch-1 slot tree's K/V back into the arena. Index leaves
    keep the arena's value — per-slot progress lives in the engine's
    ``lengths`` vector, not in the collection."""
    return _map_slots(
        lambda a, s: jax.lax.dynamic_update_slice_in_dim(
            a, s.astype(a.dtype), slot, axis=_slot_axis(a)),
        arena, slot_tree)
