"""The paged serving programs that update the arena in place
(``models/decoder.arena_in_place``): the stacked cache leaves ride the layer
scan as a carry and the program's kernel, given the stack and a layer index,
writes the new rows into their pages itself: the paged decode kernel each
slot's row, the ragged prefill kernel a pack's rows, page by page through
the table. Held against the threading every other call keeps (the collection
split along the layers, an XLA scatter a layer, the kernel read-only), on the
same weights and tables, kernels interpreted on the CPU: the same tokens and
the same arena, bit for bit, except the parking page, which only the scatter
writes (an inactive slot's parked row, a pack's pad rows; the kernels write
nothing for a slot of live length 0, a padding block or a pad row).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.models.decoder as decoder
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import spans

PS = 8  # page size; 96 positions a slot are 12 table entries
PARKING = 0


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; whole engines with
    interpreted kernels are then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def _model(shape: str, kernel="interpret", prefill=None):
    """``mistral``: one kind, grouped queries. ``by_kind``: a full and a
    window kind, keys 192 wide (stored padded to 256 lanes), values 128,
    partial rotary, a sink and a value scale on the window kind. ``eva``: a
    window of 32 that closes into summaries pooled a chunk (a page) of 4.
    ``prefill``: the packed prefill's kernel (absent: off the chip, its
    dense reference, so the pack splits the arena by layer)."""
    common = dict(vocab_size=128, embed_dim=64, mlp_dim=128, max_seq_len=96, dtype=jnp.bfloat16,
                  scan_layers=True, remat=False, decode_kernel=kernel, prefill_kernel=prefill)
    if shape == "mistral":
        cfg = DecoderConfig(num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16, **common)
    elif shape == "eva":
        cfg = DecoderConfig(num_layers=3, num_heads=2, num_kv_heads=2, head_dim=16, eva_window=32, eva_chunk=4,
                            **dict(common, max_seq_len=160))
    else:
        cfg = DecoderConfig(
            num_layers=5, num_heads=4, head_dim=192, v_head_dim=128, rope_dim=64, attn_value_scale=0.707,
            layer_kinds=(("full", dict(num_kv_heads=1)),
                         ("window", dict(num_kv_heads=2, attn_window=16, attn_sink=True, rope_theta=1e4))),
            layer_pattern=(0, 1, 1, 0, 1), **common)
    model = DecoderLM(cfg)
    params, _ = unbox_params(model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)["params"])
    return model, params


@pytest.fixture(scope="module", params=["mistral", "by_kind"])
def served(request):
    return (request.param,) + _model(request.param)


def _engine(shape, model, params, **kw):
    args = dict(num_slots=3, max_cache_len=96, page_size=PS, prefill_chunks=(8, 16), prefix_cache=False)
    if shape == "by_kind":
        args.update(num_pages=1 + 3 * 12, kind_pages={"window16": 1 + 3 * 5})
    args.update(kw)
    return ServingEngine(model, params, **args)


def _split_by_layer(monkeypatch):
    """The threading of before, at the same kernel: what the decoder does
    whenever ``arena_in_place`` says no."""
    monkeypatch.setattr(decoder, "arena_in_place", lambda *a, **k: False)


def _serve(eng, prompts, new=40):
    outs = eng.generate_batched(prompts, max_new_tokens=new)
    return [np.asarray(o) for o in outs], jax.tree_util.tree_map(np.asarray, eng._arena)


def _assert_same_arena(got, want):
    flat_g, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want)
    assert len(flat_g) == len(flat_w) and any(g.ndim == 5 for _, g in flat_g)
    for (path, g), w in zip(flat_g, flat_w):
        assert g.shape == w.shape and g.dtype == w.dtype, jax.tree_util.keystr(path)
        if g.ndim == 5:  # [L, pages, KVH, page, D]: every page but the parking page
            g, w = g[:, PARKING + 1:], w[:, PARKING + 1:]
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


def _mark() -> int:
    """The newest span's id (the ring's last is an outer span, opened, so
    numbered, before the spans inside it)."""
    return max((s[0] for s in spans.snapshot()), default=0)


def _decode_spans(mark: int, name="serving/decode_dispatch") -> list:
    """``args`` of the decode dispatches closed since ``_mark()``."""
    return [args for i, _, n, _, _, args in spans.snapshot() if i > mark and n == name]


PROMPTS = [np.arange(3, 3 + n) % 120 + 3 for n in (5, 17, 8, 30, 11)]  # five requests through three slots


def test_forty_steps_give_the_same_tokens_and_the_same_arena(served, monkeypatch):
    """Engines side by side, five requests through three slots (so slots are
    used again, sit inactive and sit mid-admission while others decode):
    the same tokens and every page but the parking page equal, and the
    in-place engine says so in its gauge and on every decode span."""
    shape, model, params = served
    eng = _engine(shape, model, params)
    assert eng.metrics()["serving/arena_in_place"] == 1 and eng.metrics()["serving/decode_kernel_active"]
    # which shape of the kernel it runs, of its first cache kind: the query heads folded over a
    # kv head (4 over 2, or 4 over the full kind's one) and the pages a block holds (the table's 12)
    assert eng.metrics()["serving/decode_rows_per_product"] == {"mistral": 2, "by_kind": 4}[shape]
    assert eng.metrics()["serving/decode_block_pages"] == eng._walk_block_pages == 8
    # two kv heads of two rows share a softmax; the full kind's one kv head has nothing to share with
    assert eng.metrics()["serving/decode_narrow_form"] == {"mistral": 1, "by_kind": 0}[shape]
    mark = _mark()
    got, arena = _serve(eng, PROMPTS)
    mine = _decode_spans(mark)
    assert len(mine) >= 40 and all(a["arena_in_place"] == 1 for a in mine)
    _split_by_layer(monkeypatch)
    want, arena_before = _serve(_engine(shape, model, params), PROMPTS)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    _assert_same_arena(arena, arena_before)


@pytest.mark.parametrize("why", ["dense_kernel_mode", "quantized_pages", "layers_not_scanned"])
def test_the_fallback_says_so(why):
    """What the in-place step does not take keeps the split threading, and
    the span reads 0 (and the gauge): the dense read, a quantized cache (its
    scale pages are the scatter's), a model whose layers are not scanned."""
    model, params = _model("mistral", kernel="dense" if why == "dense_kernel_mode" else "interpret")
    kw = dict(kv_cache_dtype="int8") if why == "quantized_pages" else {}
    if why == "layers_not_scanned":
        model = model.clone(config=dataclasses.replace(model.config, scan_layers=False))
        params, _ = unbox_params(model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)["params"])
    eng = _engine("mistral", model, params, **kw)
    assert eng.metrics()["serving/arena_in_place"] == 0
    mark = _mark()
    eng.generate_batched(PROMPTS[:2], max_new_tokens=4)
    mine = _decode_spans(mark)
    assert mine and all(a["arena_in_place"] == 0 for a in mine)
    # the pack runs its dense reference off the chip: split by layer, and says so
    packs = _decode_spans(mark, "serving/prefill_dispatch")
    assert packs and all(a["arena_in_place"] == 0 for a in packs)
    assert eng.metrics()["serving/prefill_arena_in_place"] == 0


# -- one step, tables made by hand -------------------------------------------


def _random_arena(model, params, slots, table):
    """An arena of noise (what a page holds is then visible byte for byte)."""
    from accelerate_tpu.serving.pages import init_paged_arena

    kinds = sorted({c.cache_kind for c in model.config.run_configs()}) if model.config.layer_kinds else None
    arena = init_paged_arena(model, params, slots, table.shape[1], lambda p: p, kinds=kinds)
    leaves, tree = jax.tree_util.tree_flatten(arena)
    rng = np.random.default_rng(0)
    noise = [jnp.asarray(rng.standard_normal(x.shape), x.dtype) if x.ndim == 5 else x for x in leaves]
    return jax.tree_util.tree_unflatten(tree, noise), kinds


def _one_step(model, params, arena, tokens, positions, lengths, table, kinds):
    tables = {k: table for k in kinds} if kinds else table

    @jax.jit
    def step(params, arena):
        out, mutated = model.apply(
            {"params": params, "cache": arena}, tokens[:, None], positions=positions[:, None],
            use_cache=True, decode=True, cache_positions=positions, page_table=tables,
            kv_lengths=lengths, mutable=["cache"])
        return out["logits"][:, -1], mutated["cache"]

    logits, arena = step(params, arena)
    return np.asarray(logits, np.float32), jax.tree_util.tree_map(np.asarray, arena)


def _paged_model(shape):
    model, params = _model(shape)
    pages = dict(kv_page_size=PS, kv_num_pages=24)
    cfg = model.config
    if cfg.layer_kinds:
        cfg = dataclasses.replace(cfg, layer_kinds=tuple((n, dict(o, **pages)) for n, o in cfg.layer_kinds))
    return model.clone(config=dataclasses.replace(cfg, max_cache_len=96, **pages)), params


# slot -> (table row, position written, live length); page 0 is the parking page
CASES = {
    # position 16 is row 0 of the slot's third page, which held nothing of it yet
    "a_write_opens_a_new_page": {0: ([1, 2, 3], 16, 17), 1: ([4, 5, 6], 9, 10)},
    # the last row of a page, and a slot's first token of all
    "a_write_fills_a_page_and_a_first_token": {0: ([1, 2, 3], 15, 16), 1: ([4, 5, 6], 0, 1)},
    # slot 1 is inactive (parked at the last position, a freed row of parking
    # entries); slot 2 is mid-admission: it owns pages a prefill is filling
    # and is parked likewise: neither may lose a byte
    "an_inactive_slot_and_a_slot_mid_admission": {
        0: ([1, 2, 3], 12, 13), 1: ([0, 0, 0], 95, 0), 2: ([7, 8, 9], 95, 0)},
    # page 10 is a prefix both slots read; each writes a page of its own
    "a_prefix_page_shared_by_two_slots": {0: ([10, 2, 3], 11, 12), 1: ([10, 5, 6], 20, 21)},
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("shape", ["mistral", "by_kind"])
def test_one_step_writes_the_new_row_and_nothing_else(shape, case, monkeypatch):
    model, params = _paged_model(shape)
    slots = CASES[case]
    n = len(slots)
    table = np.zeros((n, 12), np.int32)
    for s, (row, _, _) in slots.items():
        table[s, :len(row)] = row
    positions = jnp.asarray([slots[s][1] for s in range(n)], jnp.int32)
    lengths = jnp.asarray([slots[s][2] for s in range(n)], jnp.int32)
    tokens = jnp.arange(5, 5 + n, dtype=jnp.int32)
    arena0, kinds = _random_arena(model, params, n, table)
    before = jax.tree_util.tree_map(np.asarray, arena0)
    args = (model, params, arena0, tokens, positions, lengths, jnp.asarray(table), kinds)
    logits, arena = _one_step(*args)
    _split_by_layer(monkeypatch)
    logits_split, arena_split = _one_step(*args)
    live = np.asarray(lengths) > 0
    np.testing.assert_array_equal(logits[live], logits_split[live])
    _assert_same_arena(arena, arena_split)
    # in place, a page changes only where a live slot wrote its row
    written = {(int(table[s, p // PS]), p % PS) for s, (_, p, ln) in slots.items() if ln}
    changed = set()
    for b, a in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(arena)):
        if b.ndim == 5:
            diff = (b != a).any(axis=(0, 2, 4))  # [pages, rows]
            changed |= {(int(p), int(r)) for p, r in zip(*np.nonzero(diff))}
    assert changed == written


# -- the packed prefill in place ----------------------------------------------


@pytest.fixture(scope="module", params=["mistral", "by_kind", "eva"])
def packed(request):
    """Both kernels interpreted: the pack program carries the arena too."""
    return (request.param,) + _model(request.param, prefill="interpret")


def _pack_engine(shape, model, params, **kw):
    if shape == "eva":  # pages of a chunk; 160 positions are 5 windows
        kw = dict(dict(page_size=4, max_cache_len=160, num_pages=1 + 3 * 24), **kw)
    return _engine(shape, model, params, **kw)


# five requests through three slots: tails of one block with pad rows, of several packs in a row (the largest
# capacity is 16 rows), two admitted in one pack; for ``eva`` (a window of 32, chunks of 4): a pack that fills and
# pools pages (17), one that ends at a close (32), one that crosses a close (45: window by window)
PACK_PROMPTS = [np.arange(5, 5 + n) % 120 + 3 for n in (17, 32, 45, 7, 11)]


def test_packs_then_forty_steps_give_the_same_tokens_and_the_same_arena(packed, monkeypatch):
    """Engines side by side, both programs in place against both split by
    layer: the same tokens over forty decode steps after the packs, every
    page but the parking page equal, and the in-place engine says so in its
    gauges and on every dispatch span of both programs."""
    shape, model, params = packed
    eng = _pack_engine(shape, model, params)
    m = eng.metrics()
    assert m["serving/prefill_kernel_active"] and m["serving/prefill_arena_in_place"] == 1
    assert m["serving/arena_in_place"] == 1
    mark = _mark()
    got, arena = _serve(eng, PACK_PROMPTS)
    packs = _decode_spans(mark, "serving/prefill_dispatch")
    assert len(packs) >= 8 and all(a["arena_in_place"] == 1 for a in packs)
    assert all(a["arena_in_place"] == 1 for a in _decode_spans(mark))
    if shape == "eva":
        assert sum(a["pages_pooled"] for a in packs) >= 4 + 8 + 11 + 1 + 2  # whole chunks of each prompt
    _split_by_layer(monkeypatch)
    want, arena_before = _serve(_pack_engine(shape, model, params), PACK_PROMPTS)
    for g, w, prompt in zip(got, want, PACK_PROMPTS):
        assert len(g) == len(prompt) + 40
        np.testing.assert_array_equal(g, w)
    _assert_same_arena(arena, arena_before)


def test_a_quantized_cache_keeps_the_split_form_and_says_so():
    """Quantized pages: the prefill kernel returns payloads and scales for
    XLA's scatter, as before, under both programs' counters."""
    model, params = _model("mistral", prefill="interpret")
    eng = _engine("mistral", model, params, kv_cache_dtype="int8")
    m = eng.metrics()
    assert m["serving/prefill_kernel_active"] and m["serving/prefill_arena_in_place"] == 0
    mark = _mark()
    eng.generate_batched(PROMPTS[:2], max_new_tokens=4)
    packs = _decode_spans(mark, "serving/prefill_dispatch")
    assert packs and all(a["arena_in_place"] == 0 for a in packs)
    assert all(a["arena_in_place"] == 0 for a in _decode_spans(mark))


# -- one pack, tables made by hand --------------------------------------------

BT, CAP = 8, 32  # token block and rows of the hand-made packs: four blocks


def _one_pack(model, params, arena, shares, tables):
    """One packed prefill call: ``shares`` is [(slot, history, ids)], each
    slot's rows from position ``history`` on, padded to the token block.
    Returns the live rows' logits and the arena."""
    ids, row_slot, row_pos = np.zeros(CAP, np.int32), np.full(CAP, -1, np.int32), np.full(CAP, -1, np.int32)
    n_slots = next(iter(tables.values())).shape[0] if isinstance(tables, dict) else tables.shape[0]
    hist, r = np.zeros(n_slots, np.int32), 0
    for slot, s0, new in shares:
        n = len(new)
        ids[r:r + n], row_pos[r:r + n] = new, np.arange(s0, s0 + n)
        row_slot[r:r + -(-n // BT) * BT] = slot  # a tail's pad rows keep the slot, dead through position -1
        hist[slot] = s0
        r += -(-n // BT) * BT
    assert r <= CAP

    @jax.jit
    def pack(params, arena):
        out, mutated = model.apply(
            {"params": params, "cache": arena}, jnp.asarray(ids)[None], positions=jnp.maximum(row_pos, 0)[None],
            use_cache=True, decode=True, cache_positions=jnp.asarray(row_pos)[None], page_table=tables,
            ragged_slots=jnp.asarray(row_slot), slot_hist=jnp.asarray(hist), mutable=["cache"])
        return out["logits"][0], mutated["cache"]

    logits, arena = pack(params, arena)
    return np.asarray(logits, np.float32)[row_pos >= 0], arena


def _pack_model(shape):
    model, params = _model(shape, prefill="interpret")
    pages = dict(kv_page_size=PS, kv_num_pages=24)
    cfg = dataclasses.replace(model.config, prefill_kernel_block=BT)
    if cfg.layer_kinds:
        cfg = dataclasses.replace(cfg, layer_kinds=tuple((n, dict(o, **pages)) for n, o in cfg.layer_kinds))
    return model.clone(config=dataclasses.replace(cfg, max_cache_len=96, **pages)), params


_ids = lambda n, k=0: (np.arange(n) * 7 + k) % 120 + 3
# packs in a row: [(slot, history, ids)] each; tables: slot -> row (page 0 is the parking page)
PACK_CASES = {
    # two blocks, the second of 5 live rows and 3 pads; pages 1 and 2 (rows 0-4)
    "a_tail_from_position_0": dict(tables={0: [1, 2, 3]}, packs=[[(0, 0, _ids(13))]]),
    # history 11 (page 10 is a shared prefix, page 2 holds positions 8-10 of it): the first block's rows sit
    # mid-page in pages 2 and 3, the second block starts in page 3, where the first ended
    "a_tail_from_a_mid_page_prefix_hit": dict(tables={0: [10, 2, 3, 4, 5]}, packs=[[(0, 11, _ids(14))]]),
    # one block of 5 live rows: page 4's rows 5-7 stay, and so does page 5
    "a_last_block_with_pad_rows": dict(tables={0: [0, 0, 0], 1: [4, 5, 6]}, packs=[[(1, 0, _ids(5))]]),
    # slot 0 from position 0, slot 2 from a history of 20 (mid-page), then a padding block (slot -1)
    "two_co_admitted_tails": dict(tables={0: [1, 2, 3], 1: [0, 0, 0], 2: [7, 8, 9, 11, 12]},
                                  packs=[[(0, 0, _ids(9)), (2, 20, _ids(12, 5))]]),
    # each pack reads what the one before it wrote
    "a_tail_of_three_packs_in_a_row": dict(
        tables={0: [1, 2, 3, 4, 5, 6]},
        packs=[[(0, 0, _ids(16))], [(0, 16, _ids(16, 3))], [(0, 32, _ids(9, 6))]]),
    # the window kind (window 16) gave back the pages behind position 40's window: their entries are parking
    "a_window_kind_whose_early_pages_were_given_back": dict(
        tables={0: [1, 2, 3, 4, 5, 6, 7]}, window_tables={0: [0, 0, 0, 12, 13, 14, 15]},
        packs=[[(0, 40, _ids(10))]]),
}


# by_kind: wide keys (192 at 256 lanes) and a window kind; one kind has no window to give pages back behind
@pytest.mark.parametrize("shape,case", [(shape, case) for shape in ("mistral", "by_kind") for case in sorted(PACK_CASES)
                                        if shape == "by_kind" or "window_tables" not in PACK_CASES[case]])
def test_one_pack_writes_its_rows_and_nothing_else(shape, case, monkeypatch):
    """A pack in place leaves the same logits and, page by page over every
    page a slot owns, the same arena as the split form, and touches no row
    of any page but those its live rows lie in (the parking page among
    them, which the split form's pad rows write)."""
    spec = PACK_CASES[case]
    model, params = _pack_model(shape)

    def table(rows):
        t = np.zeros((len(rows), 12), np.int32)
        for s, row in rows.items():
            t[s, :len(row)] = row
        return jnp.asarray(t)

    full = table(spec["tables"])
    arena0, kinds = _random_arena(model, params, full.shape[0], np.asarray(full))
    tables = full
    if kinds:
        tables = {k: table(spec["window_tables"]) if "window" in k and "window_tables" in spec else full
                  for k in kinds}
    before = jax.tree_util.tree_map(np.asarray, arena0)

    def run():
        arena, logits = arena0, []
        for shares in spec["packs"]:
            out, arena = _one_pack(model, params, arena, shares, tables)
            logits.append(out)
        return logits, jax.tree_util.tree_map(np.asarray, arena)

    logits, arena = run()
    _split_by_layer(monkeypatch)
    logits_split, arena_split = run()
    for got, want in zip(logits, logits_split):
        np.testing.assert_array_equal(got, want)
    _assert_same_arena(arena, arena_split)
    # in place, a row changes only where a live row of a pack was written, in every kind's pages
    flat = jax.tree_util.tree_flatten_with_path(arena)[0]
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(before)):
        if a.ndim != 5:
            continue
        own = tables
        if kinds:
            run_i = int(path[0].key.split("_")[1])
            own = tables[model.config.run_configs()[run_i].cache_kind]
        own = np.asarray(own)
        written = {(int(own[s, p // PS]), p % PS) for shares in spec["packs"] for s, s0, new in shares
                   for p in range(s0, s0 + len(new))}
        diff = (a != b).any(axis=(0, 2, 4))  # [pages, rows]
        assert {(int(p), int(r)) for p, r in zip(*np.nonzero(diff))} == written, jax.tree_util.keystr(path)
