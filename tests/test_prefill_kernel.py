"""Ragged flash prefill kernel (ops/attention.py) + engine integration.

Op-level contracts of record, run through the pallas interpreter on CPU
(the compiled TPU path shares every line but the `interpret` flag):

- the packed ragged kernel (online-softmax over arena prefix pages +
  same-slot causal fresh blocks) matches the dense reference across every
  packing edge — the all-pad warmup grid, 1-token tails, prefix
  frontiers at page boundary -1/0/+1, one admission filling the whole
  grid, a 75/25 short/long mix — for every GQA group size;
- quantize-on-write emits the EXACT `utils.quantization.quantize_kv`
  payload + scales (int8 and int4) in the same pass as attention;
- pad rows are never observable: they output exactly zero and garbage in
  foreign slots' pages cannot perturb a pack;
- the walk over a slot's live pages in blocks out of the arena: a history
  of 0, one that ends inside a page, inside a block and at its edge, one of
  several blocks, one that fills the table, two slots a pack, padding blocks;
- dispatch: `prefill_kernel` resolution, the
  warn-once dense fallback off-TPU, `prefill_kernel_active` mirroring
  the gate, config validation.

Engine-level: token parity kernel-vs-reference-vs-single-stream (prefix
replay included — the block-skip phase runs against real cache state),
the pad-waste/packed-token gauges, the zero-post-steady-recompile
invariant, and the audit program set covering the new `ragged_prefill_*`
entry points.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import (
    _PREFILL_TOKEN_BLOCK,
    prefill_kernel_active,
    ragged_prefill_attention,
    resolve_prefill_kernel,
)

ATOL = 2e-5  # fp32 interpreter vs XLA softmax: reassociation-level noise


def _packed_case(rng, packs, *, h=4, kvh=2, d=16, ps=8, bt=8,
                 quant_bits=0, cap=None, table_len=None):
    """Build one packed grid from ``packs`` = [(hist, tail), ...]: rows
    of one slot contiguous and position-ordered, each pack padded up to a
    token-block boundary (pads keep the slot id, pos = -1), per-slot
    page tables position-ordered over disjoint live pages (page 0
    parked), ``slot_hist[s]`` = live prefix tokens already in the arena.
    ``cap`` past the packs' rows leaves whole padding blocks (slot -1);
    ``table_len`` cuts the tables to that many entries (the kernel reads a
    slot's history pages only)."""
    S = max(1, len(packs))
    cap = cap or max(bt, sum(-(-t // bt) * bt for _, t in packs))
    row_slot = np.full((cap,), -1, np.int32)
    row_pos = np.full((cap,), -1, np.int32)
    slot_hist = np.zeros((S,), np.int32)
    r = 0
    per = max(1, max((-(-(hi + t) // ps) for hi, t in packs), default=1))
    table = np.zeros((S, per), np.int32)
    for s, (hist, tail) in enumerate(packs):
        blocks = -(-tail // bt)
        row_slot[r:r + blocks * bt] = s
        row_pos[r:r + tail] = np.arange(hist, hist + tail)
        r += blocks * bt
        slot_hist[s] = hist
        need = -(-(hist + tail) // ps)
        table[s, :need] = 1 + s * per + np.arange(need)
    npages = 1 + S * per
    pd = d // 2 if quant_bits == 4 else d
    if quant_bits:
        qmax = 7 if quant_bits == 4 else 127
        k_pages = rng.randint(-qmax, qmax + 1,
                              (npages, kvh, ps, pd)).astype(np.int8)
        v_pages = rng.randint(-qmax, qmax + 1,
                              (npages, kvh, ps, pd)).astype(np.int8)
        k_scale = (rng.random_sample((npages, kvh, ps, 1)) + 0.1).astype(
            np.float32)
        v_scale = (rng.random_sample((npages, kvh, ps, 1)) + 0.1).astype(
            np.float32)
    else:
        k_pages = rng.standard_normal((npages, kvh, ps, pd)).astype(np.float32)
        v_pages = rng.standard_normal((npages, kvh, ps, pd)).astype(np.float32)
        k_scale = v_scale = None
    q = rng.standard_normal((1, h, cap, d)).astype(np.float32)
    k_new = rng.standard_normal((1, kvh, cap, d)).astype(np.float32)
    v_new = rng.standard_normal((1, kvh, cap, d)).astype(np.float32)
    table = table[:, :table_len]
    kw = dict(page_table=jnp.asarray(table), row_slot=jnp.asarray(row_slot),
              row_pos=jnp.asarray(row_pos), slot_hist=jnp.asarray(slot_hist),
              token_block=bt, kv_quant_bits=quant_bits)
    if quant_bits:
        kw.update(k_scale=jnp.asarray(k_scale), v_scale=jnp.asarray(v_scale))
    args = (jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(k_pages), jnp.asarray(v_pages))
    valid = (row_slot >= 0) & (row_pos >= 0)
    return args, kw, valid


def _assert_kernel_matches_dense(args, kw, valid, err=""):
    out_k = ragged_prefill_attention(*args, impl="interpret", **kw)
    out_d = ragged_prefill_attention(*args, impl="dense", **kw)
    np.testing.assert_allclose(
        np.asarray(out_k[0])[0, :, valid], np.asarray(out_d[0])[0, :, valid],
        atol=ATOL, rtol=1e-5, err_msg=err,
    )
    # pad rows exactly zero on BOTH paths — the engine's fused scatter
    # routes them at the parking page, but nothing may leak through them
    np.testing.assert_array_equal(np.asarray(out_k[0])[0, :, ~valid], 0.0)
    np.testing.assert_array_equal(np.asarray(out_d[0])[0, :, ~valid], 0.0)
    return out_k, out_d


class TestRaggedPackingEdges:
    def test_all_pad_grid_empty_tail(self):
        """The warmup shape: every row padded (slot -1). Output is exactly
        zero — a pure-cache-hit admission that packed nothing real must
        not read anything."""
        rng = np.random.RandomState(0)
        args, kw, valid = _packed_case(rng, [])
        assert not valid.any()
        _assert_kernel_matches_dense(args, kw, valid)

    @pytest.mark.parametrize("hist", [0, 10])
    def test_one_token_tail(self, hist):
        """A 1-token tail (the prefix-hit resume shape: everything but
        the last prompt token served from cache) — one real row, bt-1
        pads."""
        rng = np.random.RandomState(1)
        args, kw, valid = _packed_case(rng, [(hist, 1)])
        assert valid.sum() == 1
        _assert_kernel_matches_dense(args, kw, valid, f"hist={hist}")

    @pytest.mark.parametrize("hist", [7, 8, 9])
    def test_prefix_frontier_page_boundary(self, hist):
        """Prefix history ending at page boundary -1/0/+1 (ps=8): the
        block-skip phase must stop at ceil(hist/ps) pages and the
        partial-page frontier is masked by position, not page count."""
        rng = np.random.RandomState(2)
        args, kw, valid = _packed_case(rng, [(hist, 8)])
        _assert_kernel_matches_dense(args, kw, valid, f"hist={hist}")

    def test_single_admission_fills_grid(self):
        rng = np.random.RandomState(3)
        args, kw, valid = _packed_case(rng, [(0, 32)])
        assert valid.all()
        _assert_kernel_matches_dense(args, kw, valid)

    def test_mixed_75_25_pack(self):
        """The serving packer's target mix: one long resumed tail plus
        three short cold tails in a single grid."""
        rng = np.random.RandomState(4)
        args, kw, valid = _packed_case(
            rng, [(16, 21), (0, 7), (0, 8), (0, 5)]
        )
        _assert_kernel_matches_dense(args, kw, valid)

    @pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2), (4, 1)])
    def test_gqa_group_sizes(self, h, kvh):
        rng = np.random.RandomState(5)
        args, kw, valid = _packed_case(rng, [(10, 11), (0, 9)],
                                       h=h, kvh=kvh)
        _assert_kernel_matches_dense(args, kw, valid, f"gqa {h}/{kvh}")

    def test_foreign_pages_never_observable(self):
        """Garbage in the parking page and in OTHER slots' pages cannot
        perturb a pack: the same-slot guard + table walk never touch
        them."""
        rng = np.random.RandomState(6)
        args, kw, valid = _packed_case(rng, [(10, 6), (0, 8)])
        out_clean = ragged_prefill_attention(*args, impl="interpret", **kw)
        q, k_new, v_new, kp, vp = args
        table = np.asarray(kw["page_table"])
        big = 1e6  # finite garbage: NaN poisons even the dense reference
        touched = set(table[0, :2]) | {0}  # slot 0's live prefix + parking
        for pg in range(kp.shape[0]):
            if pg not in touched:
                kp = kp.at[pg].set(big)
                vp = vp.at[pg].set(-big)
        kp = kp.at[0].set(big)
        vp = vp.at[0].set(-big)
        out_garbage = ragged_prefill_attention(
            q, k_new, v_new, kp, vp, impl="interpret", **kw
        )
        np.testing.assert_array_equal(np.asarray(out_clean[0]),
                                      np.asarray(out_garbage[0]))


# the walk over a slot's live pages, in blocks of two pages of 8 (the
# blocks' edges at the sizes a test can hold): (packs, _packed_case's options)
WALKS = {
    "history_0": ([(0, 20)], {}),
    "history_ends_inside_a_page": ([(13, 9)], {}),
    "history_of_three_blocks": ([(45, 10)], {}),
    "history_whose_last_block_is_half_full": ([(40, 8)], {}),
    "history_fills_the_table": ([(48, 8)], dict(table_len=6)),
    "two_slots_history_ends_inside_a_block_and_at_its_edge": ([(24, 8), (32, 16)], {}),
    "two_slots_change_after_a_padded_token_block": ([(20, 5), (16, 11)], {}),
    "second_slot_has_no_history": ([(40, 16), (0, 24)], {}),
    "padding_blocks_after_the_packs": ([(17, 8)], dict(cap=32)),
    "padding_blocks_only": ([], dict(cap=24)),
    "one_kv_head_for_all_query_heads": ([(27, 12), (8, 8)], dict(kvh=1)),
}


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_walk_in_blocks_of_pages_is_the_reference(walk, monkeypatch):
    """Each edge of the arena walk and of the fresh phase's own-slot visit
    against the dense reference; pad rows and padding blocks exactly zero."""
    from accelerate_tpu.ops import attention as A

    monkeypatch.setattr(A, "_PREFILL_MAX_BLOCK_PAGES", 2)
    packs, options = WALKS[walk]
    args, kw, valid = _packed_case(np.random.RandomState(11), packs, **options)
    _assert_kernel_matches_dense(args, kw, valid, walk)


def test_walk_visits_the_live_pages_only(monkeypatch):
    """Garbage in every page the walk has no business with (other slots'
    pages, the parking page, a slot's own pages past its history: those of
    the rows being written) cannot move a pack, at several blocks a slot."""
    from accelerate_tpu.ops import attention as A

    monkeypatch.setattr(A, "_PREFILL_MAX_BLOCK_PAGES", 2)
    args, kw, valid = _packed_case(np.random.RandomState(12), [(40, 12), (21, 8), (0, 8)])
    clean = ragged_prefill_attention(*args, impl="interpret", **kw)
    q, k_new, v_new, kp, vp = args
    table, hist = np.asarray(kw["page_table"]), np.asarray(kw["slot_hist"])
    live = {int(pg) for s in range(3) for pg in table[s, :-(-int(hist[s]) // 8)]}
    assert len(live) == 5 + 3
    for pg in set(range(kp.shape[0])) - live:
        kp, vp = kp.at[pg].set(1e6), vp.at[pg].set(-1e6)
    garbage = ragged_prefill_attention(q, k_new, v_new, kp, vp, impl="interpret", **kw)
    np.testing.assert_array_equal(np.asarray(clean[0]), np.asarray(garbage[0]))


def test_walk_counts_its_pages_on_the_host():
    """``prefill_walk_pages``, what the serving engine sums into
    ``pages_walked``: the table entries from a block's first to the end of
    the slot's history."""
    from accelerate_tpu.ops.attention import prefill_walk_pages, window_span_pages

    assert [prefill_walk_pages(h, h, 16) for h in (0, 1, 16, 17, 3584)] == [0, 1, 1, 2, 224]
    # a later block of the same pack walks the same history
    assert prefill_walk_pages(768, 768 + 64, 16) == 48
    # a window layer: from the page of the first position the block's first row sees ...
    assert prefill_walk_pages(1000, 1000, 16, window=128) == 63 - 54 == window_span_pages(127, 16)
    assert prefill_walk_pages(1000, 1064, 16, window=128) == 63 - 58
    # ... which may lie past the history: the rows see fresh rows only
    assert prefill_walk_pages(1000, 1128, 16, window=128) == 1  # position 1,001 shares its page with 992-999
    assert prefill_walk_pages(1000, 1136, 16, window=128) == 0 == prefill_walk_pages(0, 0, 16, window=128)
    assert prefill_walk_pages(100, 100, 16, window=128) == 7


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernel_writes_the_packs_rows_into_the_stack(dtype):
    """The pack program's own form (``layer``): the stacked pages and a layer
    index in, each token block's live rows put into the slot's pages by the
    kernel, the stack out; against the read-only kernel followed by the
    scatter of its payloads. A tail from a history that ends mid-page (its
    blocks share pages with the history and with each other), one of a block
    with pad rows, one that starts on a page, and a padding block. Under the
    TPU interpreter too, which models the asynchronous copies (a page read
    during the walk, pages on their way back while the fresh phase runs) and
    reports none of them racing."""
    import accelerate_tpu.ops.attention as A
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.RandomState(7)
    args, kw, valid = _packed_case(rng, [(11, 14), (0, 5), (16, 8)], d=128, cap=40)
    q, k_new, v_new, kp, vp = (x.astype(dtype) for x in args)
    noise = lambda x: jnp.asarray(rng.standard_normal((2,) + x.shape), dtype).at[1].set(x)
    k_stack, v_stack = noise(kp), noise(vp)  # the pack's layer is the stack's second
    table, row_slot, row_pos, hist = kw["page_table"], kw["row_slot"], kw["row_pos"], kw["slot_hist"]
    call = lambda k_pages, v_pages, interpret, **k: A._ragged_prefill_kernel_call(
        q, k_new, v_new, k_pages, v_pages, table, row_slot, row_pos, hist, 0.25, 8, interpret, **k)
    ref, k_pay, _, v_pay, _ = call(kp, vp, True)
    ps = kp.shape[2]
    page = jnp.where(valid, table[jnp.maximum(row_slot, 0), jnp.maximum(row_pos, 0) // ps], 0)
    off = jnp.maximum(row_pos, 0) % ps
    put = lambda stack, pay: stack.at[1, page[valid], :, off[valid]].set(pay[valid])
    k_ref, v_ref = put(k_stack, k_pay), put(v_stack, v_pay)
    for interpret in (True, pltpu.InterpretParams(uninitialized_memory="nan", detect_races=True)):
        out, k_out, v_out = call(k_stack, v_stack, interpret, layer=1)
        np.testing.assert_array_equal(np.asarray(k_out, np.float32), np.asarray(k_ref, np.float32))
        np.testing.assert_array_equal(np.asarray(v_out, np.float32), np.asarray(v_ref, np.float32))
        np.testing.assert_array_equal(np.asarray(out, np.float32)[:, :, valid], np.asarray(ref, np.float32)[:, :, valid])
    assert tpu_interpreter.races.races_found is False
    with pytest.raises(ValueError, match="unquantized pages of whole lanes"):
        call(k_stack[..., :64], v_stack[..., :64], False, layer=1)  # compiled: a 64-wide page is padded by a copy


class TestQuantizeOnWrite:
    @pytest.mark.parametrize("packs", [[(10, 11), (0, 9)], [(45, 10), (24, 8)]],
                             ids=["one_block_a_slot", "several_blocks_a_slot"])
    @pytest.mark.parametrize("bits", [8, 4])
    def test_payload_matches_quantize_kv(self, bits, packs, monkeypatch):
        """Fused quantize-on-write (one pass with attention) emits the
        EXACT reference `quantize_kv` payload and scales, and interpret
        == dense bitwise on both; the quantized pages walk their scale
        pages with them, in blocks of two pages here."""
        from accelerate_tpu.ops import attention as A
        from accelerate_tpu.utils.quantization import quantize_kv

        monkeypatch.setattr(A, "_PREFILL_MAX_BLOCK_PAGES", 2)
        rng = np.random.RandomState(7)
        args, kw, valid = _packed_case(rng, packs, quant_bits=bits)
        out_k, out_d = _assert_kernel_matches_dense(args, kw, valid)
        _, kp_k, ks_k, vp_k, vs_k = out_k
        _, kp_d, ks_d, vp_d, vs_d = out_d
        np.testing.assert_array_equal(np.asarray(kp_k), np.asarray(kp_d))
        np.testing.assert_array_equal(np.asarray(vp_k), np.asarray(vp_d))
        np.testing.assert_allclose(np.asarray(ks_k), np.asarray(ks_d),
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(vs_k), np.asarray(vs_d),
                                   atol=1e-7)
        k_new, v_new = args[1], args[2]
        for got_p, got_s, src in ((kp_k, ks_k, k_new), (vp_k, vs_k, v_new)):
            ref_p, ref_s = quantize_kv(jnp.swapaxes(src[0], 0, 1), bits)
            np.testing.assert_array_equal(np.asarray(got_p),
                                          np.asarray(ref_p))
            np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                                       atol=1e-7)

    @pytest.mark.parametrize("shape", [
        dict(h=4, kvh=2, d=64, ps=8, quant_bits=0),     # a 64-wide head: pages padded to 128 lanes
        dict(h=4, kvh=2, d=192, ps=8, quant_bits=0),    # 192-wide keys as stored: padded to 256
        dict(h=4, kvh=2, d=128, ps=8, quant_bits=4),    # an int4 payload of 64 lanes
        dict(h=4, kvh=2, d=64, ps=8, quant_bits=8),     # an int8 payload of 64 lanes
        dict(h=6, kvh=3, d=128, ps=8, quant_bits=8),    # 24 scales a page: one padded lane row
        dict(h=4, kvh=4, d=128, ps=64, quant_bits=8),   # 256 scales a page: two whole lane rows
        dict(h=12, kvh=12, d=16, ps=16, quant_bits=8),  # 192 scales a page: two lane rows, the second half full
    ], ids=lambda kw: "h{h}_kvh{kvh}_d{d}_ps{ps}_bits{quant_bits}".format(**kw))
    def test_narrow_and_scale_pages_go_in_as_whole_lanes(self, shape, monkeypatch):
        """The compiled kernel copies whole pages out of HBM in whole lanes:
        a page narrower than a 128-multiple goes in zero-padded and is read
        at its stored width, and a page's scales go in as one lane-dense
        row, (kv head, token) the lanes, out of which each kv row picks its
        own. The same numbers as the reference at each shape of the views."""
        from accelerate_tpu.ops import attention as A

        monkeypatch.setattr(A, "_PREFILL_MAX_BLOCK_PAGES", 2)
        rng = np.random.RandomState(11)
        ps = shape["ps"]
        args, kw, valid = _packed_case(rng, [(2 * ps + 3, 9), (0, 8), (ps, 5)], **shape)
        out_k = ragged_prefill_attention(*args, impl="interpret", **kw)
        out_d = ragged_prefill_attention(*args, impl="dense", **kw)
        # outputs of magnitude 60 over up to 140 positions: the grid form of
        # before PR 35 lay as far from the reference's order of sums here
        # (0.0012); a wrong lane or scale is off by whole units
        np.testing.assert_allclose(np.asarray(out_k[0])[0, :, valid], np.asarray(out_d[0])[0, :, valid], atol=2e-3)
        np.testing.assert_array_equal(np.asarray(out_k[0])[0, :, ~valid], 0.0)
        if shape["quant_bits"]:
            for pay, scl in ((1, 2), (3, 4)):
                np.testing.assert_array_equal(np.asarray(out_k[pay]), np.asarray(out_d[pay]))
                np.testing.assert_allclose(np.asarray(out_k[scl]), np.asarray(out_d[scl]), atol=1e-7)

    def test_whole_lanes_pads_the_last_dimension_only(self):
        from accelerate_tpu.ops.attention import _whole_lanes

        x = jnp.arange(2 * 3 * 64, dtype=jnp.int8).reshape(2, 3, 64)
        padded = _whole_lanes(x)
        assert padded.shape == (2, 3, 128) and padded.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(padded[..., :64]), np.asarray(x))
        assert not np.asarray(padded[..., 64:]).any()
        whole = jnp.zeros((2, 3, 256), jnp.bfloat16)
        assert _whole_lanes(whole) is whole

    def test_unquantized_returns_no_scales(self):
        rng = np.random.RandomState(8)
        args, kw, valid = _packed_case(rng, [(0, 8)])
        out = ragged_prefill_attention(*args, impl="interpret", **kw)
        assert out[2] is None and out[4] is None


class TestPrefillDispatch:
    def test_resolution_order_and_validation(self):
        # the config's value, else the default
        assert resolve_prefill_kernel() == resolve_prefill_kernel(None) == "ragged"
        assert resolve_prefill_kernel("dense") == "dense"
        assert resolve_prefill_kernel("interpret") == "interpret"
        with pytest.raises(ValueError, match="prefill_kernel must be one of"):
            resolve_prefill_kernel("flash")

    def test_warn_once_dense_fallback_off_tpu(self, caplog):
        from accelerate_tpu.ops import attention as A

        rng = np.random.RandomState(9)
        args, kw, valid = _packed_case(rng, [(0, 8)])
        A._decode_fallback_warned -= {
            k for k in A._decode_fallback_warned if k.startswith("prefill:")
        }
        with caplog.at_level(logging.WARNING, logger=A.__name__):
            out = ragged_prefill_attention(*args, impl="ragged", **kw)
            again = ragged_prefill_attention(*args, impl="ragged", **kw)
        warns = [r for r in caplog.records
                 if "ragged prefill kernel unavailable" in r.getMessage()]
        assert len(warns) == 1, [r.getMessage() for r in caplog.records]
        ref = ragged_prefill_attention(*args, impl="dense", **kw)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(again[0]),
                                      np.asarray(ref[0]))

    def test_compiled_gate_takes_every_storage_the_grid_form_took(self, monkeypatch):
        """On the chip the kernel takes 64-multiple widths, bf16 / int8 /
        int4 pages (an int4 payload itself a 64-multiple), 8-multiple pages
        and token blocks, where the paged decode kernel takes whole-lane
        unquantized pages only: narrow and scale pages reach the walk as
        lane-dense views. The config-level answer says the same."""
        from accelerate_tpu.models import DecoderConfig
        from accelerate_tpu.ops import attention as A

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(A, "_decode_fallback_warned", set())
        for d, dv, bits, want in [(128, None, 0, True), (256, 128, 0, True), (64, None, 0, True), (192, 128, 0, True),
                                  (128, None, 8, True), (128, None, 4, True), (128, 64, 0, True), (64, None, 8, True),
                                  (64, None, 4, False), (128, 64, 4, False), (96, None, 0, False), (128, 32, 0, False)]:
            assert A._prefill_kernel_gate("ragged", d, 16, 64, bits, dv=dv) == (want, False), (d, dv, bits)
        assert A._prefill_kernel_gate("ragged", 128, 12, 64) == (False, False)  # page no sublane multiple
        assert A._prefill_kernel_gate("ragged", 128, 16, 12) == (False, False)  # nor the token block
        paged = dict(kv_page_size=16, kv_num_pages=64)
        assert prefill_kernel_active(DecoderConfig(num_heads=32, num_kv_heads=8, head_dim=128, **paged))
        assert prefill_kernel_active(DecoderConfig(num_heads=64, num_kv_heads=4, head_dim=192, v_head_dim=128,
                                                   embed_dim=4096, **paged))
        assert prefill_kernel_active(DecoderConfig(num_heads=12, head_dim=64, **paged))
        assert prefill_kernel_active(DecoderConfig(num_heads=32, head_dim=128, kv_cache_dtype="int8", **paged))
        assert prefill_kernel_active(DecoderConfig(num_heads=32, head_dim=128, kv_cache_dtype="int4", **paged))
        assert not prefill_kernel_active(DecoderConfig(num_heads=12, head_dim=64, kv_cache_dtype="int4", **paged))

    def test_prefill_kernel_active_mirrors_gate(self):
        from accelerate_tpu.models import DecoderConfig

        paged = dict(max_seq_len=64, kv_page_size=8, kv_num_pages=17)
        assert prefill_kernel_active(
            DecoderConfig.tiny(prefill_kernel="interpret", **paged)
        )
        assert not prefill_kernel_active(
            DecoderConfig.tiny(prefill_kernel="dense", **paged)
        )
        # CPU process: the default compiled mode falls back to chunks
        assert not prefill_kernel_active(DecoderConfig.tiny(**paged))
        # unpaged config: no arena, no packed dispatch
        assert not prefill_kernel_active(
            DecoderConfig.tiny(max_seq_len=64, prefill_kernel="interpret")
        )

    def test_config_validation(self):
        from accelerate_tpu.models import DecoderConfig

        with pytest.raises(ValueError, match="prefill_kernel"):
            DecoderConfig.tiny(prefill_kernel="flash")
        with pytest.raises(ValueError, match="prefill_kernel_block"):
            DecoderConfig.tiny(prefill_kernel_block=-8)


@pytest.fixture(scope="module")
def ragged_models():
    """One parameter set served by three model views: ragged-interpret,
    forced-dense, and the plain single-stream reference."""
    from accelerate_tpu.models import DecoderConfig, DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params

    cfg_k = DecoderConfig.tiny(max_seq_len=64, prefill_kernel="interpret")
    cfg_d = DecoderConfig.tiny(max_seq_len=64)
    model_k, model_d = DecoderLM(cfg_k), DecoderLM(cfg_d)
    variables = model_k.init_variables(jax.random.PRNGKey(0), batch_size=1,
                                       seq_len=16)
    params, _ = unbox_params(variables["params"])
    return model_k, model_d, cfg_k, params


ENG_KW = dict(num_slots=2, max_cache_len=64, prefill_chunks=(4, 8),
              page_size=8)


@pytest.mark.parametrize("capacities,block", [
    ((64, 256), 64),    # the engine's default chunks: the serving cells
    ((256, 1024), 64),  # never above the sweep's largest
    ((32, 128), 32),    # the smallest capacity is one block
    ((64,), 16),        # a single capacity still holds four blocks
    ((100,), 24),       # whole sublane tiles
    ((16,), 8), ((4, 8), 8), ((8, 32), 8),  # never under one tile
])
def test_token_block_follows_the_compiled_capacities(capacities, block):
    from accelerate_tpu.ops.attention import prefill_token_block

    assert prefill_token_block(capacities) == block


def test_engine_packs_in_the_block_its_capacities_give(ragged_models):
    """Chunks (16, 64) give a 16-row block: the engine pads tails to it,
    hands the model the same block (the kernel refuses a capacity the
    block does not divide), a named ``prefill_kernel_block`` still wins,
    and tokens equal the reference engine's (the same packs, no kernel)."""
    import dataclasses

    from accelerate_tpu.serving import ServingEngine

    model_k, model_d, cfg_k, params = ragged_models
    kw = dict(ENG_KW, prefill_chunks=(16, 64))
    eng_k = ServingEngine(model_k, params, **kw)
    assert eng_k._ragged_bt == eng_k._paged_def.config.prefill_kernel_block == 16
    assert eng_k._ragged_caps == (16, 64)
    named = model_k.clone(config=dataclasses.replace(cfg_k, prefill_kernel_block=8))
    assert ServingEngine(named, params, **kw)._ragged_bt == 8
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, 16, (n,)) for n in (37, 5, 18, 23)]
    outs_d = ServingEngine(model_d, params, **kw).generate_batched(prompts, max_new_tokens=4)
    for out_k, out_d in zip(eng_k.generate_batched(prompts, max_new_tokens=4), outs_d):
        np.testing.assert_array_equal(out_k, out_d)
    assert eng_k.metrics()["serving/prefill_packed_tokens"] == sum(p.size for p in prompts)


class TestEngineRaggedAdmission:
    def test_token_parity_and_gauges(self, ragged_models):
        """The engine on the kernel == the engine on the kernel's dense
        reference == single-stream generate(), token for token, over mixed
        prompt lengths — then the telemetry spine: packed-token / pad-waste /
        kernel-active gauges and the zero-post-steady-recompile invariant."""
        from accelerate_tpu.generation import generate
        from accelerate_tpu.serving import ServingEngine

        model_k, model_d, _, params = ragged_models
        rng = np.random.RandomState(0)
        prompts = [rng.randint(3, 16, (n,)) for n in (5, 8, 12, 3)]
        refs = [
            np.asarray(generate(model_d, params, p[None], max_new_tokens=6,
                                rng=jax.random.PRNGKey(i))[0])
            for i, p in enumerate(prompts)
        ]
        eng_d = ServingEngine(model_d, params, **ENG_KW)
        outs_d = eng_d.generate_batched(prompts, max_new_tokens=6)
        eng_k = ServingEngine(model_k, params, **ENG_KW)
        eng_k.warmup()
        eng_k.mark_steady()
        reqs = [eng_k.submit(p, max_new_tokens=6, seed=i)
                for i, p in enumerate(prompts)]
        eng_k.run()
        outs_k = [r.result() for r in reqs]
        for out_k, out_d, ref in zip(outs_k, outs_d, refs):
            np.testing.assert_array_equal(out_d, ref)
            np.testing.assert_array_equal(out_k, ref)
        m = eng_k.metrics()
        assert m["serving/prefill_kernel_active"] is True
        assert m["serving/prefill_packed_tokens"] == sum(
            p.size for p in prompts
        )
        assert m["serving/admission_recompiles"] == 0
        assert 0.0 <= m["serving/prefill_pad_waste_frac"] < 1.0
        # the reference engine packs the same tails; only the kernel differs
        m_d = eng_d.metrics()
        assert m_d["serving/prefill_kernel_active"] is False
        assert m_d["serving/prefill_packed_tokens"] == m["serving/prefill_packed_tokens"]
        # the per-request record says whether the kernel attended it — what
        # the TTFT waterfall's kernel-vs-dense annotation reads
        assert {r.prefill_kernel for r in reqs} == {"ragged"}
        req_d = eng_d.submit(prompts[0], max_new_tokens=2)
        eng_d.run()
        assert req_d.prefill_kernel == "dense"

    def test_co_admission_packs_queued_tails(self, ragged_models):
        """More queued admissions than one tail: the planner packs whole
        queued tails into the primary's grid (FIFO engines only), on the
        kernel and on its reference alike, and the pad-waste gauge shows it."""
        from accelerate_tpu.serving import ServingEngine

        model_k, model_d, _, params = ragged_models
        rng = np.random.RandomState(1)
        short = [rng.randint(3, 16, (5,)) for _ in range(4)]
        kw = dict(num_slots=4, max_cache_len=64, prefill_chunks=(16,),
                  page_size=8)
        ed = ServingEngine(model_d, params, **kw)
        od = ed.generate_batched(short, max_new_tokens=4)
        # dense wave first: the recompile counter is process-global, so
        # nothing may compile between mark_steady() and the assert
        ek = ServingEngine(model_k, params, **kw)
        ek.warmup()
        ek.mark_steady()
        ok = ek.generate_batched(short, max_new_tokens=4)
        for a, b in zip(ok, od):
            np.testing.assert_array_equal(a, b)
        assert ek.admission_recompiles == 0
        waste_k = ek.metrics()["serving/prefill_pad_waste_frac"]
        waste_d = ed.metrics()["serving/prefill_pad_waste_frac"]
        # two 5-token tails a grid of 16 rows (blocks of 8), not one
        assert waste_k == waste_d == pytest.approx(1 - 20 / 32), (waste_k, waste_d)

    def test_prefix_skip_replay_matches_reference(self, ragged_models):
        """Prefix-cache replay: the resubmitted prompt admits with a
        live arena prefix, so the kernel's block-skip phase runs against
        real cache state — tokens must equal the reference engine's."""
        from accelerate_tpu.serving import ServingEngine

        model_k, model_d, _, params = ragged_models
        rng = np.random.RandomState(2)
        prompt = rng.randint(3, 16, (12,))
        outs = {}
        for name, model in (("ragged", model_k), ("dense", model_d)):
            eng = ServingEngine(model, params, **ENG_KW)
            first = eng.generate_batched([prompt], max_new_tokens=6)
            replay = eng.generate_batched([prompt], max_new_tokens=6)
            np.testing.assert_array_equal(first[0], replay[0])
            assert eng.metrics()["serving/prefix_hit_ratio"] > 0
            outs[name] = replay[0]
        np.testing.assert_array_equal(outs["ragged"], outs["dense"])

    def test_audit_covers_ragged_programs(self, ragged_models):
        """The warmup program set enumerates every packed-grid capacity
        as `ragged_prefill_<cap>` and the full engine audit (donation on,
        trace-only) stays clean — the CI `audit` gate needs no new
        baseline entries for the kernel."""
        from accelerate_tpu.analysis import program_audit as pa
        from accelerate_tpu.serving import ServingEngine

        model_k, _, _, params = ragged_models
        eng = ServingEngine(model_k, params, donate=True, num_slots=2,
                            max_cache_len=64, prefill_chunks=(8, 16),
                            page_size=8)
        eng.warmup()
        names = {pa.EntrypointSpec.normalize(s).name
                 for s in eng.audit_entrypoints()}
        assert {"ragged_prefill_8", "ragged_prefill_16"} <= names, names
        fs = pa.audit_engine(eng)
        assert fs == [], [f.to_dict() for f in fs]
