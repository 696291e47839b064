"""The two serving kernels with a window, a sink, a value width of its own
and a value scale, interpreted, against ``mha_reference`` over the same keys
laid out densely; the window walk's first page; pages behind the window
never read (they hold NaN here)."""

import jax.numpy as jnp
import numpy as np
import pytest

import accelerate_tpu.ops.attention as A

PS, H, KVH, DK, DV = 8, 8, 2, 24, 16
W = 20  # not a multiple of the page: the window's first page is cut by it


def _arena(rng, pages, dk=DK, dv=DV):
    k = jnp.asarray(rng.normal(size=(pages, KVH, PS, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pages, KVH, PS, dv)), jnp.float32)
    return k, v


def _dense(pages, table_row, length):
    """[1, KVH, length, D] of one slot, in position order."""
    g = pages[np.asarray(table_row)]                     # [P, KVH, PS, D]
    return jnp.swapaxes(g, 0, 1).reshape(1, KVH, -1, g.shape[-1])[:, :, :length]


CASES = {
    "window": dict(window=W),
    "sink": dict(sink=True),
    "value_width_and_scale": dict(value_scale=0.707),
    "window_sink_scale": dict(window=W, sink=True, value_scale=0.707),
    "plain": dict(),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("pos", [0, 5, W - 1, W, W + PS - 1, 63, 100], ids=lambda p: f"pos{p}")
def test_paged_decode_kernel_is_the_reference(case, pos):
    kw = dict(CASES[case])
    rng = np.random.default_rng(pos)
    k_pages, v_pages = _arena(rng, 40)
    sink = jnp.asarray(rng.normal(size=(H,)), jnp.float32) if kw.pop("sink", False) else None
    window = kw.get("window")
    q = jnp.asarray(rng.normal(size=(2, H, 1, DK)), jnp.float32)
    table = np.arange(1, 33, dtype=np.int32).reshape(2, 16)
    rng.shuffle(table[0])
    positions = np.array([[pos], [max(pos - 3, 0)]], np.int32)
    if window is not None:
        # what lies wholly behind the window was given back: those entries
        # point at a page of NaN, which the kernel must never read
        k_pages, v_pages = k_pages.at[39].set(jnp.nan), v_pages.at[39].set(jnp.nan)
        for s in range(2):
            table[s, : max(0, positions[s, 0] - window + 1) // PS] = 39
    out = A.paged_decode_attention(
        q, k_pages, v_pages, page_table=jnp.asarray(table), q_positions=jnp.asarray(positions),
        impl="interpret", sink=sink, sm_scale=DK ** -0.5, **kw)
    assert out.shape == (2, H, 1, DV) and np.isfinite(np.asarray(out)).all()
    for s in range(2):
        n = int(positions[s, 0]) + 1
        lo = max(0, n - window) if window is not None else 0
        kd, vd = _dense(k_pages, table[s], n)[:, :, lo:], _dense(v_pages, table[s], n)[:, :, lo:]
        want = A.mha_reference(q[s:s + 1], kd, vd, sm_scale=DK ** -0.5, sink=sink,
                               value_scale=kw.get("value_scale", 1.0))
        np.testing.assert_allclose(np.asarray(out[s]), np.asarray(want[0]), atol=2e-5)


def test_window_walk_starts_at_the_page_of_its_first_position():
    """At most ``window_span_pages`` pages whatever the context, and one
    block of the walk holds them."""
    assert A.window_span_pages(128, 16) == 9 and A.window_span_pages(W, PS) == 4
    assert A.window_span_pages(1, 16) == 1 and A.window_span_pages(128, 16, sq=5) == 10
    assert A._paged_decode_block_pages(8, 16, 256, jnp.bfloat16, 0, 512, pdv=128, window_pages=9) == 16
    assert A._paged_decode_block_pages(4, 16, 256, jnp.bfloat16, 0, 512, pdv=128) == 64
    # the Mistral cells' shape keeps the block it had
    assert A._paged_decode_block_pages(8, 16, 128, jnp.bfloat16, 0, 256) == 64


def test_key_pages_of_192_lanes_are_stored_padded_to_256():
    assert [A.paged_key_lanes(d) for d in (64, 128, 192, 256, 320)] == [64, 128, 256, 256, 384]


def test_decode_reference_takes_the_same_options():
    """The masked-dense read (the fallback where no kernel engages) against
    ``mha_reference`` with the window as a causal offset."""
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(1, KVH, 64, DK)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, KVH, 64, DV)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, H, 1, DK)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    out = A.decode_attention(q, k, v, q_positions=jnp.asarray([[40]]), impl="interpret",
                             window=W, sink=sink, value_scale=0.5)
    want = A.mha_reference(q, k[:, :, 21:41], v[:, :, 21:41], sink=sink, value_scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _pack(rng, hists, tails, bt, cap):
    """Packed rows of several slots: (row_slot, row_pos, slot_hist)."""
    row_slot, row_pos = np.full(cap, -1, np.int32), np.full(cap, -1, np.int32)
    r = 0
    for s, (h, n) in enumerate(zip(hists, tails)):
        nb = -(-n // bt)
        row_slot[r:r + nb * bt] = s
        row_pos[r:r + n] = np.arange(h, h + n)
        r += nb * bt
    return row_slot, row_pos, np.asarray(hists, np.int32)


@pytest.mark.parametrize("block_pages", [None, 1], ids=["one_block", "blocks_of_one_page"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_prefill_kernel_is_the_reference(case, block_pages, monkeypatch):
    """Three slots in one pack: one from position 0, one far behind its
    window (its released pages hold NaN), one short; the kernel against
    ``mha_reference`` over each slot's arena prefix and fresh rows, with the
    walk in one block of pages and in several."""
    if block_pages is not None:
        monkeypatch.setattr(A, "_PREFILL_MAX_BLOCK_PAGES", block_pages)
    kw = dict(CASES[case])
    rng = np.random.default_rng(7)
    bt, cap = 8, 64
    hists, tails = [0, 61, 10], [24, 19, 5]
    k_pages, v_pages = _arena(rng, 40)
    sink = jnp.asarray(rng.normal(size=(H,)), jnp.float32) if kw.pop("sink", False) else None
    window = kw.get("window")
    table = np.arange(1, 37, dtype=np.int32).reshape(3, 12)
    if window is not None:
        k_pages, v_pages = k_pages.at[39].set(jnp.nan), v_pages.at[39].set(jnp.nan)
        for s, h in enumerate(hists):
            table[s, : max(0, h - window + 1) // PS] = 39
    row_slot, row_pos, hist = _pack(rng, hists, tails, bt, cap)
    q = jnp.asarray(rng.normal(size=(1, H, cap, DK)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(1, KVH, cap, DK)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(1, KVH, cap, DV)), jnp.float32)
    out, k_pay, _, v_pay, _ = A.ragged_prefill_attention(
        q, kn, vn, k_pages, v_pages, page_table=jnp.asarray(table), row_slot=row_slot, row_pos=row_pos,
        slot_hist=hist, impl="interpret", token_block=bt, sink=sink, sm_scale=DK ** -0.5, **kw)
    assert out.shape == (1, H, cap, DV) and np.isfinite(np.asarray(out)).all()
    assert k_pay.shape == (cap, KVH, DK) and v_pay.shape == (cap, KVH, DV)
    for s, (h, n) in enumerate(zip(hists, tails)):
        rows = np.flatnonzero((row_slot == s) & (row_pos >= 0))
        lo = max(0, h - window + 1) if window is not None else 0
        kd = jnp.concatenate([_dense(k_pages, table[s], h)[:, :, lo:], kn[:, :, rows]], axis=2)
        vd = jnp.concatenate([_dense(v_pages, table[s], h)[:, :, lo:], vn[:, :, rows]], axis=2)
        # causal over the slot's own order; the window by position
        want = A.mha_reference(q[:, :, rows], kd, vd, causal=True, sm_scale=DK ** -0.5, window=window,
                               sink=sink, value_scale=kw.get("value_scale", 1.0))
        np.testing.assert_allclose(np.asarray(out[0][:, rows]), np.asarray(want[0]), atol=3e-5)
    pads = np.flatnonzero(row_pos < 0)
    assert not np.asarray(out[0][:, pads]).any()
    # ... and the kernel's dense reference, the fallback, says the same
    ref = A.ragged_prefill_attention(
        q, kn, vn, jnp.nan_to_num(k_pages), jnp.nan_to_num(v_pages), page_table=jnp.asarray(table),
        row_slot=row_slot, row_pos=row_pos, slot_hist=hist, impl="dense", token_block=bt, sink=sink,
        sm_scale=DK ** -0.5, **kw)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_prefill_window_walk_is_as_long_as_the_window():
    """The arena walk of a window layer spans the pages that can hold the
    window - 1 positions before a block's first row, not the table's
    length, and one block of the walk holds them (the MiMo cell's window
    kind: 16 pages for 9, at 512 folded rows)."""
    assert A.window_span_pages(128 - 1, 16) == 9
    assert A.window_span_pages(W - 1, PS) == 4
    assert A._prefill_block_pages(8, 16, 256, jnp.bfloat16, 0, 512, 512, pdv=128, window_pages=9) == 16
    # a full kind's block is what the budget gives, whatever the table's length
    assert A._prefill_block_pages(4, 16, 256, jnp.bfloat16, 0, 512, 1024, pdv=128) == 32
    assert A._prefill_block_pages(8, 16, 128, jnp.bfloat16, 0, 256, 256) == 32
    assert A._prefill_block_pages(8, 16, 128, jnp.bfloat16, 0, 4, 256) == 4
    # int8 / int4 pages of that shape (payloads in whole lanes, a page's scales one
    # lane-dense row) hold as many; 32 kv heads a page hold a quarter of them
    for bits in (8, 4):
        assert A._prefill_block_pages(8, 16, 128, jnp.int8, bits, 256, 256) == 32
    assert A._prefill_block_pages(32, 16, 128, jnp.int8, 8, 128, 64) == 8
