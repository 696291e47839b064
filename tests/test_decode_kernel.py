"""Pallas ragged/paged decode-attention kernel (ops/attention.py).

Op-level contracts of record, all run through the pallas interpreter on
CPU (the compiled TPU path shares every line but the `interpret` flag):

- the paged kernel (direct page-table walk) matches the gathered
  masked-dense reference across length edges — position 0, 1, page
  boundaries, full arena, ragged mixes — for every GQA group size and for
  multi-query Sq > 1 (several rows a slot; ROADMAP R12);
- the dense-arena kernel matches the masked-dense reference for shared
  ([Sq]) and per-slot ([B, Sq]) positions at any valid kv block size;
- the parking page (page 0) is never *observable*: arbitrary garbage in
  parked/unallocated pages cannot perturb any slot's output;
- dispatch: `decode_kernel` resolution, the warn-once
  dense fallback off-TPU, and the by-design dense routing of
  prefill-size multi-query calls.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.ops.attention import (
    _DECODE_KERNEL_MAX_SQ,
    decode_attention,
    decode_kernel_active,
    gather_kv_pages,
    paged_decode_attention,
    resolve_decode_kernel,
)

ATOL = 2e-5  # fp32 interpreter vs XLA softmax: reassociation-level noise


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _paged_setup(rng, b=3, h=4, kvh=2, d=16, ps=8, per_slot=4, sq=1):
    num_pages = 1 + b * per_slot
    q = _rand(rng, (b, h, sq, d))
    k_pages = _rand(rng, (num_pages, kvh, ps, d))
    v_pages = _rand(rng, (num_pages, kvh, ps, d))
    # position-ordered tables over disjoint live pages (page 0 parked)
    table = jnp.asarray(
        1 + np.arange(b * per_slot).reshape(b, per_slot), jnp.int32
    )
    return q, k_pages, v_pages, table


class TestPagedKernelExactness:
    def test_length_edges_ragged(self):
        """Sweep the per-slot frontier across every edge the mask can
        meet: first position, page boundary -1/0/+1, full arena, ragged
        across slots — kernel == gathered masked-dense."""
        rng = np.random.RandomState(0)
        ps, per_slot = 8, 4
        q, kp, vp, table = _paged_setup(rng, ps=ps, per_slot=per_slot)
        cases = [
            [0, 0, 0],
            [1, 0, ps - 1],
            [ps - 1, ps, ps + 1],
            [ps * per_slot - 1, 0, ps],
            [3, 2 * ps + 5, ps * per_slot - 1],  # ragged mix
        ]
        for pos_list in cases:
            pos = jnp.asarray(pos_list, jnp.int32)[:, None]
            out = paged_decode_attention(
                q, kp, vp, page_table=table, q_positions=pos, impl="interpret"
            )
            ref = paged_decode_attention(
                q, kp, vp, page_table=table, q_positions=pos, impl="dense"
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=ATOL, rtol=1e-5,
                err_msg=f"positions {pos_list}",
            )

    @pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2), (4, 1)])
    def test_gqa_group_sizes(self, h, kvh):
        rng = np.random.RandomState(1)
        q, kp, vp, table = _paged_setup(rng, h=h, kvh=kvh)
        pos = jnp.asarray([[5], [17], [31]], jnp.int32)
        out = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="interpret"
        )
        ref = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="dense"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=ATOL, rtol=1e-5)

    @pytest.mark.parametrize("sq", [2, 3, 5])
    def test_multi_query_shape(self, sq):
        """Sq > 1 with per-row consecutive positions — several rows a slot
        (no engine program dispatches it; ROADMAP R12): row t attends <= its
        own position, so token i sees tokens 0..i written in the same call."""
        rng = np.random.RandomState(2)
        q, kp, vp, table = _paged_setup(rng, sq=sq)
        base = jnp.asarray([0, 7, 20], jnp.int32)
        pos = base[:, None] + jnp.arange(sq)[None, :]
        out = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="interpret"
        )
        ref = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="dense"
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=ATOL, rtol=1e-5)

    def test_parked_page_never_observable(self):
        """Garbage in the parking page (and in any unallocated page) must
        not perturb any slot's output: unallocated table entries point at
        page 0, and the kernel's mask (+ the clamped early-exit walk)
        keeps everything past the frontier at exactly zero probability."""
        rng = np.random.RandomState(3)
        q, kp, vp, table = _paged_setup(rng)
        # slots live only up to mid-arena: tail table entries -> parking
        table = jnp.asarray(np.array(table).copy())
        table = table.at[:, 2:].set(0)
        pos = jnp.asarray([[5], [9], [15]], jnp.int32)  # all within 2 pages
        out_clean = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="interpret"
        )
        big = 1e6  # large-but-finite garbage (NaN would poison even the
        # masked-dense reference through 0 * NaN)
        kp_g = kp.at[0].set(big)
        vp_g = vp.at[0].set(-big)
        out_garbage = paged_decode_attention(
            q, kp_g, vp_g, page_table=table, q_positions=pos, impl="interpret"
        )
        np.testing.assert_array_equal(np.asarray(out_clean),
                                      np.asarray(out_garbage))

    def test_matches_decode_attention_on_gathered_view(self):
        """Cross-op witness: kernel output == decode_attention (dense
        reference path) over the gathered per-slot dense view."""
        rng = np.random.RandomState(4)
        q, kp, vp, table = _paged_setup(rng)
        pos = jnp.asarray([[3], [12], [28]], jnp.int32)
        out = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="interpret"
        )
        dense_k = gather_kv_pages(kp, table)
        dense_v = gather_kv_pages(vp, table)
        ref = decode_attention(q, dense_k, dense_v, q_positions=pos,
                               impl="dense")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=ATOL, rtol=1e-5)


_EDGE_PS, _EDGE_BLOCK, _EDGE_TABLE = 8, 4, 10  # page, pages a block, table entries
_EDGES = {  # live length of the slot under test
    "1": 1,
    "page-1": _EDGE_PS - 1,
    "page": _EDGE_PS,
    "block-1": _EDGE_BLOCK * _EDGE_PS - 1,
    "block": _EDGE_BLOCK * _EDGE_PS,
    "block+1": _EDGE_BLOCK * _EDGE_PS + 1,
    "table_end": _EDGE_TABLE * _EDGE_PS,
}


def _edge_setup(rng, *, h, kvh, sq, kv, live):
    """Four slots: the length under test, a slot with NO live tokens, a
    second live one, and one more inactive at the end; shuffled,
    non-monotonic tables whose unallocated entries all park on page 0."""
    from accelerate_tpu.utils.quantization import quantize_kv

    ps, per, d = _EDGE_PS, _EDGE_TABLE, 32
    lens = np.array([live, 0, max(live // 2, sq) + 3 * ps, 0])
    lens = np.maximum(lens, np.where(lens > 0, sq, 0))  # Sq rows need Sq positions
    num_pages = 1 + len(lens) * per
    dtype = jnp.bfloat16 if kv == "bf16" else jnp.float32
    q = _rand(rng, (len(lens), h, sq, d)).astype(dtype)
    kp = _rand(rng, (num_pages, kvh, ps, d)).astype(dtype)
    vp = _rand(rng, (num_pages, kvh, ps, d)).astype(dtype)
    kw = {}
    if kv != "bf16":
        bits = int(kv[3:])
        (kp, ks), (vp, vs) = quantize_kv(kp, bits), quantize_kv(vp, bits)
        kw = {"k_scale": ks, "v_scale": vs, "kv_quant_bits": bits}
    free = list(rng.permutation(np.arange(1, num_pages)))
    table = np.zeros((len(lens), per), np.int32)  # 0: the parking page
    for s, n in enumerate(lens):
        for e in range(-(-int(n) // ps)):
            table[s, e] = free.pop()
    # the last Sq positions of a live slot; an inactive slot is handed the
    # engine's parked position, the end of the cache
    pos = np.where(lens[:, None] > 0, lens[:, None] - sq + np.arange(sq)[None, :], per * ps - 1)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32), kw


class TestPagedWalk:
    """The walk of live pages in blocks of many (PR 25): parity with the
    masked-dense reference at every edge a block boundary can meet."""

    @pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
    @pytest.mark.parametrize("sq,h,kvh", [(1, 4, 4), (5, 4, 4), (1, 32, 8), (5, 32, 8)],
                             ids=["sq1-mha", "sq5-mha", "sq1-gqa32q8kv", "sq5-gqa32q8kv"])
    @pytest.mark.parametrize("edge", sorted(_EDGES))
    def test_block_edges_zero_live_slots_shuffled_tables(self, monkeypatch, edge, sq, h, kvh, kv):
        import accelerate_tpu.ops.attention as A

        monkeypatch.setattr(A, "_PAGED_DECODE_MAX_BLOCK_PAGES", _EDGE_BLOCK)
        rng = np.random.RandomState(sorted(_EDGES).index(edge))
        q, kp, vp, table, pos, lens, kw = _edge_setup(
            rng, h=h, kvh=kvh, sq=sq, kv=kv, live=_EDGES[edge])
        out = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, kv_lengths=lens,
            impl="interpret", **kw)
        ref = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="dense", **kw)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        live = np.asarray(lens) > 0
        tol = dict(atol=2e-2, rtol=2e-2) if kv == "bf16" else dict(atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(out[live], ref[live], **tol)
        # a slot with no live tokens is not walked: zeros, whatever its
        # parked position and the parking page hold
        np.testing.assert_array_equal(out[~live], 0.0)

    def test_lengths_default_to_the_last_row_position(self):
        """Absent ``kv_lengths`` the walk is bounded by the positions, as
        before: the same output as with the lengths spelled out."""
        rng = np.random.RandomState(11)
        q, kp, vp, table, pos, lens, _ = _edge_setup(rng, h=4, kvh=2, sq=3, kv="bf16", live=21)
        live = np.asarray(lens) > 0
        kw = dict(page_table=table[live], q_positions=pos[live], impl="interpret")
        np.testing.assert_array_equal(
            np.asarray(paged_decode_attention(q[live], kp, vp, **kw), np.float32),
            np.asarray(paged_decode_attention(q[live], kp, vp, kv_lengths=lens[live], **kw), np.float32))

    def test_block_tails_never_read_uninitialized_memory(self):
        """Pages past a slot's frontier are not copied, so the tail of a
        block's buffer holds what was there before; the TPU interpreter
        fills fresh buffers with NaN, which the kernel must have cleared
        (a masked probability of zero times NaN would still be NaN)."""
        import accelerate_tpu.ops.attention as A
        from jax.experimental.pallas import tpu as pltpu

        rng = np.random.RandomState(12)
        q, kp, vp, table, pos, lens, _ = _edge_setup(rng, h=4, kvh=2, sq=1, kv="bf16", live=3)
        out = A._paged_decode_kernel_call(  # the layers' stack, here of one layer
            q, kp[None], vp[None], table, pos, lens, 0.25,
            pltpu.InterpretParams(uninitialized_memory="nan"))
        assert np.isfinite(np.asarray(out, np.float32)).all()


    def test_the_kernel_writes_the_new_rows_into_the_stack(self):
        """The decode step's own form (``k_new``/``v_new``): the stacked
        pages and a layer index in, each live slot's row put at its position
        by the kernel, the stack out; against the scatter followed by the
        read-only kernel. Under the TPU interpreter too, which models the
        asynchronous copies (a page on its way back while the block is
        attended) and reports none of them racing."""
        import accelerate_tpu.ops.attention as A
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter
        from jax.experimental.pallas import tpu as pltpu

        rng = np.random.RandomState(3)
        layers, pages, kvh, ps, d, b, h = 2, 40, 2, 8, 128, 4, 4
        kp, vp = (_rand(rng, (layers, pages, kvh, ps, d)) for _ in range(2))
        q = _rand(rng, (b, h, 1, d))
        k_new, v_new = (_rand(rng, (b, kvh, 1, d)) for _ in range(2))
        table = jnp.asarray(1 + np.arange(b * 9).reshape(b, 9) % (pages - 1), jnp.int32)
        # a walk of two blocks whose last row ends a page, a short one, a
        # slot with no live tokens (writes nothing), a row that opens a page
        pos = jnp.asarray([[71], [5], [71], [16]], jnp.int32)
        lens = jnp.asarray([72, 6, 0, 17], jnp.int32)
        rows = jnp.arange(b)[:, None]
        page, off = table[rows, pos // ps], pos % ps
        live = (lens > 0)[:, None, None, None]
        put = lambda stack, new: stack.at[1, page, :, off].set(
            jnp.where(live, jnp.swapaxes(new, 1, 2), stack[1, page, :, off]))
        k_ref, v_ref = put(kp, k_new), put(vp, v_new)
        ref = A._paged_decode_kernel_call(q, k_ref, v_ref, table, pos, lens, 0.25, True, layer=1)
        for interpret in (True, pltpu.InterpretParams(uninitialized_memory="nan", detect_races=True)):
            out, k_out, v_out = A._paged_decode_kernel_call(
                q, kp, vp, table, pos, lens, 0.25, interpret, layer=1, k_new=k_new, v_new=v_new)
            np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k_ref))
            np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v_ref))
            np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(ref, np.float32))
        assert tpu_interpreter.races.races_found is False


_NARROW_EDGES = ("inside_a_block", "on_a_blocks_edge", "in_a_blocks_first_page")


class TestNarrowQueryGroup:
    """Many kv heads under a query group of one or two (PR 43): the shape at
    which the kernel sizes its block and forms its products differently,
    against ``mha_reference`` over each slot's gathered live keys."""

    @pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
    @pytest.mark.parametrize("edge", _NARROW_EDGES)
    @pytest.mark.parametrize("kvh,group", [(32, 1), (16, 2)], ids=["32x1", "16x2"])
    def test_parity_with_the_reference_at_block_edges(self, kvh, group, edge, write):
        import accelerate_tpu.ops.attention as A

        ps, d, layers = 16, 128, 2
        h = kvh * group
        table_len = 160  # longer than the largest block the kernel may take
        n = A._paged_decode_block_pages(kvh, ps, d, jnp.bfloat16, 0, table_len)
        assert 2 * n + 2 <= table_len
        under_test = {"inside_a_block": n * ps + 5 * ps + 3, "on_a_blocks_edge": 2 * n * ps,
                      "in_a_blocks_first_page": n * ps + 7}[edge]
        # the slot under test, an empty slot, a second live one that ends in its first block
        lens = np.array([under_test, 0, 3 * ps + 1])
        need = -(-lens // ps)
        rng = np.random.RandomState(_NARROW_EDGES.index(edge) + 10 * group)
        num_pages = 1 + int(need.sum())
        free = list(rng.permutation(np.arange(1, num_pages)))
        table = np.zeros((len(lens), table_len), np.int32)  # 0: the parking page
        for s_, cnt in enumerate(need):
            for e in range(int(cnt)):
                table[s_, e] = free.pop()
        bf = lambda *shape: _rand(rng, shape).astype(jnp.bfloat16)
        q, kp, vp = bf(len(lens), h, 1, d), bf(layers, num_pages, kvh, ps, d), bf(layers, num_pages, kvh, ps, d)
        pos = jnp.asarray(np.where(lens > 0, lens - 1, table_len * ps - 1), jnp.int32)[:, None]
        table, lens_j = jnp.asarray(table), jnp.asarray(lens, jnp.int32)
        k_want, v_want, new = kp, vp, {}
        if write:
            k_new, v_new = bf(len(lens), kvh, 1, d), bf(len(lens), kvh, 1, d)
            rows = jnp.arange(len(lens))[:, None]
            page, off = table[rows, pos // ps], pos % ps
            live = (lens_j > 0)[:, None, None, None]
            put = lambda stack, x: stack.at[1, page, :, off].set(
                jnp.where(live, jnp.swapaxes(x, 1, 2), stack[1, page, :, off]))
            k_want, v_want, new = put(kp, k_new), put(vp, v_new), dict(k_new=k_new, v_new=v_new)
        got = A._paged_decode_kernel_call(q, kp, vp, table, pos, lens_j, d ** -0.5, True, layer=1, **new)
        if write:
            got, k_out, v_out = got
            np.testing.assert_array_equal(np.asarray(k_out, np.float32), np.asarray(k_want, np.float32))
            np.testing.assert_array_equal(np.asarray(v_out, np.float32), np.asarray(v_want, np.float32))
        got = np.asarray(got, np.float32)
        for s_, length in enumerate(lens):
            if not length:
                np.testing.assert_array_equal(got[s_], 0.0)
                continue
            entries = table[s_, :need[s_]]
            dense = lambda stack: jnp.swapaxes(stack[1, entries], 0, 1).reshape(1, kvh, -1, d)[:, :, :length]
            want = A.mha_reference(q[s_:s_ + 1], dense(k_want), dense(v_want), sm_scale=d ** -0.5)
            np.testing.assert_allclose(got[s_:s_ + 1], np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)


class TestDenseArenaKernel:
    def test_shared_positions_single_stream_form(self):
        """[Sq] shared positions — the single-stream generate() decode
        loop's call shape — on the dense-arena kernel."""
        rng = np.random.RandomState(5)
        b, h, kvh, d, L = 2, 4, 2, 16, 32
        q = _rand(rng, (b, h, 1, d))
        k = _rand(rng, (b, kvh, L, d))
        v = _rand(rng, (b, kvh, L, d))
        for p in (0, 1, 15, 16, L - 1):
            pos = jnp.asarray([p], jnp.int32)
            out = decode_attention(q, k, v, q_positions=pos, impl="interpret")
            ref = decode_attention(q, k, v, q_positions=pos, impl="dense")
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=ATOL, rtol=1e-5,
                                       err_msg=f"position {p}")

    def test_per_slot_positions_and_block_sweep(self):
        """[B, Sq] per-slot positions at several
        kv block sizes — block choice changes the walk, not the math."""
        rng = np.random.RandomState(6)
        b, h, kvh, d, L = 3, 4, 2, 16, 32
        q = _rand(rng, (b, h, 1, d))
        k = _rand(rng, (b, kvh, L, d))
        v = _rand(rng, (b, kvh, L, d))
        pos = jnp.asarray([[0], [13], [31]], jnp.int32)
        ref = decode_attention(q, k, v, q_positions=pos, impl="dense")
        for blk in (4, 8, 16, 32):
            out = decode_attention(q, k, v, q_positions=pos,
                                   impl="interpret", block_kv=blk)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=ATOL, rtol=1e-5,
                                       err_msg=f"block {blk}")


class TestDecodeKernelDispatch:
    def test_resolution_order_and_validation(self):
        # the config's value, else the default
        assert resolve_decode_kernel() == resolve_decode_kernel(None) == "paged"
        assert resolve_decode_kernel("dense") == "dense"
        assert resolve_decode_kernel("interpret") == "interpret"
        with pytest.raises(ValueError, match="decode_kernel must be one of"):
            resolve_decode_kernel("flash")

    def test_warn_once_dense_fallback_off_tpu(self, caplog):
        """Default mode on a CPU process: the kernel silently falls back
        to masked-dense with exactly one warning per reason (mirroring the
        fp8-without-MXU warn)."""
        from accelerate_tpu.ops import attention as A

        rng = np.random.RandomState(7)
        q, kp, vp, table = _paged_setup(rng)
        pos = jnp.asarray([[1], [2], [3]], jnp.int32)
        A._decode_fallback_warned.clear()
        with caplog.at_level(logging.WARNING, logger=A.__name__):
            out = paged_decode_attention(
                q, kp, vp, page_table=table, q_positions=pos, impl="paged"
            )
            again = paged_decode_attention(
                q, kp, vp, page_table=table, q_positions=pos, impl="paged"
            )
        warns = [r for r in caplog.records
                 if "decode-attention kernel unavailable" in r.getMessage()]
        assert len(warns) == 1, [r.getMessage() for r in caplog.records]
        ref = paged_decode_attention(
            q, kp, vp, page_table=table, q_positions=pos, impl="dense"
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(again), np.asarray(ref))

    def test_prefill_size_multi_query_stays_dense(self):
        """Sq beyond the decode-width bound (prefill chunks) routes to the
        masked-dense path by design — bitwise identical to impl='dense',
        no warning (it is not a fallback)."""
        from accelerate_tpu.ops import attention as A

        rng = np.random.RandomState(8)
        sq = _DECODE_KERNEL_MAX_SQ + 1
        b, h, kvh, d, L = 2, 4, 2, 16, 64
        q = _rand(rng, (b, h, sq, d))
        k = _rand(rng, (b, kvh, L, d))
        v = _rand(rng, (b, kvh, L, d))
        pos = jnp.arange(sq, dtype=jnp.int32)
        A._decode_fallback_warned.clear()
        out = decode_attention(q, k, v, q_positions=pos, impl="interpret")
        ref = decode_attention(q, k, v, q_positions=pos, impl="dense")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert not A._decode_fallback_warned

    def test_decode_kernel_active_mirrors_dispatch(self):
        from accelerate_tpu.models import DecoderConfig

        paged = DecoderConfig.tiny(
            max_seq_len=64, kv_page_size=8, kv_num_pages=17,
            decode_kernel="interpret",
        )
        assert decode_kernel_active(paged)
        assert not decode_kernel_active(
            DecoderConfig.tiny(max_seq_len=64, kv_page_size=8,
                               kv_num_pages=17, decode_kernel="dense")
        )
        # unpaged config: the engine's paged_decode_kernel row is not live
        assert not decode_kernel_active(DecoderConfig.tiny(max_seq_len=64))

    def test_config_validation(self):
        from accelerate_tpu.models import DecoderConfig

        with pytest.raises(ValueError, match="decode_kernel"):
            DecoderConfig.tiny(decode_kernel="flash")
