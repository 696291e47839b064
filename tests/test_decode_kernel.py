"""Pallas ragged/paged decode-attention kernel (ops/attention.py).

Op-level contracts of record, all run through the pallas interpreter on
CPU (the compiled TPU path shares every line but the `interpret` flag):

- the paged kernel (direct page-table walk) matches the gathered
  masked-dense reference across length edges — position 0, 1, page
  boundaries, full arena, ragged mixes — for every GQA group size and for
  multi-query Sq > 1 (the spec-verify shape);
- the dense-arena kernel matches the masked-dense reference for shared
  ([Sq]) and per-slot ([B, Sq]) positions at any valid kv block size;
- the parking page (page 0) is never *observable*: arbitrary garbage in
  parked/unallocated pages cannot perturb any slot's output;
- dispatch: `ATT_DECODE_KERNEL`/`decode_kernel` resolution, the warn-once
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
    def test_multi_query_spec_verify_shape(self, sq):
        """Sq > 1 with per-row consecutive positions — the spec_verify /
        fused-burst form: row t attends <= its own position, so draft
        token i sees drafts 0..i written in the same call."""
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
        """[B, Sq] per-slot positions (flat slot-arena serving) at several
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
    def test_resolution_order_and_validation(self, monkeypatch):
        monkeypatch.delenv("ATT_DECODE_KERNEL", raising=False)
        assert resolve_decode_kernel() == "paged"
        assert resolve_decode_kernel("dense") == "dense"
        monkeypatch.setenv("ATT_DECODE_KERNEL", "dense")
        assert resolve_decode_kernel() == "dense"
        assert resolve_decode_kernel("interpret") == "interpret"  # arg wins
        with pytest.raises(ValueError):
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
