"""Paged KV arena and copy-on-write prefix cache
(accelerate_tpu/serving/pages.py + the paged ServingEngine mode).

The contracts of record:
- paged decode is TOKEN-EXACT vs. the flat (masked-dense) arena AND vs.
  sequential generate() — the gather read and the dense fallback are
  bit-exact twins (asserted at the op level too);
- a prefix-cache hit skips the shared prefix's prefill chunks and still
  yields bit-identical tokens; a slot mutating a shared page forks it
  (copy-on-write) without perturbing any other slot or the cached copy;
- page free-list accounting survives admit/evict churn with no leak;
- a warmed paged engine triggers ZERO compiles across admissions, prefix
  hits and page forks (the jax.monitoring counters are the witness).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import generate
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import ServingEngine

PS = 8  # page size under test (max_cache_len 64 -> 8 pages per slot)


@pytest.fixture(scope="module")
def served_model():
    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, cfg.vocab_size, (n,)) for n in (5, 8, 12, 3)]
    return model, cfg, params, prompts


_REF_CACHE: dict = {}
_REF_NEW = 6


def _refs(model, params, prompts, max_new, temperature=0.0, top_k=None):
    """Sequential single-stream references (memoized; RNG chains are
    prefix-stable so shorter needs slice the cached stream)."""
    assert max_new <= _REF_NEW
    out = []
    for i, p in enumerate(prompts):
        key = (temperature, top_k, i, p.tobytes())
        if key not in _REF_CACHE:
            _REF_CACHE[key] = np.asarray(
                generate(
                    model, params, p[None], max_new_tokens=_REF_NEW,
                    temperature=temperature, top_k=top_k, rng=jax.random.PRNGKey(i),
                )[0]
            )
        out.append(_REF_CACHE[key][: p.size + max_new])
    return out


def _engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("prefill_chunks", (4, 8))
    kw.setdefault("page_size", PS)
    return ServingEngine(model, params, **kw)


class TestPagedParity:
    def test_greedy_matches_flat_arena_and_sequential(self, served_model):
        """Paged gather-read decode vs the flat masked-dense arena vs
        sequential generate(): token-for-token identical."""
        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts, 6)
        flat = ServingEngine(
            model, params, num_slots=2, max_cache_len=64, prefill_chunks=(4, 8)
        ).generate_batched(prompts, max_new_tokens=6)
        paged = _engine(model, params).generate_batched(prompts, max_new_tokens=6)
        for out_f, out_p, ref in zip(flat, paged, refs):
            np.testing.assert_array_equal(out_p, ref)
            np.testing.assert_array_equal(out_p, out_f)

    def test_sampled_matches_sequential(self, served_model):
        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts, 6, temperature=1.0, top_k=8)
        engine = _engine(model, params, num_slots=4, temperature=1.0, top_k=8)
        outs = engine.generate_batched(prompts, max_new_tokens=6)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_paged_attention_op_bit_exact_vs_dense(self, served_model):
        """Op-level contract: paged_decode_attention == decode_attention on
        the densified cache, bitwise (the gather is pure data movement)."""
        from accelerate_tpu.ops.attention import (
            decode_attention,
            gather_kv_pages,
            paged_decode_attention,
        )

        rng = np.random.RandomState(0)
        b, h, kvh, d, ps, per_slot, num_pages = 3, 4, 2, 8, 4, 4, 16
        q = jnp.asarray(rng.standard_normal((b, h, 2, d)), jnp.float32)
        pages = jnp.asarray(
            rng.standard_normal((num_pages, kvh, ps, d)), jnp.float32
        )
        table = jnp.asarray(
            rng.randint(0, num_pages, (b, per_slot)), jnp.int32
        )
        qpos = jnp.asarray(rng.randint(0, ps * per_slot, (b, 2)), jnp.int32)
        dense = gather_kv_pages(pages, table)
        a = paged_decode_attention(
            q, pages, pages, page_table=table, q_positions=qpos
        )
        b_ = decode_attention(q, dense, dense, q_positions=qpos)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_prefix_hit_skips_chunks_token_exact(self, served_model):
        """Second request with the same prompt maps the cached pages,
        prefills only the tail, and still matches the sequential ref."""
        model, cfg, params, prompts = served_model
        p = prompts[2]  # len 12 -> aligned entry at 8
        ref = _refs(model, params, [p], 5)[0]
        engine = _engine(model, params, num_slots=1)
        r1 = engine.submit(p, max_new_tokens=5, seed=2)
        engine.run()
        r2 = engine.submit(p, max_new_tokens=5, seed=2)
        engine.run()
        np.testing.assert_array_equal(r1.result(), ref)
        np.testing.assert_array_equal(r2.result(), ref)
        assert r1.prefix_hit == 0 and r2.prefix_hit == 8
        assert engine.prefill_chunks_skipped >= 1
        m = engine.metrics()
        assert m["serving/prefix_hit_ratio"] == 0.5
        assert m["serving/prefix_hit_tokens"] == 8

    def test_uneconomic_hit_declined(self, served_model):
        """A cached prefix whose tail would need MORE prefill dispatches
        than a cold admission (small cached head of a prompt the cold plan
        covers in one big chunk) is declined: prefill_chunks_skipped never
        goes negative, the hit gauges reflect the final decision, and the
        output is still token-exact."""
        model, cfg, params, prompts = served_model
        rng = np.random.RandomState(7)
        a = rng.randint(3, cfg.vocab_size, (8,))
        b = np.concatenate([a[:4], rng.randint(3, cfg.vocab_size, (12,))])
        engine = _engine(model, params, num_slots=1, page_size=4,
                         prefill_chunks=(4, 16))
        engine.submit(a, max_new_tokens=2, seed=0)
        engine.run()
        # b shares a's first page (4 tokens cached) but cold-plans as ONE
        # 16 chunk vs a three-4-chunk tail -> the hit must be declined
        r2 = engine.submit(b, max_new_tokens=2, seed=1)
        engine.run()
        ref = np.asarray(generate(model, params, b[None], max_new_tokens=2,
                                  rng=jax.random.PRNGKey(1))[0])
        np.testing.assert_array_equal(r2.result(), ref)
        assert r2.prefix_hit == 0
        assert engine.prefill_chunks_skipped == 0
        assert engine.metrics()["serving/prefix_hit_ratio"] == 0.0

    def test_longer_prompt_extends_partial_prefix(self, served_model):
        """A prompt extending a cached one past its partial tail page hits
        the full-length entry; the boundary page is forked (COW), and both
        requests' outputs stay exact."""
        model, cfg, params, prompts = served_model
        base = prompts[2]  # len 12: partial page [8:12)
        longer = np.concatenate([base, prompts[0]])  # len 17, same first 12
        refs = _refs(model, params, [base, longer], 4)
        engine = _engine(model, params, num_slots=1)
        r1 = engine.submit(base, max_new_tokens=4, seed=0)
        engine.run()
        r2 = engine.submit(longer, max_new_tokens=4, seed=1)
        engine.run()
        np.testing.assert_array_equal(r1.result(), refs[0])
        np.testing.assert_array_equal(r2.result(), refs[1])
        assert r2.prefix_hit == 12  # the partial (non-aligned) entry
        assert engine.page_forks >= 1


class TestCopyOnWrite:
    def test_shared_page_mutation_forks_not_corrupts(self, served_model):
        """Two slots share cached prefix pages and decode concurrently:
        the first divergent write forks, so each slot's tokens — and a
        later request reading the pristine cached page — stay
        bit-identical to their sequential refs."""
        model, cfg, params, prompts = served_model
        p = prompts[2]
        engine = _engine(model, params, num_slots=2)
        warm = engine.submit(p, max_new_tokens=2, seed=9)
        engine.run()  # populate the prefix cache; warm's own decode then
        # wrote into its cached partial page -> that write MUST have forked
        assert engine.page_forks >= 1
        # both decode from the same shared pages, different seeds diverge
        r_a = engine.submit(p, max_new_tokens=6, seed=4)
        r_b = engine.submit(p, max_new_tokens=6, seed=5)
        engine.run()
        ref_a = np.asarray(generate(model, params, p[None], max_new_tokens=6,
                                    rng=jax.random.PRNGKey(4))[0])
        ref_b = np.asarray(generate(model, params, p[None], max_new_tokens=6,
                                    rng=jax.random.PRNGKey(5))[0])
        np.testing.assert_array_equal(r_a.result(), ref_a)
        np.testing.assert_array_equal(r_b.result(), ref_b)
        assert r_a.prefix_hit > 0 and r_b.prefix_hit > 0
        # the cached copy stayed pristine through every mutation
        r_c = engine.submit(p, max_new_tokens=6, seed=4)
        engine.run()
        np.testing.assert_array_equal(r_c.result(), ref_a)
        assert r_c.prefix_hit > 0


class TestFreeList:
    def test_no_leak_across_100_admit_evict_cycles(self, served_model):
        """Page accounting survives churn: after every request retires,
        pages_in_use returns to 0 (prefix cache off) and the free list is
        byte-for-byte the size it started at."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=2, prefix_cache=False)
        free0 = engine._allocator.free_count
        rng = np.random.RandomState(1)
        for i in range(100):
            p = rng.randint(3, cfg.vocab_size, (2 + (i % 11),))
            engine.submit(p, max_new_tokens=1, seed=i)
            if i % 4 == 3:
                engine.run()
        engine.run()
        assert engine.requests_completed == 100
        assert engine._allocator.in_use == 0
        assert engine._allocator.free_count == free0
        assert engine.metrics()["serving/pages_in_use"] == 0

    def test_prefix_cache_eviction_under_pressure(self, served_model):
        """When the allocator runs dry, LRU prefix entries are evicted to
        free pages instead of failing the admission."""
        model, cfg, params, prompts = served_model
        # 1 slot x 8 pages/slot + parking + 3 spare: cached prompts must be
        # evicted once fresh admissions need their pages back
        engine = _engine(model, params, num_slots=1, num_pages=12)
        rng = np.random.RandomState(2)
        for i in range(6):
            p = rng.randint(3, cfg.vocab_size, (12,))
            engine.submit(p, max_new_tokens=2, seed=i)
            engine.run()
        assert engine.requests_completed == 6
        assert engine._allocator.in_use <= engine.num_pages - 1


class TestPagedRecompileInvariant:
    def test_zero_compiles_across_hits_and_forks(self, served_model):
        """After warmup(), admissions at fresh lengths, prefix hits and COW
        forks are ALL pure data changes: the compile counters must not
        move."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=3)
        # steady IMMEDIATELY after warmup: the invariant is deterministic,
        # not a function of what warm traffic happened to absorb first
        engine.warmup()
        engine.mark_steady()
        engine.generate_batched(prompts[:3], max_new_tokens=6)
        rng = np.random.RandomState(3)
        reqs = [
            engine.submit(rng.randint(3, cfg.vocab_size, (n,)),
                          max_new_tokens=m, seed=n)
            for n, m in [(6, 3), (11, 6), (2, 5), (7, 2)]
        ]
        reqs.append(engine.submit(prompts[2], max_new_tokens=4, seed=9))  # hit
        engine.run()
        assert all(r.done for r in reqs)
        assert engine.page_forks >= 1
        assert engine._prefix.hits >= 1
        assert engine.admission_recompiles == 0
        assert engine.metrics()["serving/admission_recompiles"] == 0


class TestPagedTelemetry:
    def test_gauges_records_and_exposition(self, served_model, tmp_path):
        """The new gauges ride the session rollup and the Prometheus
        exposition; request records carry the paged attribution
        fields and the trace CLI aggregates them."""
        import json as json_mod

        from accelerate_tpu.commands.trace import load_requests, summarize_requests
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession
        from accelerate_tpu.telemetry.exporter import prometheus_text

        model, cfg, params, prompts = served_model
        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), watchdog=False, flight_hooks=False,
        ))
        try:
            engine = _engine(model, params, num_slots=2, telemetry=session)
            p = prompts[2]
            for seed in (0, 1):
                engine.submit(p, max_new_tokens=3, seed=seed)
            engine.run()
            rollup = session.rollup()
            for key in ("serving/prefix_hit_ratio", "serving/pages_in_use",
                        "serving/page_forks"):
                assert key in rollup, key
            assert rollup["serving/prefix_hit_ratio"] == 0.5
            text = prometheus_text(session)
            for name in ("att_serving_prefix_hit_ratio",
                         "att_serving_pages_in_use"):
                assert name in text, name

            recs = [json_mod.loads(l)
                    for l in open(tmp_path / "requests-host0.jsonl")]
            assert len(recs) == 2
            by_hit = sorted(recs, key=lambda r: r["prefix_hit"])
            assert by_hit[0]["prefix_hit"] == 0 and by_hit[1]["prefix_hit"] == 8
            for rec in recs:
                assert rec["pages_allocated"] >= 1
            agg = summarize_requests(load_requests(str(tmp_path)))
            assert agg["prefix_hit_requests"] == 1
            assert agg["prefix_hit_ratio"] == 0.5
            assert agg["pages_allocated"] >= 2
        finally:
            session.close()


class TestPagedDecodeKernelServing:
    """The pallas paged decode-attention kernel wired through the serving
    engine (interpret mode on CPU; the compiled TPU path differs only by
    the `interpret` flag). Contracts: serving output stays token-exact vs
    sequential generate() with the kernel ON (both sides kernelized:
    sequential decode rides the dense-arena kernel), the post-steady
    recompile count stays 0, and the kernel shows up as its own dynamic
    roofline row in the CostRegistry/rollup."""

    @pytest.fixture(scope="class")
    def kernel_model(self, served_model):
        import dataclasses

        model, cfg, params, prompts = served_model
        kcfg = dataclasses.replace(cfg, decode_kernel="interpret")
        return model.clone(config=kcfg), kcfg, params, prompts

    def _kengine(self, model, params, **kw):
        # prefill chunks above the kernel's decode-width bound: prefill
        # stays on the (reference) dense path, decode runs the kernel
        kw.setdefault("prefill_chunks", (32,))
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_cache_len", 64)
        kw.setdefault("page_size", PS)
        return ServingEngine(model, params, **kw)

    def _krefs(self, model, params, prompts, max_new, **gen_kw):
        return [
            np.asarray(
                generate(model, params, p[None], max_new_tokens=max_new,
                         rng=jax.random.PRNGKey(i), **gen_kw)[0]
            )
            for i, p in enumerate(prompts)
        ]

    def test_greedy_token_exact_and_zero_recompiles(self, kernel_model):
        model, cfg, params, prompts = kernel_model
        refs = self._krefs(model, params, prompts, 6)
        engine = self._kengine(model, params)
        engine.warmup()
        engine.mark_steady()
        outs = engine.generate_batched(prompts, max_new_tokens=6)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)
        assert engine.admission_recompiles == 0
        assert engine.metrics()["serving/decode_kernel_active"] is True

    def test_sampled_token_exact(self, kernel_model):
        model, cfg, params, prompts = kernel_model
        refs = self._krefs(model, params, prompts, 6, temperature=1.0, top_k=8)
        engine = self._kengine(model, params, temperature=1.0, top_k=8)
        outs = engine.generate_batched(prompts, max_new_tokens=6)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_free_and_mid_admission_slots_are_skipped_whole(self, kernel_model):
        """Decode steps run while one slot is free and another is in the
        middle of a ragged admission of several dispatches: both are handed
        to the kernel with no live tokens (their parked write position is
        the end of the cache). Tokens equal the dense decode path's, and
        ``serving/decode_grow`` says what the kernel was handed."""
        import dataclasses

        from accelerate_tpu.telemetry import spans

        model, cfg, params, prompts = kernel_model
        rng = np.random.RandomState(5)
        short, long_ = prompts[0], rng.randint(3, cfg.vocab_size, (21,))

        def serve(decode_kernel):
            kcfg = dataclasses.replace(cfg, decode_kernel=decode_kernel, prefill_kernel="interpret")
            engine = self._kengine(model.clone(config=kcfg), params, num_slots=3, prefill_chunks=(8,))
            first = engine.submit(short, max_new_tokens=12, seed=0)
            engine.step()  # admitted and decoding before the long prompt arrives
            second = engine.submit(long_, max_new_tokens=4, seed=1)
            mark = max(s[0] for s in spans.snapshot())  # the ring may be full: go by id
            engine.run()
            grows = [s[5] for s in spans.snapshot() if s[0] > mark and s[2] == "serving/decode_grow"]
            return engine, [first.result(), second.result()], grows

        engine, outs, grows = serve("interpret")
        assert engine.metrics()["serving/decode_kernel_active"] is True
        for out, ref in zip(outs, serve("dense")[1]):
            np.testing.assert_array_equal(out, ref)
        block = engine._walk_block_pages * PS
        assert block == 8 * PS  # the whole table of 64 / 8 entries: one block a live slot
        # the long prompt takes three dispatches of 8 rows, the last one
        # in the iteration whose decode step already carries it: two decode
        # steps of the first request alone (2 of 3 slots skipped), then
        # both live until the long one's four tokens are out
        assert [g["skipped_slots"] for g in grows[:6]] == [2, 2, 1, 1, 1, 2]
        for g in grows:
            live = 3 - g["skipped_slots"]
            assert g["walked_blocks"] == live
            assert live * PS <= g["walked_tokens"] <= live * block


@pytest.mark.slow
class TestPagedBurstIntegration:
    def test_long_mixed_burst_exact_and_leak_free(self, served_model):
        """The long haul: dozens of requests through few slots with a mix
        of prefix hits, forks, eos finishes and staggered
        lengths — every output token-exact, zero recompiles post-warmup,
        and page accounting clean at the end."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=3,
                         temperature=1.0, top_k=8)
        engine.warmup()
        engine.generate_batched(prompts[:2], max_new_tokens=4)
        engine.mark_steady()
        rng = np.random.RandomState(11)
        cases = []
        for i in range(24):
            if i % 3 == 0:
                p = prompts[2]  # recurring template -> prefix hits
            else:
                p = rng.randint(3, cfg.vocab_size, (2 + (i * 5) % 13,))
            cases.append((p, 2 + i % 5, 100 + i))
        reqs = [engine.submit(p, max_new_tokens=m, seed=s) for p, m, s in cases]
        engine.run()
        assert engine.admission_recompiles == 0
        for req, (p, m, s) in zip(reqs, cases):
            ref = np.asarray(
                generate(model, params, p[None], max_new_tokens=m,
                         temperature=1.0, top_k=8, rng=jax.random.PRNGKey(s))[0]
            )
            np.testing.assert_array_equal(req.result(), ref)
        assert engine._prefix.hits >= 6
        # only prefix-cache refs remain; clearing them drains the arena
        engine._prefix.clear()
        assert engine._allocator.in_use == 0
