"""Quantized KV-cache arena (DecoderConfig.kv_cache_dtype int8/int4):
op-level kernel-fused dequant contracts, serving-path exactness, the
drift harness's quality bounds, and the no-re-quantization invariants.

The contracts of record:
- the quantized decode kernels (paged + dense-arena, pallas interpreter)
  match the gathered masked-dense reference at the PR 8 tolerance, and
  the reference itself is BIT-identical across the gather/dense ops on
  identical quantized inputs — dequant is one op sequence
  (utils.quantization.dequantize_kv), owned once;
- int8/int4 storage changes bytes, not programs: flat and paged int8
  engines are token-exact twins, and a warmed int8 engine triggers ZERO
  compiles across admissions, prefix hits, CoW forks and
  preempt→resume;
- preemption page-out/resume and prefix-cache hits move the QUANTIZED
  payload + scales verbatim — outputs equal the uninterrupted / cold
  quantized run bit-for-bit (no double-quantization drift);
- the drift harness (serving/drift.py) bounds the quality cost on fixed
  seeds: int8 greedy token-match >= 0.98 (the bench-asserted bound),
  sampled >= 0.85, and teacher-forced logit error stays at the
  storage-precision scale (int8 ~1e-4 relative, int4 < 5%).
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu.generation import generate
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.ops.attention import (
    decode_attention,
    gather_kv_pages,
    paged_decode_attention,
)
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.utils.quantization import (
    dequantize_kv,
    kv_cache_bits,
    quantize_kv,
    unpack_int4_kv,
)

ATOL = 2e-5  # fp32 interpreter vs XLA softmax: reassociation-level noise
PS = 8


@pytest.fixture(scope="module")
def served_model():
    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, cfg.vocab_size, (n,)) for n in (5, 8, 12, 3)]
    return model, cfg, params, prompts


def _engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("prefill_chunks", (4, 8))
    kw.setdefault("page_size", PS)
    engine = ServingEngine(model, params, **kw)
    engine.telemetry = None
    return engine


class TestKvQuantOps:
    def test_roundtrip_error_bounds_and_shapes(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.standard_normal((3, 5, 2, 16)), jnp.float32)
        for bits, bound in ((8, 0.01), (4, 0.15)):
            q, s = quantize_kv(x, bits)
            assert q.dtype == jnp.int8
            assert q.shape == (3, 5, 2, 16 if bits == 8 else 8)
            assert s.shape == (3, 5, 2, 1) and s.dtype == jnp.float32
            back = dequantize_kv(q, s, bits, jnp.float32)
            rel = float(jnp.max(jnp.abs(back - x))) / float(jnp.max(jnp.abs(x)))
            assert rel < bound, (bits, rel)

    def test_zero_rows_roundtrip_exact_and_int4_pack(self):
        z = jnp.zeros((2, 6))
        q, s = quantize_kv(z, 8)
        assert float(jnp.max(jnp.abs(dequantize_kv(q, s, 8, jnp.float32)))) == 0.0
        np.testing.assert_array_equal(np.asarray(s), 1.0)  # exact round trip
        # int4 pack/unpack is lossless on representable values
        vals = jnp.asarray([[-7, -1, 0, 3, 7, -5]], jnp.float32)
        q4, s4 = quantize_kv(vals, 4)
        assert q4.shape == (1, 3)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4_kv(q4)), np.asarray(vals, np.int8)
        )
        with pytest.raises(ValueError, match="even head_dim"):
            quantize_kv(jnp.zeros((2, 5)), 4)
        with pytest.raises(ValueError, match="8 or 4"):
            quantize_kv(jnp.zeros((2, 4)), 16)

    def _paged_setup(self, rng, bits, b=3, h=4, kvh=2, d=16, ps=PS, per_slot=4):
        num_pages = 1 + b * per_slot
        q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
        kf = jnp.asarray(rng.standard_normal((num_pages, kvh, ps, d)), jnp.float32)
        vf = jnp.asarray(rng.standard_normal((num_pages, kvh, ps, d)), jnp.float32)
        kq, ks = quantize_kv(kf, bits)
        vq, vs = quantize_kv(vf, bits)
        table = jnp.asarray(
            1 + np.arange(b * per_slot).reshape(b, per_slot), jnp.int32
        )
        return q, (kq, ks), (vq, vs), table

    @pytest.mark.parametrize("bits", [8, 4])
    def test_paged_kernel_fused_dequant_matches_oracle(self, bits):
        """Interpret-mode kernel (in-register dequant) vs the gathered
        masked-dense reference across ragged frontiers — and the
        reference's two spellings (paged fallback vs dense op on the
        dequantized gather) agree BIT-identically on identical quantized
        inputs."""
        rng = np.random.RandomState(1)
        q, (kq, ks), (vq, vs), table = self._paged_setup(rng, bits)
        for pos_list in ([0, 0, 0], [1, PS - 1, PS], [3, 2 * PS + 5, 4 * PS - 1]):
            pos = jnp.asarray(pos_list, jnp.int32)[:, None]
            kw = dict(page_table=table, q_positions=pos,
                      k_scale=ks, v_scale=vs, kv_quant_bits=bits)
            out = paged_decode_attention(q, kq, vq, impl="interpret", **kw)
            ref = paged_decode_attention(q, kq, vq, impl="dense", **kw)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=ATOL, rtol=1e-5,
                err_msg=f"bits {bits} positions {pos_list}",
            )
            # the oracle is bit-exact across its spellings: gather+dequant
            # is pure data movement + ONE shared dequant op sequence
            k_full = dequantize_kv(
                gather_kv_pages(kq, table), gather_kv_pages(ks, table),
                bits, q.dtype,
            )
            v_full = dequantize_kv(
                gather_kv_pages(vq, table), gather_kv_pages(vs, table),
                bits, q.dtype,
            )
            ref2 = decode_attention(q, k_full, v_full, q_positions=pos,
                                    impl="dense")
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref2))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_dense_arena_kernel_fused_dequant(self, bits):
        rng = np.random.RandomState(2)
        b, h, kvh, d, L = 3, 4, 2, 16, 32
        q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, kvh, L, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, kvh, L, d)), jnp.float32)
        kq, ks = quantize_kv(k, bits)
        vq, vs = quantize_kv(v, bits)
        pos = jnp.asarray([[0], [13], [31]], jnp.int32)
        kw = dict(q_positions=pos, k_scale=ks, v_scale=vs, kv_quant_bits=bits)
        ref = decode_attention(q, kq, vq, impl="dense", **kw)
        for blk in (4, 8, 16):
            out = decode_attention(q, kq, vq, impl="interpret",
                                   block_kv=blk, **kw)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=ATOL, rtol=1e-5,
                                       err_msg=f"bits {bits} block {blk}")

    def test_parked_page_garbage_unobservable_quantized(self):
        """Payload AND scale garbage in parked/unallocated pages cannot
        perturb any slot — the mask zeroes them before the dequantized
        values ever weigh in."""
        rng = np.random.RandomState(3)
        q, (kq, ks), (vq, vs), table = self._paged_setup(rng, 8)
        table = jnp.asarray(np.array(table).copy()).at[:, 2:].set(0)
        pos = jnp.asarray([[5], [9], [15]], jnp.int32)
        kw = dict(page_table=table, q_positions=pos, kv_quant_bits=8)
        clean = paged_decode_attention(
            q, kq, vq, impl="interpret", k_scale=ks, v_scale=vs, **kw)
        garbage = paged_decode_attention(
            q,
            kq.at[0].set(127), vq.at[0].set(-127), impl="interpret",
            k_scale=ks.at[0].set(1e6), v_scale=vs.at[0].set(-1e6), **kw)
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(garbage))

    def test_scale_args_required(self):
        q = jnp.zeros((1, 2, 1, 8))
        k = jnp.zeros((1, 1, 16, 8), jnp.int8)
        with pytest.raises(ValueError, match="k_scale and v_scale"):
            decode_attention(q, k, k, q_positions=jnp.zeros((1, 1), jnp.int32),
                             kv_quant_bits=8)


class TestKvQuantHostHelpers:
    """The jax-free capacity-math helpers in serving/pages.py (a router
    tier sizes arenas with these; the import lock is in test_imports)."""

    def test_bits_and_widths(self):
        from accelerate_tpu.serving import pages

        assert pages.kv_cache_bits(None) == pages.kv_cache_bits("bf16") == 16
        assert pages.kv_cache_bits("int8") == 8
        assert pages.kv_cache_bits("int4") == 4
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            pages.kv_cache_bits("fp8")
        assert pages.kv_payload_width(64, "int8") == 64
        assert pages.kv_payload_width(64, "int4") == 32
        with pytest.raises(ValueError, match="even head_dim"):
            pages.kv_payload_width(15, "int4")
        # the two spellings (host tier vs jax tier) agree
        for dt in (None, "bf16", "int8", "int4"):
            assert pages.kv_cache_bits(dt) == kv_cache_bits(dt)

    def test_token_bytes_matches_real_arena(self, served_model):
        """kv_token_bytes (the planning number) equals the bytes the real
        arena allocates per token slot — drift here would skew every
        capacity decision the router makes."""
        from accelerate_tpu.serving.pages import kv_token_bytes, paged_leaves

        model, cfg, params, prompts = served_model
        for kvq in ("bf16", "int8", "int4"):
            engine = _engine(model, params, kv_cache_dtype=kvq)
            predicted = kv_token_bytes(
                cfg.num_kv_heads, cfg.head_dim, kvq,
                cache_itemsize=jnp.dtype(cfg.dtype).itemsize,
                num_layers=cfg.num_layers,
            )
            kv_bytes = sum(  # cache_index bookkeeping scalars excluded
                int(l.nbytes) for l in paged_leaves(engine._arena)
            )
            actual = kv_bytes / (engine.num_pages * engine.page_size)
            assert predicted == actual, (kvq, predicted, actual)
            del engine


class TestKvQuantServing:
    def test_paged_int8_token_exact_with_generate(self, served_model):
        """The paged int8 engine against ``generate()`` on an int8 config:
        both quantize a row once, at its write, and read the stored cache."""
        model, cfg, params, prompts = served_model
        paged = _engine(model, params, kv_cache_dtype="int8")
        single = model.clone(config=dataclasses.replace(cfg, kv_cache_dtype="int8"))
        outs = paged.generate_batched(prompts, max_new_tokens=6)
        for i, (p, out) in enumerate(zip(prompts, outs)):
            ref = generate(single, params, p[None], max_new_tokens=6,
                           rng=jax.random.PRNGKey(i))[0]
            np.testing.assert_array_equal(out, np.asarray(ref))
        assert paged.metrics()["serving/kv_cache_bits"] == 8

    def test_arena_shrinks_with_bits(self, served_model):
        model, cfg, params, prompts = served_model
        sizes = {}
        for kvq in ("bf16", "int8", "int4"):
            engine = _engine(model, params, kv_cache_dtype=kvq)
            sizes[kvq] = engine.arena_bytes
            del engine
        # the >=1.8x slots-per-chip contract, at arena-byte granularity
        assert sizes["bf16"] / sizes["int8"] >= 1.8, sizes
        assert sizes["int8"] / sizes["int4"] >= 1.3, sizes

    def test_drift_harness_int8_greedy_bounds(self, served_model):
        from accelerate_tpu.serving import kv_quant_drift

        model, cfg, params, prompts = served_model
        r = kv_quant_drift(model, params, prompts, kv_cache_dtype="int8",
                           max_new_tokens=6, page_size=PS, max_cache_len=64)
        assert r["kv_cache_bits"] == 8
        assert r["tokens_compared"] == 4 * 6
        # the bench-asserted shippable bound, on fixed seeds
        assert r["token_match_rate"] >= 0.98, r
        assert r["logit_rel_err"] < 1e-3, r
        assert r["arena_bytes_ratio"] >= 1.8

    def test_drift_harness_int8_sampled_bound(self, served_model):
        from accelerate_tpu.serving import kv_quant_drift

        model, cfg, params, prompts = served_model
        r = kv_quant_drift(model, params, prompts, kv_cache_dtype="int8",
                           max_new_tokens=6, page_size=PS, max_cache_len=64,
                           temperature=1.0, top_k=8)
        assert r["token_match_rate"] >= 0.85, r

    def test_drift_harness_int4_bounds(self, served_model):
        from accelerate_tpu.serving import kv_quant_drift

        model, cfg, params, prompts = served_model
        r = kv_quant_drift(model, params, prompts, kv_cache_dtype="int4",
                           max_new_tokens=6, page_size=PS, max_cache_len=64)
        # int4 trades quality for another ~2x capacity: on a random tiny
        # model the greedy cascade bites early, so the hard bound lives on
        # the cascade-free teacher-forced logit error; the match rate just
        # has to stay far from noise (1/vocab)
        assert r["logit_rel_err"] < 0.05, r
        assert r["token_match_rate"] >= 0.5, r
        assert r["arena_bytes_ratio"] >= 3.0

    def test_prefix_hit_round_trips_quantized_payload(self, served_model):
        """A prefix-cache hit maps the QUANTIZED pages + scales verbatim:
        the hit stream equals the cold quantized stream bit-for-bit — if
        anything re-quantized the shared prefix, greedy tokens would
        drift."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1, kv_cache_dtype="int8")
        p = prompts[2]
        cold = engine.submit(p, max_new_tokens=6, seed=0)
        engine.run()
        hit = engine.submit(p, max_new_tokens=6, seed=0)
        engine.run()
        assert hit.prefix_hit >= PS
        np.testing.assert_array_equal(cold.result(), hit.result())

    def test_preempt_resume_no_requant_drift(self, served_model):
        """Preempt → page out → resume on the int8 arena equals the
        UNINTERRUPTED int8 run token-for-token: page-out publishes the
        quantized payload+scales and the resume replay re-quantizes the
        same fresh values to the same bytes — nothing dequantizes and
        re-quantizes."""
        from accelerate_tpu.serving import SchedulerConfig

        model, cfg, params, prompts = served_model
        # uninterrupted int8 references
        ref_engine = _engine(model, params, num_slots=2, kv_cache_dtype="int8")
        refs = ref_engine.generate_batched(
            [prompts[1], prompts[0]], max_new_tokens=10, seeds=[3, 7]
        )
        del ref_engine
        engine = _engine(model, params, num_slots=1, kv_cache_dtype="int8",
                         scheduler=SchedulerConfig())
        low = engine.submit(prompts[1], max_new_tokens=10, seed=3, priority=0)
        while len(low.tokens) < 3 and not low.done:
            engine.step()
        high = engine.submit(prompts[0], max_new_tokens=10, seed=7, priority=5)
        engine.run()
        assert engine.preemptions == 1 and engine.resumptions == 1
        assert low.preemptions == 1 and low.outcome == "finished"
        np.testing.assert_array_equal(low.result(), refs[0])
        np.testing.assert_array_equal(high.result(), refs[1])

    def test_zero_compiles_across_quantized_everything(self, served_model):
        """The acceptance invariant: warmup + mark_steady on an int8
        engine, then admissions at fresh lengths, prefix hits and CoW
        forks — 0 compiles."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=3, kv_cache_dtype="int8")
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

    def test_single_stream_generate_quantized(self, served_model):
        """generate() on a kv_cache_dtype config runs the quantized dense
        arena (prefill + scalar-index decode) end to end."""
        model, cfg, params, prompts = served_model
        qcfg = dataclasses.replace(cfg, kv_cache_dtype="int8", max_cache_len=32)
        out = generate(DecoderLM(qcfg), params, prompts[0][None],
                       max_new_tokens=6)
        assert np.asarray(out).shape == (1, prompts[0].size + 6)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            DecoderConfig.tiny(kv_cache_dtype="fp8")
        with pytest.raises(ValueError, match="even"):
            DecoderConfig.tiny(embed_dim=60, num_heads=2, head_dim=15,
                               kv_cache_dtype="int4")


class TestKvQuantReportDiff:
    def test_diff_sentry_guards_new_rows(self, tmp_path):
        """`accelerate-tpu report --diff` flattens the new bench rows
        (arena_hbm_bytes_per_slot_int8, kv_quant_token_match_rate,
        decode_int8_kv_tokens_per_sec) and flags regressions — the CI
        sentry contract for KV-quant capacity AND quality from r06 on."""
        from accelerate_tpu.commands.report import (
            collect_diff_metrics,
            diff_metrics,
        )

        def bench(path, match, bytes_, tps):
            payload = {"parsed": {
                "metric": "decoder_train_mfu", "value": 50.0,
                "extra": {
                    "kv_quant_token_match_rate": match,
                    "arena_hbm_bytes_per_slot_int8": bytes_,
                    "decode_int8_kv_tokens_per_sec": tps,
                    "serving_kv_quant": {"kv_quant_logit_mse_int8": 2e-6},
                },
            }}
            path.write_text(json.dumps(payload))
            return str(path)

        a = collect_diff_metrics(bench(tmp_path / "BENCH_r05.json", 0.99, 10000, 500.0))
        b = collect_diff_metrics(bench(tmp_path / "BENCH_r06.json", 0.70, 21000, 480.0))
        for key in ("kv_quant_token_match_rate",
                    "arena_hbm_bytes_per_slot_int8",
                    "decode_int8_kv_tokens_per_sec",
                    "serving_kv_quant.kv_quant_logit_mse_int8"):
            assert key in a and key in b, key
        diff = diff_metrics(a, b, threshold=0.1)
        flagged = {r["metric"] for r in diff["flagged"]}
        assert "kv_quant_token_match_rate" in flagged       # quality drop
        assert "arena_hbm_bytes_per_slot_int8" in flagged   # capacity move
        assert "decode_int8_kv_tokens_per_sec" not in flagged  # 4% is noise
