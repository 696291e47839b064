"""Continuous-batching serving engine (accelerate_tpu/serving/).

The contracts of record:
- batched decode is TOKEN-EXACT vs. sequential single-request generate()
  for the same per-request seeds (greedy and sampled);
- a prompt admitted over several packed dispatches == one dispatch
  (same tokens, any grid capacity);
- slot admission/eviction reuses slots with no cache clearing and no
  cross-request contamination;
- a warmed engine triggers ZERO compiles across staggered admissions at
  varying prompt lengths (the jax.monitoring counters are the witness).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import generate
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import ServingEngine, generate_batched


@pytest.fixture(scope="module")
def served_model():
    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, cfg.vocab_size, (n,)) for n in (5, 8, 12, 3)]
    return model, cfg, params, prompts


# sequential single-stream references, memoized module-wide: every ref set
# costs ~2-3 s of generate() trace/compile on the 1-core sim and several
# tests compare against the same (temperature, top_k) stream. Greedy AND
# sampled decode chains are prefix-stable (the per-step rng split does not
# depend on loop length), so tests needing fewer tokens slice these.
_REF_CACHE: dict = {}
_REF_NEW = 6  # generated tokens in every cached ref set


def _refs(model, params, prompts, max_new, temperature=0.0, top_k=None):
    assert max_new <= _REF_NEW
    out = []
    for i, p in enumerate(prompts):  # prompt i always pairs with seed i
        key = (temperature, top_k, i)
        if key not in _REF_CACHE:
            _REF_CACHE[key] = np.asarray(
                generate(
                    model, params, p[None], max_new_tokens=_REF_NEW,
                    temperature=temperature, top_k=top_k, rng=jax.random.PRNGKey(i),
                )[0]
            )
        out.append(_REF_CACHE[key][: p.size + max_new])
    return out


class TestBatchedParity:
    def test_greedy_matches_sequential_generate(self, served_model):
        """More requests than slots, chunked prefill, slot reuse — still
        token-for-token the sequential generate() output."""
        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts, 6)
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=64, prefill_chunks=(4, 8)
        )
        outs = engine.generate_batched(prompts, max_new_tokens=6)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_sampled_matches_sequential_generate(self, served_model):
        """Per-slot RNG chains split exactly like the single-stream loop's,
        so even temperature/top_k sampling reproduces the same tokens."""
        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts, 6, temperature=1.0, top_k=8)
        engine = ServingEngine(
            model, params, num_slots=4, max_cache_len=64, prefill_chunks=(4, 8),
            temperature=1.0, top_k=8,
        )
        outs = engine.generate_batched(prompts, max_new_tokens=6)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_prompt_over_several_packed_dispatches_matches_generate(self, served_model):
        """A prompt longer than the largest grid capacity, admitted over
        several packed dispatches (the last one padded), yields the same
        tokens as one dispatch that holds it whole, and as generate()."""
        model, cfg, params, prompts = served_model
        p = prompts[2]  # len 12: a grid of 16 rows holds it, one of 8 takes 8 + 4
        ref = _refs(model, params, prompts, 5)[2]
        for chunks, dispatches in [((16,), 1), ((4,), 2), ((8,), 2)]:
            engine = ServingEngine(
                model, params, num_slots=1, max_cache_len=64, prefill_chunks=chunks
            )
            req = engine.submit(p, max_new_tokens=5, seed=2)
            engine.run()
            assert req.prefill_dispatches == dispatches, chunks
            np.testing.assert_array_equal(req.result(), ref)

    def test_from_dispatched_offloaded(self, served_model):
        """Serving over a DispatchedModel: the in-graph placement transform
        rides inside the fused step, tokens still match plain params."""
        from accelerate_tpu.big_modeling import cpu_offload

        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts[:2], 4)
        engine = ServingEngine.from_dispatched(
            cpu_offload(model, params), num_slots=2, max_cache_len=64,
            prefill_chunks=(8,),
        )
        outs = engine.generate_batched(prompts[:2], max_new_tokens=4)
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)

    def test_generate_batched_helper(self, served_model):
        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts, 6)
        outs = generate_batched(
            model, params, prompts, max_new_tokens=6, max_cache_len=64,
            prefill_chunks=(8,),
        )
        for out, ref in zip(outs, refs):
            np.testing.assert_array_equal(out, ref)


class TestSlotLifecycle:
    def test_admission_eviction_reuse(self, served_model):
        """Two waves through few slots: every slot is reused without any
        cache clearing, and late requests still match their references."""
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=64, prefill_chunks=(8,)
        )
        wave1 = [engine.submit(p, max_new_tokens=3, seed=i) for i, p in enumerate(prompts)]
        engine.run()
        assert all(r.done for r in wave1)
        assert len(engine._free) == 2 and not engine._slot_req
        rng = np.random.RandomState(7)
        more = [rng.randint(3, cfg.vocab_size, (n,)) for n in (6, 10)]
        wave2 = [engine.submit(p, max_new_tokens=4, seed=40 + i) for i, p in enumerate(more)]
        engine.run()
        for i, (req, p) in enumerate(zip(wave2, more)):
            ref = np.asarray(
                generate(model, params, p[None], max_new_tokens=4,
                         rng=jax.random.PRNGKey(40 + i))[0]
            )
            np.testing.assert_array_equal(req.result(), ref)
        assert engine.requests_completed == 6

    def test_streaming_callback_and_request_state(self, served_model):
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=1, max_cache_len=64, prefill_chunks=(8,)
        )
        seen = []
        req = engine.submit(
            prompts[0], max_new_tokens=5,
            on_token=lambda tok, r: seen.append((tok, r.id)),
        )
        assert not req.done
        engine.run()
        assert req.done and len(req.tokens) == 5
        assert seen == [(t, req.id) for t in req.tokens]
        assert req.result().shape == (prompts[0].size + 5,)
        assert req.first_token_t is not None and req.finish_t is not None

    def test_eos_frees_slot_early(self, served_model):
        model, cfg, params, prompts = served_model
        ref = _refs(model, params, prompts, 6)[0]
        eos = int(ref[prompts[0].size + 2])  # third generated token
        engine = ServingEngine(
            model, params, num_slots=1, max_cache_len=64, prefill_chunks=(8,),
            eos_token_id=eos,
        )
        req = engine.submit(prompts[0], max_new_tokens=8, seed=0)
        engine.run()
        assert req.done and req.tokens[-1] == eos and len(req.tokens) == 3
        assert len(engine._free) == 1

    @pytest.mark.parametrize("new_tokens,fits", [(12, True), (13, False)])
    def test_capacity_guard(self, served_model, new_tokens, fits):
        """A slot holds its prompt and every token it may generate, to the
        last position and no further (no head-room is kept beyond them)."""
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=1, max_cache_len=32, prefill_chunks=(8,)
        )
        if fits:
            req = engine.submit(np.zeros(20, np.int32), max_new_tokens=new_tokens)
            engine.run()
            assert req.outcome == "finished" and len(req.tokens) == new_tokens
        else:
            with pytest.raises(ValueError, match="capacity"):
                engine.submit(np.zeros(20, np.int32), max_new_tokens=new_tokens)

    @pytest.mark.parametrize("option", [dict(steps_per_call=2), dict(spec_draft_len=2), dict(drafter=object())],
                             ids=lambda o: next(iter(o)))
    def test_there_is_one_decode_program_and_no_option_for_another(self, served_model, option):
        model, cfg, params, prompts = served_model
        with pytest.raises(TypeError, match=next(iter(option))):
            ServingEngine(model, params, num_slots=1, max_cache_len=32, **option)


class TestRecompileInvariant:
    def test_zero_compiles_across_staggered_admissions(self, served_model):
        """After warmup(), admissions/evictions at prompt lengths never
        seen before trigger NO compile activity — the property that makes
        continuous batching production-viable on XLA."""
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=3, max_cache_len=64, prefill_chunks=(4, 8),
        )
        engine.warmup()
        # one traffic wave through every code path (admission, eviction,
        # slot reuse), then freeze the program set
        engine.generate_batched(prompts[:3], max_new_tokens=6)
        engine.mark_steady()
        rng = np.random.RandomState(3)
        reqs = [
            engine.submit(rng.randint(3, cfg.vocab_size, (n,)), max_new_tokens=m, seed=n)
            for n, m in [(6, 3), (11, 7), (2, 5), (7, 2), (15, 6), (9, 4)]
        ]
        engine.run()
        assert all(r.done for r in reqs)
        assert engine.admission_recompiles == 0
        m = engine.metrics()
        assert m["serving/admission_recompiles"] == 0
        assert m["serving/requests_completed"] == 9

    def test_warmup_alone_covers_the_program_set(self, served_model):
        """warmup() -> mark_steady() with NO traffic wave: the very first
        real admissions must still hit only compiled programs."""
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=64, prefill_chunks=(4, 8)
        )
        engine.warmup()
        engine.mark_steady()
        engine.generate_batched(prompts, max_new_tokens=4)
        assert engine.admission_recompiles == 0


class TestRequestTracing:
    """Request-level observability (accelerate_tpu/telemetry/requests.py):
    a staggered-admission burst must leave one JSONL record per request
    reconstructing its full lifecycle, SLO histogram snapshots via both
    the session rollup and the Prometheus exposition, and request-tagged
    spans in the Chrome-trace stream."""

    def test_staggered_burst_records_rollups_and_exposition(self, served_model, tmp_path):
        import json as json_mod

        from accelerate_tpu.telemetry import (
            TelemetryConfig,
            TelemetrySession,
            load_chrome_trace,
        )
        from accelerate_tpu.telemetry.exporter import prometheus_text

        model, cfg, params, prompts = served_model
        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), watchdog=False, flight_hooks=False,
        ))
        try:
            # 2 slots, 4 requests at staggered lengths -> admissions overlap
            # in-flight decodes and late requests wait in queue
            engine = ServingEngine(
                model, params, num_slots=2, max_cache_len=64,
                prefill_chunks=(4, 8), telemetry=session,
            )
            reqs = [engine.submit(p, max_new_tokens=4, seed=i)
                    for i, p in enumerate(prompts)]
            engine.serve(should_stop=lambda: all(r.done for r in reqs))

            # (a) one record per request, full lifecycle
            recs = [json_mod.loads(l)
                    for l in open(tmp_path / "requests-host0.jsonl")]
            assert len(recs) == len(prompts)
            by_id = {r["request_id"]: r for r in recs}
            for req in reqs:
                rec = by_id[req.id]
                assert rec["prompt_len"] == req.prompt.size
                assert rec["tokens"] == 4 and rec["finish_reason"] == "budget"
                assert rec["slot"] in (0, 1)
                assert rec["queue_wait_ms"] >= 0 and rec["ttft_ms"] > 0
                assert rec["total_ms"] >= rec["ttft_ms"]
                # the chunk plan covers the prompt (padded tail included)
                covered = sum(c["bucket"] for c in rec["prefill_chunks"])
                assert covered >= rec["prompt_len"]
                assert all(c["ms"] >= 0 for c in rec["prefill_chunks"])
                assert len(rec["itl_ms"]) == 3  # 4 tokens -> 3 gaps
                assert "compiles_in_flight" in rec

            # (b) SLO snapshots through the session rollup...
            rollup = session.rollup()
            for key in ("serving/ttft_p50_ms", "serving/ttft_p95_ms",
                        "serving/ttft_p99_ms", "serving/itl_p50_ms",
                        "serving/itl_p95_ms", "serving/itl_p99_ms",
                        "serving/queue_wait_p50_ms"):
                assert rollup.get(key, 0) > 0, key
            assert rollup["serving/ttft_count"] == len(prompts)
            # ...and through the Prometheus text exposition
            text = prometheus_text(session)
            assert f'att_serving_ttft_seconds_bucket{{le="+Inf"}} {len(prompts)}' in text
            for name in ("ttft", "itl", "queue_wait"):
                for q in ("p50", "p95", "p99"):
                    assert f"att_serving_{name}_seconds_{q} " in text, (name, q)

            # request-tagged spans joined the Chrome-trace stream
            session.close()
            trace = load_chrome_trace(str(tmp_path / "trace-host0.jsonl"))
            names = {e.get("name") for e in trace["traceEvents"]}
            assert {"serving/request", "serving/prefill_chunk",
                    "serving/queue_wait"} <= names
            req_spans = [e for e in trace["traceEvents"]
                         if e.get("name") == "serving/request"]
            assert {e["args"]["request_id"] for e in req_spans} == {r.id for r in reqs}

            # the trace CLI reads the same artifacts back
            from accelerate_tpu.commands.trace import (
                load_requests,
                merge_traces,
                summarize_requests,
            )

            merged = merge_traces(str(tmp_path), request_id=reqs[0].id)
            tagged = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
            assert tagged and all(
                e["args"]["request_id"] == reqs[0].id for e in tagged
            )
            agg = summarize_requests(load_requests(str(tmp_path)))
            assert agg["requests"] == len(prompts)
            assert agg["ttft_p50_ms"] > 0 and agg["itl_p99_ms"] > 0
            assert agg["finish_reasons"] == {"budget": len(prompts)}
        finally:
            session.close()

    def test_tracing_off_means_no_artifacts_and_no_hooks(self, served_model):
        """With no session the engine's tracing layer is a single attribute
        check — no tracer, no histograms, no files."""
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=1, max_cache_len=64, prefill_chunks=(8,)
        )
        assert engine.telemetry is None and engine._tracer() is None
        engine.generate_batched(prompts[:1], max_new_tokens=3)
        assert engine.requests_completed == 1

    def test_watchdog_trip_dumps_flight_bundle_naming_inflight_requests(
        self, served_model, tmp_path
    ):
        """An induced stall mid-burst must leave a flight-recorder bundle
        naming the in-flight requests, their state/slots and last spans —
        the evidence a wedged host otherwise takes with it."""
        import json as json_mod
        import time as time_mod

        from accelerate_tpu.state import PartialState
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        PartialState()  # shared-dict heartbeat state must exist
        model, cfg, params, prompts = served_model
        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), watchdog=True, watchdog_deadline_s=0.3,
            watchdog_poll_s=0.05, flight_hooks=False,
        ))
        try:
            engine = ServingEngine(
                model, params, num_slots=2, max_cache_len=64,
                prefill_chunks=(8,), telemetry=session,
            )
            r1 = engine.submit(prompts[0], max_new_tokens=48, seed=0)
            r2 = engine.submit(prompts[1], max_new_tokens=48, seed=1)
            # admit both and decode a few steps (heartbeats flow), then stall
            while len(engine._slot_req) < 2 or engine.step_count < 4:
                engine.step()
            assert not r1.done and not r2.done
            deadline = time_mod.time() + 6.0
            while session.flight.dump_count == 0 and time_mod.time() < deadline:
                time_mod.sleep(0.05)
            assert session.watchdog.stall_count >= 1
            assert session.flight.dump_count >= 1
            data = json_mod.load(open(session.flight.last_bundle_path))
            assert data["reason"] == "watchdog_stall"
            assert "STALL" in data["stall_report"]
            inflight = {r["request_id"]: r for r in data["inflight_requests"]}
            assert set(inflight) == {r1.id, r2.id}
            for rid in (r1.id, r2.id):
                assert inflight[rid]["state"] == "decode"
                assert inflight[rid]["slot"] in (0, 1)
                assert inflight[rid]["tokens"] >= 1
                assert inflight[rid]["last_event"] in ("token", "first_token")
            assert data["last_spans"], "span ring should show recent activity"
            assert "thread_stacks" in data
            # ring carries the request lifecycle events
            kinds = {e["kind"] for e in data["events"]}
            assert "request_submit" in kinds and "step" in kinds
        finally:
            session.close()


class TestTelemetryIntegration:
    def test_metrics_flow_through_session_rollup(self, served_model, tmp_path):
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        model, cfg, params, prompts = served_model
        session = TelemetrySession(
            TelemetryConfig(trace_dir=str(tmp_path), spans=False, watchdog=False)
        )
        try:
            engine = ServingEngine(
                model, params, num_slots=2, max_cache_len=64, prefill_chunks=(8,),
                telemetry=session,
            )
            engine.mark_steady()
            engine.generate_batched(prompts[:2], max_new_tokens=4)
            rollup = session.rollup()
            assert rollup["serving/requests_completed"] == 2
            assert rollup["serving/generated_tokens"] == 8
            assert "serving/tokens_per_s" in rollup
            assert "serving/itl_p50_ms" in rollup
            assert rollup["serving/slot_occupancy"] == 0.0
            # decode steps also fed the rolling window like engine steps do
            assert rollup["sys/window_steps"] >= 1
        finally:
            session.close()


    def test_each_dispatch_bills_its_program_one_call(self, served_model, tmp_path):
        """The cost registry's rows (the roofline table's walls): the decode
        step is billed one call and its wall a step read, each packed
        prefill capacity one a dispatch."""
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        model, cfg, params, prompts = served_model
        session = TelemetrySession(TelemetryConfig(trace_dir=str(tmp_path), watchdog=False))
        try:
            engine = ServingEngine(
                model, params, num_slots=2, max_cache_len=64, prefill_chunks=(4, 8), telemetry=session,
            )
            reqs = [engine.submit(p, max_new_tokens=5, seed=i) for i, p in enumerate(prompts)]
            engine.run()
            rows = session.costs.entries
            assert rows["decode_step"]["calls"] == engine.step_count > 0
            assert rows["decode_step"]["wall_s"] == pytest.approx(sum(w for w, _ in engine._step_samples))
            packs = sum(row["calls"] for name, row in rows.items() if name.startswith("ragged_prefill_"))
            assert packs >= len(prompts) and set(rows) <= {"decode_step", "ragged_prefill_4", "ragged_prefill_8"}
            # every request's tokens but its first came from a decode step
            assert sum(n for _, n in engine._step_samples) == sum(len(r.tokens) - 1 for r in reqs)
        finally:
            session.close()


class TestPlacementSignalContract:
    """serving/load_score — the stable router contract (telemetry/fleet.py,
    docs/telemetry.md "Fleet view"): every engine exports one comparable
    scalar plus its raw components, and perturbing queue depth / slot
    occupancy / recent ITL / drain moves the score monotonically."""

    def test_every_engine_exports_score_and_components(self, served_model):
        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=64, prefill_chunks=(8,)
        )
        m = engine.metrics()
        assert m["serving/num_slots"] == 2
        assert m["serving/free_slots"] == 2
        # idle engine: nothing queued, no page out but the parking page
        assert m["serving/free_pages"] == engine.num_pages - 1
        assert m["serving/load_score"] == round(1 / engine.num_pages, 6)

    def test_score_moves_monotonically_under_perturbation(self, served_model):
        from accelerate_tpu.telemetry.fleet import DRAINING_PENALTY

        model, cfg, params, prompts = served_model
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=64, prefill_chunks=(8,)
        )
        idle = engine.metrics()["serving/load_score"]
        # queue depth: submitted-but-not-run requests raise the score
        reqs = [engine.submit(p, max_new_tokens=2, seed=i)
                for i, p in enumerate(prompts[:3])]
        queued = engine.metrics()["serving/load_score"]
        assert queued > idle
        assert engine.metrics()["serving/queue_depth"] == 3
        # recent ITL p99: a latency regression raises it further
        engine._itl.extend([0.5] * 16)
        engine._itl_emitted += 16
        slow = engine.metrics()["serving/load_score"]
        assert slow > queued
        # drain: the score jumps past anything a live replica can reach
        engine.request_drain()
        draining = engine.metrics()["serving/load_score"]
        assert draining >= slow + DRAINING_PENALTY
        assert engine.metrics()["serving/draining"] is True
        # drain still gives every queued request a definite outcome
        engine.run()
        assert all(r.outcome in ("finished", "shed") for r in reqs)
        assert engine.metrics()["serving/free_slots"] == 2

    def test_score_rides_rollup_and_exposition(self, served_model, tmp_path):
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession
        from accelerate_tpu.telemetry.exporter import prometheus_text
        from accelerate_tpu.telemetry.fleet import parse_exposition

        model, cfg, params, prompts = served_model
        session = TelemetrySession(
            TelemetryConfig(trace_dir=str(tmp_path), spans=False, watchdog=False)
        )
        try:
            engine = ServingEngine(
                model, params, num_slots=2, max_cache_len=64,
                prefill_chunks=(8,), telemetry=session,
            )
            engine.generate_batched(prompts[:2], max_new_tokens=2)
            rollup = session.rollup()
            assert "serving/load_score" in rollup
            assert rollup["serving/free_slots"] == 2
            snap = parse_exposition(prometheus_text(session))
            assert "serving_load_score" in snap.gauges
            assert snap.gauges["serving_num_slots"] == 2.0
        finally:
            session.close()


class TestTraceStitching:
    """submit(request_id=...) + the replica field: a router re-queuing one
    logical request across replicas leaves per-replica records the trace
    CLI stitches into one hop-by-hop timeline."""

    def test_external_request_id_and_replica_land_in_records(
        self, served_model, tmp_path
    ):
        import json as json_mod

        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        model, cfg, params, prompts = served_model
        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), spans=False, watchdog=False,
        ))
        try:
            engine = ServingEngine(
                model, params, num_slots=2, max_cache_len=64,
                prefill_chunks=(8,), telemetry=session, replica="replica-a",
            )
            assert engine.replica == "replica-a"
            req = engine.submit(prompts[0], max_new_tokens=2,
                                request_id="router-7")
            assert req.id == "router-7"
            auto = engine.submit(prompts[1], max_new_tokens=2)
            assert isinstance(auto.id, int)
            engine.run()
            session.close()
            recs = {r["request_id"]: r for r in (
                json_mod.loads(l)
                for l in open(tmp_path / "requests-host0.jsonl")
            )}
            assert recs["router-7"]["replica"] == "replica-a"
            assert recs["router-7"]["tokens"] == 2
            assert recs[auto.id]["replica"] == "replica-a"
        finally:
            session.close()

    def test_requeued_request_stitches_across_two_replicas(
        self, served_model, tmp_path
    ):
        """Two engines = two replicas, each with its own telemetry dir;
        the same external id submitted to both (the re-queue) stitches
        into an ordered 2-hop timeline."""
        from accelerate_tpu.commands.trace import (
            load_requests,
            stitch_request,
        )
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        model, cfg, params, prompts = served_model
        dirs = []
        for name in ("replica-a", "replica-b"):
            d = tmp_path / name
            d.mkdir()
            dirs.append(str(d))
            session = TelemetrySession(TelemetryConfig(
                trace_dir=str(d), spans=False, watchdog=False,
            ))
            try:
                engine = ServingEngine(
                    model, params, num_slots=1, max_cache_len=64,
                    prefill_chunks=(8,), telemetry=session, replica=name,
                )
                engine.submit(prompts[0], max_new_tokens=2,
                              request_id="req-42")
                engine.run()
            finally:
                session.close()

        records = load_requests(dirs)
        hops = [r for r in records if r["request_id"] == "req-42"]
        assert len(hops) == 2
        stitched = stitch_request(hops)
        assert stitched["hop_count"] == 2
        assert [h["replica"] for h in stitched["hops"]] == [
            "replica-a", "replica-b"
        ]
        assert stitched["tokens"] == 4
        assert stitched["hops"][1]["gap_ms"] is not None
        assert stitched["end_to_end_ms"] > 0

        # and through the CLI: summary over both dirs renders the hops
        import argparse
        import io
        import json as json_mod
        from contextlib import redirect_stdout

        from accelerate_tpu.commands.trace import trace_command

        args = argparse.Namespace(
            trace_cmd="summary", target=dirs, request_id="req-42", json=True
        )
        buf = io.StringIO()
        with redirect_stdout(buf):
            assert trace_command(args) == 0
        out = json_mod.loads(buf.getvalue())
        assert out["stitched"]["hop_count"] == 2
