"""Runtime-telemetry tests: metrics rollup math, Chrome-trace span JSONL,
heartbeat watchdog stall/quiet behavior, tracker gating, and the round-5
ADVICE warnings (AD/GPipe fallback naming its key, rng-less manual hooks,
per-microbatch const shape, PRNG impl resolution). Fast tier: one tiny
engine build is shared by the integration test; everything else is pure
host-side."""

from __future__ import annotations

import json
import logging
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.telemetry import TelemetryConfig, resolve_config
from accelerate_tpu.telemetry import spans as spans_mod
from accelerate_tpu.telemetry.metrics import (
    MetricsWindow,
    batch_token_count,
    decoder_flops_per_token,
    flops_per_token_fn,
    peak_flops,
)
from accelerate_tpu.telemetry.watchdog import (
    HeartbeatWatchdog,
    build_stall_report,
    publish_heartbeat_file,
)


@pytest.fixture(autouse=True)
def _disarm_spans():
    yield
    import accelerate_tpu.telemetry as tel

    if tel.current_session() is not None:
        tel.current_session().close()
    spans_mod.disarm()


class TestMetricsWindow:
    def test_rollup_math(self):
        w = MetricsWindow(size=8)
        # 4 steps: 1s each, 1000 tokens each, one with 0.25s data wait
        for i in range(4):
            w.add({"step": i + 1, "wall_s": 1.0, "steps": 1, "tokens": 1000,
                   "samples": 10, "data_wait_s": 0.25 if i == 0 else 0.0,
                   "flops": 1000 * 2e9})
        out = w.rollup(peak=200e12)
        assert out["sys/window_steps"] == 4
        assert out["sys/step_time_s"] == pytest.approx(1.0)
        assert out["sys/step_time_p50_s"] == pytest.approx(1.0)
        assert out["sys/tokens_per_s"] == pytest.approx(1000.0)
        assert out["sys/samples_per_s"] == pytest.approx(10.0)
        assert out["sys/data_wait_frac"] == pytest.approx(0.25 / 4)
        # mfu = flops/s / peak = (4000 * 2e9 / 4) / 200e12
        assert out["sys/mfu_pct"] == pytest.approx(100 * 2e12 / 200e12)

    def test_fused_multistep_records_normalize(self):
        w = MetricsWindow(size=4)
        # one fused dispatch covering K=4 optimizer steps in 2s
        w.add({"wall_s": 2.0, "steps": 4, "tokens": 4000})
        out = w.rollup()
        assert out["sys/window_steps"] == 4
        assert out["sys/step_time_s"] == pytest.approx(0.5)
        assert out["sys/step_time_p50_s"] == pytest.approx(0.5)
        assert out["sys/tokens_per_s"] == pytest.approx(2000.0)

    def test_window_evicts_old_records(self):
        w = MetricsWindow(size=2)
        w.add({"wall_s": 100.0, "tokens": 1})
        w.add({"wall_s": 1.0, "tokens": 100})
        w.add({"wall_s": 1.0, "tokens": 100})
        assert w.rollup()["sys/tokens_per_s"] == pytest.approx(100.0)

    def test_empty_window(self):
        assert MetricsWindow().rollup() == {}

    def test_compile_counters_summed(self):
        w = MetricsWindow()
        w.add({"wall_s": 1.0, "compile_events": 2, "compile_s": 0.5,
               "compile_cache_hits": 1})
        w.add({"wall_s": 1.0, "compile_events": 0, "compile_s": 0.0})
        out = w.rollup()
        assert out["sys/compile_events"] == 2
        assert out["sys/compile_s"] == pytest.approx(0.5)
        assert out["sys/compile_cache_hits"] == 1


class TestFlopsAccounting:
    def test_decoder_formula_matches_bench(self):
        # 6 x parameters + causal attention, the form the benchmark's
        # mfu_pct also takes (benchmarks/arch/<model_type>.py)
        assert decoder_flops_per_token(100, 4, 8, 16) == 6 * 100 + 6 * 4 * 8 * 16

    def test_flops_fn_from_model_config(self):
        from accelerate_tpu.models import DecoderConfig

        cfg = DecoderConfig.tiny()
        fn = flops_per_token_fn(cfg)
        assert fn(128) == decoder_flops_per_token(
            cfg.num_params, cfg.num_layers, 128, cfg.embed_dim
        )
        assert flops_per_token_fn(object()) is None

    def test_peak_flops_prefers_most_specific_kind(self):
        v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
        v5p = types.SimpleNamespace(device_kind="TPU v5p")
        assert peak_flops(v5e) == 197e12
        assert peak_flops(v5p) == 459e12
        # unknown kind -> no peak (and so no MFU), never a made-up default
        assert peak_flops(types.SimpleNamespace(device_kind="cpu")) is None

    def test_batch_token_count(self):
        ids = np.zeros((4, 16), np.int32)
        tokens, samples, seq = batch_token_count({"input_ids": ids, "labels": ids})
        assert (tokens, samples, seq) == (64, 4, 16)
        # stacked K-step batches count all steps' tokens
        tokens, samples, seq = batch_token_count({"input_ids": np.zeros((3, 4, 16))})
        assert (tokens, samples, seq) == (192, 12, 16)
        # images: samples only, no fabricated tokens
        tokens, samples, seq = batch_token_count({"images": np.zeros((8, 4, 4, 3))})
        assert tokens is None and samples == 8 and seq is None


class TestFp8Health:
    def test_reads_last_completed_slot_not_the_freshly_rolled_one(self):
        from accelerate_tpu.telemetry.metrics import fp8_amax_health

        # engine state right after a roll: slot 0 zeroed, slot 1 holds the
        # just-finished step's amaxes — a healthy run must NOT read stale
        healthy = {"dot": jnp.asarray([[0.0, 3.5, 1.0], [0.0, 2.0, 1.0]])}
        out = fp8_amax_health(healthy)
        assert out["sys/fp8_amax_stale_frac"] == 0.0
        assert out["sys/fp8_amax_max"] == pytest.approx(3.5)
        # a contraction that never records stays zero in slot 1 -> flagged
        stale = {"dot": jnp.zeros((2, 3))}
        assert fp8_amax_health(stale)["sys/fp8_amax_stale_frac"] == 1.0
        assert fp8_amax_health({}) == {}


def _ring_since(mark: int) -> list:
    """Spans recorded after ``mark`` (an id): the ring is process-wide and
    other tests write to it."""
    return [s for s in spans_mod.snapshot() if s[0] > mark]


def _ring_mark() -> int:
    spans_mod.emit("mark", 0.0, 0.0)
    return spans_mod.snapshot()[-1][0]


class TestSpans:
    def test_jsonl_is_chrome_trace(self, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        spans_mod.arm(path, process_index=3)
        with spans_mod.span("outer", phase="demo"):
            with spans_mod.span("inner"):
                time.sleep(0.01)
        spans_mod.disarm()
        lines = [json.loads(l) for l in open(path) if l.strip()]
        assert lines[0]["ph"] == "M"  # process_name metadata
        events = [e for e in lines if e["ph"] == "X"]
        by_name = {e["name"]: e for e in events}
        assert set(by_name) == {"outer", "inner"}
        for e in events:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
            assert e["pid"] == 3
        # nesting = time containment on one tid (how trace viewers render it)
        outer, inner = by_name["outer"], by_name["inner"]
        assert outer["tid"] == inner["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        # and the whole file loads as a Chrome trace object
        trace = spans_mod.load_chrome_trace(path)
        assert isinstance(trace["traceEvents"], list) and len(trace["traceEvents"]) == 3

    def test_span_noop_when_disarmed(self, tmp_path):
        """Nothing armed: no file is written, and the span is in the ring."""
        assert spans_mod.recorder() is None
        mark = _ring_mark()
        with spans_mod.span("nothing"):
            pass
        assert [s[2] for s in _ring_since(mark)] == ["nothing"]
        assert spans_mod.last_spans(1)[0]["name"] == "nothing"
        assert list(tmp_path.iterdir()) == []

    def test_ring_ids_and_parents_nest(self):
        mark = _ring_mark()
        with spans_mod.span("a") as a:
            with spans_mod.span("b") as b:
                with spans_mod.span("c"):
                    pass
            with spans_mod.span("d"):
                pass
        got = {s[2]: s for s in _ring_since(mark)}
        assert [s[2] for s in _ring_since(mark)] == ["c", "b", "d", "a"]  # as they close
        assert got["a"][1] is None and got["a"][0] == a.id
        assert got["b"][1] == a.id and got["d"][1] == a.id and got["c"][1] == b.id
        assert len({s[0] for s in got.values()}) == 4
        for child, parent in (("b", "a"), ("c", "b"), ("d", "a")):
            assert got[parent][3] <= got[child][3] <= got[child][4] <= got[parent][4]

    def test_parents_are_per_thread(self):
        import threading

        mark = _ring_mark()

        def other():
            with spans_mod.span("elsewhere"):
                pass

        with spans_mod.span("here"):
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        got = {s[2]: s for s in _ring_since(mark)}
        assert got["elsewhere"][1] is None and got["here"][1] is None

    def test_args_set_before_close_are_kept(self):
        mark = _ring_mark()
        with spans_mod.span("counted", rows=256) as sp:
            sp.args["tokens"] = 200
        with spans_mod.span("bare"):
            pass
        counted, bare = _ring_since(mark)
        assert counted[5] == {"rows": 256, "tokens": 200}
        assert bare[5] is None

    def test_emit_lands_once_in_ring_and_file(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        spans_mod.arm(path)
        mark = _ring_mark()
        with spans_mod.span("serving/step") as step:
            spans_mod.emit("serving/queue_wait", 1.0, 0.25, {"request_id": 7}, cat="serving")
        spans_mod.disarm()
        spans_mod.emit("serving/queue_wait", 2.0, 0.5, {"request_id": 8}, cat="serving")
        waits = [s for s in _ring_since(mark) if s[2] == "serving/queue_wait"]
        assert [(s[3], s[4], s[5]) for s in waits] == [
            (1.0, 1.25, {"request_id": 7}), (2.0, 2.5, {"request_id": 8})]
        # the span that caused it: the one open on this thread, none outside
        assert waits[0][1] == step.id and waits[1][1] is None
        events = [json.loads(l) for l in open(path) if l.strip()]
        in_file = [e for e in events if e["name"] == "serving/queue_wait"]
        assert len(in_file) == 1 and in_file[0]["args"] == {"request_id": 7}
        assert in_file[0]["dur"] == pytest.approx(0.25e6) and in_file[0]["cat"] == "serving"

    def test_ring_wraps_and_counts_dropped(self):
        before = spans_mod.dropped()
        mark = _ring_mark()
        n = spans_mod.RING_SPANS + 10
        for i in range(n):
            spans_mod.emit("filler", float(i), 0.0)
        ring = spans_mod.snapshot()
        assert len(ring) == spans_mod.RING_SPANS
        assert ring[-1][3] == float(n - 1) and ring[0][3] == 10.0
        assert all(s[0] > mark for s in ring)
        # at least the mark and the first ten fillers went
        assert spans_mod.dropped() - before >= 11

    def test_last_spans_ring(self):
        for name in ("a", "b", "c"):
            with spans_mod.span(name):
                pass
        last = spans_mod.last_spans(2)
        assert [s["name"] for s in last] == ["b", "c"]
        assert all(abs(s["end_unix_s"] - time.time()) < 5 and s["dur_s"] >= 0 for s in last)
        assert spans_mod.last_spans(0) == []

    def test_span_survives_an_exception_and_keeps_the_stack(self):
        mark = _ring_mark()
        with pytest.raises(ValueError):
            with spans_mod.span("outer"):
                with spans_mod.span("raises"):
                    raise ValueError("boom")
        with spans_mod.span("after"):
            pass
        got = {s[2]: s for s in _ring_since(mark)}
        assert got["raises"][1] == got["outer"][0] and got["after"][1] is None

    def test_phases_bridge(self, tmp_path):
        from accelerate_tpu.utils import phases

        path = str(tmp_path / "phases.jsonl")
        spans_mod.arm(path)
        acc = phases.collect_phases()
        with phases.phase("ckpt_read"):
            time.sleep(0.005)
        # legacy aggregate still fills...
        assert acc["ckpt_read"] >= 0.005
        # ...and the same phase landed in the span JSONL
        spans_mod.disarm()
        names = [json.loads(l)["name"] for l in open(path) if l.strip()]
        assert "ckpt_read" in names
        phases._ACTIVE = None


class TestWatchdog:
    def test_fires_on_stalled_heartbeat_with_stacks_and_spans(self, tmp_path):
        from accelerate_tpu.state import PartialState

        spans_mod.arm(str(tmp_path / "t.jsonl"))
        with spans_mod.span("last_good_step"):
            pass
        PartialState().publish_heartbeat(7)
        fired = []
        wd = HeartbeatWatchdog(deadline_s=0.15, poll_s=0.03,
                               dump_dir=str(tmp_path), on_stall=fired.append)
        wd.start()
        try:
            deadline = time.time() + 3.0
            while not fired and time.time() < deadline:
                time.sleep(0.02)
        finally:
            wd.stop()
        assert wd.stall_count == 1  # fired once and re-arms, not a stream
        report = fired[0]
        assert "STALL" in report and "step 7" in report
        assert "thread" in report and "_run" in report  # stack dump present
        assert "last_good_step" in report  # span ring made it in
        dump = tmp_path / "watchdog-host0.log"
        assert dump.exists() and "STALL" in dump.read_text()

    def test_quiet_on_healthy_heartbeat(self):
        from accelerate_tpu.state import PartialState

        state = PartialState()
        fired = []
        wd = HeartbeatWatchdog(deadline_s=0.3, poll_s=0.03, on_stall=fired.append)
        wd.start()
        try:
            for step in range(12):
                state.publish_heartbeat(step)
                time.sleep(0.05)
        finally:
            wd.stop()
        assert fired == [] and wd.stall_count == 0

    def test_no_heartbeat_means_no_fire(self):
        # compiles before step 1 can exceed any step deadline; the clock
        # must start at the FIRST beat
        wd = HeartbeatWatchdog(deadline_s=0.05, poll_s=0.02)
        wd.start()
        time.sleep(0.15)
        wd.stop()
        assert wd.stall_count == 0

    def test_stall_report_names_straggler_peer(self, tmp_path):
        hb = str(tmp_path / "hb")
        publish_heartbeat_file(hb, 0, step=12)
        publish_heartbeat_file(hb, 1, step=3)  # way behind
        report = build_stall_report(12, age_s=40.0, deadline_s=30.0,
                                    heartbeat_dir=hb, n_spans=0)
        lagging = [l for l in report.splitlines() if "host 1" in l]
        assert lagging and "STRAGGLER" in lagging[0]
        leading = [l for l in report.splitlines() if "host 0" in l]
        assert leading and "STRAGGLER" not in leading[0]


class TestCompileCounters:
    def test_record_and_snapshot(self):
        from accelerate_tpu.utils.compile_cache import (
            compile_event_counters,
            record_compile_event,
        )

        before = compile_event_counters()
        record_compile_event(0.5)
        record_compile_event(cache_hit=True)
        after = compile_event_counters()
        assert after["count"] - before["count"] == 1
        assert after["seconds"] - before["seconds"] == pytest.approx(0.5)
        assert after["cache_hits"] - before["cache_hits"] == 1


class TestConfigResolution:
    def test_resolve(self):
        assert resolve_config(False) is None
        assert resolve_config(TelemetryConfig(enabled=False)) is None
        assert isinstance(resolve_config(True), TelemetryConfig)
        cfg = TelemetryConfig(window=7)
        assert resolve_config(cfg) is cfg
        with pytest.raises(TypeError):
            resolve_config("yes")

    def test_env_gate(self, monkeypatch):
        monkeypatch.delenv("ATT_TELEMETRY", raising=False)
        monkeypatch.delenv("ATT_TELEMETRY_WATCHDOG_S", raising=False)
        assert resolve_config(None) is None
        monkeypatch.setenv("ATT_TELEMETRY", "1")
        monkeypatch.setenv("ATT_TELEMETRY_DIR", "/tmp/telem")
        cfg = resolve_config(None)
        assert cfg is not None and cfg.trace_dir == "/tmp/telem"
        monkeypatch.setenv("ATT_TELEMETRY_PROFILE_STEPS", "3:9")
        assert resolve_config(None).profile_steps == (3, 9)
        # malformed window must degrade to a warning, not crash startup
        monkeypatch.setenv("ATT_TELEMETRY_PROFILE_STEPS", "100")
        assert resolve_config(None).profile_steps is None


class TestTrackerGating:
    def test_jsonl_tracker_silent_off_main(self, tmp_path):
        from accelerate_tpu.state import PartialState
        from accelerate_tpu.tracking import JSONLTracker

        state = PartialState()
        state.process_index = 1  # shared-dict write: every instance sees it
        try:
            t = JSONLTracker("run", tmp_path)
            t.log({"sys/step_time_s": 1.0}, step=0)
            t.finish()
            assert not (tmp_path / "run").exists()
        finally:
            state.process_index = 0


class TestAdviceWarnings:
    def test_manual_hook_without_rng_warns_at_init(self, caplog):
        import optax

        from accelerate_tpu import Accelerator, Model

        class Hooky:
            config = types.SimpleNamespace(dropout_rate=0.1)

            def __call__(self, params, input_ids=None, labels=None):
                return {"loss": jnp.sum(params["w"]).astype(jnp.float32) ** 2}

            def pipeline_value_and_grad(self):
                def vag(params, input_ids, labels):  # duck-typed, no rng
                    loss = jnp.sum(params["w"]).astype(jnp.float32) ** 2
                    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
                    return loss, grads

                return vag

        acc = Accelerator()
        with caplog.at_level(logging.WARNING, logger="accelerate_tpu.accelerator"):
            model = acc.prepare_model(Model(Hooky(), {"w": jnp.ones((8, 8))}))
        assert any("rng" in r.getMessage() and "dropout" in r.getMessage().lower()
                   for r in caplog.records)
        engine = model._engine
        assert engine._manual_vag is not None
        assert engine._manual_vag_wants_rng is False

    def test_ad_fallback_warns_once_naming_key(self, caplog):
        from accelerate_tpu import Accelerator, Model

        class PipeLM:
            config = types.SimpleNamespace(dropout_rate=0.0)

            def __call__(self, params, input_ids=None, labels=None,
                         attention_mask=None):
                return {"loss": jnp.sum(params["w"]).astype(jnp.float32) ** 2}

            def pipeline_value_and_grad(self):
                def vag(params, input_ids, labels):
                    loss = jnp.sum(params["w"]).astype(jnp.float32) ** 2
                    grads = jax.tree_util.tree_map(jnp.ones_like, params)
                    return loss, grads

                return vag

        acc = Accelerator()
        model = acc.prepare_model(Model(PipeLM(), {"w": jnp.ones((4, 4))}))
        ids = jnp.zeros((2, 4), jnp.int32)
        with caplog.at_level(logging.WARNING, logger="accelerate_tpu.accelerator"):
            model(input_ids=ids, labels=ids, attention_mask=jnp.ones((2, 4)))
            model(input_ids=ids, labels=ids, attention_mask=jnp.ones((2, 4)))
        msgs = [r.getMessage() for r in caplog.records
                if "AD/GPipe fallback" in r.getMessage()]
        assert len(msgs) == 1  # once, not per step
        assert "attention_mask" in msgs[0]

        # a clean (input_ids, labels) batch takes the manual path silently
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="accelerate_tpu.accelerator"):
            model(input_ids=ids, labels=ids)
        assert not any("fallback" in r.getMessage() for r in caplog.records)


class TestPipelineMbConstShape:
    def test_wrong_leading_dim_raises(self):
        import flax.linen as nn

        from accelerate_tpu.parallel.pipeline import PipelineStages

        class Stage(nn.Module):
            @nn.compact
            def __call__(self, x, c):
                return x + self.param("b", nn.initializers.zeros, (1,)) + c[:, None]

        pipe = PipelineStages(stage_module=Stage, stage_args=(), num_stages=2,
                              num_microbatches=4, num_mb_consts=1,
                              buffer_logical_axes=("stage", "batch", "embed"),
                              outputs_logical_axes=(None, "batch", "embed"))
        x_mb = jnp.zeros((4, 2, 8))
        with pytest.raises(ValueError, match="num_microbatches"):
            pipe.init(jax.random.PRNGKey(0), x_mb, jnp.zeros((3, 2)))
        # correct [M, ...] const passes the gate
        pipe.init(jax.random.PRNGKey(0), x_mb, jnp.zeros((4, 2)))


class TestPrngImplLog:
    def test_logged_once_at_first_resolution(self, caplog):
        from accelerate_tpu.utils import random as rnd

        rnd._IMPL_LOGGED = False
        kc = rnd.KeyChain(0)
        with caplog.at_level(logging.INFO, logger="accelerate_tpu.utils.random"):
            kc.next_key("a")
            kc.next_key("b")
        hits = [r for r in caplog.records if "PRNG impl resolved" in r.getMessage()]
        assert len(hits) == 1
        assert "threefry" in hits[0].getMessage()  # CPU backend resolves to default


class TestStreamingHistogram:
    def test_quantiles_within_bucket_error(self):
        from accelerate_tpu.telemetry.histograms import StreamingHistogram

        h = StreamingHistogram()
        for i in range(1, 1001):  # 1ms .. 1s, uniform
            h.add(i / 1000)
        # geometric buckets (growth=1.25) bound relative error at ~12%
        assert h.quantile(0.50) == pytest.approx(0.5, rel=0.13)
        assert h.quantile(0.95) == pytest.approx(0.95, rel=0.13)
        assert h.quantile(0.99) == pytest.approx(0.99, rel=0.13)
        snap = h.snapshot()
        assert snap["count"] == 1000
        assert snap["min_s"] == 0.001 and snap["max_s"] == 1.0
        assert snap["sum_s"] == pytest.approx(500.5)

    def test_empty_and_garbage_inputs(self):
        from accelerate_tpu.telemetry.histograms import StreamingHistogram

        h = StreamingHistogram()
        assert h.quantile(0.5) is None and h.snapshot() == {}
        h.add(-1.0)
        h.add(float("nan"))
        assert h.count == 0
        h.add(0.0)  # at/below lo lands in bucket 0, not a crash
        assert h.count == 1 and h.quantile(0.99) == 0.0

    def test_cumulative_buckets_are_monotone_and_complete(self):
        from accelerate_tpu.telemetry.histograms import StreamingHistogram

        h = StreamingHistogram()
        for v in (0.001, 0.002, 0.004, 0.1, 0.1, 3.0):
            h.add(v)
        buckets = h.cumulative_buckets()
        les = [le for le, _ in buckets]
        cums = [c for _, c in buckets]
        assert les == sorted(les)
        assert cums == sorted(cums) and cums[-1] == h.count

    def test_merge_matches_combined_stream(self):
        from accelerate_tpu.telemetry.histograms import StreamingHistogram

        a, b, both = StreamingHistogram(), StreamingHistogram(), StreamingHistogram()
        for i, v in enumerate(x / 100 for x in range(1, 200)):
            (a if i % 2 else b).add(v)
            both.add(v)
        a.merge(b)
        assert a.count == both.count and a.sum == pytest.approx(both.sum)
        assert a.quantile(0.95) == both.quantile(0.95)

    def test_percentile_keys(self):
        from accelerate_tpu.telemetry.histograms import (
            StreamingHistogram,
            percentile_keys,
        )

        h = StreamingHistogram()
        assert percentile_keys("serving/ttft", h) == {}
        h.add(0.1)
        out = percentile_keys("serving/ttft", h)
        assert out["serving/ttft_count"] == 1
        assert out["serving/ttft_p99_ms"] == pytest.approx(100, rel=0.13)


class TestExemplarReservoir:
    """The bounded exemplar reservoir behind every SLO histogram: at most
    EXEMPLARS_PER_BUCKET entries per bucket at any observation rate, the
    max-valued entry always retained, the newest always reachable, and
    the fleet-merge union holding the same bound."""

    def test_bounded_under_10k_observations(self):
        from accelerate_tpu.telemetry.histograms import (
            EXEMPLARS_PER_BUCKET,
            StreamingHistogram,
        )

        rng = np.random.RandomState(0)
        h = StreamingHistogram()
        worst = 0.0
        for i in range(10_000):
            v = float(rng.lognormal(mean=-3.0, sigma=1.0))
            worst = max(worst, v)
            h.observe(v, exemplar={"request_id": f"req-{i}", "replica": "r0"})
        assert h.count == 10_000
        for res in h.exemplars.values():
            assert 1 <= len(res) <= EXEMPLARS_PER_BUCKET
        # the max-valued observation survived 10k displacement attempts
        from accelerate_tpu.telemetry.histograms import _entry_value

        kept = [e for res in h.exemplars.values() for e in res]
        assert max(_entry_value(e) for e in kept) == pytest.approx(worst)
        # a tail quantile names a concrete culprit from a nearby bucket
        near = h.exemplar_near_quantile(0.999)
        assert near is not None and near["value"] >= h.quantile(0.99) * 0.8
        # the per-bucket exposition pick is the NEWEST entry, and it
        # carries the normalized schema regardless of storage form
        for le, entry in h.exposition_exemplars().items():
            assert set(entry) >= {"request_id", "value", "unix_s"}
            assert entry["value"] <= le * 1.0001
            assert entry["replica"] == "r0"

    def test_disabled_and_anonymous_observations_cost_nothing(self):
        from accelerate_tpu.telemetry.histograms import StreamingHistogram

        h = StreamingHistogram()
        h.exemplars_enabled = False
        h.observe(0.1, exemplar={"request_id": "req-0"})
        h.observe(0.2)  # no exemplar at all
        h.exemplars_enabled = True
        h.observe(0.3, exemplar={"replica": "r0"})  # no request_id: dropped
        assert h.count == 3 and h.exemplars == {}
        assert h.exemplar_near_quantile(0.99) is None

    def test_merge_unions_bounded_newest_wins(self):
        from accelerate_tpu.telemetry.histograms import (
            EXEMPLARS_PER_BUCKET,
            StreamingHistogram,
        )

        a, b = StreamingHistogram(), StreamingHistogram()
        # same bucket on both sides: four candidate entries, bound is 2;
        # "a-max" carries the largest value, "b-new" the newest timestamp
        for h, rid, v, t in [(a, "a-old", 0.1000, 10.0), (a, "a-max", 0.1040, 20.0),
                             (b, "b-mid", 0.1010, 30.0), (b, "b-new", 0.1020, 40.0)]:
            h.observe(v, exemplar={"request_id": rid, "unix_s": t})
        a.merge(b)
        assert len(a.exemplars) == 1
        (res,) = a.exemplars.values()
        assert len(res) <= EXEMPLARS_PER_BUCKET
        ids = {e["request_id"] for e in res}
        # the union keeps the max-valued entry and the newest entry
        assert ids == {"a-max", "b-new"}
        assert res[0]["request_id"] == "a-max"  # max first (reservoir invariant)

    def test_percentile_keys_name_p99_culprit(self):
        from accelerate_tpu.telemetry.histograms import (
            StreamingHistogram,
            percentile_keys,
        )

        h = StreamingHistogram()
        for i in range(97):
            h.observe(0.010, exemplar={"request_id": f"fast-{i}"})
        for i in range(3):  # ~3% of traffic blows the SLO: p99 lands here
            h.observe(1.5, exemplar={"request_id": f"slow-{i}"})
        out = percentile_keys("serving/itl", h)
        assert out["serving/itl_p99_exemplar"].startswith("slow-")
        # rollup stays numeric-typed everywhere else
        assert isinstance(out["serving/itl_p99_ms"], float)

    def test_alert_exemplars_for_key_reads_live_reservoirs(self):
        from accelerate_tpu.telemetry.alerts import exemplars_for_key
        from accelerate_tpu.telemetry.histograms import StreamingHistogram

        h = StreamingHistogram()
        for i, v in enumerate((0.01, 0.02, 0.9, 0.05)):
            h.observe(v, exemplar={"request_id": f"req-{i}"})
        ids = exemplars_for_key({"serving/itl": h}, "serving/itl_recent_p99_ms")
        assert ids and ids[0] == "req-2"  # worst value leads
        assert exemplars_for_key({"serving/itl": h}, "fleet/replicas") == []


class TestArtifactWriter:
    """Durable JSONL retention: rotation below the byte cap, bounded
    generations, continuous multi-generation reads, and a torn tail that
    never costs more than itself."""

    def test_rotation_stays_bounded_with_zero_reader_errors(self, tmp_path):
        from accelerate_tpu.telemetry.artifacts import (
            ArtifactWriter,
            artifact_files,
            read_jsonl,
        )

        path = str(tmp_path / "requests-host0.jsonl")
        w = ArtifactWriter(path, max_bytes=4096, max_generations=3)
        n = 2000
        for i in range(n):
            w.write({"request_id": f"req-{i}", "seq": i, "pad": "x" * 40})
        w.close()
        assert w.rotations > 3  # the cap actually engaged, repeatedly
        files = artifact_files(str(tmp_path), "requests-host*.jsonl")
        # bounded footprint: active + at most max_generations rotated
        assert 1 <= len(files) <= 4
        for f in files:
            assert os.path.getsize(f) <= 4096 + 256  # cap + one record slack
        recs = read_jsonl(str(tmp_path), "requests-host*.jsonl")
        # oldest-generation-first means seq is strictly increasing and
        # the newest record always survives rotation
        seqs = [r["seq"] for r in recs]
        assert seqs == sorted(seqs)
        assert seqs[-1] == n - 1

    def test_torn_tail_skipped_earlier_records_intact(self, tmp_path):
        from accelerate_tpu.telemetry.artifacts import ArtifactWriter, read_jsonl

        path = str(tmp_path / "alerts-host0.jsonl")
        w = ArtifactWriter(path)
        for i in range(5):
            w.write({"seq": i})
        w.close()
        with open(path, "ab") as fh:  # a kill -9 mid-append
            fh.write(b'{"seq": 5, "never_fini')
        recs = read_jsonl(path)
        assert [r["seq"] for r in recs] == [0, 1, 2, 3, 4]

    def test_family_loaders_read_across_generations(self, tmp_path):
        from accelerate_tpu.telemetry.alerts import load_alerts
        from accelerate_tpu.telemetry.artifacts import ArtifactWriter

        path = str(tmp_path / "alerts-host0.jsonl")
        w = ArtifactWriter(path, max_bytes=512, max_generations=2)
        n = 40
        for i in range(n):
            w.write({"rule": "itl_burn_rate", "state": "firing",
                     "t_unix_s": 1000.0 + i, "severity": "page"})
        w.close()
        assert w.rotations > 0
        events = load_alerts(str(tmp_path)).get("events")
        # rotated-away history is gone by design; what survives is the
        # continuous suffix, in order, ending at the newest event
        ts = [e["t_unix_s"] for e in events]
        assert ts == sorted(ts) and ts[-1] == 1000.0 + n - 1


class TestRecompileForensics:
    """Signature-diff cause detection: shape, dtype, new-static-arg — and
    the compile-counter attribution that rides each diagnosed record."""

    def _rec(self, tmp_path=None):
        from accelerate_tpu.telemetry.forensics import ForensicsRecorder

        path = str(tmp_path / "forensics.jsonl") if tmp_path is not None else None
        return ForensicsRecorder(path)

    def test_shape_change_names_argument_and_avals(self):
        rec = self._rec()
        first = rec.note_call("train_step", {"batch": {"input_ids": np.zeros((8, 128), np.int32)}})
        assert first["event"] == "first_compile"
        assert rec.note_call(  # same signature: fast path, no event
            "train_step", {"batch": {"input_ids": np.zeros((8, 128), np.int32)}}
        ) is None
        evt = rec.note_call("train_step", {"batch": {"input_ids": np.zeros((8, 136), np.int32)}})
        assert evt["event"] == "recompile"
        (cause,) = evt["causes"]
        assert cause["kind"] == "shape"
        assert cause["arg"] == "batch['input_ids']"
        assert (cause["before"], cause["after"]) == ("i32[8,128]", "i32[8,136]")
        assert "batch['input_ids'] changed i32[8,128] -> i32[8,136]" in evt["cause"]
        rec.close()

    def test_dtype_change_detected(self):
        rec = self._rec()
        rec.note_call("eval_fwd", {"x": np.zeros((4,), np.float32)})
        evt = rec.note_call("eval_fwd", {"x": np.zeros((4,), np.float16)})
        assert evt["causes"][0]["kind"] == "dtype"
        assert "f32[4] -> f16[4]" in evt["cause"]
        rec.close()

    def test_new_static_arg_detected(self):
        rec = self._rec()
        rec.note_call("fwd", {"ids": np.zeros((2, 8), np.int32)})
        evt = rec.note_call(
            "fwd", {"ids": np.zeros((2, 8), np.int32), "deterministic": False}
        )
        (cause,) = evt["causes"]
        assert cause["kind"] == "new_static" and cause["arg"] == "deterministic"
        assert "arg deterministic is new (static:False)" in evt["cause"]
        # flipping the static is a `static` cause, not a new arg
        evt2 = rec.note_call(
            "fwd", {"ids": np.zeros((2, 8), np.int32), "deterministic": True}
        )
        assert evt2["causes"][0]["kind"] == "static"
        rec.close()

    def test_compile_delta_attributed_and_jsonl_written(self, tmp_path):
        from accelerate_tpu.utils.compile_cache import record_compile_event

        rec = self._rec(tmp_path)
        rec.note_call("step", {"x": np.zeros((4,), np.float32)})
        record_compile_event(1.25)  # the compile the dispatch paid
        record_compile_event(cache_hit=True)
        rec.note_call("step", {"x": np.zeros((6,), np.float32)})  # finalizes pending
        rec.flush()
        recs = [json.loads(l) for l in open(tmp_path / "forensics.jsonl")]
        assert [r["event"] for r in recs] == ["first_compile", "recompile"]
        assert recs[0]["compile_events"] == 1
        assert recs[0]["compile_s"] == pytest.approx(1.25)
        assert recs[0]["compile_cache_hits"] == 1
        assert recs[1]["causes"][0]["before"] == "f32[4]"
        rec.close()

    def test_module_level_noop_when_disarmed(self):
        from accelerate_tpu.telemetry import forensics

        forensics.note_call("anything", {"x": np.zeros((2,))})  # must not raise
        assert forensics.recorder() is None


class TestGoodputLedger:
    def test_fractions_sum_to_one_under_synthetic_session(self):
        from accelerate_tpu.telemetry.goodput import GoodputLedger

        now = [0.0]
        led = GoodputLedger(clock=lambda: now[0])
        # 10s of session wall: 6 compute-ish steps + checkpoint + stall
        for _ in range(6):
            led.on_step(wall_s=1.0, compile_s=0.2, data_wait_s=0.1)
        led.note_phase("checkpoint/save", 1.5)
        led.note_phase("dispatch_total", 9.0)  # non-checkpoint phase: ignored
        led.note_stall(0.5)
        now[0] = 10.0
        fr = led.fractions()
        assert sum(fr.values()) == pytest.approx(1.0)
        assert fr["compute"] == pytest.approx(0.42)   # 6 * (1.0 - 0.3) / 10
        assert fr["compile"] == pytest.approx(0.12)
        assert fr["data_wait"] == pytest.approx(0.06)
        assert fr["checkpoint"] == pytest.approx(0.15)
        assert fr["stall"] == pytest.approx(0.05)
        assert fr["idle"] == pytest.approx(0.20)
        keys = led.rollup_keys()
        assert keys["goodput/goodput_frac"] == pytest.approx(0.42)
        assert sum(keys[f"goodput/{b}_frac"]
                   for b in ("compute", "compile", "checkpoint", "data_wait",
                             "stall", "idle")) == pytest.approx(1.0, abs=0.01)

    def test_overlapping_instrumentation_renormalizes(self):
        from accelerate_tpu.telemetry.goodput import GoodputLedger

        now = [0.0]
        led = GoodputLedger(clock=lambda: now[0])
        led.on_step(wall_s=8.0)
        led.note_stall(4.0)  # stall interval later covered by the step wall
        now[0] = 10.0
        fr = led.fractions()
        assert sum(fr.values()) == pytest.approx(1.0)

    def test_compute_clamps_when_compile_exceeds_wall(self):
        from accelerate_tpu.telemetry.goodput import GoodputLedger

        led = GoodputLedger()
        led.on_step(wall_s=0.5, compile_s=2.0)  # other-thread compile billed here
        t = led.totals()
        assert t["compute"] == 0.0 and t["compile"] == pytest.approx(2.0)

    def test_checkpoint_phase_feeds_armed_ledger(self):
        from accelerate_tpu.telemetry import goodput
        from accelerate_tpu.utils import phases

        led = goodput.arm(goodput.GoodputLedger())
        try:
            with phases.phase("checkpoint/save"):
                time.sleep(0.01)
            assert led.totals()["checkpoint"] >= 0.01
        finally:
            goodput.disarm()
        assert goodput.ledger() is None


class TestCostRegistry:
    class _Compiled:
        """Duck-typed stand-in for a jax Compiled (cost/memory analysis)."""

        def __init__(self, flops, hbm, temp=1024):
            self._flops, self._hbm, self._temp = flops, hbm, temp

        def cost_analysis(self):
            return {"flops": self._flops, "bytes accessed": self._hbm}

        def memory_analysis(self):
            class MA:
                argument_size_in_bytes = 100
                output_size_in_bytes = 50
                temp_size_in_bytes = self._temp
                generated_code_size_in_bytes = 10
            return MA()

    def test_classification_on_matmul_heavy_and_gather_heavy_jitted_fns(self):
        """The real thing: XLA's own cost_analysis on a matmul-heavy vs a
        gather-heavy jitted fn must land on opposite sides of an explicit
        roofline ridge."""
        from accelerate_tpu.telemetry.costs import CostRegistry

        reg = CostRegistry(peak_flops=1e12, peak_bw=1e11)  # ridge = 10
        mm = jax.jit(lambda a, b: a @ b).lower(
            jnp.zeros((256, 256)), jnp.zeros((256, 256))
        ).compile()
        row_mm = reg.capture("matmul_step", mm)
        ga = jax.jit(lambda t, i: t[i]).lower(
            jnp.zeros((4096, 64)), jnp.zeros((512,), jnp.int32)
        ).compile()
        row_ga = reg.capture("gather_step", ga)
        assert row_mm["roofline"] == "compute-bound"
        assert row_ga["roofline"] == "memory-bound"
        assert row_mm["arith_intensity"] > 10 > row_ga["arith_intensity"]

    def test_wall_attribution_and_model_mfu(self):
        from accelerate_tpu.telemetry.costs import CostRegistry

        reg = CostRegistry(peak_flops=1e12, peak_bw=1e11)
        reg.capture("step", self._Compiled(flops=1e9, hbm=1e7))
        for _ in range(10):
            reg.note_wall("step", 0.01)
        (row,) = reg.rows()
        # 1e9 flops * 10 calls / 0.1s / 1e12 peak = 10% model MFU
        assert row["mfu_model_pct"] == pytest.approx(10.0)
        assert row["bw_util_pct"] == pytest.approx(1.0)
        assert row["roofline"] == "compute-bound"  # AI 100 vs ridge 10
        keys = reg.rollup_keys()
        assert keys["exe/step_mfu_model_pct"] == pytest.approx(10.0)
        assert keys["exe/step_compute_bound"] is True
        assert keys["exe/step_calls"] == 10

    def test_capture_survives_backends_without_cost_analysis(self):
        from accelerate_tpu.telemetry.costs import CostRegistry

        class Broken:
            def cost_analysis(self):
                raise NotImplementedError

        reg = CostRegistry()
        assert reg.capture("x", Broken()) is None
        reg.note_wall("only_wall", 0.5)  # wall without costs still rows
        (row,) = reg.rows()
        assert row["name"] == "only_wall" and "mfu_model_pct" not in row

    def test_peak_hbm_bw_table_prefers_most_specific_kind(self):
        from accelerate_tpu.telemetry.costs import peak_hbm_bw

        assert peak_hbm_bw(types.SimpleNamespace(device_kind="TPU v5 lite")) == 819e9
        assert peak_hbm_bw(types.SimpleNamespace(device_kind="TPU v5p")) == 2.765e12
        assert peak_hbm_bw(types.SimpleNamespace(device_kind="cpu")) is None


class TestDeviceMemoryStats:
    def test_tolerates_none_partial_and_tracks_peak_deltas(self):
        from accelerate_tpu.telemetry import metrics as metrics_mod

        class Dev:
            def __init__(self, id, stats):
                self.id = id
                self._stats = stats

            def memory_stats(self):
                if isinstance(self._stats, Exception):
                    raise self._stats
                return self._stats

        metrics_mod._PEAK_MARKS.clear()
        d0 = Dev(0, {"bytes_in_use": 10, "peak_bytes_in_use": 100})
        d1 = Dev(1, None)                            # CPU-sim style
        d2 = Dev(2, {"peak_bytes_in_use": 50})       # partial keys
        d3 = Dev(3, RuntimeError("backend gone"))
        out = metrics_mod.device_memory_stats(per_device=True, devices=[d0, d1, d2, d3])
        assert out["sys/mem_bytes_in_use"] == 10
        assert out["sys/mem_peak_bytes"] == 100
        assert "sys/mem_bytes_limit" not in out      # absent key stays absent
        assert out["sys/mem_peak_delta_bytes_d0"] == 0  # first snapshot = baseline
        # peaks grow between snapshots -> per-device watermark deltas
        d0._stats["peak_bytes_in_use"] = 160
        d2._stats["peak_bytes_in_use"] = 55
        out2 = metrics_mod.device_memory_stats(per_device=True, devices=[d0, d1, d2, d3])
        assert out2["sys/mem_peak_delta_bytes_d0"] == 60
        assert out2["sys/mem_peak_delta_bytes_d2"] == 5
        assert out2["sys/mem_peak_delta_bytes"] == 60
        # a backend with nothing to say yields {}
        assert metrics_mod.device_memory_stats(devices=[Dev(9, None)]) == {}
        metrics_mod._PEAK_MARKS.clear()


class TestFlightRecorder:
    def test_ring_bounded_and_bundle_contents(self, tmp_path):
        from accelerate_tpu.telemetry.recorder import FlightRecorder

        fr = FlightRecorder(None, dump_dir=str(tmp_path), capacity=16)
        for i in range(40):
            fr.note("evt", i=i)
        assert len(fr.ring) == 16  # bounded: cheap enough to leave on
        path = fr.dump("manual", extra={"marker": "x"})
        data = json.load(open(path))
        assert data["reason"] == "manual" and data["marker"] == "x"
        assert [e["i"] for e in data["events"]] == list(range(24, 40))
        assert "thread_stacks" in data and "compile_counters" in data

    def test_excepthook_chains_and_dumps(self, tmp_path):
        import sys

        from accelerate_tpu.telemetry.recorder import FlightRecorder

        fr = FlightRecorder(None, dump_dir=str(tmp_path))
        prev_called = []
        old_hook = sys.excepthook
        sys.excepthook = lambda *a: prev_called.append(a)
        try:
            fr.install_hooks()
            try:
                raise ValueError("boom-for-the-bundle")
            except ValueError:
                sys.excepthook(*sys.exc_info())
            assert fr.dump_count == 1
            assert prev_called, "previous excepthook must still run"
            data = json.load(open(fr.last_bundle_path))
            assert data["reason"] == "unhandled_exception"
            assert "boom-for-the-bundle" in data["exception"]
        finally:
            fr.uninstall_hooks()
            sys.excepthook = old_hook

    def test_sigterm_dumps_bundle_in_subprocess(self, tmp_path):
        """SIGTERM (the preemption path) must leave a debug bundle behind
        and still terminate the process with the default disposition."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import os, signal\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession\n"
            f"s = TelemetrySession(TelemetryConfig(trace_dir={str(tmp_path)!r}, "
            "spans=False, watchdog=False))\n"
            "s.flight.note('marker', detail='pre-term')\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "raise SystemExit('unreachable: SIGTERM must terminate')\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300, cwd=repo)
        assert r.returncode == -15, (r.returncode, r.stdout, r.stderr)
        bundles = sorted(tmp_path.glob("flightrec-host0-*.json"))
        assert bundles, r.stderr
        data = json.load(open(bundles[-1]))
        assert data["reason"] == "sigterm"
        assert any(e.get("kind") == "marker" for e in data["events"])


class TestRequestTracerDrain:
    def test_close_drains_inflight_as_evicted(self, tmp_path):
        """Requests still in flight at tracer close must reconcile: one
        record each with finish_reason 'evicted', not a silent gap."""
        from accelerate_tpu.telemetry.requests import RequestTracer

        path = str(tmp_path / "requests.jsonl")
        tracer = RequestTracer(None, path)
        req = types.SimpleNamespace(prompt=np.zeros(4, np.int32), id=7,
                                    max_new_tokens=8, submit_t=time.perf_counter())
        tracer.on_submit(req)
        assert [r["request_id"] for r in tracer.inflight()] == [7]
        tracer.close()
        recs = [json.loads(l) for l in open(path)]
        assert len(recs) == 1
        assert recs[0]["request_id"] == 7
        assert recs[0]["finish_reason"] == "evicted"
        assert recs[0]["total_ms"] >= 0 and "compiles_in_flight" in recs[0]
        assert tracer.inflight() == []


class TestCaptureWindow:
    def test_configured_step_window_opens_and_closes(self):
        from accelerate_tpu.telemetry.recorder import CaptureWindow

        calls = []
        cw = CaptureWindow("out", start_step=3, stop_step=5,
                           start_fn=lambda d: calls.append(("start", d)),
                           stop_fn=lambda: calls.append(("stop",)))
        for step in range(1, 9):
            cw.on_step(step)
        assert calls == [("start", "out"), ("stop",)]
        assert cw.captures == 1 and not cw.active

    def test_arm_opens_bounded_window_with_trigger_budget(self):
        from accelerate_tpu.telemetry.recorder import CaptureWindow

        calls = []
        cw = CaptureWindow("out", window_steps=3, max_auto_arms=1,
                           start_fn=lambda d: calls.append("start"),
                           stop_fn=lambda: calls.append("stop"))
        assert cw.arm("watchdog_stall")
        for step in range(10, 20):
            cw.on_step(step)
        assert calls == ["start", "stop"]  # window closed after 3 steps
        assert not cw.arm("again"), "auto-arm budget must bound trigger storms"

    def test_itl_slo_breach_auto_arms_via_session(self, tmp_path):
        """ITL p99 crossing the configured threshold arms a capture window
        on the very next recorded step."""
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), spans=False, watchdog=False,
            flight_hooks=False, profile_trigger_itl_p99_ms=5.0,
            profile_window_steps=2,
        ))
        try:
            calls = []
            session.capture._start_fn = lambda d: calls.append("start")
            session.capture._stop_fn = lambda: calls.append("stop")
            engine = types.SimpleNamespace(step_count=0)
            itl = session.histogram("serving/itl")
            for _ in range(20):
                itl.add(0.001)  # healthy: 1ms, under the 5ms SLO
            engine.step_count = 1
            session.on_step(engine, 0.01)
            assert calls == [] and session.capture.captures == 0
            for _ in range(8):
                itl.add(0.5)  # tail blows through the SLO
            for step in (2, 3, 4, 5):
                engine.step_count = step
                session.on_step(engine, 0.01)
            assert calls == ["start", "stop"]
            assert session.capture.captures == 1
        finally:
            session.close()


class TestExporter:
    def test_prometheus_text_renders_gauges_and_histograms(self, tmp_path):
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession
        from accelerate_tpu.telemetry.exporter import prometheus_text

        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), spans=False, watchdog=False,
            flight_hooks=False,
        ))
        try:
            h = session.histogram("serving/ttft")
            for v in (0.01, 0.02, 0.5):
                h.add(v)
            session.window.add({"step": 1, "wall_s": 0.5, "tokens": 100})
            text = prometheus_text(session)
            assert "# TYPE att_sys_tokens_per_s gauge" in text
            assert "# TYPE att_serving_ttft_seconds histogram" in text
            assert 'att_serving_ttft_seconds_bucket{le="+Inf"} 3' in text
            assert "att_serving_ttft_seconds_count 3" in text
            assert "att_serving_ttft_seconds_p99" in text
            # cumulative bucket counts are monotone
            cums = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
                    if l.startswith("att_serving_ttft_seconds_bucket")]
            assert cums == sorted(cums)
        finally:
            session.close()

    def test_scrape_thread_serves_metrics(self, tmp_path):
        import urllib.request

        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), spans=False, watchdog=False,
            flight_hooks=False, exporter_port=0,
        ))
        try:
            assert session.exporter is not None and session.exporter.port
            session.histogram("serving/itl").add(0.002)
            url = f"http://127.0.0.1:{session.exporter.port}/metrics"
            body = urllib.request.urlopen(url, timeout=10).read().decode()
            assert "att_serving_itl_seconds_count 1" in body
        finally:
            session.close()


class TestEngineIntegration:
    """Acceptance: a CPU-sim run with telemetry on produces per-step
    records through the JSONL tracker (step time, tokens/s, MFU), a valid
    Chrome-trace span file, and zero-cost hooks when disabled."""

    def test_fused_steps_feed_metrics_spans_and_tracker(self, tmp_path):
        import optax

        from accelerate_tpu import Accelerator, Model
        from accelerate_tpu.models import DecoderConfig, DecoderLM

        tel_dir = tmp_path / "telemetry"
        acc = Accelerator(
            log_with="jsonl", project_dir=str(tmp_path),
            telemetry=TelemetryConfig(trace_dir=str(tel_dir), metrics_jsonl=True),
        )
        acc.init_trackers("run")
        cfg = DecoderConfig.tiny(max_seq_len=64)
        model_def = DecoderLM(cfg, mesh=acc.mesh)
        variables = model_def.init_variables(jax.random.PRNGKey(0), batch_size=8, seq_len=16)
        model, opt = acc.prepare(Model(model_def, variables), optax.sgd(1e-3))
        step = acc.build_train_step()
        ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 16))
        batch = acc.prepare_for_eval({"input_ids": ids, "labels": ids})
        for _ in range(3):
            step(batch)
        # deliberately shape-varied step: forensics must diagnose the
        # recompile it pays, naming the argument and the aval change
        ids_v = np.random.RandomState(1).randint(0, cfg.vocab_size, (8, 24))
        step(acc.prepare_for_eval({"input_ids": ids_v, "labels": ids_v}))

        values = acc.log_system_metrics()
        for key in ("sys/step_time_s", "sys/tokens_per_s",
                    "sys/loss", "sys/grad_norm", "sys/step"):
            assert key in values, key
        # the CPU has no entry in the peak table: no MFU against a made-up peak
        assert "sys/mfu_pct" not in values
        assert values["sys/step"] == 4
        assert values["sys/tokens_per_s"] > 0

        # goodput ledger: every bucket present, fractions sum to ~1.0
        from accelerate_tpu.telemetry.goodput import BUCKETS

        fracs = [values[f"goodput/{b}_frac"] for b in BUCKETS]
        assert sum(fracs) == pytest.approx(1.0, abs=0.02)
        assert values["goodput/compile_frac"] > 0  # this run compiled
        # cost registry: the train-step executable has a roofline row
        assert values["exe/train_step_calls"] == 4
        assert values["exe/train_step_wall_s"] > 0
        assert "exe/train_step_arith_intensity" in values
        # forensics: the shape-varied recompile is diagnosed immediately
        # (still pending compile-delta attribution until finalized)
        assert values["sys/recompiles_diagnosed"] == 1

        # heartbeat published through the shared-dict state
        from accelerate_tpu.state import PartialState

        hb = PartialState().heartbeat
        assert hb is not None and hb[0] == 4

        acc.end_training()

        # (a) per-step records + rollup through the JSONL tracker
        tracked = [json.loads(l) for l in open(tmp_path / "run" / "metrics.jsonl")]
        assert any("sys/tokens_per_s" in rec["values"] for rec in tracked)
        per_step = [json.loads(l) for l in open(tel_dir / "metrics-host0.jsonl")]
        assert [r["step"] for r in per_step] == [1, 2, 3, 4]
        for rec in per_step[:3]:
            assert rec["tokens"] == 8 * 16
            assert "tokens_per_s" in rec and "wall_s" in rec
            assert "mfu_pct" not in rec  # no peak for the CPU, so no MFU

        # (b) the span file is a loadable Chrome trace with engine steps
        trace = spans_mod.load_chrome_trace(str(tel_dir / "trace-host0.jsonl"))
        steps_in_trace = [e for e in trace["traceEvents"]
                          if e.get("name") == "engine/train_step"]
        assert len(steps_in_trace) == 4
        assert all(e["ph"] == "X" and e["dur"] > 0 for e in steps_in_trace)

        # (c) the offline artifacts the report CLI reads landed at close
        gp = json.load(open(tel_dir / "goodput-host0.json"))
        assert sum(gp["fractions"].values()) == pytest.approx(1.0, abs=0.02)
        costs = json.load(open(tel_dir / "costs-host0.json"))
        names = [r["name"] for r in costs["executables"]]
        assert "train_step" in names
        # the recompile record finalized at close with its compile delta
        forens = [json.loads(l) for l in open(tel_dir / "forensics-host0.jsonl")]
        recompiles = [r for r in forens if r["event"] == "recompile"]
        assert len(recompiles) == 1
        assert "batch['input_ids'] changed" in recompiles[0]["cause"]
        assert "[8,16]" in recompiles[0]["cause"] and "[8,24]" in recompiles[0]["cause"]
        assert recompiles[0]["compile_events"] > 0

    def test_disabled_by_default_and_hooks_dormant(self):
        from accelerate_tpu import Accelerator

        acc = Accelerator()
        assert acc.telemetry is None
        with pytest.raises(RuntimeError, match="telemetry is not enabled"):
            acc.log_system_metrics()
