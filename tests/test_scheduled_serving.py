"""SLO-aware multi-tenant scheduling through the serving engine
(scheduler.py + faults.py wired into ServingEngine).

The contracts of record:
- preempted-and-resumed requests are TOKEN-EXACT vs uninterrupted
  generate() (greedy AND sampled) — page-out publishes the KV to the
  prefix cache, re-admission replays via cache hits and restores the
  saved RNG chain;
- post-steady scheduling actions (admit, preempt, page-out, re-admit,
  shed) incur ZERO recompiles (compile counters are the witness);
- admission control and load shedding are values, not exceptions:
  bounded queues, watermark sheds and page exhaustion all terminate
  requests with a definite outcome — ``step()``/``serve()`` never raise
  on pressure;
- under a seeded tenant-A prefill storm, tenant B's ITL p99 degrades by
  a bounded, asserted factor, and EVERY submitted request terminates
  with an explicit outcome (finished/shed/cancelled — never hung);
- page accounting survives 100 preempt → page-out → re-admit cycles
  (with forks and prefix hits interleaved) with refcounts at baseline;
- drain()/SIGTERM shutdown mid-burst finishes or sheds every request
  instead of abandoning the queue.
"""

import time

import numpy as np
import pytest

import jax

from accelerate_tpu.generation import generate
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import FaultInjector, SchedulerConfig, ServingEngine
from accelerate_tpu.serving.faults import poison_on_token
from accelerate_tpu.serving.scheduler import TenantConfig

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
    kw.setdefault("scheduler", SchedulerConfig())
    return ServingEngine(model, params, **kw)


def _ref(model, params, p, max_new, seed, temperature=0.0, top_k=None):
    return np.asarray(
        generate(model, params, p[None], max_new_tokens=max_new,
                 temperature=temperature, top_k=top_k,
                 rng=jax.random.PRNGKey(seed))[0]
    )


def _preempt_once(engine, low, high_kwargs):
    """Run until ``low`` has a few tokens, then submit a higher-priority
    request that steals its slot. Returns the high request."""
    while len(low.tokens) < 3 and not low.done:
        engine.step()
    high = engine.submit(**high_kwargs)
    return high


class TestPreemptResumeExactness:
    def test_greedy_paged_preempt_resume_token_exact(self, served_model):
        """The acceptance contract: page out mid-generation, re-admit via
        the prefix cache, and the final tokens equal an uninterrupted
        generate() run."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1)
        low = engine.submit(prompts[1], max_new_tokens=10, seed=3, priority=0)
        high = _preempt_once(engine, low, dict(
            prompt=prompts[0], max_new_tokens=4, seed=7, priority=5))
        engine.run()
        assert engine.preemptions == 1 and engine.resumptions == 1
        assert low.preemptions == 1 and low.outcome == "finished"
        assert high.outcome == "finished"
        # the replay rode the prefix cache the page-out populated
        assert low.prefix_hit >= PS
        np.testing.assert_array_equal(
            low.result(), _ref(model, params, prompts[1], 10, 3))
        np.testing.assert_array_equal(
            high.result(), _ref(model, params, prompts[0], 4, 7))

    def test_sampled_preempt_resume_token_exact(self, served_model):
        """Preemption must save/restore the slot's RNG chain exactly —
        sampled decoding is where a chain slip shows."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1, temperature=1.0, top_k=8)
        low = engine.submit(prompts[2], max_new_tokens=8, seed=11, priority=0)
        high = _preempt_once(engine, low, dict(
            prompt=prompts[3], max_new_tokens=3, seed=5, priority=9))
        engine.run()
        assert low.preemptions == 1
        np.testing.assert_array_equal(
            low.result(),
            _ref(model, params, prompts[2], 8, 11, temperature=1.0, top_k=8))
        np.testing.assert_array_equal(
            high.result(),
            _ref(model, params, prompts[3], 3, 5, temperature=1.0, top_k=8))

    def test_no_prefix_cache_preempt_resume_token_exact(self, served_model):
        """Without the prefix cache the resume re-prefills prompt+generated
        in full — slower, still exact (the evict-and-replay preemption mode)."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1, prefix_cache=False)
        low = engine.submit(prompts[1], max_new_tokens=8, seed=3, priority=0)
        high = _preempt_once(engine, low, dict(
            prompt=prompts[0], max_new_tokens=3, seed=2, priority=5))
        engine.run()
        assert low.preemptions == 1 and low.outcome == "finished"
        assert low.prefix_hit == 0  # nothing was published: a whole replay
        np.testing.assert_array_equal(
            low.result(), _ref(model, params, prompts[1], 8, 3))
        np.testing.assert_array_equal(
            high.result(), _ref(model, params, prompts[0], 3, 2))

    def test_scheduling_actions_zero_recompiles_post_steady(self, served_model):
        """The acceptance invariant: after warmup()+mark_steady(), admit /
        preempt / page-out / re-admit / shed are pure data changes — the
        compile counters must not move."""
        model, cfg, params, prompts = served_model
        engine = _engine(
            model, params, num_slots=1,
            scheduler=SchedulerConfig(max_queue_depth=3),
        )
        engine.warmup()
        engine.mark_steady()
        low = engine.submit(prompts[1], max_new_tokens=10, seed=3, priority=0)
        high = _preempt_once(engine, low, dict(
            prompt=prompts[0], max_new_tokens=4, seed=7, priority=5))
        # overflow the bounded queue post-steady -> shed (no device work)
        extra = [engine.submit(prompts[3], max_new_tokens=2, seed=9)
                 for _ in range(4)]
        engine.run()
        assert engine.preemptions >= 1 and engine.resumptions >= 1
        assert any(r.outcome == "shed" for r in extra)
        assert low.outcome == high.outcome == "finished"
        assert engine.admission_recompiles == 0
        m = engine.metrics()
        assert m["serving/admission_recompiles"] == 0
        assert m["serving/preemptions"] == engine.preemptions


class TestAdmissionControlAndShedding:
    def test_bounded_queue_sheds_at_submit(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(
            model, params,
            scheduler=SchedulerConfig(max_queue_depth=2),
        )
        reqs = [engine.submit(prompts[0], max_new_tokens=2, seed=i)
                for i in range(5)]
        shed = [r for r in reqs if r.outcome == "shed"]
        assert len(shed) == 3
        assert all(r.shed_reason == "queue_full" and r.done for r in shed)
        engine.run()
        assert all(r.outcome in ("finished", "shed") for r in reqs)
        assert engine.metrics()["serving/shed"] == 3

    def test_per_tenant_bound_isolates_the_noisy_tenant(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(
            model, params,
            scheduler=SchedulerConfig(
                tenants={"noisy": TenantConfig(max_queued=1)}),
        )
        noisy = [engine.submit(prompts[0], max_new_tokens=2, seed=i,
                               tenant="noisy") for i in range(4)]
        quiet = engine.submit(prompts[3], max_new_tokens=2, seed=9,
                              tenant="quiet")
        assert sum(r.outcome == "shed" for r in noisy) >= 1
        assert quiet.outcome is None  # the bound is per tenant
        engine.run()
        assert quiet.outcome == "finished"

    def test_page_exhaustion_sheds_instead_of_raising(self, served_model):
        """The overcommit failure-mode fix: an admission that cannot get
        pages (even after LRU eviction) is shed with a telemetry-visible
        reason; step()/run() never raise, and later smaller requests
        still serve."""
        model, cfg, params, prompts = served_model
        # 1 slot, only 3 usable pages (24 tokens of KV) and no prefix
        # cache to evict: a 12-token prompt + 20 new tokens cannot fit
        engine = _engine(model, params, num_slots=1, num_pages=4,
                         prefix_cache=False)
        big = engine.submit(prompts[2], max_new_tokens=20, seed=0)
        engine.run()  # must not raise
        assert big.outcome == "shed" and big.shed_reason == "page_exhausted"
        small = engine.submit(prompts[3], max_new_tokens=3, seed=1)
        engine.run()
        assert small.outcome == "finished"
        np.testing.assert_array_equal(
            small.result(), _ref(model, params, prompts[3], 3, 1))
        assert engine.metrics()["serving/shed"] == 1

    def test_generate_batched_raises_loudly_on_overcommit(self, served_model):
        """The batch API must never hand back silently truncated output:
        with no scheduler to preempt for it, a shed-under-pressure request
        turns the whole generate_batched() call into a RuntimeError (the
        pre-scheduler behavior, kept loud)."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1, num_pages=4,
                         prefix_cache=False, scheduler=None)
        with pytest.raises(RuntimeError, match="did not finish"):
            engine.generate_batched([prompts[2]], max_new_tokens=20)

    def test_admission_pressure_preempts_lower_priority_victim(self, served_model):
        """A high-priority ADMISSION that cannot get pages pages out a
        strictly-lower victim before giving up — same ladder as live-slot
        growth. Shedding the admission first would drop the highest-
        priority work under pressure (priority inversion)."""
        model, cfg, params, prompts = served_model
        # 4 usable pages. The low request grows to 3 pages (12-token
        # prompt past position 16), leaving 1 free — the high admission
        # needs 2, so its second prefill chunk hits PagePressure with a
        # free slot available (no _maybe_preempt) and must preempt low.
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=24,
            prefill_chunks=(4, 8), page_size=PS, num_pages=5,
            prefix_cache=False, scheduler=SchedulerConfig(),
        )
        low = engine.submit(prompts[2], max_new_tokens=10, seed=1, priority=0)
        while len(low.tokens) < 7 and not low.done:
            engine.step()
        assert not low.done
        high = engine.submit(prompts[1], max_new_tokens=4, seed=2, priority=5)
        engine.run()
        assert high.outcome == "finished"  # was shed before the fix
        assert engine.preemptions >= 1 and low.preemptions >= 1
        np.testing.assert_array_equal(
            high.result(), _ref(model, params, prompts[1], 4, 2))
        # the victim still terminates definitely; exact if it finished
        assert low.outcome in ("finished", "shed")
        if low.outcome == "finished":
            np.testing.assert_array_equal(
                low.result(), _ref(model, params, prompts[2], 10, 1))

    def test_decode_growth_pressure_preempts_lower_priority_victim(self, served_model):
        """When a live high-priority slot cannot grow its pages, the
        scheduler pages out a strictly-lower-priority victim instead of
        wedging — and the victim still finishes exactly after resume."""
        model, cfg, params, prompts = served_model
        # 2 slots x 3 pages/slot worth of KV, but only 5 usable pages:
        # both slots growing past their shared budget forces the fight —
        # the high-priority slot's page-2 grow finds the arena dry and
        # must page out the low slot rather than raise. Both requests run
        # long enough (16 and 20 tokens) that neither finishes before the
        # other needs its third page.
        engine = ServingEngine(
            model, params, num_slots=2, max_cache_len=24,
            prefill_chunks=(4, 8), page_size=PS, num_pages=6,
            prefix_cache=False, scheduler=SchedulerConfig(),
        )
        low = engine.submit(prompts[1], max_new_tokens=16, seed=1, priority=0)
        high = engine.submit(prompts[3], max_new_tokens=20, seed=2, priority=5)
        engine.run()
        assert high.outcome == "finished"
        assert low.outcome in ("finished", "shed")
        assert engine.preemptions >= 1
        np.testing.assert_array_equal(
            high.result(), _ref(model, params, prompts[3], 20, 2))
        if low.outcome == "finished":
            np.testing.assert_array_equal(
                low.result(), _ref(model, params, prompts[1], 16, 1))

    def test_watermark_shed_under_injected_page_squeeze(self, served_model):
        """A fault-injected page squeeze drops the free fraction below
        the watermark: the newest lowest-priority queued request is shed
        (lowest-priority-first), higher classes keep flowing."""
        model, cfg, params, prompts = served_model
        faults = FaultInjector(seed=0).squeeze_pages(
            at_step=0, pages=64, hold_steps=10_000
        )
        engine = ServingEngine(
            model, params, num_slots=1, max_cache_len=64,
            prefill_chunks=(4, 8), page_size=PS,
            num_pages=1 + 8 + 64,  # squeeze leaves ~1 slot's worth free
            scheduler=SchedulerConfig(page_low_watermark=0.5),
            faults=faults,
        )
        hi = engine.submit(prompts[3], max_new_tokens=2, seed=0, priority=5)
        lo = [engine.submit(prompts[0], max_new_tokens=2, seed=i, priority=0)
              for i in range(3)]
        engine.run()
        faults.release_all(engine)
        assert hi.outcome == "finished"
        assert any(r.outcome == "shed" and r.shed_reason == "page_pressure"
                   for r in lo)
        assert any(k == "squeeze_pages" for _, k, _ in faults.log)

    def test_watermark_shed_never_drops_work_preemption_could_place(self, served_model):
        """Priority-inversion guard: under watermark pressure the shed
        pick is bounded to classes no live slot loses to. A lone queued
        high-priority request with low-priority slots pinning the arena
        is preemption's job — shedding it first would drop the highest-
        priority work in the system."""
        model, cfg, params, prompts = served_model
        # armed at step 3: lo must be LIVE (pinning its pages) before the
        # squeeze, or the watermark shed drops it straight out of the queue
        faults = FaultInjector(seed=0).squeeze_pages(
            at_step=3, pages=68, hold_steps=10_000
        )
        engine = ServingEngine(
            model, params, num_slots=1, max_cache_len=64,
            prefill_chunks=(4, 8), page_size=PS, num_pages=1 + 8 + 64,
            scheduler=SchedulerConfig(page_low_watermark=0.5),
            faults=faults,
        )
        lo = engine.submit(prompts[2], max_new_tokens=10, seed=1, priority=0)
        while len(lo.tokens) < 1 and not lo.done:
            engine.step()
        assert not lo.done
        hi = engine.submit(prompts[3], max_new_tokens=2, seed=0, priority=5)
        engine.run()
        faults.release_all(engine)
        # hi was never shed: the low-priority slot was paged out for it
        assert hi.outcome == "finished" and engine.preemptions >= 1
        np.testing.assert_array_equal(
            hi.result(), _ref(model, params, prompts[3], 2, 0))
        assert lo.outcome in ("finished", "shed")

    def test_preemptible_submit_requires_replayable_worst_case(self, served_model):
        """A preemptible request must be re-admittable at any progress
        point: a prompt that plans fine cold but whose worst-case replay
        (prompt + all-but-one generated) cannot chunk-plan within the
        slot is rejected at submit — not an index error mid-resume."""
        model, cfg, params, prompts = served_model
        rng = np.random.RandomState(9)
        p16 = rng.randint(3, cfg.vocab_size, (16,))
        # bucket 16, cap 24: the prompt is one 16-chunk, but a replay of
        # 16+7=23 tokens pads to two 16-chunks = 32 > 24
        kw = dict(num_slots=1, max_cache_len=24, prefill_chunks=(16,),
                  page_size=PS)
        engine = ServingEngine(model, params, scheduler=SchedulerConfig(), **kw)
        with pytest.raises(ValueError, match="KV capacity"):
            engine.submit(p16, max_new_tokens=8, seed=0)
        # with preemption off the cold plan is the only one that must fit
        engine2 = ServingEngine(
            model, params, scheduler=SchedulerConfig(preemption=False), **kw)
        assert engine2.submit(p16, max_new_tokens=8, seed=0).outcome is None

    def test_idle_steps_do_not_move_the_itl_controller(self, served_model):
        """The controller observes fresh ITL gaps, not wall-clock steps:
        an idle engine polling in serve() must not replay the last
        window's p99 into breaches/budget at step rate."""
        model, cfg, params, prompts = served_model
        engine = _engine(
            model, params,
            scheduler=SchedulerConfig(itl_slo_ms=1e-6),  # unreachable SLO
        )
        req = engine.submit(prompts[1], max_new_tokens=12, seed=0)
        engine.run()
        assert req.outcome == "finished"
        breaches = engine._controller.breaches
        budget = engine._controller.budget
        assert breaches > 0  # the run itself breached the absurd SLO
        for _ in range(64):  # idle iterations: no new gaps, no new evidence
            engine.step()
        assert engine._controller.breaches == breaches
        assert engine._controller.budget == budget

    def test_poisoned_request_cancelled_not_loop_killed(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(model, params)
        bad = engine.submit(prompts[0], max_new_tokens=4, seed=0,
                            on_token=poison_on_token)
        ok = engine.submit(prompts[3], max_new_tokens=3, seed=1)
        engine.run()  # must not raise
        assert bad.outcome == "cancelled" and bad.finish_reason == "callback_error"
        assert ok.outcome == "finished"
        assert engine.metrics()["serving/cancelled"] == 1


class TestCancelAndTimeout:
    def test_cancel_frees_slot_and_pages_immediately(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1, prefix_cache=False)
        req = engine.submit(prompts[1], max_new_tokens=30, seed=0)
        while len(req.tokens) < 2:
            engine.step()
        pages_live = engine._allocator.in_use
        assert pages_live > 0
        assert req.cancel()
        engine.step()
        assert req.outcome == "cancelled" and req.finish_reason == "cancelled"
        assert req.slot is None and engine._allocator.in_use == 0
        assert len(engine._free) == 1
        # the engine is immediately reusable
        nxt = engine.submit(prompts[3], max_new_tokens=2, seed=4)
        engine.run()
        assert nxt.outcome == "finished"

    def test_timeout_cancels_queued_and_live(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1)
        live = engine.submit(prompts[0], max_new_tokens=40, seed=0,
                             timeout_s=0.001)
        queued = engine.submit(prompts[1], max_new_tokens=2, seed=1,
                               timeout_s=0.001)
        fresh = engine.submit(prompts[3], max_new_tokens=2, seed=2)
        time.sleep(0.01)
        engine.run()
        assert live.outcome == "cancelled" and live.finish_reason == "timeout"
        assert queued.outcome == "cancelled" and queued.finish_reason == "timeout"
        assert fresh.outcome == "finished"

    def test_cancelled_lands_in_request_log_as_cancelled(self, served_model, tmp_path):
        """Satellite contract: a cancelled/timed-out request is a
        ``cancelled`` record in requests-host*.jsonl at finish time — not
        an ``evicted`` orphan at tracer close."""
        import json as json_mod

        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        model, cfg, params, prompts = served_model
        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), watchdog=False, flight_hooks=False,
        ))
        try:
            engine = _engine(model, params, num_slots=1, telemetry=session)
            req = engine.submit(prompts[1], max_new_tokens=30, seed=0)
            while len(req.tokens) < 2:
                engine.step()
            req.cancel()
            done = engine.submit(prompts[3], max_new_tokens=2, seed=1)
            engine.run()
            # records exist BEFORE session close — no evicted drain needed
            recs = [json_mod.loads(l)
                    for l in open(tmp_path / "requests-host0.jsonl")]
            by_id = {r["request_id"]: r for r in recs}
            assert by_id[req.id]["outcome"] == "cancelled"
            assert by_id[req.id]["finish_reason"] == "cancelled"
            assert by_id[done.id]["outcome"] == "finished"
            assert by_id[req.id]["tenant"] == "default"
        finally:
            session.close()


class TestDrain:
    def test_drain_mid_burst_finishes_or_sheds_everything(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1)
        reqs = [engine.submit(prompts[i % 4], max_new_tokens=4, seed=i)
                for i in range(5)]
        while not any(r.tokens for r in reqs):
            engine.step()
        summary = engine.drain()
        assert all(r.done and r.outcome in ("finished", "shed") for r in reqs)
        assert any(r.outcome == "shed" and r.shed_reason == "draining"
                   for r in reqs)
        assert summary["completed"] + summary["shed"] == len(reqs)
        # drained engines refuse new work with a shed, not a hang
        late = engine.submit(prompts[0], max_new_tokens=2, seed=9)
        assert late.outcome == "shed" and late.shed_reason == "draining"

    def test_drain_timeout_cancels_stragglers(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1)
        req = engine.submit(prompts[0], max_new_tokens=50, seed=0)
        while len(req.tokens) < 1:
            engine.step()
        engine.drain(timeout_s=0.0)
        assert req.outcome == "cancelled" and req.finish_reason == "drain_timeout"
        assert not engine._slot_req and len(engine._free) == engine.num_slots

    def test_sigterm_drains_serving_in_subprocess(self, served_model, tmp_path):
        """The SIGTERM flight-recorder hook requests a drain: shutdown
        mid-burst leaves EVERY submitted request with a definite outcome
        in the request log (finished or shed) — never an abandoned-queue
        ``evicted``."""
        import json as json_mod
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = (
            "import os, signal, sys, json\n"
            "import numpy as np\n"
            "import jax\n"
            "from accelerate_tpu.generation import generate\n"
            "from accelerate_tpu.models import DecoderConfig, DecoderLM\n"
            "from accelerate_tpu.parallel.sharding import unbox_params\n"
            "from accelerate_tpu.serving import SchedulerConfig, ServingEngine\n"
            "from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession\n"
            "signal.signal(signal.SIGTERM, lambda *a: None)  # benign chain target\n"
            f"session = TelemetrySession(TelemetryConfig(trace_dir={str(tmp_path)!r}, "
            "spans=False, watchdog=False, flight_hooks=True))\n"
            "cfg = DecoderConfig.tiny(max_seq_len=64)\n"
            "model = DecoderLM(cfg)\n"
            "v = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)\n"
            "params, _ = unbox_params(v['params'])\n"
            "rng = np.random.RandomState(0)\n"
            "engine = ServingEngine(model, params, num_slots=1, max_cache_len=64, "
            "prefill_chunks=(4, 8), page_size=8, scheduler=SchedulerConfig(), "
            "telemetry=session)\n"
            "reqs = [engine.submit(rng.randint(3, cfg.vocab_size, (6,)), "
            "max_new_tokens=4, seed=i) for i in range(4)]\n"
            "while not any(r.tokens for r in reqs):\n"
            "    engine.step()\n"
            "os.kill(os.getpid(), signal.SIGTERM)  # dump + request_drain + chain\n"
            "assert engine._draining, 'SIGTERM hook must request the drain'\n"
            "engine.serve()  # finishes in-flight, queued already shed\n"
            "session.close()\n"
            "print('OUTCOMES ' + json.dumps([r.outcome for r in reqs]))\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=240, cwd=repo)
        assert r.returncode == 0, r.stdout + r.stderr
        outcomes = json_mod.loads(r.stdout.split("OUTCOMES ", 1)[1])
        assert all(o in ("finished", "shed") for o in outcomes), outcomes
        assert "shed" in outcomes and "finished" in outcomes
        recs = [json_mod.loads(l)
                for l in open(tmp_path / "requests-host0.jsonl")]
        assert len(recs) == 4
        assert all(rec["outcome"] in ("finished", "shed") for rec in recs)
        assert not any(rec["outcome"] == "evicted" for rec in recs)
        # the bundle the hook dumped before draining is there too
        assert sorted(tmp_path.glob("flightrec-host0-*.json"))


class TestPageLeak:
    def test_no_leak_across_100_preempt_resume_cycles(self, served_model):
        """Satellite contract: allocator refcounts return to baseline
        after 100 preempt → page-out → re-admit cycles with COW forks and
        prefix hits interleaved."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, num_slots=1)
        free0 = engine._allocator.free_count
        rng = np.random.RandomState(5)
        hits = forks0 = 0
        for i in range(100):
            if i % 3 == 0:
                p = prompts[2]  # recurring template -> prefix hits + forks
            else:
                p = rng.randint(3, cfg.vocab_size, (4 + i % 9,))
            low = engine.submit(p, max_new_tokens=4, seed=i, priority=0)
            while len(low.tokens) < 2 and not low.done:
                engine.step()
            hi = engine.submit(prompts[3], max_new_tokens=1, seed=i,
                               priority=5)
            engine.run()
            assert low.outcome == "finished" and hi.outcome == "finished"
            hits = engine._prefix.hits
        assert engine.preemptions >= 90  # nearly every cycle preempted
        assert engine.resumptions == engine.preemptions
        assert hits >= 30 and engine.page_forks >= 1
        # only prefix-cache refs remain; clearing them drains the arena
        engine._prefix.clear()
        assert engine._allocator.in_use == 0
        assert engine._allocator.free_count == free0


def _isolation_burst(model, cfg, params, *, storm: bool, chunk_delay_s: float,
                     slo_ms: float):
    """One seeded mixed-tenant run. Tenant B ('interactive', priority 5)
    sends short prompts; with ``storm``, tenant A ('batch', priority 0)
    floods long prompts mid-flight via the fault injector. Injected
    prefill delays make chunk cost deterministic, so B's ITL measures
    *scheduling* interference, not CPU noise. Returns (b_gaps_ms, reqs,
    engine)."""
    rng = np.random.RandomState(42)
    stamps = {}  # request id -> [perf_counter per token]

    def stamp(tok, req):
        stamps.setdefault(req.id, []).append(time.perf_counter())

    faults = FaultInjector(seed=1).delay_prefill(every=1, delay_s=chunk_delay_s)
    a_prompts = [rng.randint(3, cfg.vocab_size, (24,)) for _ in range(4)]
    a_reqs = []

    if storm:
        def fire(engine):
            for i, p in enumerate(a_prompts):
                a_reqs.append(engine.submit(
                    p, max_new_tokens=3, seed=100 + i,
                    tenant="batch", priority=0,
                ))
        faults.storm(at_step=2, fire=fire)

    engine = ServingEngine(
        model, params, num_slots=2, max_cache_len=64, prefill_chunks=(4,),
        page_size=PS, scheduler=SchedulerConfig(itl_slo_ms=slo_ms),
        faults=faults,
    )
    engine.warmup()
    engine.mark_steady()
    b_prompts = [rng.randint(3, cfg.vocab_size, (4,)) for _ in range(4)]
    b_reqs = [engine.submit(p, max_new_tokens=12, seed=i, tenant="interactive",
                            priority=5, on_token=stamp)
              for i, p in enumerate(b_prompts)]
    engine.run()
    gaps = []
    for req in b_reqs:
        ts = stamps.get(req.id, [])
        gaps += [1e3 * (b - a) for a, b in zip(ts, ts[1:])]
    return gaps, b_reqs + a_reqs, engine


class TestMixedTenantIsolation:
    def test_storm_isolation_smoke(self, served_model):
        """Tier-1 smoke (small arena, seeded faults): tenant A's prefill
        storm moves tenant B's ITL p99 by a bounded factor, every request
        terminates with an explicit outcome, and the burst is
        zero-recompile post-steady."""
        model, cfg, params, prompts = served_model
        delay = 0.012
        slo = 1e3 * delay + 10.0
        base_gaps, base_reqs, base_engine = _isolation_burst(
            model, cfg, params, storm=False, chunk_delay_s=delay, slo_ms=slo)
        storm_gaps, storm_reqs, storm_engine = _isolation_burst(
            model, cfg, params, storm=True, chunk_delay_s=delay, slo_ms=slo)
        p99_base = float(np.percentile(base_gaps, 99))
        p99_storm = float(np.percentile(storm_gaps, 99))
        # the bounded-degradation contract: with the ITL-budget controller
        # interleaving at most ~1 storm chunk between B's tokens, B's p99
        # under the storm is bounded by its clean p99 plus one injected
        # chunk (x3 margin for scheduler + dispatch overhead). An
        # unisolated interleave would stack several 12 ms chunks per gap.
        bound = 3.0 * (p99_base + 1e3 * delay)
        assert p99_storm <= bound, (p99_storm, p99_base, bound)
        # every submitted request reached a definite outcome — never hung
        for req in base_reqs + storm_reqs:
            assert req.done and req.outcome in ("finished", "shed", "cancelled")
        # B (priority 5) never queued behind the storm: all finished
        assert all(r.outcome == "finished" for r in storm_reqs
                   if r.tenant == "interactive")
        # post-steady storm scheduling was zero-recompile
        assert storm_engine.admission_recompiles == 0
        m = storm_engine.metrics()
        assert "serving/itl_budget" in m
        assert m["serving/quota_interactive_tokens_used"] >= 12

    def test_controller_cuts_prefill_budget_under_breach(self, served_model):
        """The observe→act loop: with an unreachable SLO the controller
        must back the chunks-per-step budget off its starting point."""
        model, cfg, params, prompts = served_model
        _, reqs, engine = _isolation_burst(
            model, cfg, params, storm=True, chunk_delay_s=0.012, slo_ms=2.0)
        assert engine._controller.breaches > 0
        assert engine._controller.budget < 1.0
        assert engine.metrics()["serving/itl_budget"] < 1.0
        assert all(r.done for r in reqs)


@pytest.mark.slow
class TestFaultSweep:
    def test_seeded_fault_sweep_every_request_terminates(self, served_model):
        """The long haul: delays + page squeezes + storms + a poisoned
        request across several seeds — every request reaches a definite
        outcome, no leak, zero recompiles post-steady."""
        model, cfg, params, prompts = served_model
        for seed in (0, 1, 2):
            rng = np.random.RandomState(seed)
            faults = (
                FaultInjector(seed=seed)
                .delay_decode(prob=0.2, delay_s=0.002)
                .delay_prefill(every=3, delay_s=0.004)
                .squeeze_pages(at_step=6, pages=10, hold_steps=6)
            )
            engine = ServingEngine(
                model, params, num_slots=3, max_cache_len=64,
                prefill_chunks=(4, 8), page_size=PS,
                scheduler=SchedulerConfig(
                    itl_slo_ms=25.0, max_queue_depth=12,
                    tenants={"noisy": TenantConfig(max_queued=3, quota=64.0)},
                ),
                faults=faults,
            )
            engine.warmup()
            engine.mark_steady()
            reqs = []
            for i in range(18):
                tenant = ("noisy", "steady", "vip")[i % 3]
                prio = {"noisy": 0, "steady": 2, "vip": 5}[tenant]
                kw = {}
                if i == 7:
                    kw["on_token"] = poison_on_token
                if i == 11:
                    kw["timeout_s"] = 0.0
                reqs.append(engine.submit(
                    rng.randint(3, cfg.vocab_size, (3 + (i * 7) % 20,)),
                    max_new_tokens=2 + i % 6, seed=i, tenant=tenant,
                    priority=prio, **kw,
                ))
                if i % 5 == 4:
                    for _ in range(3):
                        engine.step()
            engine.run()
            faults.release_all(engine)
            for req in reqs:
                assert req.done, (seed, req.id)
                assert req.outcome in ("finished", "shed", "cancelled"), (
                    seed, req.id, req.outcome)
            assert any(r.outcome == "cancelled" for r in reqs)
            assert engine.admission_recompiles == 0
            engine._prefix.clear()
            assert engine._allocator.in_use == 0
