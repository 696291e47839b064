"""Multi-replica serving data plane: replica server, KV handoff, kill
drills (accelerate_tpu/serving/replica_server.py + router.py over real
engines).

The contracts of record:
- the HTTP JSONL surface streams exactly the engine's tokens (submit /
  stream / cancel), and SIGTERM-style drain finishes in-flight streams
  while shedding new work with shed_reason=draining;
- KV handoff ships quantized payload+scales pages VERBATIM: a replica
  importing a peer's cached prefix admits it on the prefix-hit path
  (prefill chunks skipped) with a BIT-IDENTICAL stream vs local
  warm-cache admission — and the import itself compiles nothing on a
  warmed engine;
- THE kill drill (tier-1, 2 in-process replicas; slow-marked
  3-subprocess SIGKILL variant): hard-fail a replica mid-burst and
  every submitted request reaches a definite outcome via router
  re-queue, token-exact vs a single-replica reference, the victim is
  excluded within one poll, and the survivor reports ZERO post-steady
  recompiles.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving.engine import ServingEngine
from accelerate_tpu.serving.replica_server import ReplicaServer
from accelerate_tpu.serving.router import Router, RouterConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAGE = 4
CACHE = 64
CHUNKS = (4, 8)


@pytest.fixture(scope="module")
def served_model():
    cfg = DecoderConfig.tiny(max_seq_len=CACHE)
    model = DecoderLM(cfg)
    variables = model.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=16
    )
    params, _ = unbox_params(variables["params"])
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, cfg.vocab_size, (n,)) for n in (12, 8, 5, 10)]
    return model, cfg, params, prompts


def _engine(model, params, name=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_cache_len", CACHE)
    kw.setdefault("prefill_chunks", CHUNKS)
    kw.setdefault("page_size", PAGE)
    return ServingEngine(model, params, replica=name, **kw)


def _refs(model, params, prompts, max_new, seeds):
    """Single-replica reference streams (generated tails), one fresh
    engine — the token-exactness oracle every drill compares against."""
    engine = _engine(model, params)
    outs = engine.generate_batched(prompts, max_new_tokens=max_new,
                                   seeds=seeds)
    return [
        [int(t) for t in out[p.size:]] for out, p in zip(outs, prompts)
    ]


def _post_jsonl(url, payload, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return [json.loads(l) for l in resp.read().splitlines() if l.strip()]


class TestReplicaServerHttp:
    def test_stream_matches_engine_and_scrape_serves(self, served_model):
        model, cfg, params, prompts = served_model
        refs = _refs(model, params, prompts[:2], 5, seeds=[0, 1])
        engine = _engine(model, params, name="solo")
        engine.warmup()
        server = ReplicaServer(engine, name="solo").start()
        try:
            for p, ref, seed in zip(prompts[:2], refs, [0, 1]):
                events = _post_jsonl(f"{server.url}/v1/submit", {
                    "prompt": [int(t) for t in p], "max_new_tokens": 5,
                    "seed": seed, "stream": True,
                })
                toks = [e["token"] for e in events if e["event"] == "token"]
                done = events[-1]
                assert done["event"] == "done"
                assert done["outcome"] == "finished"
                assert done["replica"] == "solo"
                assert toks == ref
                assert done["tokens"] == ref
            # non-streamed variant: one JSON document
            req = urllib.request.Request(
                f"{server.url}/v1/submit",
                data=json.dumps({
                    "prompt": [int(t) for t in prompts[0]],
                    "max_new_tokens": 5, "seed": 0, "stream": False,
                }).encode(),
            )
            with urllib.request.urlopen(req, timeout=30) as resp:
                done = json.loads(resp.read())
            assert done["tokens"] == refs[0]
            # the Prometheus scrape rides the same port: the fleet
            # collector (and through it the router) needs nothing else
            with urllib.request.urlopen(f"{server.url}/metrics", timeout=5) as r:
                text = r.read().decode()
            assert "att_serving_load_score" in text
            assert "att_serving_generated_tokens" in text
        finally:
            server.close()

    def test_cancel_endpoint_frees_the_request(self, served_model):
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, name="c")
        engine.warmup()
        server = ReplicaServer(engine).start()
        try:
            events = []

            def run():
                events.extend(_post_jsonl(f"{server.url}/v1/submit", {
                    "prompt": [int(t) for t in prompts[2]],
                    "max_new_tokens": 40, "seed": 0, "stream": True,
                    "request_id": "kill-me",
                }, timeout=60))

            t = threading.Thread(target=run, daemon=True)
            t.start()
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    got = _post_jsonl(f"{server.url}/v1/cancel",
                                      {"request_id": "kill-me"})
                except urllib.error.HTTPError:
                    got = None  # 404: the submit has not registered yet
                if got and got[0].get("ok"):
                    break
                time.sleep(0.01)
            t.join(timeout=30)
            assert not t.is_alive(), "cancelled stream never terminated"
            done = events[-1]
            assert done["event"] == "done"
            assert done["outcome"] in ("cancelled", "finished")
        finally:
            server.close()

    def test_drain_sheds_new_work_finishes_streams(self, served_model):
        """The drain choreography: request_drain() mid-stream -> the
        in-flight stream still reaches its terminal event; a subsequent
        submit sheds with shed_reason=draining; /metrics exports the
        draining gauge the health machine keys on."""
        model, cfg, params, prompts = served_model
        engine = _engine(model, params, name="d")
        engine.warmup()
        server = ReplicaServer(engine).start()
        try:
            events = []

            def run():
                events.extend(_post_jsonl(f"{server.url}/v1/submit", {
                    "prompt": [int(t) for t in prompts[1]],
                    "max_new_tokens": 12, "seed": 0, "stream": True,
                }, timeout=60))

            t = threading.Thread(target=run, daemon=True)
            t.start()
            deadline = time.time() + 30
            while not engine._slot_req and time.time() < deadline:
                time.sleep(0.005)  # wait until the request is live
            server.request_drain()
            t.join(timeout=30)
            assert not t.is_alive()
            assert events[-1]["event"] == "done"
            assert events[-1]["outcome"] == "finished"  # stream completed
            late = _post_jsonl(f"{server.url}/v1/submit", {
                "prompt": [int(t) for t in prompts[2]],
                "max_new_tokens": 4, "seed": 0, "stream": True,
            })
            assert late[-1]["outcome"] == "shed"
            assert late[-1]["shed_reason"] == "draining"
            assert server.serve_until_drained(timeout_s=30)
        finally:
            server.close()


class TestKvHandoff:
    def test_handoff_prefix_hit_bit_identical_vs_local_warm_cache(
        self, served_model
    ):
        """The acceptance contract: A serves a prompt (warming its
        prefix cache), hands the pages to B verbatim; B's admission of
        that prompt takes the prefix-hit path (prefill chunks skipped,
        same hit length as A's own warm re-admission) and the whole
        stream — first sampled token included — is bit-identical."""
        model, cfg, params, prompts = served_model
        p = prompts[0]  # 12 tokens: 3 full pages at PAGE=4
        a = _engine(model, params, name="A")
        b = _engine(model, params, name="B")
        a.warmup()
        b.warmup()
        # wave 1 on A: cold admission, fills + publishes the pages
        a.submit(p, max_new_tokens=4, seed=0)
        a.run()
        # wave 2 on A: the LOCAL warm-cache reference admission
        ra = a.submit(p, max_new_tokens=4, seed=7)
        skipped_before = a.prefill_chunks_skipped
        a.run()
        assert ra.prefix_hit > 0, "local warm admission must hit"
        assert a.prefill_chunks_skipped >= skipped_before

        handoff = a.export_prefix_kv(p)
        assert handoff is not None
        assert handoff["page_size"] == PAGE
        assert handoff["n_pages"] == -(-handoff["token_len"] // PAGE)
        assert handoff["replica"] == "A"
        # wire format: verbatim bytes per K/V leaf (payload AND any
        # scale leaves travel together)
        assert all(l["data"] for l in handoff["leaves"])
        # the handoff survives a JSON round trip (it IS the wire format)
        handoff = json.loads(json.dumps(handoff))

        b.mark_steady()
        installed = b.import_prefix_kv(handoff)
        assert installed == handoff["token_len"]
        rb = b.submit(p, max_new_tokens=4, seed=7)
        b.run()
        assert rb.prefix_hit == ra.prefix_hit, (
            "imported pages must admit exactly like the local warm cache"
        )
        assert b.prefill_chunks_skipped > 0
        # bit-identical: first sampled token and the whole stream
        assert rb.tokens == ra.tokens
        # zero post-steady recompiles across import + hit admission:
        # the install program was compiled at warmup
        assert b.admission_recompiles == 0
        m = b.metrics()
        assert m["serving/kv_pages_imported"] == handoff["n_pages"]
        assert a.metrics()["serving/kv_pages_exported"] == handoff["n_pages"]

    def test_import_rejects_incompatible_wire_format(self, served_model):
        model, cfg, params, prompts = served_model
        a = _engine(model, params)
        b = _engine(model, params)
        a.warmup()
        b.warmup()
        a.submit(prompts[0], max_new_tokens=2, seed=0)
        a.run()
        handoff = a.export_prefix_kv(prompts[0])
        bad = dict(handoff, page_size=PAGE * 2)
        with pytest.raises(ValueError, match="page_size"):
            b.import_prefix_kv(bad)
        # a peer on the build before the int4 layout change: same schema,
        # other bytes — the version is what tells them apart
        bad = dict(handoff, version=1)
        with pytest.raises(ValueError, match="version"):
            b.import_prefix_kv(bad)
        bad = dict(handoff, kv_cache_dtype="int8")
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            b.import_prefix_kv(bad)
        bad = dict(handoff, leaves=handoff["leaves"][:-1])
        with pytest.raises(ValueError, match="leaves"):
            b.import_prefix_kv(bad)
        # an engine without the prefix cache has nothing to hand off
        bare = ServingEngine(model, params, num_slots=1, max_cache_len=CACHE,
                             prefill_chunks=CHUNKS, prefix_cache=False)
        with pytest.raises(ValueError, match="prefix cache"):
            bare.export_prefix_kv(prompts[0])

    def test_quantized_handoff_ships_scales_verbatim(self, served_model):
        """int8 arena: the scale leaves ride the same wire and the
        imported admission still matches the local warm one."""
        model, cfg, params, prompts = served_model
        p = prompts[0]
        a = _engine(model, params, kv_cache_dtype="int8")
        b = _engine(model, params, kv_cache_dtype="int8")
        a.warmup()
        b.warmup()
        a.submit(p, max_new_tokens=3, seed=0)
        a.run()
        ra = a.submit(p, max_new_tokens=3, seed=9)
        a.run()
        handoff = a.export_prefix_kv(p)
        # int8 payloads + fp32 scales both present in the leaf set
        dtypes = {l["dtype"] for l in handoff["leaves"]}
        assert "int8" in dtypes and "float32" in dtypes
        assert b.import_prefix_kv(handoff) == handoff["token_len"]
        rb = b.submit(p, max_new_tokens=3, seed=9)
        b.run()
        assert rb.prefix_hit == ra.prefix_hit > 0
        assert rb.tokens == ra.tokens


class TestKillDrillTwoReplicas:
    """THE robustness acceptance drill, tier-1 form: two in-process
    replicas behind the router; the one serving the burst hard-fails
    mid-stream (the in-process stand-in for SIGKILL)."""

    def test_kill_mid_burst_every_request_token_exact(self, served_model):
        model, cfg, params, prompts = served_model
        max_new = 8
        seeds = list(range(len(prompts)))
        # reference FIRST: its compiles must not land on the replicas'
        # post-steady counters (the compile counter is process-global)
        refs = _refs(model, params, prompts, max_new, seeds)

        ea = _engine(model, params, name="A")
        eb = _engine(model, params, name="B")
        ea.warmup()
        eb.warmup()
        ea.mark_steady()
        eb.mark_steady()
        a = ReplicaServer(ea, name="A").start()
        b = ReplicaServer(eb, name="B").start()
        router = Router(
            {"A": a.url, "B": b.url},
            config=RouterConfig(backoff_base_s=0.01, backoff_cap_s=0.05,
                                max_retries=6, poll_interval_s=0.1,
                                migrate_session_kv=False),
        )
        router.collector.poll_once()
        try:
            first_token = threading.Event()
            results = [None] * len(prompts)

            def one(i):
                results[i] = router.submit(
                    [int(t) for t in prompts[i]], max_new_tokens=max_new,
                    seed=seeds[i],
                    on_token=lambda t, r: first_token.set(),
                )

            threads = [
                threading.Thread(target=one, args=(i,), daemon=True)
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            # the kill lands MID-BURST: wait until tokens are flowing,
            # then hard-fail whichever replica placement chose first
            assert first_token.wait(timeout=60), "burst never started"
            victim_name = "A" if any(
                s.id is not None for s in ea._slot_req.values()
            ) or ea._pending() else "B"
            victim, survivor = (a, b) if victim_name == "A" else (b, a)
            victim.kill()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads), (
                "a request HUNG through the kill — no definite outcome"
            )

            # 1) every submitted request reached a definite outcome and
            #    (with a survivor available) actually finished
            assert all(r is not None and r.done for r in results)
            assert all(r.outcome == "finished" for r in results), [
                (r.outcome, r.shed_reason) for r in results
            ]
            # 2) token-exact vs the single-replica reference, re-queued
            #    or not (same seed => same chain on the survivor)
            for r, ref in zip(results, refs):
                assert r.tokens == ref, (r.hops, r.tokens, ref)
            # 3) at least one request actually crossed the failure (the
            #    drill is vacuous otherwise) and its hops record it
            requeued = [
                r for r in results
                if any("error" in h for h in r.hops)
            ]
            assert requeued, "the kill never interrupted a request"
            for r in requeued:
                assert r.replica == survivor.name
                failed_hops = [h for h in r.hops if "error" in h]
                assert all(h["replica"] == victim.name for h in failed_hops)
            assert router.requeues >= len(requeued)
            assert router.requeue_success == len(requeued)
            # 4) the victim is excluded: immediately router-side, and
            #    within one health poll fleet-side
            assert victim.name in router._failed_now(time.time())
            router.collector.poll_once()
            view = {r["replica"] for r in router.collector.placement_view()}
            assert victim.name not in view
            # 5) the survivor recompiled NOTHING post-steady while
            #    absorbing the re-queued load
            assert survivor.engine.admission_recompiles == 0
        finally:
            router.close()
            a.close()
            b.close()

    def test_session_kv_follows_migration_between_real_engines(
        self, served_model
    ):
        """Session affinity + drain: the session's first request lands
        on one replica; that replica drains; the next request for the
        same session is placed on the survivor WITH the session's KV
        migrated through the handoff endpoints — admitted as a prefix
        hit, bit-identical stream."""
        model, cfg, params, prompts = served_model
        p = prompts[0]
        ea = _engine(model, params, name="A")
        eb = _engine(model, params, name="B")
        ea.warmup()
        eb.warmup()
        eb.mark_steady()
        a = ReplicaServer(ea, name="A").start()
        b = ReplicaServer(eb, name="B").start()
        # pin the first placement to A deterministically: poll while B
        # is not yet registered
        router = Router(
            {"A": a.url},
            config=RouterConfig(backoff_base_s=0.01, poll_interval_s=0.1),
        )
        router.collector.poll_once()
        try:
            r1 = router.submit([int(t) for t in p], max_new_tokens=4,
                               seed=0, session="chat-1")
            assert r1.outcome == "finished" and r1.replica == "A"
            # the reference: A's own warm-cache admission of the same
            # (prompt, seed) — captured BEFORE the drain (A's loop
            # thread serves it; poll, don't step from this thread)
            ra = ea.submit(p, max_new_tokens=4, seed=7)
            deadline = time.time() + 60
            while not ra.done and time.time() < deadline:
                time.sleep(0.005)
            assert ra.outcome == "finished" and ra.prefix_hit > 0
            router.register_replica("B", b.url)
            # A drains: takes no new placements, still answers KV export
            a.request_drain()
            deadline = time.time() + 30
            while time.time() < deadline:
                router.collector.poll_once()
                if not any(
                    row["replica"] == "A"
                    for row in router.collector.placement_view()
                ):
                    break
                time.sleep(0.02)
            r2 = router.submit([int(t) for t in p], max_new_tokens=4,
                               seed=7, session="chat-1")
            assert r2.outcome == "finished" and r2.replica == "B"
            assert router.kv_migrations == 1
            assert r2.prefix_hit > 0, "migrated session lost its warm KV"
            # the migrated admission is exactly A's warm-cache stream
            assert r2.tokens == [int(t) for t in ra.tokens]
            assert eb.admission_recompiles == 0  # import + hit: no compiles
        finally:
            router.close()
            a.close()
            b.close()


REPLICA_CMD = [
    sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli",
    "serve", "replica", "--config", "tiny", "--port", "0",
    "--num-slots", "2", "--page-size", "4", "--prefill-chunks", "4,8",
    "--max-seq-len", "64", "--init-seed", "0",
]


@pytest.mark.slow
class TestKillDrillThreeProcesses:
    """The full acceptance drill: 3 replica subprocesses (real engines,
    real scrape servers, launched through `accelerate-tpu serve
    replica`), SIGKILL one mid-burst."""

    def test_sigkill_one_of_three(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        procs, urls = {}, {}
        names = ("r0", "r1", "r2")
        router = None
        try:
            for name in names:
                p = subprocess.Popen(
                    REPLICA_CMD + ["--name", name],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, env=env, cwd=REPO,
                )
                procs[name] = p
            for name, p in procs.items():
                line = p.stdout.readline()
                assert line, p.stderr.read()
                urls[name] = json.loads(line)["url"]
            router = Router(
                {n: urls[n] for n in names},
                config=RouterConfig(backoff_base_s=0.02, backoff_cap_s=0.2,
                                    max_retries=8, poll_interval_s=0.1,
                                    migrate_session_kv=False),
            )
            router.collector.poll_once()

            # reference: the same deterministic model the subprocesses
            # built (same --config/--init-seed), served single-replica
            from accelerate_tpu.commands.serve import build_replica_engine
            import argparse

            ref_engine = build_replica_engine(argparse.Namespace(
                config="tiny", max_seq_len=64, init_seed=0, num_slots=2,
                max_cache_len=None, prefill_chunks="4,8", page_size=4,
                temperature=0.0, top_k=None,
                kv_cache_dtype=None, name=None,
            ))
            rng = np.random.RandomState(0)
            prompts = [rng.randint(3, 256, (n,)) for n in (12, 8, 5, 10, 6)]
            max_new = 8
            refs = [
                [int(t) for t in out[p.size:]]
                for out, p in zip(
                    ref_engine.generate_batched(
                        prompts, max_new_tokens=max_new,
                        seeds=list(range(len(prompts))),
                    ),
                    prompts,
                )
            ]

            first_token = threading.Event()
            results = [None] * len(prompts)

            def one(i):
                results[i] = router.submit(
                    [int(t) for t in prompts[i]], max_new_tokens=max_new,
                    seed=i, on_token=lambda t, r: first_token.set(),
                )

            threads = [
                threading.Thread(target=one, args=(i,), daemon=True)
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            assert first_token.wait(timeout=120), "burst never started"
            # equal idle scores rank by name, so the burst lands on r0
            # first — SIGKILL it while its streams are live
            victim = names[0]
            procs[victim].kill()
            procs[victim].wait(timeout=30)
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads), "a request hung"
            assert all(r is not None and r.outcome == "finished"
                       for r in results), [
                (r.outcome, r.shed_reason, r.hops) for r in results
            ]
            for r, ref in zip(results, refs):
                assert r.tokens == ref, (r.hops, r.tokens, ref)
            requeued = [r for r in results
                        if any("error" in h for h in r.hops)]
            assert requeued, "the SIGKILL never interrupted a request"
            router.collector.poll_once()
            assert victim not in {
                r["replica"] for r in router.collector.placement_view()
            }
        finally:
            if router is not None:
                router.close()
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass

    def test_sigterm_drains_cleanly(self):
        """SIGTERM (vs SIGKILL): the replica drains — finishes in-flight
        work, exits 0 — the PR 7 choreography through the CLI."""
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        p = subprocess.Popen(
            REPLICA_CMD, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=REPO,
        )
        try:
            line = p.stdout.readline()
            assert line, p.stderr.read()
            url = json.loads(line)["url"]
            events = _post_jsonl(f"{url}/v1/submit", {
                "prompt": [5, 6, 7, 8], "max_new_tokens": 4, "seed": 0,
            }, timeout=120)
            assert events[-1]["outcome"] == "finished"
            p.send_signal(signal.SIGTERM)
            assert p.wait(timeout=60) == 0, p.stderr.read()
        finally:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
