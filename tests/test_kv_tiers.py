"""Hierarchical KV tiering (accelerate_tpu/serving/tiers.py + the
engine's demote-on-evict / restore paths).

The contracts of record:
- a restored hit is bit-identical to a never-evicted hit (greedy AND
  sampled, int8-quantized KV included): demote→restore is pure data
  movement through the handoff format, never a recompute;
- page/byte accounting survives 100 demote/restore cycles with no leak
  (allocator free list back to baseline, tier bytes drain to exactly 0
  through the usage hook);
- tiering adds ZERO post-steady compiles (the gather/install programs
  are warmup-compiled);
- a torn or corrupt disk blob is rejected (deleted + counted) and the
  admission falls back to a cold prefill — never installs bad pages;
- the peer tier pulls a warm prefix from another engine over the
  directory + export wire, counting kv_pages_exported/imported.
"""

import json
import os

import numpy as np
import pytest

import jax

from accelerate_tpu.generation import generate
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.serving.tiers import (
    BLOB_SUFFIX,
    TierConfig,
    TieredStore,
    TierEntry,
    entry_nbytes,
    entry_to_handoff,
    handoff_to_entry,
)

PS = 8


@pytest.fixture(scope="module")
def served_model():
    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=16
    )
    params, _ = unbox_params(variables["params"])
    return model, cfg, params


def _engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_cache_len", 64)
    kw.setdefault("prefill_chunks", (4, 8))
    kw.setdefault("page_size", PS)
    return ServingEngine(model, params, **kw)


def _ref(model, params, p, max_new, seed, temperature=0.0, top_k=None):
    return np.asarray(generate(
        model, params, np.asarray(p)[None], max_new_tokens=max_new,
        temperature=temperature, top_k=top_k, rng=jax.random.PRNGKey(seed),
    )[0])


def _evict_all(engine):
    """Force-demote everything the HBM prefix cache holds."""
    while engine._prefix.evict_lru():
        pass


class TestRestoredHitExactness:
    @pytest.mark.parametrize(
        "temperature,top_k,kv_dtype",
        [(0.0, None, None), (1.0, 8, None), (0.0, None, "int8"),
         (1.0, 8, "int8")],
        ids=["greedy", "sampled", "greedy-int8", "sampled-int8"],
    )
    def test_restore_from_host_bit_identical(self, served_model,
                                             temperature, top_k, kv_dtype):
        """Warm a prompt, evict it into the host tier, resubmit: the
        admission restores from host and the tokens are bit-identical
        to a never-evicted hit on a twin engine (THE tiering contract:
        demote→restore is data movement, not recompute — for quantized
        KV the payload+scales pages travel verbatim, no requant)."""
        model, cfg, params = served_model
        kw = dict(temperature=temperature, top_k=top_k,
                  kv_cache_dtype=kv_dtype)
        rng = np.random.RandomState(7)
        p = rng.randint(3, cfg.vocab_size, (12,))
        # twin engine, never evicted: warm + plain HBM hit
        warm = _engine(model, params, **kw)
        warm.submit(p, max_new_tokens=2, seed=3)
        warm.run()
        ref_req = warm.submit(p, max_new_tokens=6, seed=3)
        warm.run()
        assert ref_req.prefix_hit >= PS
        ref = ref_req.result()

        engine = _engine(
            model, params, kv_tiers=TierConfig(host_entries=8), **kw
        )
        engine.submit(p, max_new_tokens=2, seed=3)
        engine.run()
        _evict_all(engine)
        assert engine._tiers.demotions_host >= 1
        assert engine.metrics()["serving/kv_host_entries"] >= 1
        req = engine.submit(p, max_new_tokens=6, seed=3)
        engine.run()
        np.testing.assert_array_equal(req.result(), ref)
        if kv_dtype is None:
            # unquantized: also exactly the sequential single-stream ref
            np.testing.assert_array_equal(
                req.result(), _ref(model, params, p, 6, 3, temperature, top_k)
            )
        assert req.kv_restore_tier == "host"
        assert req.kv_restore_pages >= 1
        assert req.prefix_hit >= PS
        assert engine.kv_tier_hits["host"] == 1
        m = engine.metrics()
        assert m["serving/kv_restores"] == 1
        assert m["serving/kv_tier_hit_ratio_host"] > 0

    def test_restore_from_disk_and_durability(self, served_model, tmp_path):
        """Host overflow cascades to disk; a FRESH store over the same
        directory (a restarted replica) still serves the restore."""
        model, cfg, params = served_model
        disk_dir = str(tmp_path / "kv")
        engine = _engine(
            model, params,
            kv_tiers=TierConfig(host_entries=1, disk_entries=8,
                                disk_dir=disk_dir),
        )
        rng = np.random.RandomState(8)
        prompts = [rng.randint(3, cfg.vocab_size, (12,)) for _ in range(3)]
        for i, p in enumerate(prompts):
            engine.submit(p, max_new_tokens=2, seed=i)
            engine.run()
        _evict_all(engine)
        assert engine._tiers.demotions_disk >= 1
        assert any(
            n.endswith(BLOB_SUFFIX) for n in os.listdir(disk_dir)
        )
        # restart: a second engine over the same disk dir restores the
        # blob a previous process demoted
        engine2 = _engine(
            model, params,
            kv_tiers=TierConfig(host_entries=1, disk_entries=8,
                                disk_dir=disk_dir),
        )
        assert len(engine2._tiers.disk.entries) >= 1
        hit_any = False
        for i, p in enumerate(prompts):
            req = engine2.submit(p, max_new_tokens=6, seed=i)
            engine2.run()
            ref = _ref(model, params, p, 6, i)
            np.testing.assert_array_equal(req.result(), ref)
            hit_any = hit_any or req.kv_restore_tier == "disk"
        assert hit_any


class TestLeakBaseline:
    def test_100_demote_restore_cycles_no_leak(self, served_model):
        """Churn demote/restore 100 times; the allocator free list ends
        byte-for-byte where it started and tier bytes drain to 0."""
        model, cfg, params = served_model
        held = {"host": 0, "disk": 0}

        engine = _engine(
            model, params, num_slots=2,
            kv_tiers=TierConfig(host_entries=16),
        )
        engine._tiers.on_bytes = (
            lambda tenant, tier, delta: held.__setitem__(
                tier, held[tier] + delta
            )
        )
        free0 = engine._allocator.free_count
        rng = np.random.RandomState(9)
        prompts = [rng.randint(3, cfg.vocab_size, (10 + (i % 3),))
                   for i in range(5)]
        for i in range(100):
            p = prompts[i % len(prompts)]
            engine.submit(p, max_new_tokens=1, seed=i % len(prompts))
            engine.run()
            if i % 2 == 1:
                _evict_all(engine)  # demote; the next submit restores
        assert engine.requests_completed == 100
        assert engine.kv_restores >= 10
        assert engine._tiers.demotions_host >= 10
        _evict_all(engine)
        assert engine._allocator.in_use == 0
        assert engine._allocator.free_count == free0
        engine._tiers.clear()
        assert held["host"] == 0 and held["disk"] == 0
        assert engine.metrics()["serving/kv_host_bytes"] == 0


class TestZeroRecompile:
    def test_tiering_adds_zero_post_steady_compiles(self, served_model):
        """Steady immediately after warmup; demotions (gather) and
        restores (install) are warmup-compiled programs — the compile
        counters must not move."""
        model, cfg, params = served_model
        engine = _engine(
            model, params, kv_tiers=TierConfig(host_entries=8),
        )
        engine.warmup()
        engine.mark_steady()
        rng = np.random.RandomState(10)
        prompts = [rng.randint(3, cfg.vocab_size, (n,)) for n in (12, 11, 10)]
        for i, p in enumerate(prompts):
            engine.submit(p, max_new_tokens=2, seed=i)
            engine.run()
        _evict_all(engine)
        assert engine._tiers.demotions_host >= 1
        reqs = [engine.submit(p, max_new_tokens=3, seed=i)
                for i, p in enumerate(prompts)]
        engine.run()
        assert all(r.done for r in reqs)
        assert engine.kv_restores >= 1
        assert engine.admission_recompiles == 0
        assert engine.metrics()["serving/admission_recompiles"] == 0


def _store_entry(key_tokens, n_pages=2, ps=PS, dtype=np.float32):
    tokens = np.asarray(key_tokens, np.int32)
    rng = np.random.RandomState(int(tokens.sum()) % 100)
    arrays = [rng.rand(n_pages, 2, ps, 4).astype(dtype)]
    from accelerate_tpu.serving.pages import _digest

    return TierEntry(
        key=_digest(tokens), token_len=int(tokens.size), tokens=tokens,
        n_pages=n_pages, arrays=arrays, paths=["['layers']['attn']['cached_key']"],
        nbytes=entry_nbytes(arrays, tokens),
    )


class TestDiskBlobIntegrity:
    def _store(self, tmp_path, **kw):
        kw.setdefault("host_entries", 1)
        kw.setdefault("disk_entries", 8)
        return TieredStore(
            TierConfig(disk_dir=str(tmp_path / "kv"), **kw), page_size=PS,
        )

    def _demote_two(self, store):
        e1 = _store_entry(np.arange(3, 19), n_pages=2)
        e2 = _store_entry(np.arange(40, 56), n_pages=2)
        store.put(e1)   # host
        store.put(e2)   # host overflows -> e1 cascades to disk
        assert store.demotions_disk == 1
        return e1

    def test_truncated_blob_rejected_and_deleted(self, tmp_path):
        store = self._store(tmp_path)
        e1 = self._demote_two(store)
        [blob] = [os.path.join(store.config.disk_dir, n)
                  for n in os.listdir(store.config.disk_dir)]
        with open(blob, "r+") as fh:
            fh.truncate(os.path.getsize(blob) // 2)  # torn write
        assert store.probe(e1.tokens) is None
        assert store.disk_corrupt_dropped == 1
        assert not os.path.exists(blob)
        assert len(store.disk.entries) == 0

    def test_bitflipped_blob_fails_checksum(self, tmp_path):
        store = self._store(tmp_path)
        e1 = self._demote_two(store)
        [blob] = [os.path.join(store.config.disk_dir, n)
                  for n in os.listdir(store.config.disk_dir)]
        with open(blob) as fh:
            doc = json.load(fh)
        data = doc["leaves"][0]["data"]
        doc["leaves"][0]["data"] = ("B" if data[0] == "A" else "A") + data[1:]
        with open(blob, "w") as fh:
            json.dump(doc, fh)  # checksum now stale: a bit flip
        assert store.probe(e1.tokens) is None
        assert store.disk_corrupt_dropped == 1
        assert not os.path.exists(blob)

    def test_blob_of_the_earlier_int4_layout_is_dropped_at_startup(self, tmp_path):
        """A disk directory that outlives the upgrade: a version-1 int4 blob
        (even/odd nibble interleave) is intact by its own checksum and has
        the dtype and shape of today's split-half payload, so only the wire
        version keeps its scrambled nibbles out of the arena. The start-up
        scan drops and counts it; a blob written today is kept."""
        from accelerate_tpu.serving.tiers import (
            BLOB_SUFFIX, KV_WIRE_VERSION, blob_checksum, entry_to_handoff,
        )

        disk_dir = tmp_path / "kv"
        disk_dir.mkdir()
        for name, version, toks in (("old", 1, np.arange(3, 19)),
                                    ("new", KV_WIRE_VERSION, np.arange(40, 56))):
            doc = entry_to_handoff(_store_entry(toks, dtype=np.int8), page_size=PS,
                                   kv_cache_dtype="int4")
            doc["version"] = version
            doc["checksum"] = blob_checksum(doc)
            (disk_dir / (name + BLOB_SUFFIX)).write_text(json.dumps(doc))
        store = TieredStore(
            TierConfig(disk_dir=str(disk_dir), host_entries=1, disk_entries=8),
            page_size=PS, kv_cache_dtype="int4",
        )
        assert store.disk_corrupt_dropped == 1
        assert sorted(os.listdir(disk_dir)) == ["new" + BLOB_SUFFIX]
        assert store.probe(np.arange(3, 19)) is None
        assert store.probe(np.arange(40, 56))["tier"] == "disk"

    def test_corrupt_blob_cold_fallback_end_to_end(self, served_model,
                                                   tmp_path, monkeypatch):
        """Engine-level: a corrupt blob must not crash or skew tokens —
        the admission just pays the cold prefill."""
        model, cfg, params = served_model
        disk_dir = str(tmp_path / "kv")
        engine = _engine(
            model, params,
            kv_tiers=TierConfig(host_entries=1, disk_entries=8,
                                disk_dir=disk_dir),
        )
        rng = np.random.RandomState(11)
        prompts = [rng.randint(3, cfg.vocab_size, (12,)) for _ in range(3)]
        for i, p in enumerate(prompts):
            engine.submit(p, max_new_tokens=2, seed=i)
            engine.run()
        _evict_all(engine)
        for name in os.listdir(disk_dir):
            path = os.path.join(disk_dir, name)
            with open(path, "r+") as fh:
                fh.truncate(10)
        engine._tiers.host.entries.clear()
        engine._tiers.host.index.clear()
        for i, p in enumerate(prompts):
            req = engine.submit(p, max_new_tokens=6, seed=i)
            engine.run()
            np.testing.assert_array_equal(
                req.result(), _ref(model, params, p, 6, i)
            )
            assert req.kv_restore_tier is None  # cold, not corrupt-restored
        assert engine._tiers.disk_corrupt_dropped >= 1
        assert engine.metrics()["serving/kv_disk_corrupt_dropped"] >= 1


class TestPeerTier:
    def test_pull_between_two_engines(self, served_model):
        """Engine B misses; its peer tier pulls A's warm prefix through
        the directory + export wire (injected fetch — no sockets) and
        the restored output is bit-identical. Export/import gauges count
        the pages that moved."""
        model, cfg, params = served_model
        a = _engine(model, params)
        rng = np.random.RandomState(12)
        p = rng.randint(3, cfg.vocab_size, (12,))
        a.submit(p, max_new_tokens=2, seed=5)
        a.run()
        exported0 = a.kv_pages_exported

        def fetch(url, path, payload=None, timeout_s=None):
            assert url == "http://peer-a"
            if path == "/v1/kv/directory":
                return a.kv_directory()
            if path == "/v1/kv/export":
                return a.export_prefix_kv(payload["tokens"])
            raise AssertionError(path)

        b = _engine(
            model, params,
            kv_tiers=TierConfig(host_entries=4,
                                peers=(("a", "http://peer-a"),)),
        )
        b._tiers._fetch = fetch
        req = b.submit(p, max_new_tokens=6, seed=5)
        b.run()
        np.testing.assert_array_equal(req.result(), _ref(model, params, p, 6, 5))
        assert req.kv_restore_tier == "peer"
        assert b.kv_tier_hits["peer"] == 1
        assert a.kv_pages_exported > exported0
        assert b.kv_pages_imported >= 1
        assert b._tiers.peer_pulls == 1
        m = b.metrics()
        assert m["serving/kv_peer_pulls"] == 1
        assert m["serving/kv_pages_imported"] >= 1

    def test_stale_directory_counts_failure_and_falls_back(self, served_model):
        model, cfg, params = served_model
        rng = np.random.RandomState(13)
        p = rng.randint(3, cfg.vocab_size, (12,))
        from accelerate_tpu.serving.pages import _digest

        def fetch(url, path, payload=None, timeout_s=None):
            if path == "/v1/kv/directory":
                # advertises the prefix, but the export below fails —
                # the peer evicted since advertising
                return {"prefixes": [
                    {"digest": _digest(np.asarray(p[:n], np.int32)).hex(),
                     "token_len": n} for n in (8, 11)
                ]}
            return None

        b = _engine(
            model, params,
            kv_tiers=TierConfig(host_entries=4,
                                peers=(("a", "http://peer-a"),)),
        )
        b._tiers._fetch = fetch
        req = b.submit(p, max_new_tokens=6, seed=5)
        b.run()
        np.testing.assert_array_equal(req.result(), _ref(model, params, p, 6, 5))
        assert req.kv_restore_tier is None
        assert b._tiers.peer_pull_failures >= 1


class TestTierFormat:
    def test_handoff_round_trip_preserves_bytes(self):
        e = _store_entry(np.arange(3, 19), n_pages=2)
        doc = entry_to_handoff(e, page_size=PS, kv_cache_dtype="bf16")
        back = handoff_to_entry(doc)
        assert back.key == e.key and back.token_len == e.token_len
        np.testing.assert_array_equal(back.tokens, e.tokens)
        for x, y in zip(back.arrays, e.arrays):
            np.testing.assert_array_equal(x, y)

    def test_prefix_slicing_serves_shorter_lengths(self, tmp_path):
        """One long demoted entry serves its aligned shorter prefixes —
        the dedup contract (pages never stored twice across lengths)."""
        store = TieredStore(TierConfig(host_entries=4), page_size=PS)
        e = _store_entry(np.arange(3, 19), n_pages=2)  # 16 tokens, 2 pages
        store.put(e)
        assert len(store.host.entries) == 1
        hit = store.probe(e.tokens[:PS], min_len=0)
        assert hit is not None and hit["tier"] == "host"
        assert hit["token_len"] == PS
        assert hit["arrays"][0].shape[0] == 1  # one page sliced off
        np.testing.assert_array_equal(
            hit["arrays"][0], e.arrays[0][:1]
        )
        # re-demoting the shorter prefix is a no-op (already covered)
        from accelerate_tpu.serving.pages import _digest

        assert store.covers(_digest(e.tokens[:PS]))

    def test_min_len_excludes_hits_hbm_already_serves(self):
        store = TieredStore(TierConfig(host_entries=4), page_size=PS)
        e = _store_entry(np.arange(3, 19), n_pages=2)
        store.put(e)
        assert store.probe(e.tokens, min_len=16) is None
        assert store.probe(e.tokens, min_len=8)["token_len"] == 16


class TestUsageByteSeconds:
    def test_tier_byte_seconds_accrue_and_drain(self):
        from accelerate_tpu.telemetry.usage import UsageAccountant

        t = [0.0]
        u = UsageAccountant(clock=lambda: t[0])
        u.note_tier_bytes("acme", "host", 1000)
        t[0] = 2.0
        u.note_tier_bytes("acme", "host", -1000)
        u.note_tier_bytes("acme", "disk", 500)
        t[0] = 6.0
        u.note_tier_bytes("acme", "disk", -500)
        totals = u.totals()
        assert totals["host_byte_seconds"] == pytest.approx(2000.0)
        assert totals["disk_byte_seconds"] == pytest.approx(2000.0)
        snap = u.snapshot()["tenants"]["acme"]
        assert snap["host_bytes_held"] == 0
        assert snap["disk_bytes_held"] == 0
        # unmatched release clamps (same stance as note_pages)
        u.note_tier_bytes("acme", "host", -999)
        assert u.snapshot()["tenants"]["acme"]["host_bytes_held"] == 0

    def test_engine_wires_store_bytes_to_usage(self, served_model, tmp_path):
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        model, cfg, params = served_model
        session = TelemetrySession(TelemetryConfig(
            trace_dir=str(tmp_path), watchdog=False, flight_hooks=False,
        ))
        try:
            engine = _engine(
                model, params, telemetry=session,
                kv_tiers=TierConfig(host_entries=8),
            )
            rng = np.random.RandomState(14)
            p = rng.randint(3, cfg.vocab_size, (12,))
            engine.submit(p, max_new_tokens=2, seed=0, tenant="acme")
            engine.run()
            _evict_all(engine)
            usage = session.usage
            held = usage.snapshot()["tenants"]["acme"]["host_bytes_held"]
            assert held > 0
            engine._tiers.clear()
            assert usage.snapshot()["tenants"]["acme"]["host_bytes_held"] == 0
        finally:
            session.close()


class TestWaterfallStage:
    def test_kv_restore_stage_sums_exactly(self):
        """A joined record with kv_restore_ms carves the restore out of
        the replica TTFT; the stages still sum to the hop wall."""
        from accelerate_tpu.telemetry.waterfall import (
            STAGES, waterfall_stages,
        )

        assert "kv_restore" in STAGES
        router_rec = {
            "request_id": "r1", "submit_unix_s": 100.0,
            "hops": [{
                "replica": "a", "t_unix_s": 100.0,
                "place_start_unix_s": 100.010,
                "connect_unix_s": 100.020,
                "first_token_unix_s": 100.120,
            }],
        }
        replica_rec = {"request_id": "r1", "queue_wait_ms": 10.0,
                       "kv_restore_ms": 30.0, "ttft_ms": 90.0}
        row = waterfall_stages(router_rec, replica_rec)
        st = row["stages"]
        assert st["kv_restore"] == pytest.approx(30.0, abs=0.01)
        assert st["prefill"] == pytest.approx(50.0, abs=0.01)
        assert sum(st.values()) == pytest.approx(
            (100.120 - 100.0) * 1e3, abs=0.05
        )
        # a record with no kv_restore_ms (older replica) defaults to 0
        row0 = waterfall_stages(
            router_rec, {"request_id": "r1", "queue_wait_ms": 10.0,
                         "ttft_ms": 90.0},
        )
        assert row0["stages"]["kv_restore"] == 0.0
