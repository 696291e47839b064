"""The spans and counts ``ServingEngine.step()`` records (docs/telemetry.md,
"Serving iteration spans"): one ``serving/step`` an iteration with its phases
as children, in order, and the request-level stamps and spans beside them.

One engine run a path (the packed prefill on its kernel and on its dense
reference, quantized pages, a prefix cache that evicts and none), read back
from the process-wide span ring.
"""

import dataclasses
import json

import numpy as np
import pytest

import jax

from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import ServingEngine
from accelerate_tpu.telemetry import spans as spans_mod

# a step's children, in the order they can occur (the admission group
# repeats when a scheduler grants more than one dispatch an iteration): the
# two dispatches are enqueued before either result is read, the previous
# step's tokens first (the older dispatch), then this iteration's first tokens
PHASES = ("serving/reap", "serving/admit_plan", "serving/prefill_dispatch",
          "serving/decode_grow", "serving/decode_dispatch", "serving/token_fetch",
          "serving/emit", "serving/prefill_fetch", "serving/prefill_commit")
# the admission's host work, each under the phase that encloses it (its parent
# in the ring): recorded only where the work exists
CHILDREN = {"serving/prefix_lookup": "serving/admit_plan", "serving/page_grow": "serving/admit_plan",
            "serving/pack_upload": "serving/admit_plan", "serving/prefix_insert": "serving/prefill_dispatch"}
PROMPT_LENS = (20, 5, 12, 3, 9)
NEW_TOKENS = 5
# (iterations, share of them that carry a pack) of each serving cell's traced run on the chip at PR 40 (chat,
# re-ask, batch, MiMo, the state-space cell, EvaByte): the whole of a run of the benchmark's length, which is the
# cell's warm-in (100 to 1,100 iterations) and BENCHMARK.json's ``run_seconds``, 45 s, at the iteration's pace of
# PR 39-40 (12.5 to 30 ms). One builder's runs: a PR that shortens the iteration or lengthens the run takes the
# count again from a chip run's ``len(snapshot())`` and raises ``RING_SPANS`` if the longest passes two thirds.
# MiMo's is PR 41's, whose step fell from 21.3 to 11.4 ms: 3,034 iterations, 2,359 with a pack, 36,187 spans
# held (it was (2032, 0.70) and 24,558); EvaByte's is PR 43's, whose step fell from 13.5 to 9.4 ms: 1,000 of
# warm-in, 2,966 in the measured 40 s and about 370 traced, 40,394 spans held (it was (3636, 0.31) and 33,990)
RUNS_ON_THE_CHIP = ((1913, 0.75), (1420, 0.94), (2448, 0.34), (3034, 0.78), (2891, 0.55), (4336, 0.31))

PATHS = {
    "ragged": dict(page_size=8, kernels=True),
    "dense": dict(page_size=8),  # the packed dispatch on its reference
    # quantized pages: the kernels with the split-threading arm of the decode step
    "int8": dict(page_size=8, kernels=True, kv_cache_dtype="int8"),
    # a prefix cache of two entries: every insert evicts (a 20-token prompt registers three prefixes)
    "evict": dict(page_size=8, kernels=True, prefix_max_entries=2),
    "noprefix": dict(page_size=8, kernels=True, prefix_cache=False),
    # a model by kind each (``KINDS``), on the programs' references and with no prefix cache (it is refused them)
    "window": dict(page_size=8, prefix_cache=False, kind="window"),
    "closing": dict(page_size=4, prefix_cache=False, kind="closing"),
    "heads": dict(page_size=8, prefix_cache=False, kind="heads"),
}
# what each model by kind states beside the tiny model's own widths: a window kind beside a full one, a closing
# window of 16 in chunks of 4, and a state with heads beside an attention layer. (Latent attention and experts
# keep host work between the phases, ``latent_tokens`` reckoned before the dispatch span opens and the expert load
# written between fetch and emit: more than the cover this file asks of a step on the CPU.)
KINDS = {
    "window": dict(num_layers=3, layer_pattern=(0, 1, 1), layer_kinds=(
        ("full", dict(num_kv_heads=1)), ("window", dict(num_kv_heads=2, attn_window=16)))),
    "closing": dict(eva_window=16, eva_chunk=4),
    "heads": dict(num_layers=3, rope_dim=0, layer_pattern=(0, 1, 0), layer_kinds=(
        ("state_space", dict(mixer="ssd", ssm_num_heads=4, ssm_head_dim=16, ssm_state_dim=8)),
        ("attention", dict(mixer="attention")))),
}
NO_PREFIX = ("noprefix", *KINDS)


def _served(**kind):
    """The tiny model, with what a kind states beside its widths."""
    cfg = DecoderConfig.tiny(max_seq_len=64, **kind)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    return model, cfg, params


@pytest.fixture(scope="module")
def model_and_params():
    return _served()


def _mark():
    spans_mod.emit("mark", 0.0, 0.0)
    return spans_mod.snapshot()[-1][0]


def _spans_since(mark):
    ring = spans_mod.snapshot()
    assert ring[0][0] <= mark  # the mark is still there: the ring did not wrap since
    return [s for s in ring if s[0] > mark]


def _engine(model, cfg, params, *, kernels=False, **kw):
    """The tests' engine; ``kernels``: both serving kernels, interpreted."""
    if kernels:
        cfg = dataclasses.replace(cfg, decode_kernel="interpret", prefill_kernel="interpret")
        model = model.clone(config=cfg)
    args = dict(num_slots=2, max_cache_len=64, page_size=8, prefill_chunks=(8, 16))
    args.update(kw)
    return ServingEngine(model, params, **args)


class Run:
    """One engine driven to the end, with what the ring holds of it."""

    def __init__(self, model, cfg, params, **kw):
        mark = _mark()
        self.engine = _engine(model, cfg, params, **kw)
        self.engine.warmup()
        rng = np.random.RandomState(0)
        self.requests = [self.engine.submit(rng.randint(3, cfg.vocab_size, (n,)),
                                            max_new_tokens=NEW_TOKENS, seed=i)
                         for i, n in enumerate(PROMPT_LENS)]
        self.engine.run()
        self.spans = _spans_since(mark)

    def named(self, name):
        return [s for s in self.spans if s[2] == name]

    def children(self, parent):
        return sorted((s for s in self.spans if s[1] == parent[0] and s[2] in PHASES),
                      key=lambda s: s[3])

    def inside(self, parent):
        """The admission's child spans whose parent in the ring is ``parent``."""
        return sorted((s for s in self.spans if s[1] == parent[0] and s[2] in CHILDREN),
                      key=lambda s: s[3])

    def of_request(self, name, req):
        return sorted((s for s in self.named(name) if s[5]["request_id"] == req.id),
                      key=lambda s: s[3])


@pytest.fixture(scope="module")
def runs(model_and_params):
    cache = {}

    def get(path):
        if path not in cache:
            kw = dict(PATHS[path])
            served = _served(**KINDS[kw.pop("kind")]) if "kind" in kw else model_and_params
            cache[path] = Run(*served, **kw)
        return cache[path]

    return get


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_step_has_its_phases_in_order_and_they_cover_it(runs, path):
    run = runs(path)
    steps = run.named("serving/step")
    assert len(steps) == run.engine.iterations > 0
    assert [s[5]["iteration"] for s in steps] == list(range(1, len(steps) + 1))
    dispatched, uncovered = 0, 0.0
    for step in steps:
        kids = run.children(step)
        names = [k[2] for k in kids]
        assert len(kids) + 1 <= 12, names  # the budget: at most 12 spans an iteration
        # in order, none twice (no scheduler here: one admission an iteration)
        order = [PHASES.index(n) for n in names]
        assert order == sorted(set(order)), names
        assert names[:2] == ["serving/reap", "serving/admit_plan"]
        by_name = {k[2]: k for k in kids}
        if "serving/prefill_dispatch" in names:
            dispatched += 1
            # the packed dispatch's first tokens are fetched and committed in
            # the iteration that sent it, every time
            assert "serving/prefill_commit" in names and "serving/prefill_fetch" in names
            # ... behind the decode dispatch, where there is one
            assert by_name["serving/prefill_fetch"][5]["in_flight"] == int("serving/decode_dispatch" in names)
        if "serving/decode_dispatch" in names:
            assert "serving/decode_grow" in names
        if "serving/token_fetch" in names:
            # the tokens read are the previous dispatch's: read behind this
            # iteration's dispatch, or alone once nothing is left to enqueue
            assert names[names.index("serving/token_fetch") + 1] == "serving/emit"
            assert by_name["serving/token_fetch"][5]["in_flight"] == int("serving/decode_dispatch" in names)
            assert by_name["serving/emit"][5]["discarded"] == 0  # no eos here
        # children lie inside the step, one after another, and cover it
        for a, b in zip(kids, kids[1:]):
            assert a[4] <= b[3]
        assert step[3] <= kids[0][3] and kids[-1][4] <= step[4]
        # the admission's host work: each child under the phase that encloses
        # it, inside it, one after another, covering no more than it
        for phase in kids:
            inner = run.inside(phase)
            assert all(CHILDREN[c[2]] == phase[2] for c in inner), (phase[2], [c[2] for c in inner])
            assert all(phase[3] <= c[3] and c[4] <= phase[4] for c in inner)
            assert all(a[4] <= b[3] for a, b in zip(inner, inner[1:]))
            assert sum(c[4] - c[3] for c in inner) <= phase[4] - phase[3]
            found = [c[2] for c in inner]
            if phase[2] == "serving/admit_plan" and "serving/prefill_dispatch" in names:
                # a pack: growth for each packed request, then one upload
                assert found.count("serving/pack_upload") == 1 and found[-1] == "serving/pack_upload"
                assert found.count("serving/page_grow") == by_name["serving/prefill_dispatch"][5]["requests"]
            if phase[2] == "serving/prefill_dispatch":
                assert len(found) <= phase[5]["requests"]  # an insert a request that goes live
        # ... to within 5% of its duration (and 2 ms of scheduling noise: the
        # suite's workers share this host's cores; on the chip an iteration is
        # 330-900 ms and its own time 0.3 ms)
        own = (step[4] - step[3]) - sum(k[4] - k[3] for k in kids)
        # (a collection that fell between two phases is the collector's time, a span of its own)
        own -= sum(s[4] - s[3] for s in run.named("host/gc") if s[1] == step[0])
        assert own <= 0.05 * (step[4] - step[3]) + 2e-3, names
        uncovered += own
    # (a tenth of a millisecond of Python between the spans of an iteration: on
    # the CPU a step of the dense path is hardly more than a millisecond now
    # that the device works while the host does)
    assert uncovered <= 0.05 * sum(s[4] - s[3] for s in steps) + 1e-4 * len(steps)
    assert dispatched == len(run.named("serving/prefill_dispatch")) > 0
    # every decode dispatch's tokens were read, one fetch each, all but the
    # last behind the next dispatch
    fetches = run.named("serving/token_fetch")
    assert len(fetches) == len(run.named("serving/decode_dispatch")) > 0
    overlapped = sum(f[5]["in_flight"] for f in fetches)
    assert overlapped >= len(fetches) - len(PROMPT_LENS)
    assert run.engine.metrics()["serving/dispatch_depth"] == 0  # run() leaves nothing unread
    # no span per token or per slot: everything recorded is one of these
    allowed = set(PHASES) | set(CHILDREN) | {"serving/step", "serving/warmup", "serving/queue_wait",
                                             "serving/prefill_chunk", "serving/first_token", "host/gc"}
    assert {s[2] for s in run.spans} <= allowed
    # every child's parent is a phase of its kind (none escaped the loop above)
    by_id = {s[0]: s for s in run.spans}
    assert all(by_id[s[1]][2] == CHILDREN[s[2]] for s in run.spans if s[2] in CHILDREN)
    # a lookup and an insert a request where there is a prefix cache, none where there is none
    per_request = 0 if path in NO_PREFIX else len(PROMPT_LENS)
    assert len(run.named("serving/prefix_lookup")) == len(run.named("serving/prefix_insert")) == per_request
    assert len(run.named("serving/pack_upload")) == dispatched


@pytest.mark.parametrize("path", sorted(PATHS))
def test_counts_at_the_spans_sum_to_the_engines_counters(runs, path):
    run = runs(path)
    eng = run.engine
    dispatches = run.named("serving/prefill_dispatch")
    assert sum(s[5]["rows"] for s in dispatches) == eng._prefill_rows_dispatched
    assert all(0 < s[5]["tokens"] <= s[5]["rows"] and s[5]["requests"] >= 1 for s in dispatches)
    assert sum(s[5]["tokens"] for s in dispatches) == eng.prefill_packed_tokens
    if path == "ragged":
        assert any(s[5]["requests"] > 1 for s in dispatches)  # the long prompt's tail and a short prompt share a grid
    # the pack program carries the arena and its kernel writes the pack's pages: wherever that kernel runs over
    # unquantized pages
    in_place = int(path in ("ragged", "evict", "noprefix"))
    assert {s[5]["arena_in_place"] for s in dispatches} == {in_place}
    assert eng.metrics()["serving/prefill_arena_in_place"] == in_place
    assert sum(s[5]["emitted"] for s in run.named("serving/step")) == eng.generated_tokens
    firsts = sum(s[5]["first_tokens"] for s in run.named("serving/prefill_commit"))
    decoded = sum(s[5]["emitted"] for s in run.named("serving/emit"))
    assert firsts == len(PROMPT_LENS) and firsts + decoded == eng.generated_tokens
    assert sum(s[5]["finished"] for s in run.named("serving/emit")) + sum(
        1 for r in run.requests if len(r.tokens) == 1) == eng.requests_completed == len(PROMPT_LENS)
    last = run.named("serving/step")[-1][5]
    assert last["queued"] == 0 and last["live"] == 0
    grows = run.named("serving/decode_grow")
    packed = run.named("serving/page_grow")
    # every page comes from the growth for a pack's rows or for a decode step
    assert sum(s[5]["pages_allocated"] for s in grows + packed) == eng.pages_allocated
    assert sum(s[5]["pages_allocated"] for s in packed) > 0
    assert {s[5]["request_id"] for s in packed} == {r.id for r in run.requests}
    uploads = run.named("serving/pack_upload")
    assert [s[5]["rows"] for s in uploads] == [s[5]["rows"] for s in dispatches]
    # the counts each span carries are those a reader under benchmarks/metrics/ takes, and no other
    assert all(set(s[5]) == {"rows"} for s in uploads)
    assert all(set(s[5]) == {"request_id", "pages_allocated", "evictions", "evict_scanned"} for s in packed)
    # the prefix cache's work, counted where it is done: the spans' counts sum to its counters
    lookups, inserts = run.named("serving/prefix_lookup"), run.named("serving/prefix_insert")
    cache = eng._prefix
    if cache is None:
        assert not lookups and not inserts
        assert all(s[5]["evictions"] == s[5]["evict_scanned"] == 0 for s in packed)
    else:
        total = lambda key, spans: sum(s[5][key] for s in spans)
        assert total("probes", lookups + inserts) == cache.digests + cache.ghost.digests
        assert total("hashed_tokens", lookups + inserts) == cache.digested_tokens + cache.ghost.digested_tokens
        assert total("ghost_probes", lookups) == cache.ghost.digests
        assert total("evictions", inserts + packed) == cache.evictions
        assert total("evict_scanned", inserts + packed) == cache.evict_scanned
        assert (cache.evictions > 0) == (path == "evict")
        assert total("hit_tokens", lookups) == cache.hit_tokens == sum(r.prefix_hit for r in run.requests)
        assert len(cache.entries) == inserts[-1][5]["entries"]
        assert all(set(s[5]) == {"request_id", "entries", "probes", "ghost_probes", "hashed_tokens", "hit_tokens"}
                   for s in lookups)
        assert all(set(s[5]) == {"request_id", "probes", "hashed_tokens", "evictions", "evict_scanned", "entries"}
                   for s in inserts)
        assert len(lookups) == cache.lookups and lookups[0][5]["entries"] == 0
        # a lookup visits an entry a length it probed: no more than the distinct lengths, whatever the cache holds
        distinct = len({length for r in run.requests for length in (*range(8, r.prompt.size + 1, 8), r.prompt.size)})
        assert total("entries", lookups) == cache.entries_probed
        assert all(s[5]["entries"] <= min(distinct, s[5]["probes"] - s[5]["ghost_probes"]) for s in lookups)
        # an insert digests every page-aligned prefix and the prompt itself in one pass over the prompt
        by_request = {r.id: r for r in run.requests}
        for s in inserts:
            n = by_request[s[5]["request_id"]].prompt.size
            lengths = list(range(8, n + 1, 8)) + ([n] if n % 8 else [])
            assert (s[5]["probes"], s[5]["hashed_tokens"]) == (len(lengths), n)
    assert all(s[5]["walked_tokens"] % eng.page_size == 0 and s[5]["walked_tokens"] > 0 for s in grows)
    assert last["pages_in_use"] + last["pages_free"] > 0
    reaps = run.named("serving/reap")
    assert all(s[5] == {"reaped": 0, "shed": 0, "preempted": 0} for s in reaps)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_request_stamps_and_spans_agree(runs, path):
    run = runs(path)
    steps = run.named("serving/step")
    waited_behind = 0
    for req in run.requests:
        assert req.outcome == "finished"
        chunks = run.of_request("serving/prefill_chunk", req)
        assert req.prefill_dispatches == len(chunks) >= 1
        assert req.submit_t <= req.admit_t <= chunks[0][3] <= req.first_token_t
        (wait,) = run.of_request("serving/queue_wait", req)
        assert (wait[3], wait[4]) == (req.submit_t, req.admit_t)
        (first,) = run.of_request("serving/first_token", req)
        assert (first[3], first[4]) == (req.submit_t, req.first_token_t)
        assert first[5]["dispatches"] == req.prefill_dispatches
        assert first[5]["prompt_len"] == req.prompt.size and first[5]["prefix_hit"] == req.prefix_hit
        assert first[5]["queue_wait_ms"] == pytest.approx(1e3 * (req.admit_t - req.submit_t), abs=1e-3)
        # rows of one request lie end to end
        assert [c[5]["start"] for c in chunks] == sorted(c[5]["start"] for c in chunks)
        iterations = sum(1 for s in steps if s[3] <= req.first_token_t and s[4] >= req.submit_t)
        assert req.prefill_dispatches <= iterations
        waited_behind += req.prefill_dispatches < iterations
    # five requests through two slots: the later ones wait for iterations in
    # which nothing of theirs is dispatched, which an outside count cannot tell
    assert waited_behind >= 2
    # the longest prompt needs more than one dispatch of at most 16 rows
    assert run.requests[0].prefill_dispatches == 2


def test_warmup_is_one_span_with_its_compile_counts(runs):
    (warm,) = runs("ragged").named("serving/warmup")
    assert warm[1] is None and warm[4] > warm[3]
    assert warm[5]["programs"] >= warm[5]["compiles"] >= 0 and warm[5]["compile_s"] >= 0


def test_an_idle_poll_records_nothing(runs):
    engine = runs("dense").engine
    mark, before = _mark(), engine.iterations
    assert engine.step() is False and engine.step() is False
    assert _spans_since(mark) == []
    assert engine.iterations == before


def test_request_spans_land_once_in_ring_and_file(model_and_params, tmp_path):
    """Under a telemetry session the file holds what the ring holds: one
    ``serving/queue_wait`` a request and one ``serving/prefill_chunk`` a
    request and dispatch, from the engine's one call site each; the request
    tracer keeps its record and histogram."""
    from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

    session = TelemetrySession(TelemetryConfig(trace_dir=str(tmp_path), watchdog=False,
                                               flight_hooks=False))
    try:
        run = Run(*model_and_params, telemetry=session, **PATHS["ragged"])
        hist = session.histogram("serving/queue_wait")
    finally:
        session.close()
    (trace_file,) = tmp_path.glob("trace-host*.jsonl")
    events = [json.loads(l) for l in open(trace_file) if l.strip()]
    for name in ("serving/queue_wait", "serving/prefill_chunk", "serving/first_token",
                 "serving/step", "serving/request"):
        in_file = [e for e in events if e["name"] == name]
        in_ring = [s for s in run.spans if s[2] == name]
        assert len(in_file) == len(in_ring) > 0, name
        assert [e.get("args") for e in in_file] == [s[5] for s in in_ring], name
    assert len(run.named("serving/queue_wait")) == len(PROMPT_LENS)
    assert sum(r.prefill_dispatches for r in run.requests) == len(run.named("serving/prefill_chunk"))
    assert hist.count == len(PROMPT_LENS)
    (req_file,) = tmp_path.glob("requests-host*.jsonl")
    records = [json.loads(l) for l in open(req_file) if l.strip()]
    assert sorted(len(r["prefill_chunks"]) for r in records) == sorted(
        r.prefill_dispatches for r in run.requests)
    assert all("queue_wait_ms" in r for r in records)


def test_pages_walked_is_the_count_the_page_table_gives(model_and_params):
    """``pages_walked`` on ``serving/prefill_dispatch`` is the sum over the
    pack's token blocks of the live pages the kernel's walk visits: after
    each iteration, for every request the dispatch carried, the slot's table
    entries before the rows' first position (all live: a full kind gives
    nothing back), once a block of 8 rows. The known histories: a 40-token
    prompt takes three dispatches of 16 rows at histories 0, 16 and 32, the
    short prompt that finds a free slot packs beside its tail at history 0, and the prompt asked
    again finds its pages in the prefix cache."""
    model, cfg, params = model_and_params
    eng = _engine(model, cfg, params, kernels=True)
    assert eng.metrics()["serving/prefill_kernel_active"] is True
    eng.warmup()
    rng = np.random.RandomState(1)
    long_prompt = rng.randint(3, cfg.vocab_size, (40,))
    prompts = [long_prompt] + [rng.randint(3, cfg.vocab_size, (n,)) for n in (5, 12)] + [long_prompt]
    reqs = [eng.submit(p, max_new_tokens=3, seed=i) for i, p in enumerate(prompts)]
    bt, ps, tables = eng._ragged_bt, eng.page_size, eng._kinds[0].tables
    seen, total = [], 0
    while True:
        mark = _mark()
        if not eng.step():
            break
        new = _spans_since(mark)
        dispatches = [s for s in new if s[2] == "serving/prefill_dispatch"]
        chunks = [s[5] for s in new if s[2] == "serving/prefill_chunk"]
        assert len(dispatches) <= 1 and bool(chunks) == bool(dispatches)
        if not dispatches:
            continue
        want = 0
        for chunk in chunks:
            before = tables.rows[chunk["slot"]][:-(-chunk["start"] // ps)]
            assert tables.parking not in before
            want += -(-chunk["bucket"] // bt) * len(before)
        assert dispatches[0][5]["pages_walked"] == want
        seen.append((sorted(c["start"] for c in chunks), want))
        total += want
    assert all(r.outcome == "finished" for r in reqs)
    # 16 rows are two blocks over 2 pages, then over 4; 8 rows one block over 4
    assert seen[:3] == [([0], 0), ([16], 4), ([0, 32], 4)]
    # the prompt again: everything but its last page is cached, one block walks those pages
    assert reqs[3].prefix_hit == 32 and seen[-1] == ([32], 4) and total == 12
    # on its dense reference the engine walks nothing, and says so
    dense = _engine(model, cfg, params)
    assert dense.metrics()["serving/prefill_kernel_active"] is False
    mark = _mark()
    dense.generate_batched([long_prompt], max_new_tokens=2)
    assert {s[5]["pages_walked"] for s in _spans_since(mark) if s[2] == "serving/prefill_dispatch"} == {0}


def test_a_long_collection_is_a_span_under_what_was_open(model_and_params):
    """``host/gc`` (telemetry/spans.record_gc): one ``gc.callbacks`` hook a
    process, installed by the first engine; a collection of a millisecond or
    more lands once in the ring, its parent the span open on the collecting
    thread, and a shorter one leaves nothing."""
    import gc
    import time

    model, cfg, params = model_and_params
    _engine(model, cfg, params)
    _engine(model, cfg, params)
    assert gc.callbacks.count(spans_mod._on_gc) == 1  # a second engine installs no second hook
    slow = lambda phase, info: time.sleep(0.003) if phase == "start" else None  # after the hook's own start
    was_enabled = gc.isenabled()
    gc.disable()  # no collection of the interpreter's own choosing in between
    try:
        gc.collect(0)  # empty the young generation: the next one has nothing to do
        mark = _mark()
        with spans_mod.span("test/open") as outer:
            gc.collect(0)
            assert _spans_since(mark) == []  # tens of microseconds: no span
            gc.callbacks.append(slow)
            try:
                gc.collect(0)
            finally:
                gc.callbacks.remove(slow)
        (pause, closed) = _spans_since(mark)
    finally:
        if was_enabled:
            gc.enable()
    assert closed[2] == "test/open" and pause[2] == "host/gc"
    assert pause[1] == outer.id and outer.t0 <= pause[3] <= pause[4] <= outer.t1
    assert pause[4] - pause[3] >= 0.003 >= spans_mod.GC_SPAN_S
    assert pause[5]["generation"] == 0 and pause[5]["collected"] >= 0


@pytest.mark.parametrize("held", ["ring", "recorder"])
def test_a_collection_that_starts_under_a_lock_of_the_ring_does_not_wait_for_it(monkeypatch, tmp_path, held):
    """The interpreter starts a collection wherever it checks for one, also
    right after the call inside ``with _ring_lock:`` or a recorder's
    ``with self._lock:``, on the thread that holds the lock. The hook takes
    neither: it notes the pause, and the next span to close carries it into
    the ring and the stream, on the collecting thread's row."""
    import gc
    import threading
    import time

    spans_mod.record_gc()
    rec = spans_mod.arm(str(tmp_path / "spans.jsonl"))
    # locks of the test's own: a hook that blocked on one would hang this thread alone
    ring_lock, rec_lock = threading.Lock(), threading.Lock()
    monkeypatch.setattr(spans_mod, "_ring_lock", ring_lock)
    monkeypatch.setattr(rec, "_lock", rec_lock)
    slow = lambda phase, info: time.sleep(0.003) if phase == "start" else None
    seen = {}

    def collect_under_the_lock():
        with spans_mod.span("test/open") as outer:
            seen["outer"] = outer.id
            with ring_lock if held == "ring" else rec_lock:
                gc.collect(0)
        seen["thread"] = threading.get_ident() & 0xFFFFFFFF

    mark = _mark()
    gc.callbacks.append(slow)
    try:
        worker = threading.Thread(target=collect_under_the_lock, daemon=True)
        worker.start()
        worker.join(10)
    finally:
        gc.callbacks.remove(slow)
        spans_mod.disarm()
    assert not worker.is_alive()  # it did not wait for the lock it held
    new = _spans_since(mark)
    (pause,) = [s for s in new if s[2] == "host/gc" and s[1] == seen["outer"]]
    (outer,) = [s for s in new if s[0] == seen["outer"]]
    assert new.index(pause) < new.index(outer) and outer[3] <= pause[3] <= pause[4] <= outer[4]
    with open(tmp_path / "spans.jsonl") as fh:
        events = [json.loads(line) for line in fh]
    (streamed,) = [e for e in events if e["name"] == "host/gc" and e["tid"] == seen["thread"]]
    assert streamed["cat"] == "host" and streamed["dur"] >= 3000


def test_the_ring_holds_a_whole_run_of_iterations(model_and_params):
    """What ``RING_SPANS`` is sized by (telemetry/spans.py): the spans an
    iteration closes. An engine whose every iteration admits (a queue of
    prompts of three dispatches each, so most dispatches are mid-prompt, as
    in the serving cells) closes ``serving/step``, six phases, three more
    around the prefill dispatch and a ``serving/prefill_chunk`` a packed
    request, plus ``serving/queue_wait`` and ``serving/first_token`` a
    request; since PR 40 also a ``serving/pack_upload`` a pack, a
    ``serving/page_grow`` a packed request, and a ``serving/prefix_lookup``
    and a ``serving/prefix_insert`` a request: 13 to 15 where it was 11 to
    13. An iteration that only decodes closes 7 (5 where it has nothing
    left to enqueue and only reads the last tokens), as before. The runs on
    the chip (PERF.md section 6, PR 40): 12.4 spans an iteration in chat
    (23,650 of 1,913 iterations in a traced run of 45 s), 13.4 in re-ask
    (18,997 of 1,420), 9.7 in batch (23,637 of 2,448), 12.1 in MiMo's mix,
    11.3 in the state-space cell (32,727 of 2,891) and 9.3 in EvaByte's
    (33,990 of 3,636 at PR 40, 40,394 of about 4,340 since PR 43: the longest run, under two thirds of the ring). ``host/gc`` spans are the
    collector's and few (7 to 36 a run): not counted here. A span added to
    the iteration shows here before a benchmark run loses its ring-read
    metrics to a wrapped ring."""
    model, cfg, params = model_and_params
    eng = _engine(model, cfg, params, kernels=True)
    eng.warmup()
    rng = np.random.RandomState(2)
    for i in range(6):
        eng.submit(rng.randint(3, cfg.vocab_size, (40,)), max_new_tokens=4, seed=i)
    admitting, decoding = [], []
    while True:
        mark = _mark()
        if not eng.step():
            break
        new = [s for s in _spans_since(mark) if s[2] != "host/gc"]
        assert new[-1][2] == "serving/step"  # the iteration's own span closes last
        names = [s[2] for s in new]
        (admitting if "serving/prefill_dispatch" in names else decoding).append(len(new))
        if "serving/prefill_dispatch" not in names:
            assert len(new) == (7 if "serving/decode_dispatch" in names else 5), names
    assert len(admitting) >= 18 and decoding
    # step + 9 phases + a chunk + an upload and a growth, and in one dispatch of
    # three the two request spans, in another the lookup, in the third the insert
    assert 13 <= max(admitting) <= 16
    per_admitting = sum(admitting) / len(admitting)
    assert 13 <= per_admitting <= 14.5
    # every iteration admitting, 4,000 of them fit (5,000 did at 11 to 12.5 spans an admitting iteration, before
    # PR 40's children); the chip's longest runs (warm-in and 45 s, above; PERF.md section 6, PR 40) stay under
    # two thirds of the ring, which leaves a run half again as many iterations before it wraps
    assert spans_mod.RING_SPANS >= 4000 * per_admitting
    for iterations, admit_share in RUNS_ON_THE_CHIP:
        assert iterations * (admit_share * per_admitting + (1 - admit_share) * 7) <= 2 / 3 * spans_mod.RING_SPANS


def test_a_latent_cache_says_what_its_kernels_are_handed():
    """A model with latent attention and experts (the gigachat3 cell's, at
    its rehearsal's widths): ``latent_tokens`` on ``serving/decode_dispatch``
    is the page-rounded entries one layer's kernel reads for the slots the
    step grew; ``latent_pairs`` / ``latent_entries`` / ``latent_expanded``
    on ``serving/prefill_dispatch`` the visible (row, entry) pairs of the
    pack, the entries its rows see, and the cached entries it up-projected
    (none: both programs read absorbed); ``expert_chunks`` on both the
    grouped products the expert layers made; the gauges the stored width."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax.numpy as jnp
    import manifest
    import weights

    arch = manifest.load_arch("deepseek_v3")
    with open(os.path.join(root, "benchmarks", "configs", "gigachat3.1-702b-serve-6l-ep32.json")) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    c["num_hidden_layers"] = 3
    cfg = dataclasses.replace(arch.decoder_config(c, max_seq_len=128, remat=False), dtype=jnp.float32,
                              decode_kernel="interpret", prefill_kernel="interpret")
    params = weights.make_jit(arch.reference, c, 3, jnp.float32, adapt=arch.to_program_tree(c))
    eng = ServingEngine(arch.module(cfg), params, num_slots=2, max_cache_len=128, page_size=8,
                        prefill_chunks=(16, 32), prefix_cache=False)
    m = eng.metrics()
    assert m["serving/latent_bytes_per_token"] == 40 * 4 and m["serving/mla_kernel_active"] == 1
    eng.warmup()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(3, 512, (n,)) for n in (45, 7, 20)]
    mark = _mark()
    reqs = [eng.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    eng.run()
    assert all(r.outcome == "finished" for r in reqs)
    new = _spans_since(mark)
    packs = [s[5] for s in new if s[2] == "serving/prefill_dispatch"]
    steps = [s[5] for s in new if s[2] == "serving/decode_dispatch"]
    grows = [s[5] for s in new if s[2] == "serving/decode_grow"]
    assert packs and steps and len(steps) == len(grows)
    # every prompt row sees the entries up to its own: sum over prompts of n (n + 1) / 2
    assert sum(a["latent_pairs"] for a in packs) == sum(n * (n + 1) // 2 for n in (45, 7, 20))
    assert all(a["latent_expanded"] == 0 and a["arena_in_place"] == 1 for a in packs)
    # the 45-token prompt takes two packs: the second's rows see 45 entries, those of the first cached
    assert max(a["latent_entries"] for a in packs) >= 45 and len(packs) >= 2
    # one latent layer's walk is the first kind's walk of the same round: the kind is the only one
    assert [a["latent_tokens"] for a in steps] == [g["walked_tokens"] for g in grows]
    assert all(a["latent_tokens"] > 0 and a["latent_tokens"] % 8 == 0 and a["arena_in_place"] == 1 for a in steps)
    # two expert layers: a chunk a layer that got a held pair, more only where a burst overflowed
    for a in packs + steps:
        assert 0 <= a["expert_chunks"] and (a["expert_pairs"] == 0) == (a["expert_chunks"] == 0)
    assert any(a["expert_chunks"] >= 1 for a in steps)


def test_a_state_with_heads_and_experts_in_a_latent_say_what_their_kernels_are_handed():
    """A model of half-blocks (the nemotron3 cell's, at its rehearsal's
    widths, kernels interpreted): ``ssm_slots`` / ``ssm_rows`` on
    ``serving/decode_dispatch`` the slots whose state a step advances;
    ``ssm_rows`` / ``ssm_slots`` / ``ssm_fresh_slots`` on
    ``serving/prefill_dispatch`` a pack's live rows, its slots and those it
    zeroes; on both ``expert_pairs`` the pairs sent to held experts,
    ``experts_touched`` the held experts of a layer that got one (with
    ``experts_idle`` all those held), ``expert_chunks`` the grouped products
    made; the gauges the state's bytes a slot and which recurrence's kernel
    is engaged."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax.numpy as jnp
    import manifest
    import weights

    arch = manifest.load_arch("nemotron_h")
    with open(os.path.join(root, "benchmarks", "configs", "nemotron3-super-120b-serve-11l-ep4.json")) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    c["published"] = {"n_routed_experts": 32}
    cfg = dataclasses.replace(arch.decoder_config(c, max_seq_len=128, remat=False, decode_kernel="interpret",
                                                  prefill_kernel="interpret"), dtype=jnp.float32)
    params = weights.make_jit(arch.reference, c, 3, jnp.float32, adapt=arch.to_program_tree(c))
    eng = ServingEngine(arch.module(cfg), params, num_slots=2, max_cache_len=128, page_size=8,
                        prefill_chunks=(16, 32), prefix_cache=False)
    m = eng.metrics()
    state = 64 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 4  # a layer: heads x head_dim x state, and the convolution's 3 rows
    assert m["serving/state_bytes_per_slot"] == 2 * state and m["serving/state_in_place"] == 1
    assert (m["serving/ssd_kernel_active"], m["serving/ssm_kernel_active"]) == (1, 0)
    assert m["serving/experts_from_stack"] == 1
    eng.warmup()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(3, 512, (n,)) for n in (45, 7, 20)]
    mark = _mark()
    reqs = [eng.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    eng.run()
    assert all(r.outcome == "finished" for r in reqs)
    new = _spans_since(mark)
    packs = [s[5] for s in new if s[2] == "serving/prefill_dispatch"]
    steps = [s[5] for s in new if s[2] == "serving/decode_dispatch"]
    assert packs and steps
    assert sum(a["ssm_rows"] for a in packs) == 45 + 7 + 20 and sum(a["ssm_fresh_slots"] for a in packs) == 3
    assert all(1 <= a["ssm_slots"] <= 2 for a in packs)
    assert all(a["ssm_slots"] == a["ssm_rows"] == a["slots"] for a in steps)
    held = 2 * 8  # two expert layers of 8 held experts
    for a in packs + steps:
        assert a["experts_touched"] + a["experts_idle"] == held
        assert a["experts_touched"] <= a["expert_pairs"] <= a["expert_pairs_all"]
        assert (a["expert_pairs"] == 0) == (a["expert_chunks"] == 0)
    # 4 experts a token of 32 outputs, 8 held: a step of two slots sends the held quarter a pair or two a layer
    assert any(a["experts_touched"] >= 1 for a in steps)
    assert sum(a["expert_pairs_all"] for a in steps) == sum(a["slots"] for a in steps) * 2 * 4


@pytest.mark.parametrize("kernel", ["interpret", None], ids=["kernels_interpreted", "jax_numpy"])
def test_a_delta_rule_state_and_narrow_experts_say_what_their_kernels_are_handed(kernel):
    """A model of Gated DeltaNet and gated attention layers with experts in
    every layer (the qwen3-next cell's, at its rehearsal's widths, kernels
    interpreted): ``ssm_slots`` / ``ssm_rows`` on ``serving/decode_dispatch``
    the slots whose delta-rule state a step advances; ``ssm_rows`` /
    ``ssm_slots`` / ``ssm_fresh_slots`` on ``serving/prefill_dispatch`` a
    pack's live rows, its slots and those it zeroes; on both ``expert_pairs``
    the pairs sent to held experts, ``experts_idle`` the held experts of a
    layer that got none, ``expert_chunks`` the grouped products made; the
    gauges the state's bytes a slot and whether the delta rule's kernel is the
    one engaged (not where ``jax.numpy`` walks the rows)."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax.numpy as jnp
    import manifest
    import weights

    arch = manifest.load_arch("qwen3_next")
    with open(os.path.join(root, "benchmarks", "configs", "qwen3-next-80b-serve-12l-ep8.json")) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    c["published"] = {"num_experts": 32}
    cfg = dataclasses.replace(arch.decoder_config(c, max_seq_len=128, remat=False, decode_kernel=kernel,
                                                  prefill_kernel=kernel),
                              dtype=jnp.float32)
    params = weights.make_jit(arch.reference, c, 3, jnp.float32, adapt=arch.to_program_tree(c))
    eng = ServingEngine(arch.module(cfg), params, num_slots=2, max_cache_len=128, page_size=8,
                        prefill_chunks=(16, 32), prefix_cache=False)
    m = eng.metrics()
    state = 8 * 8 * 8 * 4 + 3 * (2 * 4 * 8 + 8 * 8) * 4  # a layer: value heads x dk x dv, and the convolution's 3 rows
    assert m["serving/state_bytes_per_slot"] == 3 * state == arch.slot_state_bytes(c) and m["serving/state_in_place"] == 1
    on = int(kernel == "interpret")
    assert (m["serving/gdn_kernel_active"], m["serving/ssd_kernel_active"], m["serving/ssm_kernel_active"]) == (on, 0, 0)
    assert m["serving/experts_from_stack"] == on
    eng.warmup()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(3, 512, (n,)) for n in (45, 7, 20)]
    mark = _mark()
    reqs = [eng.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    eng.run()
    assert all(r.outcome == "finished" for r in reqs)
    new = _spans_since(mark)
    packs = [s[5] for s in new if s[2] == "serving/prefill_dispatch"]
    steps = [s[5] for s in new if s[2] == "serving/decode_dispatch"]
    assert packs and steps
    assert sum(a["ssm_rows"] for a in packs) == 45 + 7 + 20 and sum(a["ssm_fresh_slots"] for a in packs) == 3
    assert all(1 <= a["ssm_slots"] <= 2 for a in packs)
    assert all(a["ssm_slots"] == a["ssm_rows"] == a["slots"] for a in steps)
    held = 4 * 8  # four layers of 8 held experts
    for a in packs + steps:
        assert a["experts_touched"] + a["experts_idle"] == held
        assert a["experts_touched"] <= a["expert_pairs"] <= a["expert_pairs_all"]
        assert (a["expert_pairs"] == 0) == (a["expert_chunks"] == 0)
    assert any(a["experts_touched"] >= 1 for a in steps)
    assert sum(a["expert_pairs_all"] for a in steps) == sum(a["slots"] for a in steps) * 4 * 4
