"""The spans and counts ``ServingEngine.step()`` records (docs/telemetry.md,
"Serving iteration spans"): one ``serving/step`` an iteration with its phases
as children, in order, and the request-level stamps and spans beside them.

One engine run a path (the packed prefill on its kernel and on its dense
reference, quantized pages, fused burst, speculative verify), read back
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
# speculative verify keeps depth 0 (its page growth follows the fetched
# acceptance counts): every result is read before the next dispatch
SERIAL_PHASES = PHASES[:3] + PHASES[7:] + PHASES[3:7]
SERIAL_PATHS = ("verify",)
PROMPT_LENS = (20, 5, 12, 3, 9)
NEW_TOKENS = 5

PATHS = {
    "ragged": dict(page_size=8, kernels=True),
    "dense": dict(page_size=8),  # the packed dispatch on its reference
    # quantized pages: the kernels with the split-threading arm of the decode step
    "int8": dict(page_size=8, kernels=True, kv_cache_dtype="int8"),
    "burst": dict(page_size=8, kernels=True, steps_per_call=2),
    "verify": dict(page_size=8, kernels=True, spec_draft_len=2),
}


@pytest.fixture(scope="module")
def model_and_params():
    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    return model, cfg, params


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
        cfg = dataclasses.replace(cfg, decode_kernel="interpret", decode_kernel_block=8,
                                  prefill_kernel="interpret")
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

    def of_request(self, name, req):
        return sorted((s for s in self.named(name) if s[5]["request_id"] == req.id),
                      key=lambda s: s[3])


@pytest.fixture(scope="module")
def runs(model_and_params):
    cache = {}

    def get(path):
        if path not in cache:
            cache[path] = Run(*model_and_params, **PATHS[path])
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
        phases = SERIAL_PHASES if path in SERIAL_PATHS else PHASES
        order = [phases.index(n) for n in names]
        assert order == sorted(set(order)), names
        assert names[:2] == ["serving/reap", "serving/admit_plan"]
        by_name = {k[2]: k for k in kids}
        if "serving/prefill_dispatch" in names:
            dispatched += 1
            # the packed dispatch's first tokens are fetched and committed in
            # the iteration that sent it, every time
            assert "serving/prefill_commit" in names and "serving/prefill_fetch" in names
            # ... behind the decode dispatch, where there is one
            assert by_name["serving/prefill_fetch"][5]["in_flight"] == int(
                "serving/decode_dispatch" in names and path not in SERIAL_PATHS)
        if "serving/decode_dispatch" in names:
            assert "serving/decode_grow" in names
        if path in SERIAL_PATHS:
            if "serving/decode_dispatch" in names:
                assert names[-3:] == ["serving/decode_dispatch", "serving/token_fetch", "serving/emit"]
                assert by_name["serving/token_fetch"][5]["in_flight"] == 0
        elif "serving/token_fetch" in names:
            # the tokens read are the previous dispatch's: read behind this
            # iteration's dispatch, or alone once nothing is left to enqueue
            assert names[names.index("serving/token_fetch") + 1] == "serving/emit"
            assert by_name["serving/token_fetch"][5]["in_flight"] == int("serving/decode_dispatch" in names)
            assert by_name["serving/emit"][5]["discarded"] == 0  # no eos here
        # children lie inside the step, one after another, and cover it
        for a, b in zip(kids, kids[1:]):
            assert a[4] <= b[3]
        assert step[3] <= kids[0][3] and kids[-1][4] <= step[4]
        # ... to within 5% of its duration (and 2 ms of scheduling noise: the
        # suite's workers share this host's cores; on the chip an iteration is
        # 330-900 ms and its own time 0.3 ms)
        own = (step[4] - step[3]) - sum(k[4] - k[3] for k in kids)
        assert own <= 0.05 * (step[4] - step[3]) + 2e-3, names
        uncovered += own
    # (a tenth of a millisecond of Python between the spans of an iteration: on
    # the CPU a step of the dense path is hardly more than a millisecond now
    # that the device works while the host does)
    assert uncovered <= 0.05 * sum(s[4] - s[3] for s in steps) + 1e-4 * len(steps)
    assert dispatched == len(run.named("serving/prefill_dispatch")) > 0
    # every decode dispatch's tokens were read, one fetch each, and on the
    # overlapped paths all but the last behind the next dispatch
    fetches = run.named("serving/token_fetch")
    assert len(fetches) == len(run.named("serving/decode_dispatch")) > 0
    overlapped = sum(f[5]["in_flight"] for f in fetches)
    assert overlapped == 0 if path in SERIAL_PATHS else overlapped >= len(fetches) - len(PROMPT_LENS)
    assert run.engine.metrics()["serving/dispatch_depth"] == 0  # run() leaves nothing unread
    # no span per token or per slot: everything recorded is one of these
    allowed = set(PHASES) | {"serving/step", "serving/warmup", "serving/queue_wait",
                             "serving/prefill_chunk", "serving/first_token"}
    assert {s[2] for s in run.spans} <= allowed


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
    # unquantized pages (the decode dispatch's own counter says 0 on every verify dispatch; the pack's does not)
    in_place = int(path in ("ragged", "burst", "verify"))
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
    assert sum(s[5]["pages_allocated"] for s in grows) <= eng.pages_allocated
    assert all(s[5]["walked_tokens"] % 8 == 0 and s[5]["walked_tokens"] > 0 for s in grows)
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
    assert eng.metrics()["serving/prefill_page_walk"] == 1
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
    assert dense.metrics()["serving/prefill_page_walk"] == 0
    mark = _mark()
    dense.generate_batched([long_prompt], max_new_tokens=2)
    assert {s[5]["pages_walked"] for s in _spans_since(mark) if s[2] == "serving/prefill_dispatch"} == {0}


def test_the_ring_holds_a_whole_run_of_iterations(model_and_params):
    """What ``RING_SPANS`` is sized by (telemetry/spans.py): the spans an
    iteration closes. An engine whose every iteration admits (a queue of
    prompts of three dispatches each, so most dispatches are mid-prompt, as
    in the serving cells) closes ``serving/step``, six phases, three more
    around the prefill dispatch and a ``serving/prefill_chunk`` a packed
    request, plus ``serving/queue_wait`` and ``serving/first_token`` a
    request; an iteration that only decodes closes 7 (5 where it has
    nothing left to enqueue and only reads the last tokens). One dispatch
    in flight changed the order of the phases and not their number. The
    ring holds 5,000 iterations that all admit, 6,000 of MiMo's mix (43 of
    79 traced iterations admit; PERF.md section 6), and 6,000 of the
    state-space cell's (53% admit), which runs the most iterations at the
    pace of PR 37: 1,100 of warm-in and a 51 s window at 26 ms an
    iteration, 3,100 in all, against 2,500 before. At the pace of PR 39
    (a pack iteration near 30 ms where it was 62-80) EvaByte's cell runs the
    most: 1,000 of warm-in and a window at 17 ms an iteration, 3,631 in a
    traced run of 40 s that held 31,111 spans (PERF.md section 6), 4,000 in
    one of 51 s, 38% of them admitting. ``arena_in_place`` on the prefill
    dispatch is an attribute and no span: the counts stand. A span added to
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
        new = _spans_since(mark)
        assert new[-1][2] == "serving/step"  # the iteration's own span closes last
        names = [s[2] for s in new]
        (admitting if "serving/prefill_dispatch" in names else decoding).append(len(new))
        if "serving/prefill_dispatch" not in names:
            assert len(new) == (7 if "serving/decode_dispatch" in names else 5), names
    assert len(admitting) >= 18 and decoding
    # step + 9 phases + a chunk, and two request spans in one dispatch of three
    assert 11 <= max(admitting) <= 14
    per_admitting = sum(admitting) / len(admitting)
    assert 11 <= per_admitting <= 12.5
    assert spans_mod.RING_SPANS >= 5000 * per_admitting
    assert spans_mod.RING_SPANS >= 6000 * (43 / 79 * per_admitting + 36 / 79 * 7)
    assert spans_mod.RING_SPANS >= 6000 * (0.53 * per_admitting + 0.47 * 7)
    assert spans_mod.RING_SPANS >= 1.5 * 4000 * (0.38 * per_admitting + 0.62 * 7)  # EvaByte since PR 39, half again
