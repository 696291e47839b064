"""The readers of the program's own spans (``benchmarks/program_spans.py``):
the window, the clock offset and the division of idle gaps on hand-made
records, then both serving cells end to end at the rehearsal's size."""

import json
import os
import subprocess
import sys
import types

import pytest

import cells
import manifest as M
import program_spans as P

RUN = os.path.join(M.BENCH_DIR, "run.py")
PREFIX = "[CPU REHEARSAL, not a chip run] "
# the metrics read from the program's own spans, and for each served cell of
# the manifest those of them that list it
FROM_SPANS = {"queue_wait_p50_ms", "prefill_own_dispatches_p50", "prefill_pack_fill_pct",
              "prefill_device_us_per_token", "idle_host_work_pct", "idle_result_wait_pct"}
NEW = {w: {m["name"] for m in cells.find(w)["per_layer"] if m["name"].split(".")[0] in FROM_SPANS}
       for w in cells.by_driver("closed_loop")}
ALL_FROM_SPANS = set().union(*NEW.values())
OFFSET = 5_000_000_000.0  # the trace's clock runs 5 s ahead of perf_counter


def _records(iterations):
    """``Spans.records`` of ``iterations`` iterations of 1 s from t = 10 s:
    submit 0.1, step 0.8, emit 0.1."""
    out = []
    for i in range(iterations):
        t = 10.0 + i
        out += [("bench/submit", t, t + 0.1), ("bench/step", t + 0.1, t + 0.9), ("bench/emit", t + 0.9, t + 1.0)]
    return types.SimpleNamespace(records=out)


def _trace(spans, traced, gaps=(), modules=()):
    """A trace that holds the last ``traced`` iterations' ``bench/`` spans."""
    raw = [(n, int(s * 1e9 + OFFSET), int((e - s) * 1e9)) for n, s, e in spans.records[-3 * traced:]] if traced else []
    t0, t1 = (raw[0][1], raw[-1][1] + raw[-1][2]) if raw else (0, 0)
    return {"raw": {"spans": raw, "devices": {0: {"modules": list(modules)}}},
            "reduced": {"window_s": (t1 - t0) / 1e9, "devices": {0: {"gaps": [list(g) for g in gaps]}}}}


def test_window_counts_back_from_the_traced_iterations():
    spans, counters = _records(10), {"iterations": 4, "window_s": 3.5}
    win = P.window(spans, counters, _trace(spans, 2))
    # warm-in 4, window 4 (the last crossing its end), traced 2
    assert win["run_t0"] == 10.0 and win["measured"] == (14.0, 17.5) and win["traced"] == (18.0, 20.0)
    assert P.window(spans, counters, None)["measured"] == (16.0, 19.5)  # no trace: the last four
    assert P.window(spans, counters, None)["traced"] is None


@pytest.mark.parametrize("spans,counters", [
    (None, {"iterations": 4, "window_s": 3.5}), (_records(10), {}), (_records(10), None),
    (_records(3), {"iterations": 4, "window_s": 3.5}), (_records(10), {"iterations": 4})])
def test_no_window_no_reading(spans, counters):
    assert P.window(spans, counters, None) is None


def test_clock_offset_is_the_median_over_the_traced_bench_spans():
    spans = _records(6)
    trace = _trace(spans, 3)
    assert P.clock_offset(spans, trace) == OFFSET
    # one pair far off (a late annotation) does not move the median
    n, s, d = trace["raw"]["spans"][4]
    trace["raw"]["spans"][4] = (n, s + 40_000_000, d)
    assert P.clock_offset(spans, trace) == OFFSET
    assert P.clock_offset(spans, _trace(spans, 0)) is None and P.clock_offset(spans, None) is None


def _ns(t):
    return int(t * 1e9 + OFFSET)


def _program(step_t0):
    """One ``serving/step`` of 0.8 s with five of its phases, on the trace's clock."""
    t = step_t0
    phases = [("serving/reap", t, t + 0.01), ("serving/admit_plan", t + 0.01, t + 0.05),
              ("serving/decode_dispatch", t + 0.06, t + 0.10), ("serving/token_fetch", t + 0.10, t + 0.70),
              ("serving/emit", t + 0.70, t + 0.79)]
    out = [(1, None, "serving/step", _ns(t), _ns(t + 0.8))]
    return out + [(2 + i, 1, n, _ns(s), _ns(e)) for i, (n, s, e) in enumerate(phases)]


def test_a_gap_spanning_three_spans_is_split_by_overlap():
    spans = _records(1)
    # one gap from the middle of the fetch, through emit, to the end of the
    # iteration's bench/emit; one inside the step's own time; one before it all
    gaps = [(_ns(9.5), _ns(9.75)), (_ns(10.15), _ns(10.155)), (_ns(10.60), _ns(11.0))]
    by = P.idle_by_span(_trace(spans, 1, gaps), _program(10.1))
    assert by == pytest.approx({
        "_no_span_": 0.25,                 # before the first bench/ span
        "serving/step": 0.005 + 0.01,      # the step's own time: 10.15-10.155, and 10.89-10.90
        "serving/token_fetch": 0.2, "serving/emit": 0.09,
        "bench/emit": 0.1})                # what no serving/ span covers falls to the bench/ span
    assert sum(by.values()) == pytest.approx(0.25 + 0.005 + 0.4)
    # given whole to the span open at its start, the long gap would read fetch 0.4
    assert by["serving/token_fetch"] < 0.4


def test_innermost_takes_children_out_of_their_parent():
    pieces = P.innermost(_program(10.1))
    assert [p[0] for p in pieces] == ["serving/reap", "serving/admit_plan", "serving/step",
                                      "serving/decode_dispatch", "serving/token_fetch", "serving/emit",
                                      "serving/step"]
    assert all(a[2] <= b[1] for a, b in zip(pieces, pieces[1:]))
    assert sum(e - s for _, s, e in pieces) == pytest.approx(0.8e9)


def test_dispatch_misfit_reads_zero_on_one_clock_and_the_skew_off_it():
    spans = _records(1)
    modules = [("jit_step(7)", _ns(10.17), int(0.5e9)), ("jit_ragged_prefill(9)", _ns(10.0), int(0.05e9))]
    trace = _trace(spans, 1, modules=modules)
    assert P.dispatch_misfit_ns(trace, _program(10.1)) == [0]
    late = [(i, p, n, s + 30_000_000, e + 30_000_000) for i, p, n, s, e in _program(10.1)]
    assert P.dispatch_misfit_ns(trace, late) == [pytest.approx(20_000_000)]


def _ring(first_id=1):
    """A ring of one run over ``_records(10)``: two requests with their
    first tokens in the window (16.0-19.5), one before it."""
    ring, i = [], first_id

    def add(name, t0, t1, **args):
        nonlocal i
        ring.append((i, None, name, t0, t1, args or None))
        i += 1

    for rid, submit, admit, first, n in ((0, 10.05, 10.2, 12.7, 3), (1, 13.05, 15.2, 16.7, 2), (2, 14.05, 16.2, 18.7, 4)):
        add("serving/queue_wait", submit, admit, request_id=rid, slot=rid)
        add("serving/first_token", submit, first, request_id=rid, prompt_len=700, prefix_hit=0, dispatches=n,
            queue_wait_ms=1e3 * (admit - submit))
    for t, rows, tokens in ((12.2, 256, 200), (16.2, 256, 250), (17.2, 128, 70), (18.2, 256, 256), (19.7, 256, 100)):
        add("serving/prefill_dispatch", t, t + 0.4, rows=rows, tokens=tokens, requests=1)
    return sorted(ring, key=lambda s: s[4])


def _read(name, trace, spans, counters):
    return M.load_metric_reader(name).read(trace, spans, counters, {"chips": 1, "peaks": {}})


def test_readers_take_the_windows_spans(monkeypatch):
    spans, counters = _records(10), {"iterations": 4, "window_s": 3.5}
    monkeypatch.setattr(P, "program_ring", lambda: (_ring(), 0))
    assert _read("queue_wait_p50_ms", None, spans, counters) == pytest.approx(2150.0)
    assert _read("prefill_own_dispatches_p50", None, spans, counters) == 3.0
    assert _read("prefill_pack_fill_pct", None, spans, counters) == pytest.approx(100 * 576 / 640)
    for name in ALL_FROM_SPANS - {"queue_wait_p50_ms", "prefill_own_dispatches_p50", "prefill_pack_fill_pct"}:
        assert _read(name, None, spans, counters) is None  # no trace
    # a trace of the last iteration: one prefill dispatch of 100 tokens in it, 0.3 s on the device
    trace = _trace(spans, 1, gaps=[(_ns(19.0), _ns(19.05))])
    trace["reduced"]["devices"][0].update(module_s={"jit_ragged_prefill": 0.3, "jit_step": 0.5}, busy_s=0.95)
    assert _read("prefill_device_us_per_token", trace, spans, counters) == pytest.approx(3000.0)
    assert _read("idle_host_work_pct.chat", trace, spans, counters) == pytest.approx(5.0)  # under bench/submit
    assert _read("idle_result_wait_pct.chat", trace, spans, counters) == 0


@pytest.mark.parametrize("ring,dropped", [
    (None, 0),                       # a program that records no ring: the parent commit
    ([], 0),                         # nothing recorded
    (_ring(), 3),                    # the ring wrapped inside this run
    ([(1, None, "serving/first_token", 3.0, 5.0, {"request_id": 0, "dispatches": 9})], 0)],  # an earlier engine's
    ids=["no_ring", "empty", "wrapped", "earlier_engine"])
def test_readers_return_none_without_what_they_need(monkeypatch, ring, dropped):
    spans, counters = _records(10), {"iterations": 4, "window_s": 3.5}
    monkeypatch.setattr(P, "program_ring", lambda: (ring, dropped))
    trace = _trace(spans, 1, gaps=[(_ns(19.0), _ns(19.05))])
    trace["reduced"]["devices"][0].update(module_s={"jit_ragged_prefill": 0.3}, busy_s=0.95)
    for name in ALL_FROM_SPANS:
        value = _read(name, trace, spans, counters)
        if ring and not dropped and name.startswith("idle_"):
            continue  # the traced window's idle time is read from the trace and the bench/ spans
        assert value is None, name


def test_a_ring_that_wrapped_before_the_run_is_read(monkeypatch):
    spans, counters = _records(10), {"iterations": 4, "window_s": 3.5}
    old = [(1, None, "serving/step", 1.0, 2.0, None)]
    monkeypatch.setattr(P, "program_ring", lambda: (old + _ring(2), 500))
    assert _read("prefill_own_dispatches_p50", None, spans, counters) == 3.0


def test_a_program_without_the_ring_is_read_as_none(monkeypatch):
    """What the parent commit gives: its telemetry.spans has no snapshot()."""
    from accelerate_tpu.telemetry import spans as program

    monkeypatch.delattr(program, "snapshot")
    assert P.program_ring() == (None, 0)


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_rehearsal_prints_the_new_metrics(workload):
    env = dict(os.environ, PYTHONPATH=M.ROOT)
    env.pop("JAX_DISABLE_MOST_OPTIMIZATIONS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--cpu-rehearsal"], capture_output=True, text=True, env=env, cwd=M.ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads([l for l in proc.stdout.splitlines() if l.strip()][-1][len(PREFIX):])
    got = {k.split(":", 1)[1]: v["value"] for k, v in line["metrics"].items() if k.startswith("rehearsal:")}
    assert len(got) == len(line["metrics"]) and NEW[workload] <= set(got)
    (idle,) = [v for k, v in got.items() if k.startswith("device_idle_pct")]
    shares = {k.split(".")[0]: v for k, v in got.items() if k.startswith("idle_")}
    if shares:
        host, wait = shares["idle_host_work_pct"], shares["idle_result_wait_pct"]
        assert host >= 0 and wait >= 0 and host + wait <= idle + 1e-9
        assert idle - (host + wait) <= 0.3  # what no span covers, in points of the window
    if "queue_wait_p50_ms" in got:
        assert got["queue_wait_p50_ms"] >= 0 and got["prefill_own_dispatches_p50"] >= 1
        assert 0 < got["prefill_pack_fill_pct"] <= 100


def test_walked_tokens_inside_equal_the_drivers_iteration_by_iteration(optimized_xla):
    """``serving/decode_grow`` counts, where the engine grows the slots, the
    page-rounded tokens the decode kernel will walk; the driver counts the
    same from outside (``costs.page_rounded`` over what each request shows
    after the step). Equal in every iteration, so the inside counter can
    take over when a benchmark issue retires the outside one."""
    import run as R
    from accelerate_tpu.telemetry import spans as program

    args = types.SimpleNamespace(seed=7, seconds=2.0, trace=0, cpu_rehearsal=True, control=None)
    ctx = R.Context(cells.find(cells.by_driver("closed_loop")[0]), args)
    driver = M.load_driver("closed_loop")
    engine = driver.build_engine(ctx)
    program.emit("mark", 0.0, 0.0)
    mark = program.snapshot()[-1][0]
    loop = driver.ClosedLoop(engine, ctx.traffic, ctx.seed, ctx.arch, ctx.settings, ctx.spans)
    for _ in range(30):
        loop.iterate()
    ring = [s for s in program.snapshot() if s[0] > mark]
    steps = [s for s in ring if s[2] == "serving/step"]
    assert len(steps) == len(loop.iters) == 30
    grown = {s[1]: s[5]["walked_tokens"] for s in ring if s[2] == "serving/decode_grow"}
    inside = [grown.get(step[0], 0) for step in steps]
    assert inside == [it["walked_tokens"] for it in loop.iters] and sum(inside) > 0
    assert [s[5]["emitted"] for s in steps] == [it["emitted"] for it in loop.iters]
