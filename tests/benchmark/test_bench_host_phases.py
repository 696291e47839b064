"""The readers of the host's share of an iteration (``benchmarks/host_phases.py``)
over a ring made by hand, every value reckoned by hand beside it; then the two
cells that read the admission's child spans, end to end at the rehearsal's size."""

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
OFFSET = 5_000_000_000.0  # the trace's clock runs 5 s ahead of perf_counter
COUNTERS = {"iterations": 4, "window_s": 3.5}
# the metrics this file holds, by what they read
HOST = {"iter_host_ms_p50", "iter_device_wait_pct", "admit_host_ms_per_pack", "pack_upload_ms_per_pack",
        "prefix_lookup_ms_per_pack", "prefix_insert_ms_per_pack", "page_grow_ms_per_pack",
        "prefix_hashed_tokens_per_prompt_token", "prefix_entries_scanned_per_admission", "idle_admission_pct",
        "iter_stalls_per_1000", "stall_gc_share_pct", "page_grow_share_of_step_pct", "prefix_evictions_per_admission",
        "page_grow_pages_per_admission", "prefix_ghost_probe_share_pct"}
NEW = {w: {m["name"] for m in cells.find(w)["per_layer"]
           if m["name"].split(".")[0] in HOST or m["name"] in ("idle_host_work_pct.reask", "idle_result_wait_pct.reask")}
       for w in cells.by_driver("closed_loop")}
ALL_NEW = sorted(set().union(*NEW.values()))
# the cells that read the admission's child spans: they run a prefix cache
ADMISSION_CELLS = sorted(w for w, names in NEW.items() if "admit_host_ms_per_pack" in names)


def _records():
    """``Spans.records`` of ten iterations of 1 s from t = 10 s: submit 0.1,
    step 0.8, emit 0.1. Untraced, the window is the last four (16.0-19.5)."""
    out = []
    for i in range(10):
        t = 10.0 + i
        out += [("bench/submit", t, t + 0.1), ("bench/step", t + 0.1, t + 0.9), ("bench/emit", t + 0.9, t + 1.0)]
    return types.SimpleNamespace(records=out)


def _ns(t):
    return int(t * 1e9 + OFFSET)


def _trace(spans, gaps):
    """A trace of the last iteration (19.0-20.0) with the device idle in ``gaps``."""
    raw = [(n, _ns(s), int((e - s) * 1e9)) for n, s, e in spans.records[-3:]]
    return {"raw": {"spans": raw, "devices": {0: {"modules": []}}},
            "reduced": {"window_s": 1.0, "devices": {0: {"gaps": [[_ns(a), _ns(b)] for a, b in gaps]}}}}


def _ring():
    """Four iterations in the window. A (16.1, 0.20 s): a pack of one request.
    B (17.1, 0.20 s): a plan with nothing to dispatch. C (18.1, 0.20 s): a
    pack of a cached prompt beside a long prompt's middle rows (no insert),
    whose first growth, under pressure, reads the step in flight for 1 ms.
    D (19.1, 0.80 s, a stall): a pack whose insert evicts, and a collection of
    0.3 s inside ``serving/emit``. Before the window, an iteration whose every
    number would spoil the readings if it were taken."""
    ring, ids = [], iter(range(1, 1000))

    def add(name, t0, dur, parent=None, **args):
        i = next(ids)
        ring.append((i, parent, name, t0, t0 + dur, args or None))
        return i

    def pack(step, t, plan_s, lookup, grows, upload, dispatch_s, insert, tokens, rid):
        plan = add("serving/admit_plan", t, plan_s, step)
        at = t + 0.001
        if lookup:
            add("serving/prefix_lookup", at, lookup[0], plan, request_id=rid, entries=lookup[1], probes=9,
                ghost_probes=3, hashed_tokens=lookup[2], hit_tokens=lookup[3])
            add("serving/queue_wait", at - 1.0, 1.0, plan, request_id=rid, slot=0)
            at += lookup[0]
        for dur, scanned, *settle in grows:
            grow = add("serving/page_grow", at, dur, plan, request_id=rid, pages_allocated=2,
                       evictions=int(scanned > 0), evict_scanned=scanned)
            if settle:
                add("serving/token_fetch", at + 0.0005, settle[0], grow, in_flight=0)
            at += dur
        add("serving/pack_upload", at, upload, plan, rows=128)
        if dispatch_s is None:
            return
        disp = add("serving/prefill_dispatch", t + plan_s, dispatch_s, step, rows=128, tokens=tokens, requests=1)
        if insert:
            add("serving/prefix_insert", t + plan_s + 0.002, insert[0], disp, request_id=rid, probes=4,
                hashed_tokens=insert[1], evictions=int(insert[2] > 0), evict_scanned=insert[2], entries=12)

    early = add("serving/step", 12.1, 0.7, iteration=3)
    pack(early, 12.1, 0.3, (0.2, 500, 90000, 0), [(0.05, 400)], 0.04, 0.3, (0.25, 80000, 300), 7, rid=0)
    add("serving/token_fetch", 12.75, 0.01, early, in_flight=1)

    a = add("serving/step", 16.1, 0.20, iteration=7)
    pack(a, 16.10, 0.04, (0.010, 10, 1000, 0), [(0.005, 0)], 0.005, 0.03, (0.010, 500, 0), 100, rid=1)
    add("serving/token_fetch", 16.20, 0.05, a, in_flight=1)
    add("serving/prefill_fetch", 16.25, 0.05, a, in_flight=1)

    b = add("serving/step", 17.1, 0.20, iteration=8)
    add("serving/admit_plan", 17.10, 0.01, b)  # nothing to admit
    add("serving/token_fetch", 17.15, 0.12, b, in_flight=1)

    c = add("serving/step", 18.1, 0.20, iteration=9)
    pack(c, 18.10, 0.02, (0.006, 11, 600, 64), [(0.004, 0, 0.001), (0.002, 0)], 0.003, 0.02, None, 136, rid=2)
    add("serving/token_fetch", 18.18, 0.10, c, in_flight=1)

    d = add("serving/step", 19.1, 0.80, iteration=10)
    pack(d, 19.10, 0.06, (0.020, 12, 2000, 0), [(0.010, 8)], 0.007, 0.04, (0.030, 1500, 12), 300, rid=3)
    add("serving/token_fetch", 19.30, 0.10, d, in_flight=1)
    emit = add("serving/emit", 19.45, 0.40, d, emitted=8, finished=0, discarded=0)
    add("host/gc", 19.50, 0.30, emit, generation=2, collected=1234)
    return sorted(ring, key=lambda s: s[4])


def _read(name, trace, spans, counters):
    return M.load_metric_reader(name).read(trace, spans, counters, {"chips": 1, "peaks": {}})


# every reader of the measured window with no profiler anywhere: the window is 16.0-19.5
BY_HAND = {
    # host = step - fetches: A 0.20 - 0.10, B 0.20 - 0.12, C 0.20 - 0.101 (one under its growth), D 0.80 - 0.10
    "iter_host_ms_p50": 99.5,
    "iter_device_wait_pct": 100 * 0.421 / 1.40,
    # plan + dispatch of the three packs: 0.07, 0.04, 0.10 (B's plan dispatched nothing)
    "admit_host_ms_per_pack": 70.0,
    "pack_upload_ms_per_pack": 5.0,          # 5, 3, 7
    "prefix_lookup_ms_per_pack": 10.0,       # 10, 6, 20
    "prefix_insert_ms_per_pack": 20.0,       # 10 and 30: C's pack goes live with no insert
    "page_grow_ms_per_pack": 5.0,            # 5, (4 - 1 of the fetch under it) + 2, 10
    "page_grow_share_of_step_pct": 100 * 0.020 / 1.40,   # the same three, summed, over the four iterations
    "prefix_evictions_per_admission": 2 / 3,  # D's growth and D's insert evict one each; three requests
    "page_grow_pages_per_admission": 8 / 3,   # four growths of two pages
    "prefix_ghost_probe_share_pct": 100 * 9 / 27,  # 3 of the 9 digests of each of the three lookups
    # (1000 + 600 + 2000 looked up + 500 + 1500 inserted) over (100 + 136 + 300 prefilled + 64 found cached)
    "prefix_hashed_tokens_per_prompt_token": 5600 / 600,
    # entries at the lookups 10 + 11 + 12, scanned by evict_lru 8 (D's growth) + 12 (D's insert), three requests
    "prefix_entries_scanned_per_admission": 53 / 3,
    "iter_stalls_per_1000": 250.0,           # D: 0.80 > 3 x 0.20
    "stall_gc_share_pct": 50.0,              # 0.30 of D's 0.60 beyond the median
}


@pytest.mark.parametrize("name", [n for n in ALL_NEW if not n.startswith("idle_")])
def test_a_reader_gives_the_value_reckoned_by_hand(monkeypatch, name):
    monkeypatch.setattr(P, "program_ring", lambda: (_ring(), 0))
    assert _read(name, None, _records(), COUNTERS) == pytest.approx(BY_HAND[name.split(".")[0]])


@pytest.mark.parametrize("name", [n for n in ALL_NEW if n.startswith("idle_")])
def test_an_idle_share_reads_the_traced_iteration(monkeypatch, name):
    """D is the traced iteration. The chip idles 5 ms under its lookup, 3 ms in
    the dispatch's own time, 20 ms under the token fetch, 10 ms under emit and
    10 ms under the driver's ``bench/emit``; the traced window is 1 s."""
    monkeypatch.setattr(P, "program_ring", lambda: (_ring(), 0))
    spans = _records()
    gaps = [(19.112, 19.117), (19.193, 19.196), (19.32, 19.34), (19.60, 19.61), (19.95, 19.96)]
    want = {"idle_admission_pct": 0.8, "idle_host_work_pct": 2.8, "idle_result_wait_pct": 2.0}[name.split(".")[0]]
    assert _read(name, _trace(spans, gaps), spans, COUNTERS) == pytest.approx(want)
    assert _read(name, None, spans, COUNTERS) is None  # no trace


@pytest.mark.parametrize("ring,dropped", [(None, 0), ([], 0), (_ring(), 3)], ids=["no_ring", "empty", "wrapped"])
def test_readers_return_none_where_the_run_has_none(monkeypatch, ring, dropped):
    monkeypatch.setattr(P, "program_ring", lambda: (ring, dropped))
    spans = _records()
    for name in ALL_NEW:
        assert _read(name, _trace(spans, [(19.32, 19.34)]), spans, COUNTERS) is None, name
    monkeypatch.setattr(P, "program_ring", lambda: (_ring(), 0))
    for name in ALL_NEW:
        assert _read(name, None, None, {}) is None, name  # no window


def test_a_program_without_the_new_spans_is_read_as_none(monkeypatch):
    """What the parent commit gives: the iteration's spans and no child of the
    admission, no ``host/gc``. The readers of what it has read it (the
    collector's share of the stalls as 0); the others leave their metric out."""
    import host_phases

    gone = set(host_phases.CHILDREN) | {host_phases.GC}
    monkeypatch.setattr(P, "program_ring", lambda: ([s for s in _ring() if s[2] not in gone], 0))
    read = {n: _read(n, None, _records(), COUNTERS) for n in ALL_NEW if not n.startswith("idle_")}
    there = {n for n, v in read.items() if v is not None}
    assert {n.split(".")[0] for n in there} == {"iter_host_ms_p50", "iter_device_wait_pct", "admit_host_ms_per_pack",
                                                 "iter_stalls_per_1000", "stall_gc_share_pct"}
    assert read["admit_host_ms_per_pack"] == pytest.approx(70.0) and read["stall_gc_share_pct.chat"] == 0.0


@pytest.mark.parametrize("workload", ADMISSION_CELLS)
def test_traced_rehearsal_prints_every_new_metric_of_the_cell(workload):
    env = dict(os.environ, PYTHONPATH=M.ROOT)
    env.pop("JAX_DISABLE_MOST_OPTIMIZATIONS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "2147483693", "--seconds", "3", "--trace", "1",
         "--cpu-rehearsal"], capture_output=True, text=True, env=env, cwd=M.ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads([l for l in proc.stdout.splitlines() if l.strip()][-1][len(PREFIX):])
    got = {k.split(":", 1)[1]: v["value"] for k, v in line["metrics"].items()}
    assert NEW[workload] <= set(got), sorted(NEW[workload] - set(got))
    one = lambda stem: next(v for k, v in got.items() if k.split(".")[0] == stem)
    # the parts are no more than their wholes (medians over the same packs do not add up: each alone)
    for child in ("pack_upload_ms_per_pack", "prefix_lookup_ms_per_pack", "page_grow_ms_per_pack",
                  "prefix_insert_ms_per_pack"):
        assert 0 < got[child] and got["pack_upload_ms_per_pack"] < got["admit_host_ms_per_pack"]
    assert 0 < one("iter_host_ms_p50") and 0 <= one("iter_device_wait_pct") <= 100
    assert 0 <= one("idle_admission_pct") <= one("idle_host_work_pct") + 1e-9
    # an insert digests every page-aligned prefix of its prompt: far more than one pass
    assert got["prefix_hashed_tokens_per_prompt_token"] > 2
    assert got["prefix_entries_scanned_per_admission"] > 0 and got["prefix_evictions_per_admission"] >= 0
    assert got["page_grow_pages_per_admission"] > 0 and 0 < got["page_grow_share_of_step_pct"] < 100
    assert 0 < got["prefix_ghost_probe_share_pct"] < 100
    assert one("iter_stalls_per_1000") >= 0
    if "stall_gc_share_pct.chat" in NEW[workload]:
        assert 0 <= one("stall_gc_share_pct") <= 100
