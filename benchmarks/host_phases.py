"""The host's share of a serving iteration, from the program's own spans.

The readers under ``metrics/`` that say what the host did between
dispatches take their arithmetic from here. All but ``idle_admission_pct``
read the measured window of the run (``program_spans.Run``, part
``"measured"``), which in a ``--trace 1`` run ends before the profiler
starts: these are the host's milliseconds without the profiler's Python
tracer. A program that records no such span (a commit before it did), a run
without a window or a ring that wrapped inside the run gives None.
"""

from __future__ import annotations

import bisect
import statistics

import program_spans
from program_spans import FETCHES, STEP

PLAN, DISPATCH = "serving/admit_plan", "serving/prefill_dispatch"
LOOKUP, GROW, UPLOAD, INSERT = ("serving/prefix_lookup", "serving/page_grow", "serving/pack_upload",
                                "serving/prefix_insert")
CHILDREN = (LOOKUP, GROW, UPLOAD, INSERT)  # the first three under PLAN, the last under DISPATCH
GC = "host/gc"
STALL = 3.0  # an iteration this many times the window's median is a stall


def _dur(span) -> float:
    return span[4] - span[3]


def _inside(run, names, outer: list) -> list:
    """For each span of ``outer`` (disjoint, sorted by start), the seconds the
    ring's spans of ``names`` that start inside it cover."""
    starts = [s[3] for s in outer]
    covered = [0.0] * len(outer)
    for s in run.ring:
        if s[2] in names:
            i = bisect.bisect_right(starts, s[3]) - 1
            if i >= 0 and s[3] <= outer[i][4]:
                covered[i] += min(s[4], outer[i][4]) - s[3]
    return covered


def iterations(trace, spans, counters):
    """``(seconds, of them under the two result fetches)`` of each
    ``serving/step`` that starts in the measured window; None without one."""
    run = program_spans.Run.of(trace, spans, counters)
    steps = sorted(run.named(STEP), key=lambda s: s[3]) if run else []
    if not steps:
        return None
    return list(zip(map(_dur, steps), _inside(run, FETCHES, steps)))


def iter_host_ms_p50(trace, spans, counters):
    """An iteration less what its fetches cover of it: the host's own work
    between dispatches, median over the window's iterations."""
    its = iterations(trace, spans, counters)
    return 1e3 * statistics.median(step - wait for step, wait in its) if its else None


def iter_device_wait_pct(trace, spans, counters):
    """The share of its iterations the host spends waiting for a result: near
    0 the host sets the pace and the chip idles."""
    its = iterations(trace, spans, counters)
    return 100.0 * sum(wait for _, wait in its) / sum(step for step, _ in its) if its else None


def packs(run) -> list:
    """``(admit_plan, prefill_dispatch)`` of every dispatched pack whose plan
    starts in the measured window: a plan followed by no dispatch (nothing to
    admit, a restore in flight, an admission shed) is left out."""
    out, plan = [], None
    for s in sorted(run.named(PLAN) + run.named(DISPATCH), key=lambda s: s[3]):
        if s[2] == PLAN:
            plan = s
        elif plan is not None and plan[1] == s[1]:  # the same iteration's
            out.append((plan, s))
            plan = None
    return out


def _host_s(run, child: str) -> list:
    """``(span, its seconds of host work)`` of every span called ``child``:
    its duration less the result fetches that lie under it (page growth
    under pressure reads what is in flight: the chip's time, not the host's)."""
    waits = {}  # seconds under the two fetches by the span that encloses them
    for s in run.ring:
        if s[2] in FETCHES:
            waits[s[1]] = waits.get(s[1], 0.0) + _dur(s)
    return [(s, _dur(s) - waits.get(s[0], 0.0)) for s in run.ring if s[2] == child]


def pack_ms_p50(trace, spans, counters, child=None):
    """Median over the window's packs of the host's milliseconds in
    ``serving/admit_plan`` plus ``serving/prefill_dispatch`` (``child``
    None), or in the spans called ``child`` under the two, over the packs
    that hold one (a pack in the middle of a long prompt looks nothing up)."""
    run = program_spans.Run.of(trace, spans, counters)
    if run is None:
        return None
    if child is None:
        values = [_dur(plan) + _dur(dispatch) for plan, dispatch in packs(run)]
    else:
        under = {}  # seconds of ``child`` by the span that encloses it
        for s, seconds in _host_s(run, child):
            under[s[1]] = under.get(s[1], 0.0) + seconds
        sums = (sum(under.get(phase[0], 0.0) for phase in pack) for pack in packs(run))
        values = [v for v in sums if v]
    return 1e3 * statistics.median(values) if values else None


def page_grow_share_of_step_pct(trace, spans, counters):
    """Seconds of host work under the window's ``serving/page_grow`` spans
    over seconds under its ``serving/step`` spans: a sum, so the one growth
    of a hundred milliseconds a cold document shows where a median over
    packs (``page_grow_ms_per_pack``) hides it."""
    run = program_spans.Run.of(trace, spans, counters)
    steps = run.named(STEP) if run else []
    if not steps:
        return None
    t0, t1 = run.win["measured"]
    grown = [seconds for s, seconds in _host_s(run, GROW) if t0 <= s[3] <= t1]
    return 100.0 * sum(grown) / sum(map(_dur, steps)) if grown else None


def _total(spans, key: str) -> int:
    return sum((s[5] or {}).get(key, 0) for s in spans)


def hashed_tokens_per_prompt_token(trace, spans, counters):
    """Tokens the prefix cache's digests read over the prompt tokens admitted
    in the window (prefilled or found cached): 2 would be one pass a lookup
    and one an insert."""
    run = program_spans.Run.of(trace, spans, counters)
    lookups = run.named(LOOKUP) if run else []
    admitted = _total(run.named(DISPATCH), "tokens") + _total(lookups, "hit_tokens") if lookups else 0
    return _total(lookups + run.named(INSERT), "hashed_tokens") / admitted if admitted else None


def _per_admission(trace, spans, counters, *keys_of):
    """The sum of ``(span name, count)`` pairs over the window's spans, over
    the requests admitted in it; None where the window looked nothing up (an
    engine without a prefix cache, a program without these spans)."""
    run = program_spans.Run.of(trace, spans, counters)
    admitted = len(run.named("serving/queue_wait", at=4)) if run and run.named(LOOKUP) else 0
    return sum(_total(run.named(name), key) for name, key in keys_of) / admitted if admitted else None


def entries_scanned_per_admission(trace, spans, counters):
    """Prefix-cache entries walked an admitted request: those held at each
    lookup (``_candidate_lengths`` visits every one) and those ``evict_lru``
    looked at, under page pressure or past ``max_entries`` at an insert."""
    return _per_admission(trace, spans, counters, (LOOKUP, "entries"), (GROW, "evict_scanned"),
                          (INSERT, "evict_scanned"))


def evictions_per_admission(trace, spans, counters):
    """Prefix-cache entries evicted an admitted request, under page pressure
    (``serving/page_grow``) or past ``max_entries`` (``serving/prefix_insert``);
    ``entries_scanned_per_admission`` over it is what one eviction costs."""
    return _per_admission(trace, spans, counters, (GROW, "evictions"), (INSERT, "evictions"))


def pages_per_admission(trace, spans, counters):
    """Pages the growth for the packs' rows allocated an admitted request:
    what a prefix hit spares."""
    return _per_admission(trace, spans, counters, (GROW, "pages_allocated"))


def ghost_probe_share_pct(trace, spans, counters):
    """Of the digests the window's lookups computed, the share that is the
    ghost shadows' own: what the cache-economics gauges cost an admission."""
    run = program_spans.Run.of(trace, spans, counters)
    probes = _total(run.named(LOOKUP), "probes") if run else 0
    return 100.0 * _total(run.named(LOOKUP), "ghost_probes") / probes if probes else None


def idle_admission_pct(trace, spans, counters):
    """The idle share of the traced window that lies under the admission's two
    phases and their children: the part of ``idle_host_work_pct`` that is the
    admission's (``program_spans.Run.idle_pct`` with the children's names)."""
    run = program_spans.Run.of(trace, spans, counters) if trace else None
    if run is None or run.win["traced"] is None or run.offset_ns is None or not trace.get("reduced"):
        return None
    t0, t1 = run.win["traced"]
    names = (STEP,) + program_spans.PHASES + CHILDREN
    by = program_spans.idle_by_span(trace, program_spans.on_trace_clock(run.ring, run.offset_ns, t0, t1, names))
    return 100.0 * sum(by.get(n, 0.0) for n in (PLAN, DISPATCH) + CHILDREN) / trace["reduced"]["window_s"]


def stalls(trace, spans, counters):
    """``(iterations in the window, their median seconds, those longer than
    STALL medians with the seconds host/gc covers of each)``."""
    run = program_spans.Run.of(trace, spans, counters)
    steps = sorted(run.named(STEP), key=lambda s: s[3]) if run else []
    if not steps:
        return None
    median = statistics.median(map(_dur, steps))
    stalled = [s for s in steps if _dur(s) > STALL * median]
    return len(steps), median, list(zip(stalled, _inside(run, (GC,), stalled)))


def iter_stalls_per_1000(trace, spans, counters):
    found = stalls(trace, spans, counters)
    return 1e3 * len(found[2]) / found[0] if found else None


def stall_gc_share_pct(trace, spans, counters):
    """Of the stalled iterations' time beyond the median, the share the
    collector's pauses inside them cover (of one iteration no more than its
    own excess: a pause in a short iteration explains that stall whole); 0
    where no iteration stalled, and for a program that records no ``host/gc``."""
    found = stalls(trace, spans, counters)
    if found is None:
        return None
    _, median, stalled = found
    beyond = sum(_dur(step) - median for step, _ in stalled)
    return 100.0 * sum(min(gc, _dur(step) - median) for step, gc in stalled) / beyond if beyond else 0.0
