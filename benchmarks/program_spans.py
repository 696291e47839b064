"""The program's own spans beside the benchmark's and the device trace.

``ServingEngine.step()`` records its phases as ``serving/`` spans in a ring
in memory (``accelerate_tpu.telemetry.spans``: ``(id, parent_id, name, t0,
t1, args)`` on ``time.perf_counter``, counts at the same boundary in
``args``). The readers under ``metrics/`` that take them call ``Run.of`` in
the process that ran the engine, once the driver has freed it. A program
without such a ring (a commit before it recorded any), a run without a
window, or a ring that has wrapped past the run's start gives None, and the
metric is left out of the line.

Three clocks meet here: the ring and the benchmark's ``Spans.records`` share
``time.perf_counter``; the device trace has its own, and the benchmark's
``bench/`` annotations lie in it, so the same spans on both give the offset.
"""

from __future__ import annotations

import statistics

import trace_reduce

STEP = "serving/step"
FETCHES = ("serving/prefill_fetch", "serving/token_fetch")
# the children of serving/step, in the order they occur
PHASES = ("serving/reap", "serving/admit_plan", "serving/prefill_dispatch", "serving/prefill_fetch",
          "serving/prefill_commit", "serving/decode_grow", "serving/decode_dispatch",
          "serving/token_fetch", "serving/emit")
NO_SPAN = "_no_span_"


def program_ring():
    """``(spans, dropped)`` of the program's ring, or ``(None, 0)`` where the
    program has none."""
    try:
        from accelerate_tpu.telemetry import spans as program

        return program.snapshot(), program.dropped()
    except (ImportError, AttributeError):
        return None, 0


def window(spans, counters, trace):
    """The measured window and the traced part on ``perf_counter``, from the
    benchmark's own records: the traced iterations are the last ones, as
    many as the trace holds ``bench/step`` events, and the window is the
    ``counters["iterations"]`` before them. None where there is no window."""
    records = list(getattr(spans, "records", None) or ())
    steps = [i for i, r in enumerate(records) if r[0] == "bench/step"]
    n = int((counters or {}).get("iterations") or 0)
    raw = (trace or {}).get("raw") or {}
    n_traced = sum(1 for name, _, _ in raw.get("spans", ()) if name == "bench/step")
    if n <= 0 or len(steps) < n + n_traced or "window_s" not in (counters or {}):
        return None

    def iteration_start(i):  # an iteration opens with bench/submit
        return records[i - 1][1] if i and records[i - 1][0] == "bench/submit" else records[i][1]

    first = steps[len(steps) - n_traced - n]
    w0 = iteration_start(first)
    out = {"run_t0": records[0][1], "measured": (w0, w0 + float(counters["window_s"])), "traced": None}
    if n_traced:
        out["traced"] = (iteration_start(steps[-n_traced]), records[-1][2])
    return out


def clock_offset(spans, trace):
    """Nanoseconds to add to a ``perf_counter`` time (in ns) to reach the
    trace's clock: the median over the pairs (record, trace event) of the
    same ``bench/`` spans, the trace's being the last of each name."""
    raw = (trace or {}).get("raw") or {}
    diffs = []
    for name in {n for n, _, _ in raw.get("spans", ())}:
        events = sorted(s for n, s, _ in raw["spans"] if n == name)
        records = [r for r in getattr(spans, "records", ()) if r[0] == name][-len(events):]
        if len(records) == len(events):
            diffs += [s - r[1] * 1e9 for r, s in zip(records, events)]
    return statistics.median(diffs) if diffs else None


def on_trace_clock(ring, offset_ns: float, t0: float, t1: float, names=(STEP,) + PHASES) -> list:
    """The ring's spans of ``names`` that lie in ``[t0, t1]``, as ``(id,
    parent_id, name, start_ns, end_ns)`` on the trace's clock."""
    return [(i, p, n, s * 1e9 + offset_ns, e * 1e9 + offset_ns)
            for i, p, n, s, e, _ in ring if n in names and e >= t0 and s <= t1]


def innermost(program_spans) -> list:
    """Disjoint ``(name, start, end)`` pieces, sorted: every span less what
    its children cover."""
    children = {}
    for i, p, n, s, e in program_spans:
        children.setdefault(p, []).append((s, e - s))
    pieces = []
    for i, p, n, s, e in program_spans:
        for ps, pe in trace_reduce.subtract([[s, e]], trace_reduce.union(children.get(i, ()))):
            pieces.append((n, ps, pe))
    return sorted(pieces, key=lambda x: x[1])


def _divide(gaps, pieces, by: dict) -> list:
    """Add to ``by[name]`` the time each piece covers of the gaps; returns
    what no piece covers. Both lists sorted and disjoint."""
    rest, j = [], 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][2] <= gs:
            j += 1
        cur, k = gs, j
        while k < len(pieces) and pieces[k][1] < ge:
            name, s, e = pieces[k]
            if s > cur:
                rest.append([cur, s])
            lo, hi = max(s, gs), min(e, ge)
            if hi > lo:
                by[name] = by.get(name, 0) + (hi - lo)
            cur = max(cur, hi)
            k += 1
        if cur < ge:
            rest.append([cur, ge])
    return rest


def idle_by_span(trace, program_spans) -> dict:
    """Seconds of the first device's idle time by what the host was doing:
    each gap divided among the innermost ``serving/`` spans by the time each
    covers of it; what none covers falls to the ``bench/`` span, and what
    is left to ``_no_span_``. The values sum to the idle time."""
    devices = trace["reduced"]["devices"]
    gaps = devices[min(devices)]["gaps"]
    by = {}
    rest = _divide(gaps, innermost(program_spans), by)
    bench = sorted(((n, s, s + d) for n, s, d in trace["raw"]["spans"]), key=lambda x: x[1])
    rest = _divide(rest, bench, by)
    left = trace_reduce.total(rest)
    if left:
        by[NO_SPAN] = left
    return {k: v / 1e9 for k, v in by.items()}


def dispatch_misfit_ns(trace, program_spans, program: str = "jit_step") -> list:
    """For each execution of ``program`` on the first device, by how much it
    starts before the ``serving/decode_dispatch`` that sent it or ends after
    the ``serving/token_fetch`` that waited for it (0 where it fits): the
    check that ring and trace are on one clock."""
    devices = trace["raw"]["devices"]
    runs = [(s, s + d) for n, s, d in devices[min(devices)]["modules"] if trace_reduce.base_name(n) == program]
    sent = sorted((s, e) for _, _, n, s, e in program_spans if n == "serving/decode_dispatch")
    waited = sorted((s, e) for _, _, n, s, e in program_spans if n == "serving/token_fetch")
    out = []
    for (ds, _), (_, fe) in zip(sent, waited):
        inside = [(s, e) for s, e in runs if s < fe and e > ds]
        out += [max(ds - s, e - fe, 0) for s, e in inside]
    return out


class Run:
    """One run's ring, windows and clock, as the readers take them."""

    def __init__(self, ring, win, offset_ns):
        self.ring, self.win, self.offset_ns = ring, win, offset_ns

    @classmethod
    def of(cls, trace, spans, counters):
        ring, lost = program_ring()
        win = window(spans, counters, trace) if ring else None
        if win is None or (lost and ring[0][4] >= win["run_t0"]):
            return None  # no window, or the ring wrapped inside this run
        ring = [s for s in ring if s[4] >= win["run_t0"]]  # not an earlier engine's
        return cls(ring, win, clock_offset(spans, trace))

    def named(self, name: str, part: str = "measured", at: int = 3) -> list:
        """Spans of ``name`` whose start (``at`` 3) or end (4) lies in the
        measured or the traced part."""
        if self.win[part] is None:
            return []
        t0, t1 = self.win[part]
        return [s for s in self.ring if s[2] == name and t0 <= s[at] <= t1]

    def first_tokens(self) -> list:
        return self.named("serving/first_token", at=4)

    def queue_waits_ms(self) -> list:
        """``serving/queue_wait`` of the requests whose first token falls in
        the window, by the identifier their spans share."""
        waits = {s[5]["request_id"]: s for s in self.ring if s[2] == "serving/queue_wait"}
        ids = [s[5]["request_id"] for s in self.first_tokens()]
        return [1e3 * (waits[i][4] - waits[i][3]) for i in ids if i in waits]

    def idle_pct(self, trace) -> dict:
        """Idle seconds of the traced window by span, as a share of it."""
        if self.win["traced"] is None or self.offset_ns is None or not trace.get("reduced"):
            return None
        t0, t1 = self.win["traced"]
        by = idle_by_span(trace, on_trace_clock(self.ring, self.offset_ns, t0, t1))
        return {k: 100.0 * v / trace["reduced"]["window_s"] for k, v in by.items()}


def idle_share_pct(trace, spans, counters, fetches: bool):
    """The idle share of the traced window that the two result fetches
    cover (the chip waits for a result to cross to the host), or that any
    other ``serving/`` or ``bench/`` span covers (the chip waits for host
    work). The two and ``_no_span_`` sum to the device's idle share."""
    run = Run.of(trace, spans, counters) if trace else None
    by = run.idle_pct(trace) if run else None
    if by is None:
        return None
    return sum(v for k, v in by.items() if (k in FETCHES) == fetches and k != NO_SPAN)
