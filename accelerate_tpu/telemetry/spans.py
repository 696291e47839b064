"""Nestable span tracing: one process-wide ring in memory, always on, and an
optional Chrome-trace-compatible JSONL stream per host.

Every closed span lands in a bounded ring (``RING_SPANS`` entries, 65,536:
a whole measured run of the serving engine, see the constant) as
``(id, parent_id, name, t0, t1, args)`` on ``time.perf_counter``;
``parent_id`` is the span that enclosed it on the same thread, and
request-level spans carry ``request_id`` in ``args`` so the spans of one
request share an identifier. ``snapshot()`` returns the ring's content,
``dropped()`` how many spans the ring has forgotten since the process began
(a reader that needs a whole run checks it: past 0 the run's first spans may
be gone),
``last_spans()`` the newest few as the watchdog and the flight recorder
print them. There is no switch: the serving engine's iteration spans
(``serving/step`` and its children, docs/telemetry.md) are recorded whether
or not anyone listens, at about two microseconds each (PERF.md, section 6).

Every span also enters ``jax.profiler.TraceAnnotation``, which the runtime
ignores while no profiler runs; under ``jax.profiler.start_trace`` the spans
lie in the xplane on the device events' clock, so XProf shows what the host
was doing in each idle gap of the device.

``arm(path)`` additionally streams closed spans to ``path``. Each line of
that file is one complete Chrome trace event (``"ph": "X"``), so the file
doubles as

- a JSONL stream (tail it, grep it, load line-by-line), and
- the body of a Chrome ``traceEvents`` array: ``load_chrome_trace()``
  wraps the lines into ``{"traceEvents": [...]}``, which Perfetto /
  ``chrome://tracing`` ingest directly (the JSON Array Format tolerates
  the missing brackets too).

Spans on the same thread nest by time containment — exactly how the trace
viewers render them — so no name mangling is needed.
"""

from __future__ import annotations

import gc
import itertools
import json
import threading
import time
from collections import deque
from typing import Optional

# The ring holds a whole run of the serving engine, so that a reader of
# one (benchmarks/program_spans.py) never finds its beginning overwritten.
# An iteration closes 7 spans when it only decodes (serving/step and its six
# phases) and 13 to 15 when it admits: three more phases around the prefill
# dispatch, a serving/pack_upload a pack, a serving/page_grow and a
# serving/prefill_chunk a packed request, and serving/queue_wait,
# serving/first_token, serving/prefix_lookup and serving/prefix_insert a
# request (tests/test_engine_spans.py counts them). On the serving cells'
# mixes that is 9.4 to 13.4 spans an iteration, and a traced run of 45 s
# with its warm-in holds 18,997 (re-ask, 1,420 iterations) to 36,187 (MiMo,
# 3,034 iterations of which 78% admit, since PR 41 took the experts' copies
# out of its step: 24,558 in 2,032 before; 40,394 in EvaByte's about 4,340
# iterations since PR 43 shortened its step (PERF.md, PR 43), 33,990 in 3,636
# before; 32,727 in the state-space cell's 2,891) of the
# 65,536 (PERF.md, PR 40 and PR 41): the longest stays under two thirds of
# the ring, 43,690, which is the mark at which to raise the constant. The
# mark is for a run of the benchmark's length, its ``run_seconds`` of 45 and a
# cell's warm-in before them: a shorter iteration (ROADMAP S15) or a longer
# run moves the count with it, and tests/test_engine_spans.py fails first.
# 16,384 wrapped inside a run as soon as an iteration fell from 66 to 55 ms
# (PERF.md, PR 33). No knob: a deque's append costs the same at any length,
# and 65,536 tuples with their args are tens of MB of host memory at the most.
# Not raised ahead of need either: a full ring is 130,000 objects that every
# full collection of a long-running server walks, 5 ms more a collection at
# 65,536 entries and 10 at 131,072 (a CPU's figure; PERF.md, PR 40).
RING_SPANS = 65536

_RECORDER: Optional["SpanRecorder"] = None
_tls = threading.local()
_ring: deque = deque(maxlen=RING_SPANS)
_ring_lock = threading.Lock()
_ids = itertools.count(1)
_closed = 0  # spans ever recorded; what the ring no longer holds was dropped


def _record(span_id, parent_id, name, t0, t1, args, cat):
    global _closed
    if _gc_pending:
        _flush_gc()
    with _ring_lock:
        _ring.append((span_id, parent_id, name, t0, t1, args))
        _closed += 1
    rec = _RECORDER
    if rec is not None:
        rec.write_span(name, t0, t1 - t0, cat, args)


def snapshot() -> list:
    """The ring's content, oldest first: ``(id, parent_id, name, t0, t1,
    args)`` tuples on ``time.perf_counter`` (``args`` a dict or None)."""
    if _gc_pending:
        _flush_gc()
    with _ring_lock:
        return list(_ring)


def dropped() -> int:
    """Spans the ring has forgotten (it wrapped) since the process began."""
    if _gc_pending:
        _flush_gc()
    with _ring_lock:
        return _closed - len(_ring)


def last_spans(n: int = 16) -> list:
    """The most recently closed spans (newest last) as ``{"name",
    "end_unix_s", "dur_s"}`` — what a stall report prints."""
    if _gc_pending:
        _flush_gc()
    with _ring_lock:
        recent = list(itertools.islice(reversed(_ring), max(n, 0)))[::-1]
    unix_minus_perf = time.time() - time.perf_counter()
    return [{"name": name, "end_unix_s": t1 + unix_minus_perf, "dur_s": t1 - t0}
            for _, _, name, t0, t1, _ in recent]


_TraceAnnotation = None


def _annotation(name: str):
    # jax is imported at the first span, not with the module: the telemetry
    # package stays importable on a machine that only holds the log files
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


def _parent_id():
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def emit(name: str, t0: float, dur_s: float, args: Optional[dict] = None,
         cat: str = "span") -> None:
    """Record a span that was timed elsewhere (a queue wait: the stamps lie
    iterations apart). Its parent is the span open on this thread now."""
    _record(next(_ids), _parent_id(), name, t0, t0 + dur_s, args, cat)


# A collection of Python's garbage collector that took this long or longer
# is a span of its own, ``host/gc``; the shorter ones (the young generation's,
# tens of microseconds, some hundred a second under a serving engine) are not.
GC_SPAN_S = 1e-3
_gc_t0 = 0.0  # collections do not nest, and one thread collects at a time
# The pauses the hook has timed and no later span has carried into the ring
# yet, as ``(ring entry, thread)``. The hook takes no lock: the interpreter
# starts a collection wherever it checks for one, also on a thread that holds
# ``_ring_lock`` or a recorder's lock, right after the call inside the ``with``.
_gc_pending: list = []


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    t1 = time.perf_counter()
    if t1 - _gc_t0 >= GC_SPAN_S:
        args = {"generation": info["generation"], "collected": info["collected"]}
        _gc_pending.append(((next(_ids), _parent_id(), "host/gc", _gc_t0, t1, args), threading.get_ident()))


def _flush_gc() -> None:
    """Move the pauses the hook has left into the ring (and the stream), ahead
    of whatever closes next: the next ``_record`` and every reader of the ring
    call it, so a pause lies in the ring before the span that enclosed it."""
    global _closed
    while _gc_pending:
        try:
            entry, thread = _gc_pending.pop(0)
        except IndexError:  # another thread took it
            return
        with _ring_lock:
            _ring.append(entry)
            _closed += 1
        rec = _RECORDER
        if rec is not None:
            rec.write_span(entry[2], entry[3], entry[4] - entry[3], "host", entry[5], thread=thread)


def record_gc() -> None:
    """From now on every garbage collection of ``GC_SPAN_S`` or longer lands
    in the ring as ``host/gc`` (``generation``, ``collected``), its parent
    the span open on the collecting thread: a pause inside ``serving/emit``
    lies under it, and ahead of it in the ring (the hook itself only notes
    the pause; the next span to close carries it in). One ``gc.callbacks``
    hook a process, however often this is called (the first ``ServingEngine``
    calls it); between collections it costs nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


class span:
    """Time a nestable region: ``with span("serving/step") as s: ...;
    s.args["emitted"] = n``. ``args`` (keyword arguments, and whatever the
    body adds before the region closes) are recorded with the span."""

    __slots__ = ("name", "cat", "args", "id", "t0", "t1", "_ann")

    def __init__(self, name: str, cat: str = "span", **args):
        self.name, self.cat, self.args = name, cat, args

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        stack = _tls.stack
        stack.pop()
        _record(self.id, stack[-1] if stack else None, self.name, self.t0,
                self.t1, self.args or None, self.cat)
        return False


class SpanRecorder:
    """Streams closed spans to ``path`` (one Chrome trace event per line)."""

    def __init__(self, path: str, process_index: int = 0):
        self.path = path
        self.process_index = process_index
        # one clock for every ts in this file: perf_counter, rebased so the
        # trace starts near 0 (viewers dislike 10^9-microsecond offsets)
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        from .artifacts import ArtifactWriter

        self._fh = ArtifactWriter(path)
        self._write({
            "name": "process_name", "ph": "M", "pid": process_index, "tid": 0,
            "args": {"name": f"host{process_index}", "epoch_unix_s": time.time()},
        })

    def write_span(self, name: str, t0: float, dur_s: float, cat: str = "span",
                   args: Optional[dict] = None, thread: Optional[int] = None):
        """One closed span (``t0`` on the perf_counter clock) to the file, on
        the calling thread's row unless ``thread`` names another."""
        evt = {
            "name": name,
            "ph": "X",
            "cat": cat,
            "ts": round(max(t0 - self._epoch, 0.0) * 1e6, 3),
            "dur": round(dur_s * 1e6, 3),
            "pid": self.process_index,
            "tid": (thread or threading.get_ident()) & 0xFFFFFFFF,
        }
        if args:
            evt["args"] = args
        self._write(evt)

    def _write(self, obj: dict):
        with self._lock:
            if self._fh.closed:
                return
            self._fh.write_line(json.dumps(obj))

    def close(self):
        with self._lock:
            if not self._fh.closed:
                self._fh.close()


def arm(path: str, process_index: int = 0) -> SpanRecorder:
    """Stream spans to ``path`` from now on (replacing any previous file)."""
    global _RECORDER
    if _RECORDER is not None:
        _RECORDER.close()
    _RECORDER = SpanRecorder(path, process_index)
    return _RECORDER


def disarm():
    global _RECORDER
    if _RECORDER is not None:
        _RECORDER.close()
        _RECORDER = None


def recorder() -> Optional[SpanRecorder]:
    return _RECORDER


def load_chrome_trace(path: str) -> dict:
    """Parse a span JSONL back into the Chrome ``{"traceEvents": [...]}``
    object (what Perfetto's JSON importer and ``chrome://tracing`` accept)."""
    events = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return {"traceEvents": events}
