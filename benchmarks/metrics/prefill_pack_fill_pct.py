"""Live prompt tokens over grid rows of the window's ``serving/prefill_dispatch`` spans: useful over attempted."""

import metriclib
import program_spans

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    packs = run.named("serving/prefill_dispatch") if run else []
    return metriclib.pct(sum(s[5]["tokens"] for s in packs), sum(s[5]["rows"] for s in packs))
