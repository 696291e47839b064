"""Prefill dispatches that carried a request's own rows (``dispatches`` of its ``serving/first_token`` span),
over the requests whose first token falls in the window; median."""

import metriclib
import program_spans

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "count"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    return metriclib.median([s[5]["dispatches"] for s in run.first_tokens()]) if run else None
