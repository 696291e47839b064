"""Submit to admission (popped and given a slot), as the engine stamps it: the ``serving/queue_wait`` spans
of the requests whose ``serving/first_token`` ends in the window; median."""

import metriclib
import program_spans

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    return metriclib.median(run.queue_waits_ms()) if run else None
