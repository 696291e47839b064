"""Submit to the start of the first engine step that carries the request; median."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return metriclib.median(counters.get("admit_wait_ms"))
