"""As ttft_p50_ms, the 90th percentile: too few readings in a window for an end-to-end tail."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def read(trace, spans, counters, cell):
    return metriclib.percentile(counters.get("ttft_ms"), 90)
