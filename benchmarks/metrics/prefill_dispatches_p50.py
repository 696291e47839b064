"""Admission dispatches between a request's submit and its first token, counted by the loop; median."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "count"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return metriclib.median(counters.get("prefill_dispatches"))
