"""Live slots over num_slots, mean over the window's iterations."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return metriclib.occupancy_pct(counters)
