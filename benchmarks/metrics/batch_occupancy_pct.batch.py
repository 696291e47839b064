"""Live slots over num_slots, mean over the window's iterations."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return metriclib.occupancy_pct(counters)
