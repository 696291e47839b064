"""Live slots over num_slots, mean over the window's iterations."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"
CELLS = ("mistral7b_serve_batch",)


def read(trace, spans, counters, cell):
    return metriclib.occupancy_pct(counters)
