"""Live slots over num_slots, mean over the window's iterations."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"
CELLS = ("mistral7b_serve_chat_closed",)


def read(trace, spans, counters, cell):
    return metriclib.occupancy_pct(counters)
