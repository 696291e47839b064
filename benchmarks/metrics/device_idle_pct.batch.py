"""1 minus the union of device operation intervals over the traced window, mean over the chips."""

import metriclib

LAYER = "device"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"
CELLS = ("mistral7b_serve_batch",)


def read(trace, spans, counters, cell):
    return metriclib.device_idle_pct(trace)
