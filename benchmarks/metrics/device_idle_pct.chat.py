"""1 minus the union of device operation intervals over the traced window, mean over the chips."""

import metriclib

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return metriclib.device_idle_pct(trace)
