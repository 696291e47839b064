"""Device time of the prefill programs over device busy time."""

import metriclib

LAYER = "model step (the engine's jitted programs over models/decoder.py)"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return metriclib.prefill_device_share_pct(trace)
