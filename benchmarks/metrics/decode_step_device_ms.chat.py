"""Median device duration of the decode program in the trace."""

import metriclib

LAYER = "model step (the engine's jitted programs over models/decoder.py)"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return metriclib.decode_step_device_ms(trace)
