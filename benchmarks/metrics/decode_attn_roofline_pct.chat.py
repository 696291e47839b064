"""Paged decode attention against its memory bound (metriclib.decode_attn_roofline_pct)."""

import metriclib

LAYER = "kernels (ops/attention.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return metriclib.decode_attn_roofline_pct(trace, counters, cell)
