"""The pooling of a decode step's filled pages against its memory bound: the bytes the ``eva_pool`` kernel has to move
for the pages the traced decode steps filled (``arch.pool_page_bytes``: a chunk's keys and values in and one entry
out, over all layers; ``pages_pooled`` of the traced ``serving/decode_grow`` spans), over the peak bandwidth, over the
kernel's time in ``jit_step``. (A pack pools what it fills by XLA's gather and scatter, which the trace does not name:
not in it.) A program that pools nothing has no such count and no such kernel."""

import metriclib
import traced_ring

LAYER = "EVA attention (ops/eva.py, serving/pages.py closing kind)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^eva_pool$"


def read(trace, spans, counters, cell):
    moved = lambda arch, c, a: a["pages_pooled"] * arch.pool_page_bytes(c) if a.get("pages_pooled") else None
    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/decode_grow", metriclib.DECODE_PROGRAM, KERNEL, moved)
