"""The recurrence with heads (Mamba-2) of the decode step against its memory bound: the bytes the recurrence has to move
for the slots whose state advanced (``arch.ssd_scan_bytes``: each live slot's float32 state, heads x head_dim x state,
once in and once out, and its one row, over all Mamba-2 layers; ``ssm_slots`` of the traced ``serving/decode_dispatch``
spans), over the peak bandwidth, over the ``ssd_scan`` kernel's time in ``jit_step``."""

import metriclib
import traced_ring

LAYER = "state-space mixer (models/ssm.py, ops/ssm.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^ssd_scan$"


def read(trace, spans, counters, cell):
    moved = lambda arch, c, a: arch.ssd_scan_bytes(c, a["ssm_slots"], a["ssm_slots"]) if "ssm_slots" in a else None
    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/decode_dispatch", metriclib.DECODE_PROGRAM, KERNEL, moved)
