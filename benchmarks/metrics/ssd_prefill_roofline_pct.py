"""The recurrence with heads (Mamba-2) of the packed prefill against its memory bound: the bytes the recurrence has to
move for the traced packs (``arch.ssd_scan_bytes``: the rows' inputs and outputs and each packed slot's state once in
and once out, over all Mamba-2 layers; ``ssm_rows`` and ``ssm_slots`` of the traced ``serving/prefill_dispatch`` spans),
over the peak bandwidth, over the ``ssd_scan`` kernel's time in ``jit_ragged_prefill``. A pack walks its rows one after
another, 5 operations a state element a row, so this share says how far the rows' arithmetic, not the state's bytes,
bounds a pack: it falls as a pack's slots carry more rows each."""

import metriclib
import traced_ring

LAYER = "state-space mixer (models/ssm.py, ops/ssm.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^ssd_scan$"


def read(trace, spans, counters, cell):
    moved = lambda arch, c, a: arch.ssd_scan_bytes(c, a["ssm_rows"], a["ssm_slots"]) if "ssm_rows" in a else None
    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/prefill_dispatch", metriclib.PREFILL_PROGRAM, KERNEL, moved)
