"""The state-space recurrence of the packed prefill against the memory bound: the bytes the ``ssm_scan`` kernel has to
move for the traced packs (``arch.ssm_scan_bytes``: the rows' inputs and outputs and each packed slot's state once in
and once out; ``ssm_rows`` and ``ssm_slots`` of the traced ``serving/prefill_dispatch`` spans), over the peak bandwidth,
over the kernel's time in ``jit_ragged_prefill``. The kernel is bound by the vector unit there, for which ``peaks.json``
has no figure, so this share reads low."""

import metriclib
import traced_ring

LAYER = "state-space mixer (models/ssm.py, ops/ssm.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^ssm_scan$"


def read(trace, spans, counters, cell):
    moved = lambda arch, c, a: arch.ssm_scan_bytes(c, a["ssm_rows"], a["ssm_slots"]) if "ssm_rows" in a else None
    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/prefill_dispatch", metriclib.PREFILL_PROGRAM, KERNEL, moved)
