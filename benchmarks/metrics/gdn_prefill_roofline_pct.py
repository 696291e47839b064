"""The delta rule (Gated DeltaNet) of the packed prefill against its memory bound: the bytes the rule has to move for
the traced packs (``arch.gdn_scan_bytes``: the rows' q, k, v, g, beta and o and each packed slot's state once in and
once out, over all DeltaNet layers; ``ssm_rows`` and ``ssm_slots`` of the traced ``serving/prefill_dispatch`` spans),
over the peak bandwidth, over the time in ``jit_ragged_prefill`` of the ``gdn_scan`` kernel. The kernel walks a pack's
rows one after another, 7 operations a state element a row on the vector unit, so this share says how far the rows'
arithmetic, not the state's bytes, bounds a pack."""

import metriclib
import traced_ring

LAYER = "state-space mixer (models/ssm.py, ops/ssm.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^gdn_scan$"


def read(trace, spans, counters, cell):
    def moved(arch, c, a):
        if "ssm_rows" not in a or not hasattr(arch, "gdn_scan_bytes"):
            return None
        return arch.gdn_scan_bytes(c, a["ssm_rows"], a["ssm_slots"])

    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/prefill_dispatch", metriclib.PREFILL_PROGRAM, KERNEL, moved)
