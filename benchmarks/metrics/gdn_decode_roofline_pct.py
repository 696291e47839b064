"""The delta rule (Gated DeltaNet) of the decode step against its memory bound: the bytes the rule has to move for the
slots whose state advanced (``arch.gdn_scan_bytes``: each live slot's float32 state, value heads x dk x dv, once in and
once out, and its one row's q, k, v, g, beta and o, over all DeltaNet layers; ``ssm_slots`` of the traced
``serving/decode_dispatch`` spans), over the peak bandwidth, over the ``gdn_scan`` kernel's time in ``jit_step``. An
architecture that counts no such bytes, or a program without the kernel, gives nothing to read."""

import metriclib
import traced_ring

LAYER = "state-space mixer (models/ssm.py, ops/ssm.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^gdn_scan$"


def read(trace, spans, counters, cell):
    def moved(arch, c, a):
        if "ssm_slots" not in a or not hasattr(arch, "gdn_scan_bytes"):
            return None
        return arch.gdn_scan_bytes(c, a["ssm_slots"], a["ssm_slots"])

    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/decode_dispatch", metriclib.DECODE_PROGRAM, KERNEL, moved)
