"""The two-matrix experts' multiplication against its memory bound: the weight bytes of the (layer, expert) pairs that
got a token in the traced decode steps (``arch.expert_weight_bytes`` of ``experts_touched`` of the traced
``serving/decode_dispatch`` spans: two matrices in the latent an expert), over the peak bandwidth, over the
``moe_experts_relu2`` kernel's time in ``jit_step``."""

import metriclib
import traced_ring

LAYER = "experts (models/moe.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^moe_experts_relu2$"


def read(trace, spans, counters, cell):
    moved = lambda arch, c, a: arch.expert_weight_bytes(c, a["experts_touched"]) if "experts_touched" in a else None
    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/decode_dispatch", metriclib.DECODE_PROGRAM, KERNEL, moved)
