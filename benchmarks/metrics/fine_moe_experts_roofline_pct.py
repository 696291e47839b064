"""Many narrow gated experts' multiplication against its memory bound: the weight bytes of the (layer, expert) pairs
that got a token in the traced decode steps (``arch.expert_weight_bytes`` of the held experts, ``num_experts`` a layer
over ``arch.expert_layers`` layers, less ``experts_idle`` of the traced ``serving/decode_dispatch`` spans: three matrices
of hidden x expert width an expert), over the peak bandwidth, over the ``moe_experts`` kernel's time in ``jit_step``."""

import metriclib
import traced_ring

LAYER = "experts (models/moe.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^moe_experts$"


def read(trace, spans, counters, cell):
    def moved(arch, c, a):
        if "experts_idle" not in a or "num_experts" not in c or not hasattr(arch, "expert_layers"):
            return None
        return arch.expert_weight_bytes(c, c["num_experts"] * arch.expert_layers(c) - a["experts_idle"])

    return traced_ring.kernel_roofline_pct(
        trace, spans, counters, cell, "serving/decode_dispatch", metriclib.DECODE_PROGRAM, KERNEL, moved)
