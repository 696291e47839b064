"""Share of the device's busy time in the ``moe_experts_relu2`` kernel, over every program of the traced window. The
router, the sort, the gather and the combine, the two latent projections and the shared expert are XLA fusions that the
trace names ``fusion``, so they are not in it (PERF.md section 7)."""

import metriclib

LAYER = "experts (models/moe.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    share = metriclib.op_share_pct(trace, r"^moe_experts_relu2$")
    return share or None  # no such kernel in the trace: nothing to read
