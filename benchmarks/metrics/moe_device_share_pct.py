"""Share of the device's busy time in the ``moe_experts`` kernel, over every program of the traced window. The
router, the sort and the combine are XLA fusions that the trace names ``fusion``, so they are not in it."""

import metriclib

LAYER = "experts (models/moe.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    share = metriclib.op_share_pct(trace, r"^moe_experts$")
    return share or None  # no such kernel in the trace: nothing to read
