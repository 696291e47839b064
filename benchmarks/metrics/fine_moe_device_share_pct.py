"""Share of the device's busy time in the ``moe_experts`` kernel, over every program of the traced window, in a cell
whose configuration holds ``num_experts`` narrow experts a layer. The router, the sort, the gather and the combine, the
shared expert and its gate are XLA fusions that the trace names ``fusion``, so they are not in it (PERF.md section 7)."""

import metriclib

LAYER = "experts (models/moe.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    if "num_experts" not in cell.get("config_values", {}):
        return None
    share = metriclib.op_share_pct(trace, r"^moe_experts$")
    return share or None  # no such kernel in the trace: nothing to read
