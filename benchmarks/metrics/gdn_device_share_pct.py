"""Share of the device's busy time in the ``gdn_scan`` kernel, over both programs of the traced window. The mixer's
projections, its convolution, its norms and its gate are XLA fusions that the trace does not name, so they are not in
it."""

import metriclib

LAYER = "state-space mixer (models/ssm.py, ops/ssm.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    share = metriclib.op_share_pct(trace, r"^gdn_scan$")
    return share or None  # no such kernel in the trace: nothing to read
