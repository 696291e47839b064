"""Share of the device's busy time in EVA attention, over both programs of the traced window: the ``eva_pool`` kernel
(the decode steps' pooling) and the two kernels that read the entry lists (the paged decode kernel ``attn`` and
``ragged_prefill_attn``). A pack's pooling is XLA fusions the trace does not name: not in it. Nothing where the trace
has no ``eva_pool``: the other two alone are any attention layer's."""

import metriclib

LAYER = "EVA attention (ops/eva.py, serving/pages.py closing kind)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    if not metriclib.op_share_pct(trace, r"^eva_pool$"):
        return None  # no such kernel in the trace: nothing to read
    return metriclib.op_share_pct(trace, r"^eva_pool$|^attn$|^ragged_prefill_attn$")
