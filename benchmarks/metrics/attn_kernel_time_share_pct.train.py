"""Flash kernel device time, forward and backward, over device busy time."""

import metriclib

LAYER = "kernels (ops/attention.py)"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return metriclib.op_share_pct(trace, metriclib.FLASH_KERNEL)
