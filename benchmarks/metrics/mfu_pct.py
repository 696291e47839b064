"""Model FLOP/s utilisation: the operations forward and backward need a token (costs.train_flops_per_token, recomputation not counted) times tokens/s over chips times peak."""

import metriclib

LAYER = "train step (accelerator.py build_train_step, optimizer.py)"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(trace, spans, counters, cell):
    return metriclib.mfu_pct(counters, cell)
