"""Device time in collectives during which no other operation runs on that device, over the traced window; mean over the chips."""

import metriclib

LAYER = "sharding and collectives (parallel/sharding.py, parallel/context.py)"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return metriclib.collective_exposed_pct(trace)
