"""Host time inside next(loader) over the window, from the bench/next_batch spans."""

import metriclib

LAYER = "input (data.py)"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return metriclib.pct(counters.get("next_batch_s"), counters.get("window_s"))
