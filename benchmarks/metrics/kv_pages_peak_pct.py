"""Most pages in use (engine.metrics(), cached prefixes included) over num_pages."""

import metriclib

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "%"
MOVES = "out_tokens_per_s"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return metriclib.pct(counters.get("pages_in_use_peak"), counters.get("num_pages"))
