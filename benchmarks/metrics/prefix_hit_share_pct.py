"""Prompt tokens served from the prefix cache (Request.prefix_hit) over the prompt tokens of the window's first tokens."""

import metriclib

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return metriclib.pct(counters.get("prefix_hit_tokens", 0), counters.get("prompt_tokens"))
