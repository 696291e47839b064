"""``evictions`` of the measured window's ``serving/page_grow`` and ``serving/prefix_insert`` spans over the requests
admitted in it (host_phases.evictions_per_admission)."""

import host_phases

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "count"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return host_phases.evictions_per_admission(trace, spans, counters)
