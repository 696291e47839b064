"""Seconds of host work under the measured window's ``serving/page_grow`` spans (fetches under them taken out) over seconds
under its ``serving/step`` spans: the sum a median over packs hides (host_phases.page_grow_share_of_step_pct)."""

import host_phases

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.page_grow_share_of_step_pct(trace, spans, counters)
