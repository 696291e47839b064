"""The ``serving/page_grow`` spans under one pack's ``serving/admit_plan``, summed; median over the
measured window's packs that hold one (host_phases.pack_ms_p50)."""

import host_phases

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.pack_ms_p50(trace, spans, counters, host_phases.GROW)
