"""Of the stalled iterations' time beyond the window's median (``iter_stalls_per_1000``), the share that ``host/gc`` spans
inside them cover; 0 where none stalled (host_phases.stall_gc_share_pct)."""

import host_phases

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.stall_gc_share_pct(trace, spans, counters)
