"""The measured window's ``serving/step`` spans longer than three times the window's median, a thousand iterations
(host_phases.iter_stalls_per_1000)."""

import host_phases

LAYER = "device"
UNIT = "count"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.iter_stalls_per_1000(trace, spans, counters)
