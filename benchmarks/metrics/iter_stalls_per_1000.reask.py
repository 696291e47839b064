"""The measured window's ``serving/step`` spans longer than three times the window's median, a thousand iterations: in
re-ask the growth that evicts for a cold document (host_phases.iter_stalls_per_1000)."""

import host_phases

LAYER = "device"
UNIT = "count"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.iter_stalls_per_1000(trace, spans, counters)
