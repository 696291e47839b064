"""A ``serving/step`` less what ``serving/token_fetch`` and ``serving/prefill_fetch`` cover of it: the host's own work an
iteration, median over the measured window's iterations, so without the profiler (host_phases.iter_host_ms_p50)."""

import host_phases

LAYER = "device"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.iter_host_ms_p50(trace, spans, counters)
