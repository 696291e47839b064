"""``serving/admit_plan`` plus ``serving/prefill_dispatch`` of one dispatched pack (a plan followed by no dispatch is left
out), median over the measured window's packs: the host's milliseconds an admission (host_phases.pack_ms_p50)."""

import host_phases

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.pack_ms_p50(trace, spans, counters)
