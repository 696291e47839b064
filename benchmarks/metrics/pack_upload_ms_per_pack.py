"""``serving/pack_upload`` of one pack (the host arrays built and handed to the device), median over the measured window's
packs (host_phases.pack_ms_p50)."""

import host_phases

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.pack_ms_p50(trace, spans, counters, host_phases.UPLOAD)
