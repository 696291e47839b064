"""Share of the traced window in which the chip idles while the host waits for a result to cross
(``serving/prefill_fetch``, ``serving/token_fetch``): program_spans.idle_share_pct."""

import program_spans

LAYER = "device"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return program_spans.idle_share_pct(trace, spans, counters, fetches=True)
