"""Share of the traced window in which the chip idles while the host works: idle time under every
``serving/`` span but the two fetches, and under the ``bench/`` spans (program_spans.idle_share_pct)."""

import program_spans

LAYER = "device"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return program_spans.idle_share_pct(trace, spans, counters, fetches=False)
