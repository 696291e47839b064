"""Device time of the prefill program in the traced window over the live prompt tokens its dispatches packed
(``tokens`` of the traced ``serving/prefill_dispatch`` spans)."""

import metriclib
import program_spans

LAYER = "model step (the engine's jitted programs over models/decoder.py)"
UNIT = "us"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    _, dev = metriclib.first_device(trace)
    run = program_spans.Run.of(trace, spans, counters) if dev else None
    tokens = sum(s[5]["tokens"] for s in run.named("serving/prefill_dispatch", "traced")) if run else 0
    return 1e6 * metriclib.module_seconds(dev, metriclib.PREFILL_PROGRAM) / tokens if tokens else None
