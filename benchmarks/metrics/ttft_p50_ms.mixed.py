"""Submit to first token, median over the requests whose first token arrives in the window: what
``ttft_p50_ms`` is end to end in the cells where it repeats. Here it stands per layer: the 75 readings
of a ``mixed-lengths`` window lie 4-9% apart around their middle, so one rank moves it by a bound."""

import metriclib

LAYER = "serving scheduler (serving/engine.py admission, serving/scheduler.py)"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def read(trace, spans, counters, cell):
    return metriclib.percentile(counters.get("ttft_ms"), 50)
