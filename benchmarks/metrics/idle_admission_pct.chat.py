"""Share of the traced window in which the chip idles under ``serving/admit_plan``, ``serving/prefill_dispatch`` and their four
children: the part of ``idle_host_work_pct`` that is the admission's (host_phases.idle_admission_pct)."""

import host_phases

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    return host_phases.idle_admission_pct(trace, spans, counters)
