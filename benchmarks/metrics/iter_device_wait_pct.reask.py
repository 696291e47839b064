"""Seconds under the two result fetches over seconds under ``serving/step``, summed over the measured window: the share of
its iteration the host waits for the chip, without the profiler. Near 0 the host sets the pace (host_phases.iter_device_wait_pct)."""

import host_phases

LAYER = "device"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def read(trace, spans, counters, cell):
    return host_phases.iter_device_wait_pct(trace, spans, counters)
