"""Of ``probes`` of the measured window's ``serving/prefix_lookup`` spans, the share that is ``ghost_probes``: the digests the
ghost shadows compute for their gauges (host_phases.ghost_probe_share_pct)."""

import host_phases

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return host_phases.ghost_probe_share_pct(trace, spans, counters)
