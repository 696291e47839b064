"""``hashed_tokens`` of the measured window's ``serving/prefix_lookup`` and ``serving/prefix_insert`` spans over the prompt
tokens admitted in it (``tokens`` of the packs plus ``hit_tokens``): 2 would be one pass each (host_phases)."""

import host_phases

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "ratio"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    return host_phases.hashed_tokens_per_prompt_token(trace, spans, counters)
