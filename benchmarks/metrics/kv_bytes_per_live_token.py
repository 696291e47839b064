"""Arena bytes in use over live tokens, at the window's peak of bytes in use (``kv_bytes_in_use`` and
``live_tokens`` of the ``serving/step`` spans): pages of every cache kind at the widths they store."""

import program_spans

LAYER = "KV pages and prefix cache (serving/pages.py, serving/arena.py)"
UNIT = "bytes"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    steps = [s[5] for s in run.named(program_spans.STEP)
             if s[5] and s[5].get("live_tokens") and "kv_bytes_in_use" in s[5]] if run else []
    if not steps:
        return None
    peak = max(steps, key=lambda a: a["kv_bytes_in_use"])
    return peak["kv_bytes_in_use"] / peak["live_tokens"]
