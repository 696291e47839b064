"""Cache entries held over live context positions (``entries_held`` over ``live_tokens`` of the window's
``serving/step`` spans, summed): 1.0 for a cache that keeps every token, toward 1 / chunk_size as contexts grow past
their closed windows. The compression the closing kind exists for."""

import program_spans

LAYER = "EVA attention (ops/eva.py, serving/pages.py closing kind)"
UNIT = "ratio"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    steps = [s[5] for s in run.named(program_spans.STEP)
             if s[5] and s[5].get("live_tokens") and "entries_held" in s[5]] if run else []
    if not steps:
        return None
    return sum(a["entries_held"] for a in steps) / sum(a["live_tokens"] for a in steps)
