"""Windows closed a hundred iterations (``windows_closed`` of the window's ``serving/step`` spans: the table rewritten,
a window's token pages given back, its summaries put in their place), in packs and decode steps together."""

import program_spans

LAYER = "EVA attention (ops/eva.py, serving/pages.py closing kind)"
UNIT = "count"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    steps = [s[5] for s in run.named(program_spans.STEP) if s[5] and "windows_closed" in s[5]] if run else []
    if not steps:
        return None
    return 100.0 * sum(a["windows_closed"] for a in steps) / len(steps)
