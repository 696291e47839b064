"""How full a touched expert's rows are at a decode step: pairs on held experts (``expert_pairs``) over the held
experts that got one (``experts_touched``), over the window's ``serving/decode_dispatch`` spans. 10 experts a token over
512 outputs give a held expert 2.5 pairs of 128 live slots, the deployment's load (8 chips x 16 slots route to it), and a
touched one a little more; the experts' weights are read once for that many rows. A program without the count gives
nothing to read."""

import program_spans

LAYER = "experts (models/moe.py)"
UNIT = "pairs"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    steps = [s[5] for s in run.named("serving/decode_dispatch")
             if s[5] and s[5].get("experts_touched")] if run else []
    if not steps:
        return None
    return sum(a["expert_pairs"] for a in steps) / sum(a["experts_touched"] for a in steps)
