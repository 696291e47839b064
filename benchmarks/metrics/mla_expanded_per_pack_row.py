"""Which form the packs took: cached latent entries that the window's packs up-projected into keys and values
(``latent_expanded`` of the ``serving/prefill_dispatch`` spans) over the rows they carried (``tokens``). 0 where every
pack read its cached context absorbed, as it is stored; a pack that expands a slot's whole context adds that context's
length for each of its rows' slots. A program without the count (the parent commit) gives nothing to read."""

import program_spans

LAYER = "latent attention (models/decoder.py LatentAttention, ops/attention.py latent mode)"
UNIT = "ratio"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    run = program_spans.Run.of(trace, spans, counters)
    packs = [s[5] for s in run.named("serving/prefill_dispatch")
             if s[5] and "latent_expanded" in s[5] and s[5].get("tokens")] if run else []
    if not packs:
        return None
    return sum(a["latent_expanded"] for a in packs) / sum(a["tokens"] for a in packs)
