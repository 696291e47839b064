"""Share of the device's busy time in latent attention's kernels (``mla_attn`` of the decode step, ``mla_prefill_attn``
of the packs, ``mla_expand`` where a pack up-projects cached entries under that name), over every program of the traced
window. The projections around them (the queries' bottleneck, the latent's down-projection, the two absorbed products)
are XLA fusions that the trace does not name, so they are not in it."""

import metriclib

LAYER = "latent attention (models/decoder.py LatentAttention, ops/attention.py latent mode)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(trace, spans, counters, cell):
    share = metriclib.op_share_pct(trace, r"^mla_attn$|^mla_prefill_attn$|^mla_expand$")
    return share or None  # no such kernel in the trace: nothing to read
