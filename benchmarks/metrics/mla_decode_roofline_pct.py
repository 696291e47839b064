"""Latent attention of the decode step against its roofline: the work of the mathematics for the entries the traced
steps read (``arch.mla_decode_work`` of ``latent_tokens`` of the traced ``serving/decode_dispatch`` spans: each
page-rounded entry's 576 values once and the absorbed form's products, over all layers), the larger of bytes over the
peak bandwidth and operations over the peak rate, over the ``mla_attn`` kernel's time in ``jit_step``. A program
without that count or that kernel (the parent commit) gives nothing to read."""

import metriclib
import traced_ring

LAYER = "latent attention (models/decoder.py LatentAttention, ops/attention.py latent mode)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^mla_attn$"


def read(trace, spans, counters, cell):
    import manifest

    dev_id, dev = metriclib.first_device(trace)
    calls = traced_ring.args_of(trace, spans, counters, "serving/decode_dispatch")
    if dev is None or not calls or not cell.get("peaks"):
        return None
    tokens = [a["latent_tokens"] for a in calls if "latent_tokens" in a]
    kernel_s = metriclib.kernel_seconds_inside(trace, dev_id, metriclib.DECODE_PROGRAM, KERNEL)
    if not tokens or kernel_s <= 0:
        return None
    c, peaks = cell["config_values"], cell["peaks"]
    arch = manifest.load_arch(c["model_type"], cell["bench_dir"])
    moved, flops = arch.mla_decode_work(c, sum(tokens))
    return metriclib.pct(max(moved / peaks["hbm_bytes_per_s"], flops / peaks["flops_per_s_bf16"]), kernel_s)
