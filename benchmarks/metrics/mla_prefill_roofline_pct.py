"""Latent attention of the packs against its roofline: the work of the mathematics for the traced packs
(``arch.mla_prefill_work`` of ``latent_pairs``, ``tokens`` and ``latent_entries`` of the traced
``serving/prefill_dispatch`` spans: the expanded form's products for every visible pair, the rows and the entries they
see once), the larger of bytes over the peak bandwidth and operations over the peak rate, over the time of the latent
prefill kernel (``mla_prefill_attn``) and, where a pack up-projects cached entries under a name, of that
(``mla_expand``), in ``jit_ragged_prefill``. The absorbed form multiplies 2.8 times the operations counted here, so it
cannot read over 35%. A program without those counts or kernels (the parent commit) gives nothing to read."""

import metriclib
import traced_ring

LAYER = "latent attention (models/decoder.py LatentAttention, ops/attention.py latent mode)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^mla_prefill_attn$|^mla_expand$"


def read(trace, spans, counters, cell):
    import manifest

    dev_id, dev = metriclib.first_device(trace)
    calls = traced_ring.args_of(trace, spans, counters, "serving/prefill_dispatch")
    if dev is None or not calls or not cell.get("peaks"):
        return None
    packs = [a for a in calls if "latent_pairs" in a]
    kernel_s = metriclib.kernel_seconds_inside(trace, dev_id, metriclib.PREFILL_PROGRAM, KERNEL)
    if not packs or kernel_s <= 0:
        return None
    c, peaks = cell["config_values"], cell["peaks"]
    arch = manifest.load_arch(c["model_type"], cell["bench_dir"])
    moved, flops = arch.mla_prefill_work(
        c, sum(a["latent_pairs"] for a in packs), rows=sum(a.get("tokens", 0) for a in packs),
        entries=sum(a.get("latent_entries", 0) for a in packs))
    return metriclib.pct(max(moved / peaks["hbm_bytes_per_s"], flops / peaks["flops_per_s_bf16"]), kernel_s)
