"""The expert multiplication against its memory bound: the weight bytes of the (layer, expert) pairs that got a
token in the traced decode steps (``arch.expert_weight_bytes``; held experts less ``experts_idle`` of the
``serving/decode_dispatch`` spans), over the peak bandwidth, over the ``moe_experts`` kernel's time in ``jit_step``."""

import metriclib
import program_spans

LAYER = "experts (models/moe.py)"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = r"^moe_experts$"


def read(trace, spans, counters, cell):
    import manifest

    dev_id, dev = metriclib.first_device(trace)
    run = program_spans.Run.of(trace, spans, counters) if trace else None
    if dev is None or run is None or not cell.get("peaks"):
        return None
    steps = [s[5] for s in run.named("serving/decode_dispatch", "traced") if s[5] and "experts_idle" in s[5]]
    kernel_s = metriclib.kernel_seconds_inside(trace, dev_id, metriclib.DECODE_PROGRAM, KERNEL)
    if not steps or kernel_s <= 0:
        return None
    c = cell["config_values"]
    arch = manifest.load_arch(c["model_type"], cell["bench_dir"])
    held = c["n_routed_experts"] * sum(1 for _, m in arch.layer_kinds(c) if m)
    touched = sum(held - a["experts_idle"] for a in steps)
    return metriclib.pct(arch.expert_weight_bytes(c, touched) / cell["peaks"]["hbm_bytes_per_s"], kernel_s)
