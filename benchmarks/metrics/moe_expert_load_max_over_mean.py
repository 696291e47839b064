"""Routing skew over the held experts: ``expert_load_max`` over the mean pairs a held expert of a layer got
(``expert_pairs`` / held experts / expert layers), mean over the window's ``serving/decode_dispatch`` spans."""

import program_spans

LAYER = "experts (models/moe.py)"
UNIT = "ratio"
MOVES = "itl_p95_ms"
SOURCE = "program_counter"


def read(trace, spans, counters, cell):
    import manifest

    run = program_spans.Run.of(trace, spans, counters)
    steps = [s[5] for s in run.named("serving/decode_dispatch")
             if s[5] and s[5].get("expert_pairs")] if run else []
    if not steps:
        return None
    c = cell["config_values"]
    arch = manifest.load_arch(c["model_type"], cell["bench_dir"])
    held = c["n_routed_experts"] * sum(1 for _, m in arch.layer_kinds(c) if m)
    return sum(a["expert_load_max"] * held / a["expert_pairs"] for a in steps) / len(steps)
