"""The traced tail of the program's span ring, for the readers that count
what the program says it dispatched while the profiler ran.

``program_spans.Run.of`` gives None when the ring wrapped anywhere in the
run; a reader that needs only the traced part (the run's last seconds) can
still be served then, as long as the ring's oldest span is older than the
traced part's start. This asks for no more than that.
"""

from __future__ import annotations

import program_spans


def args_of(trace, spans, counters, name: str):
    """The ``args`` of the ring's spans called ``name`` that began inside the
    traced part, oldest first; None where there is no ring, no traced part,
    or the ring's oldest span is younger than the traced part's start."""
    if not trace:
        return None
    ring, _ = program_spans.program_ring()
    win = program_spans.window(spans, counters, trace) if ring else None
    if not win or win["traced"] is None:
        return None
    t0, t1 = win["traced"]
    if ring[0][3] > t0:
        return None
    return [s[5] for s in ring if s[2] == name and t0 <= s[3] <= t1 and s[5]]


def kernel_roofline_pct(trace, spans, counters, cell, span: str, program: str, kernel: str, moved):
    """A kernel's share of its memory bound over the traced part: the bytes
    ``moved(arch, config, args)`` gives for each traced span called ``span``
    (None: the span says nothing of this kernel), over the peak bandwidth,
    over the time of the operations matching ``kernel`` inside the program
    matching ``program``. None where any of it is missing."""
    import manifest
    import metriclib

    dev_id, dev = metriclib.first_device(trace)
    calls = args_of(trace, spans, counters, span)
    if dev is None or not calls or not cell.get("peaks"):
        return None
    c = cell["config_values"]
    arch = manifest.load_arch(c["model_type"], cell["bench_dir"])
    counts = [n for n in (moved(arch, c, a) for a in calls) if n is not None]
    kernel_s = metriclib.kernel_seconds_inside(trace, dev_id, program, kernel)
    if not counts or kernel_s <= 0:
        return None
    return metriclib.pct(sum(counts) / cell["peaks"]["hbm_bytes_per_s"], kernel_s)
