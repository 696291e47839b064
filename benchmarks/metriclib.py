"""Shared arithmetic of the per-layer metric readers (``metrics/<name>.py``).

A reader is ``read(trace, spans, counters, cell)``: ``trace`` is
``{"reduced": trace_reduce.reduce(...), "raw": trace_reduce.load(...)}``
(both None without a trace), ``spans`` the benchmark's ``Spans``,
``counters`` what the driver counted, ``cell`` the manifest's cell with its
configuration, traffic and the device's peaks. A reader that finds nothing
to read returns None and the metric is left out of the line.
"""

from __future__ import annotations

import re
import statistics

import numpy as np

import trace_reduce

# program and kernel names as the v5e trace of PR 23 shows them
DECODE_PROGRAM = r"^jit_step$"
PREFILL_PROGRAM = r"^jit_ragged_prefill$"
DECODE_ATTN_KERNEL = r"^attn$"   # the pallas custom call; decode and prefill kernels share the name
FLASH_KERNEL = r"^shard_map$|^attn$|flash"  # inside the trainer's shard_map the kernels carry its name


def median(values):
    return statistics.median(values) if values else None


def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) if values else None


def pct(num, den):
    return 100.0 * num / den if den and num is not None else None


def first_device(trace):
    if not trace or not trace.get("reduced") or not trace["reduced"]["devices"]:
        return None, None
    dev_id = min(trace["reduced"]["devices"])
    return dev_id, trace["reduced"]["devices"][dev_id]


def module_seconds(dev: dict, pattern: str) -> float:
    pat = re.compile(pattern)
    return sum(v for k, v in dev["module_s"].items() if pat.search(k))


def module_durations(dev: dict, pattern: str) -> list:
    pat = re.compile(pattern)
    return [d for k, ds in dev["module_durations_s"].items() if pat.search(k) for d in ds]


def kernel_seconds_inside(trace, dev_id: int, module_pattern: str, kernel_pattern: str) -> float:
    pat = re.compile(module_pattern)
    dev = trace["reduced"]["devices"][dev_id]
    return sum(trace_reduce.ops_inside(trace["raw"], dev_id, name, kernel_pattern)
               for name in dev["module_s"] if pat.search(name))


def device_idle_pct(trace):
    if not trace or not trace.get("reduced"):
        return None
    r = trace["reduced"]
    return pct(r["window_s"] - r["busy_s"], r["window_s"])


def decode_step_device_ms(trace):
    _, dev = first_device(trace)
    durs = module_durations(dev, DECODE_PROGRAM) if dev else []
    return 1e3 * median(durs) if durs else None


def decode_attn_roofline_pct(trace, counters, cell):
    """Memory-bound: the cache bytes the traced decode steps had to read (the
    architecture's ``decode_kv_bytes``, page-rounded and by layer kind) over
    the peak bandwidth, divided by the kernel's device time inside the decode
    program."""
    dev_id, dev = first_device(trace)
    traced = (counters or {}).get("traced")
    if dev is None or not traced or not cell.get("peaks"):
        return None
    kernel_s = kernel_seconds_inside(trace, dev_id, DECODE_PROGRAM, DECODE_ATTN_KERNEL)
    if kernel_s <= 0:
        return None
    least_s = traced["decode_kv_bytes"] / cell["peaks"]["hbm_bytes_per_s"]
    return pct(least_s, kernel_s)


def occupancy_pct(counters):
    occ = (counters or {}).get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None


def prefill_device_share_pct(trace):
    _, dev = first_device(trace)
    return pct(module_seconds(dev, PREFILL_PROGRAM), dev["busy_s"]) if dev else None


def op_share_pct(trace, pattern: str):
    _, dev = first_device(trace)
    if dev is None:
        return None
    pat = re.compile(pattern)
    return pct(sum(v for k, v in dev["op_s"].items() if pat.search(k)), dev["busy_s"])


def mfu_pct(counters, cell):
    rate = (counters or {}).get("train_tokens_per_s")
    if not rate or not cell.get("peaks"):  # no peak, no share: a rehearsal has no device in the table
        return None
    return pct(counters["flops_per_token"] * rate, cell["chips"] * cell["peaks"]["flops_per_s_bf16"])


def collective_exposed_pct(trace):
    if not trace or not trace.get("reduced") or not trace["reduced"]["devices"]:
        return None
    r = trace["reduced"]
    devs = list(r["devices"].values())
    return pct(sum(d["collective_exposed_s"] for d in devs) / len(devs), r["window_s"])
