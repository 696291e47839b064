"""From the profiler's ``.xplane.pb`` to numbers: busy intervals per device,
durations by program and by operation, kernel time inside a program,
collective time and its exposed part, and the host's spans on the same
clock. Read with ``jax.profiler.ProfileData`` alone.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Modules``
holds one event a program execution and whose line ``XLA Ops`` holds one
event an operation; host threads are lines of ``/host:CPU`` and carry the
``bench/...`` annotations. All times are nanoseconds on one clock.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
LINES = {"XLA Modules": "modules", "XLA Ops": "ops", "Async XLA Ops": "async_ops"}
# a loop or a branch spans its body's operations: it is neither work of its
# own nor something a collective could hide behind
CONTAINERS = ("while", "conditional", "call")


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``jit_step(4567)`` -> ``jit_step``; an
    operation given as its HLO line, ``%attn.9 = bf16[...] custom-call(...)``
    -> ``attn``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def load(path: str, span_prefix: str = "bench/") -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return read_planes(data.planes, span_prefix)


def read_planes(planes, span_prefix: str = "bench/") -> dict:
    devices, spans, cpu_ops = {}, [], []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": [], "async_ops": []}
            for line in plane.lines:
                key = LINES.get(line.name)
                if key:
                    dev[key] = sorted((e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events)
                    dev[key].sort(key=lambda x: x[1])
            devices[int(m.group(2))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append((e.name, int(e.start_ns), int(e.duration_ns)))
                    elif line.name.startswith("tf_XLAPjRtCpuClient") and e.duration_ns > 0:
                        cpu_ops.append((e.name, int(e.start_ns), int(e.duration_ns)))
    if not devices and cpu_ops:
        # the CPU backend has no device plane: its operations run on host
        # threads (a rehearsal of the reduction, never a device number)
        devices[0] = {"modules": [], "async_ops": [], "ops": sorted(cpu_ops, key=lambda x: x[1])}
    spans.sort(key=lambda x: x[1])
    return {"devices": devices, "spans": spans}


def describe(path: str, top: int = 12) -> str:
    """What a trace holds, for a first look by hand."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name}: {len(events)} events")
            by = {}
            for e in events:
                by.setdefault(base_name(e.name), [0, 0.0])
                by[base_name(e.name)][0] += 1
                by[base_name(e.name)][1] += e.duration_ns
            for name, (n, ns) in sorted(by.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {name}: {n} x, {ns / 1e6:.3f} ms")
    return "\n".join(out)


def union(intervals) -> list:
    """Merged [start, end) intervals of (start, duration) pairs."""
    merged = []
    for s, d in sorted(intervals):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a: list, b: list) -> list:
    """The part of merged intervals ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events, t0: int, t1: int):
    return [(n, max(s, t0), min(s + d, t1) - max(s, t0)) for n, s, d in events if s < t1 and s + d > t0]


def window_of(trace: dict) -> tuple:
    """The traced window: from the first to the last ``bench/`` span where
    there are any, else from the first to the last device event."""
    if trace["spans"]:
        return trace["spans"][0][1], max(s + d for _, s, d in trace["spans"])
    starts = [ev[1] for dev in trace["devices"].values() for ev in dev["ops"]]
    ends = [ev[1] + ev[2] for dev in trace["devices"].values() for ev in dev["ops"]]
    return min(starts), max(ends)


def reduce(trace: dict) -> dict:
    """Every number the metric readers take, over the traced window."""
    t0, t1 = window_of(trace)
    out = {"window_s": (t1 - t0) / 1e9, "devices": {}, "span_names": sorted({n for n, _, _ in trace["spans"]})}
    for dev_id, dev in sorted(trace["devices"].items()):
        ops = [e for e in clip(dev["ops"], t0, t1) if base_name(e[0]) not in CONTAINERS]
        modules = clip(dev["modules"], t0, t1)
        busy = union((s, d) for _, s, d in ops)
        # a collective is one on the operations' line or an asynchronous one
        # (start to done) on its own line; what hides it is any other operation
        coll = union((s, d) for n, s, d in ops + clip(dev.get("async_ops", []), t0, t1)
                     if COLLECTIVE.match(base_name(n)))
        compute = union((s, d) for n, s, d in ops if not COLLECTIVE.match(base_name(n)))
        by_op, by_module, module_durs = {}, {}, {}
        for n, s, d in ops:
            by_op[base_name(n)] = by_op.get(base_name(n), 0) + d
        for n, s, d in modules:
            by_module[base_name(n)] = by_module.get(base_name(n), 0) + d
            module_durs.setdefault(base_name(n), []).append(d / 1e9)
        gaps = subtract([[t0, t1]], busy)
        out["devices"][dev_id] = {
            "busy_s": total(busy) / 1e9,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(subtract(coll, compute)) / 1e9,
            "op_s": {k: v / 1e9 for k, v in by_op.items()},
            "module_s": {k: v / 1e9 for k, v in by_module.items()},
            "module_durations_s": module_durs,
            "gaps": gaps,
        }
    n = max(len(out["devices"]), 1)
    out["busy_s"] = sum(d["busy_s"] for d in out["devices"].values()) / n
    out["idle_gaps"] = attribute_gaps(trace, out)
    return out


def ops_inside(trace: dict, dev_id: int, module: str, op_pattern: str) -> float:
    """Seconds of operations matching ``op_pattern`` that ran inside
    executions of the program ``module`` (by containment in time)."""
    t0, t1 = window_of(trace)
    dev = trace["devices"][dev_id]
    spans = union((s, d) for n, s, d in clip(dev["modules"], t0, t1) if base_name(n) == module)
    pat = re.compile(op_pattern)
    hits = union((s, d) for n, s, d in clip(dev["ops"], t0, t1) if pat.search(base_name(n)))
    return (total(hits) - total(subtract(hits, spans))) / 1e9


def attribute_gaps(trace: dict, reduced: dict, top: int = 10) -> list:
    """Idle time of the first device by the host span that was open when the
    gap began (``_no_span_`` where none was)."""
    if not reduced["devices"]:
        return []
    first = reduced["devices"][min(reduced["devices"])]
    spans = trace["spans"]
    by = {}
    j = 0
    for s, e in first["gaps"]:
        while j < len(spans) and spans[j][1] + spans[j][2] <= s:
            j += 1
        name = "_no_span_"
        k = j
        while k < len(spans) and spans[k][1] <= s:
            if spans[k][1] + spans[k][2] > s:
                name = spans[k][0]
            k += 1
        by[name] = by.get(name, 0) + (e - s)
    return [[k, v / 1e9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def breakdown(reduced: dict, top: int = 10) -> dict:
    first = reduced["devices"][min(reduced["devices"])]
    programs = [[f"program:{k}", v] for k, v in sorted(first["module_s"].items(), key=lambda kv: -kv[1])]
    ops = [[k, v] for k, v in sorted(first["op_s"].items(), key=lambda kv: -kv[1])]
    device_ops = (programs[:4] + ops)[:top]
    return {"device_ops": device_ops, "idle_gaps": reduced["idle_gaps"][:top]}
