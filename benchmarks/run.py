#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process for every run: finds the cell in ``BENCHMARK.json``, loads its
configuration and traffic files, hands them to the mix's driver, which makes
the weights from the seed on the device, warms the cell's shapes (set-up),
measures for ``--seconds``, and compares what the window served with the
plain reference once the window has closed. The last line of standard output
is the result. Without the cell's chips it fails: ``--cpu-rehearsal`` is the
only way onto the CPU (tiny widths for the tests; every line says so and no
metric is printed under its name).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import glob
import json
import os
import re
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest as manifest_mod
from spans import Spans

REHEARSAL = "[CPU REHEARSAL, not a chip run] "
OUT_DIR = os.path.join(ROOT, ".bench_out")


class Context:
    """What a driver is given, and what it reports back through."""

    def __init__(self, cell, args):
        self.cell = cell
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearsal = bool(args.trace), args.cpu_rehearsal
        self.control = args.control
        # a rehearsal's window may be counted in iterations: what it compares is then the same on every machine
        self.iterations = getattr(args, "iterations", None)
        settings = json.loads(json.dumps(cell["config_values"]))
        traffic = dict(cell["traffic_values"])
        if self.rehearsal:  # tiny widths and a short trace, from the same files
            rehearsal = settings.pop("rehearsal")
            # limits of its own stand alone: a rehearsal may hold another percentile than the cell
            settings["limits"] = rehearsal.pop("limits", settings["limits"])
            for group, over in rehearsal.items():
                if isinstance(settings.get(group), dict):
                    settings[group].update(over)
                else:
                    settings[group] = over
            traffic.update(traffic.pop("rehearsal", {}))
        self.settings, self.traffic = settings, traffic
        # the configuration whole goes to the architecture's module, found by its model_type
        self.arch = manifest_mod.load_arch(settings["model_type"], cell["bench_dir"])
        self.limits = settings["limits"]
        self.trace_seconds = float(getattr(args, "trace_seconds", None) or traffic.get("trace_seconds", 5))
        self.spans = Spans()
        self.t_start = T_START
        self.setup_s = None
        self.prefix = REHEARSAL if self.rehearsal else ""
        # a directory a process: two runs of a cell side by side (the tests' workers) would empty each other's
        self.trace_dir = os.path.join(OUT_DIR, "trace", f"{cell['name']}.{os.getpid()}")
        self.trace_file = None

    def say(self, msg: str):
        print(self.prefix + msg, flush=True)

    def setup_done(self):
        self.setup_s = time.perf_counter() - self.t_start
        self.say(f"set-up done after {self.setup_s:.3f}s; the window opens")

    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        self.spans.annotate = True

    def stop_trace(self):
        import jax

        self.spans.annotate = False
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        self.trace_file = found[0] if found else None


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny widths on the CPU for the tests; never a result")
    ap.add_argument("--control", choices=("bfloat16", "fp8"), default=None,
                    help="also put the reference at this lower precision in the program's place "
                         "and print what the comparison says of it (set-up of a limit; not a run)")
    ap.add_argument("--iterations", type=int, default=None,
                    help="with --cpu-rehearsal: close the window after this many iterations of the serving loop, "
                         "not after --seconds, so that a test compares the same requests at every pace")
    ap.add_argument("--trace-seconds", type=float, default=None, help="override the mix's traced seconds")
    ap.add_argument("--dump", default=None, help="write the run's counters and per-iteration record here (JSON)")
    ap.add_argument("--keep-trace", default=None, help="copy the .xplane.pb and its description here")
    args = ap.parse_args(argv)
    if args.iterations and not args.cpu_rehearsal:
        ap.error("--iterations counts a rehearsal's window; a run on the chip measures for --seconds")
    return args


def check_devices(cell: dict, rehearsal: bool):
    import jax

    devices = jax.devices()
    want = "cpu" if rehearsal else "tpu"
    if devices[0].platform != want or len(devices) != cell["chips"]:
        print(f"benchmarks/run.py: found {len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind}), {cell['name']} needs {cell['chips']} x {want}; "
              "there is no fallback", file=sys.stderr)
        sys.exit(2)
    return devices


def per_layer_metrics(cell: dict, result: dict, reduced, trace, peaks) -> dict:
    out = {}
    ctx_cell = dict(cell, peaks=peaks)
    for m in cell["per_layer"]:
        reader = manifest_mod.load_metric_reader(m["name"], cell["bench_dir"])
        value = reader.read({"reduced": reduced, "raw": trace}, result.get("spans"), result["counters"], ctx_cell)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = manifest_mod.find_cell(manifest_mod.load_manifest(ROOT), args.workload, ROOT)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={cell['chips']}".strip()
    devices = check_devices(cell, args.cpu_rehearsal)
    from accelerate_tpu.utils.compile_cache import (
        ensure_persistent_compile_cache,
        install_compile_listeners,
    )

    install_compile_listeners()
    cache_dir = ensure_persistent_compile_cache()
    ctx = Context(cell, args)
    ctx.say(f"{cell['name']}: {len(devices)} x {devices[0].platform} ({devices[0].device_kind}), "
            f"seed {args.seed}, {args.seconds}s, trace {args.trace}; compile cache {cache_dir}")
    peaks = None if args.cpu_rehearsal else manifest_mod.load_peaks(devices[0].device_kind, cell["bench_dir"])
    driver = manifest_mod.load_driver(ctx.traffic["driver"], cell["bench_dir"])
    result = driver.run(ctx)
    result["spans"] = ctx.spans

    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
        with open(args.dump, "w") as f:
            json.dump({"workload": cell["name"], "seed": args.seed, "setup_s": ctx.setup_s,
                       "values": result["values"], "counters": result["counters"],
                       "check": result.get("check"), "iterations": result.get("iterations")}, f)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    values = dict(result["values"], setup_s=ctx.setup_s)
    if args.trace:
        import trace_reduce

        if not ctx.trace_file:
            print("benchmarks/run.py: the profiler wrote no .xplane.pb", file=sys.stderr)
            return 3
        trace = trace_reduce.load(ctx.trace_file)
        reduced = trace_reduce.reduce(trace)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(ctx.trace_file, os.path.join(args.keep_trace, f"{cell['name']}.xplane.pb"))
            with open(os.path.join(args.keep_trace, f"{cell['name']}.describe.txt"), "w") as f:
                f.write(trace_reduce.describe(ctx.trace_file))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if reduced["busy_s"] <= 0:
            print("benchmarks/run.py: no operation ran on the device in the traced window", file=sys.stderr)
            return 3
        metrics = per_layer_metrics(cell, result, reduced, trace, peaks)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    if args.cpu_rehearsal:
        metrics = {f"rehearsal:{k}": v for k, v in metrics.items()}
    line.update(metrics=metrics, device=device)
    ordered = {k: line[k] for k in ("correct", "attempted", "failed", "metrics", "device", "breakdown") if k in line}
    # every number compared beside its limit: last in the line, and the last lines on standard error
    ordered["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in result.get("compared", {}).items()}
    print(ctx.prefix + json.dumps(ordered), flush=True)
    for k, c in ordered["compared"].items():
        print(f"{ctx.prefix}compared {k}: {c['value']} limit {c['limit']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
