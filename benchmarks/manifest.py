"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A later PR adds a configuration, an architecture, a traffic mix, a driver or
a per-layer metric as a new file plus a manifest entry; nothing here names
any of them.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "drivers", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no driver {name!r}: {path} is missing")
    return _load_module(path, f"bench_driver_{name}")


def load_arch(model_type: str, bench_dir: str = BENCH_DIR):
    """The one seam by architecture, found by a configuration's ``model_type``:
    ``arch/<model_type>.py`` (counts from shapes, the adapter to the program)
    with the published layout and plain reference ``reference/<model_type>.py``
    beside it as ``.reference``. Drivers reach an architecture through this
    and through nothing else."""
    found = {}
    for kind in ("arch", "reference"):
        path = os.path.join(bench_dir, kind, f"{model_type}.py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} for model_type {model_type!r}: {path} is missing")
        found[kind] = _load_module(path, f"bench_{kind}_" + model_type.replace(".", "_").replace("-", "_"))
    found["arch"].reference = found["reference"]
    return found["arch"]


def load_metric_reader(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader for per-layer metric {name!r}: {path} is missing")
    return _load_module(path, "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, "traffic", f"{name}.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic["name"] = name
    return traffic


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json; add it with its source")
    return table[device_kind]


def find_cell(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """The cell with its configuration, traffic and metric entries resolved."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = dict(cells[workload])
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cell["config_values"] = json.load(f)
    cell["config_entry"] = entry
    bench_dir = os.path.join(root, manifest["paths"][0])
    cell["traffic_values"] = load_traffic(cell["traffic"], bench_dir)
    cell["end_to_end"] = [m for m in manifest["end_to_end"]
                          if "workloads" not in m or workload in m["workloads"]]
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if "workloads" not in m or workload in m["workloads"]]
    cell["bench_dir"] = bench_dir
    return cell
