"""Per-executable cost registry: roofline attribution for every compiled
program the runtime dispatches.

Aggregate MFU says how far the *run* is from peak; it cannot say which
executable is leaving the gap, or whether closing it is even possible —
a gather-heavy program at 3% MFU may be saturating HBM bandwidth, which
is its actual roof. At first compile the registry captures XLA's own
``cost_analysis()`` (flops, bytes accessed) and ``memory_analysis()``
per executable, derives the **arithmetic intensity** (flops / HBM bytes)
and classifies it against the device's roofline ridge
(``peak FLOP/s ÷ peak HBM B/s``): above the ridge the program is
**compute-bound** and MFU is the honest utilization number; below it the
program is **memory-bound** and bandwidth utilization is.

Measured wall then attributes per executable from the same step hooks
that feed the metrics window, so every rollup (and the Prometheus
exposition, and ``accelerate-tpu report``) carries per-fn rows:
cost-model MFU (``flops*calls / wall / peak``), bandwidth utilization,
arithmetic intensity, and the roofline class.

Import-free of jax: ``capture()`` duck-types the compiled object, and the
peak tables key on ``device_kind`` strings — the report CLI reads the
snapshots on machines with no accelerator stack.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

# peak HBM bandwidth per chip, bytes/s (public spec sheets) — the
# denominator of the roofline ridge; the FLOP/s numerator lives in
# telemetry.metrics.PEAK_FLOPS (one table per axis, same matching rule)
PEAK_HBM_BW = {
    "TPU v4": 1.2e12,
    "TPU v5": 2.765e12,   # v5p
    "TPU v5 lite": 819e9,  # v5e
    "TPU v5e": 819e9,
    "TPU v6 lite": 1.64e12,  # v6e / Trillium
    "TPU v6e": 1.64e12,
    "TPU v7": 7.37e12,    # Ironwood
}


def peak_hbm_bw(device) -> Optional[float]:
    """Peak HBM bytes/s for a jax device; None for a ``device_kind`` not in
    the table (the CPU included), so no roofline is drawn against a
    made-up peak."""
    kind = getattr(device, "device_kind", "cpu").lower()
    for name, bw in sorted(PEAK_HBM_BW.items(), key=lambda kv: -len(kv[0])):
        if name.lower() in kind:
            return bw
    return None


def _cost_dict(compiled) -> dict:
    return dict(compiled.cost_analysis() or {})


class CostRegistry:
    """Static cost capture + measured-wall attribution, keyed by the
    entry-point names the engines already use for forensics."""

    def __init__(self, peak_flops: Optional[float] = None,
                 peak_bw: Optional[float] = None,
                 peak_flops_fn=None, peak_bw_fn=None):
        self._peak_flops = peak_flops
        self._peak_bw = peak_bw
        self._peak_flops_fn = peak_flops_fn
        self._peak_bw_fn = peak_bw_fn
        self._lock = threading.Lock()
        self.entries: dict = {}  # name -> row dict

    # -- peaks (resolved lazily so construction never touches a backend) --

    def peak_flops(self) -> Optional[float]:
        if self._peak_flops is None and self._peak_flops_fn is not None:
            try:
                self._peak_flops = float(self._peak_flops_fn())
            except Exception:
                self._peak_flops_fn = None
        return self._peak_flops

    def peak_bw(self) -> Optional[float]:
        if self._peak_bw is None and self._peak_bw_fn is not None:
            try:
                self._peak_bw = float(self._peak_bw_fn())
            except Exception:
                self._peak_bw_fn = None
        return self._peak_bw

    def ridge(self) -> Optional[float]:
        pf, pb = self.peak_flops(), self.peak_bw()
        if pf and pb:
            return pf / pb
        return None

    # -- producers ---------------------------------------------------------

    def capture(self, name: str, compiled) -> Optional[dict]:
        """Record one executable's static costs at (first) compile. Safe to
        call again — the row refreshes but measured wall is preserved.
        Every probe is fail-soft: a backend without cost_analysis simply
        yields no row, never an error on the compile path."""
        try:
            ca = _cost_dict(compiled)
        except Exception:
            return None
        flops = float(ca.get("flops") or 0.0)
        hbm_bytes = float(ca.get("bytes accessed") or 0.0)
        row = {
            "name": name,
            "flops_per_call": flops,
            "hbm_bytes_per_call": hbm_bytes,
            "captured_unix_s": round(time.time(), 3),
        }
        try:
            ma = compiled.memory_analysis()
            for key in ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(ma, key, None)
                if isinstance(v, (int, float)):
                    row[key] = int(v)
        except Exception:
            pass
        if flops > 0 and hbm_bytes > 0:
            ai = flops / hbm_bytes
            row["arith_intensity"] = round(ai, 4)
            ridge = self.ridge()
            if ridge is not None:
                row["ridge_intensity"] = round(ridge, 4)
                row["roofline"] = "compute-bound" if ai >= ridge else "memory-bound"
        with self._lock:
            old = self.entries.get(name)
            if old is not None:
                row["wall_s"] = old.get("wall_s", 0.0)
                row["calls"] = old.get("calls", 0)
            else:
                row["wall_s"] = 0.0
                row["calls"] = 0
            self.entries[name] = row
        return row

    def capture_lowered(self, name: str, lowered) -> Optional[dict]:
        """Capture from a ``jax.stages.Lowered``: the flops/bytes analysis
        is free (pre-optimization HLO) and is all the roofline math needs.
        Deliberately NEVER calls ``.compile()``: even with the persistent
        cache on, entries under its min-compile-time threshold are not
        persisted, so a compile here could silently double a program's
        compile bill AND pollute the monitoring counters with a
        telemetry-induced compile the forensics layer can't explain. Rows
        captured this way just lack the ``memory_analysis`` fields (those
        come from call sites that already hold a compiled executable)."""
        return self.capture(name, lowered)

    def note_wall(self, name: str, wall_s: float):
        """Attribute measured wall to an executable (one dict update per
        step — the whole per-step cost of the attribution)."""
        with self._lock:
            row = self.entries.get(name)
            if row is None:
                row = self.entries[name] = {"name": name, "wall_s": 0.0, "calls": 0}
            row["wall_s"] = row.get("wall_s", 0.0) + float(wall_s)
            row["calls"] = row.get("calls", 0) + 1

    # -- consumers ---------------------------------------------------------

    def executable_names(self) -> list:
        """Every executable the registry has a row for — the second half
        of the registry-exposure contract the static auditor
        (``accelerate_tpu.analysis``) audits its coverage against."""
        with self._lock:
            return sorted(self.entries)

    def rows(self, probe: bool = True) -> list:
        """Per-executable roofline rows (wall-descending), with the derived
        utilization numbers where both cost and wall are known.
        ``probe=False`` uses only already-resolved peaks — the watchdog /
        flight-dump path must never trigger a device query."""
        pf = self.peak_flops() if probe else self._peak_flops
        pb = self.peak_bw() if probe else self._peak_bw
        out = []
        with self._lock:
            entries = [dict(r) for r in self.entries.values()]
        for row in entries:
            wall, calls = row.get("wall_s", 0.0), row.get("calls", 0)
            flops, hbm = row.get("flops_per_call", 0.0), row.get("hbm_bytes_per_call", 0.0)
            if wall > 0 and calls > 0:
                if flops and pf:
                    row["mfu_model_pct"] = round(100.0 * flops * calls / wall / pf, 3)
                if hbm:
                    # achieved HBM bytes/s over the attributed wall
                    row["hbm_gbps"] = round(hbm * calls / wall / 1e9, 3)
                    if pb:
                        row["bw_util_pct"] = round(100.0 * hbm * calls / wall / pb, 3)
                row["wall_s"] = round(wall, 4)
            out.append(row)
        out.sort(key=lambda r: -r.get("wall_s", 0.0))
        return out

    def rollup_keys(self, probe: bool = True) -> dict:
        """Flat ``exe/<name>_*`` scalars for the session rollup and the
        Prometheus exposition (strings stay out; the class travels as a
        0/1 ``_compute_bound`` gauge)."""
        out = {}
        for row in self.rows(probe=probe):
            base = f"exe/{row['name']}"
            for src, dst in (("wall_s", "wall_s"), ("calls", "calls"),
                             ("arith_intensity", "arith_intensity"),
                             ("mfu_model_pct", "mfu_model_pct"),
                             ("bw_util_pct", "bw_util_pct"),
                             ("hbm_gbps", "hbm_gbps")):
                v = row.get(src)
                if isinstance(v, (int, float)):
                    out[f"{base}_{dst}"] = v
            if "roofline" in row:
                out[f"{base}_compute_bound"] = row["roofline"] == "compute-bound"
        return out

    def snapshot(self) -> dict:
        """JSON-serializable registry state — what ``accelerate-tpu
        report`` reads offline."""
        return {
            "peak_flops": self.peak_flops(),
            "peak_hbm_bw": self.peak_bw(),
            "ridge_intensity": self.ridge(),
            "executables": self.rows(),
        }

    def write_snapshot(self, path: str):
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(self.snapshot(), fh, indent=1)
        os.replace(tmp, path)
