"""Recompile forensics: WHY did this jitted entry point compile again?

The compile counters (``utils/compile_cache``) say *that* a step paid a
trace/compile; a 100x step-time outlier then reads ``compile_events: 1``
with no culprit. This module closes the loop: every registered jitted
entry point fingerprints the **abstract signature** of each call — per
argument, the aval (shape/dtype/sharding) for arrays and the value for
statics — and when a call arrives with a signature the function has not
seen, the diff against the previous signature IS the cause:

    train_step recompiled: arg batch['input_ids'] changed
    i32[8,128] -> i32[8,136]

Each diagnosed event becomes one JSONL record in
``forensics-host<i>.jsonl`` (cause list, compile seconds, whether the
persistent cache absorbed the backend compile) plus a tagged
``forensics/recompile`` span in the Chrome-trace stream, so the recompile
lands on the same timeline as the step that ate it. ``accelerate-tpu
report`` renders the records next to the goodput ledger's compile bucket.

Signature extraction is a pure-python pytree walk (dicts/sequences/
array-likes) — no jax import, so the module stays legal on log-only
machines and costs the producer a few dict writes per call. The fast
path (signature already seen) is one frozenset hash + set lookup.
"""

from __future__ import annotations

import enum
import json
import threading
import time
from typing import Optional

_ACTIVE: Optional["ForensicsRecorder"] = None

# numpy dtype name -> the short aval spelling jax uses in error messages
_DTYPE_SHORT = {
    "float32": "f32", "float64": "f64", "float16": "f16", "bfloat16": "bf16",
    "int32": "i32", "int64": "i64", "int16": "i16", "int8": "i8",
    "uint32": "u32", "uint64": "u64", "uint16": "u16", "uint8": "u8",
    "bool": "bool", "complex64": "c64", "complex128": "c128",
    "float8_e4m3fn": "f8_e4m3fn", "float8_e5m2": "f8_e5m2",
}


def _aval_str(leaf) -> str:
    """``i32[8,128]`` (+ ``@sharding`` when the leaf carries a non-trivial
    one) for any array-like; the jit cache keys on exactly these facts."""
    dt = str(getattr(leaf, "dtype", "?"))
    dt = _DTYPE_SHORT.get(dt, dt)
    shape = ",".join(str(int(d)) for d in leaf.shape)
    out = f"{dt}[{shape}]"
    sh = getattr(leaf, "sharding", None)
    if sh is not None:
        spec = getattr(sh, "spec", None)
        if spec is not None and any(p is not None for p in tuple(spec)):
            dims = ",".join(
                "+".join(p) if isinstance(p, (tuple, list)) else str(p)
                for p in tuple(spec)
            )
            out += f"@P({dims})"
    return out


def signature_of(tree, prefix: str = "") -> dict:
    """Flat ``{arg path: descriptor}`` signature of a call pytree.

    Array-likes (anything with ``.shape`` and ``.dtype``) describe as
    avals; everything else is a static and describes as its (bounded)
    repr — a changed static is as much a recompile cause as a changed
    shape. Dict entries path as ``prefix['key']``, sequence entries as
    ``prefix[i]``, mirroring how the user spells the argument."""
    out: dict = {}
    _walk(tree, prefix, out)
    return out


def _walk(node, path: str, out: dict):
    if hasattr(node, "shape") and hasattr(node, "dtype"):
        out[path or "arg"] = _aval_str(node)
        return
    if isinstance(node, dict) or (hasattr(node, "items") and hasattr(node, "keys")):
        # plain dicts and Mapping-likes (flax FrozenDict included)
        for k in sorted(node, key=repr):
            if not path and isinstance(k, str) and k.isidentifier():
                child = k  # root arg names spell bare: batch['input_ids']
            else:
                child = f"{path}[{k!r}]"
            _walk(node[k], child, out)
        return
    if isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            _walk(v, f"{path}[{i}]", out)
        return
    if node is None:
        return  # absent optionals are not arguments
    if isinstance(node, (bool, int, float, complex, str, bytes, enum.Enum)):
        out[path or "arg"] = "static:" + repr(node)[:80]
    else:
        # unknown leaf: describe by type only — repr() of a device-backed
        # container would force a host transfer on the step hot path
        out[path or "arg"] = f"static:<{type(node).__name__}>"


def diff_signatures(before: dict, after: dict) -> list:
    """The cause list for one recompile: every argument whose descriptor
    differs between the cached signature and the new call."""
    causes = []
    for path in sorted(set(before) | set(after)):
        old, new = before.get(path), after.get(path)
        if old == new:
            continue
        if old is None:
            kind = "new_static" if str(new).startswith("static:") else "new_arg"
        elif new is None:
            kind = "removed_arg"
        elif old.startswith("static:") or str(new).startswith("static:"):
            kind = "static"
        else:
            o, n = old.split("@")[0], new.split("@")[0]
            if o.split("[")[0] != n.split("[")[0]:
                kind = "dtype"
            elif o != n:
                kind = "shape"
            else:
                kind = "sharding"
        causes.append({"arg": path, "kind": kind, "before": old, "after": new})
    return causes


def format_causes(fn: str, causes: list) -> str:
    """One human-readable line per diagnosed recompile."""
    if not causes:
        return f"{fn} recompiled: no signature change detected (first call, " \
               "donated-buffer reuse, or an untracked entry point)"
    parts = []
    for c in causes:
        if c["before"] is None:
            parts.append(f"arg {c['arg']} is new ({c['after']})")
        elif c["after"] is None:
            parts.append(f"arg {c['arg']} removed (was {c['before']})")
        else:
            what = "static " if c["kind"] == "static" else ""
            parts.append(
                f"{what}arg {c['arg']} changed {c['before']} -> {c['after']}"
            )
    return f"{fn} recompiled: " + "; ".join(parts)


class ForensicsRecorder:
    """Per-process signature cache + JSONL emitter for recompile causes.

    ``note_call`` is the one producer hook: engines call it right before
    dispatching a registered jitted entry point, passing the call pytree
    (typically ``{"batch": batch}``). A signature already in the cache is
    a hash + set lookup; a new one opens a *pending* event that the next
    ``note_call``/``flush`` finalizes with the compile-counter delta the
    dispatch actually incurred (compile seconds, persistent-cache hits).
    """

    def __init__(self, path: Optional[str] = None, process_index: int = 0,
                 max_signatures: int = 64):
        self.path = path
        self.process_index = process_index
        self.max_signatures = max(2, int(max_signatures))
        self.records: list = []   # diagnosed events (in-memory mirror)
        self._seen: dict = {}     # fn -> {sig_key: signature}
        self._last: dict = {}     # fn -> signature of the previous call
        self._static_info: dict = {}  # fn -> registration metadata
        self._pending: Optional[dict] = None
        self._lock = threading.Lock()
        self._fh = None
        if path:
            from .artifacts import ArtifactWriter

            self._fh = ArtifactWriter(path)

    @staticmethod
    def _counters() -> dict:
        from ..utils.compile_cache import compile_event_counters

        return compile_event_counters()

    def register(self, fn: str, donate=None, statics=None, **meta):
        """Optional registration metadata for one entry point (donated
        argnums, compiled-in statics); rides every record for that fn."""
        info = dict(meta)
        if donate is not None:
            info["donate"] = list(donate) if not isinstance(donate, int) else [donate]
        if statics is not None:
            info["statics"] = {k: repr(v)[:80] for k, v in dict(statics).items()}
        self._static_info[fn] = info

    def registered_entrypoints(self) -> dict:
        """name -> registration metadata for every entry point that has
        registered OR fingerprinted a call — the enumeration surface the
        static auditor (``accelerate_tpu.analysis``) cross-checks its
        coverage against, so a new jitted program wired into an engine
        cannot silently skip the audit."""
        with self._lock:
            out = {fn: dict(info) for fn, info in self._static_info.items()}
            for fn in self._seen:
                out.setdefault(fn, {})
            return out

    def note_call(self, fn: str, tree) -> Optional[dict]:
        """Fingerprint one call of ``fn``. Returns the newly-opened event
        record when the signature is new (the fast path returns None)."""
        sig = signature_of(tree)
        key = hash(frozenset(sig.items()))
        with self._lock:
            self._finalize_locked()
            seen = self._seen.setdefault(fn, {})
            prev = self._last.get(fn)
            self._last[fn] = sig
            if key in seen:
                return None
            if len(seen) >= self.max_signatures:
                seen.pop(next(iter(seen)))
            seen[key] = sig
            first = prev is None
            causes = [] if first else diff_signatures(prev, sig)
            rec = {
                "fn": fn,
                "event": "first_compile" if first else "recompile",
                "time_unix_s": round(time.time(), 3),
                "signature": sig,
                "causes": causes,
                "cause": (f"{fn}: first compile of this entry point" if first
                          else format_causes(fn, causes)),
            }
            info = self._static_info.get(fn)
            if info:
                rec["registered"] = info
            self._pending = {"rec": rec, "mark": self._counters(),
                             "t0": time.perf_counter()}
            return rec

    def _finalize_locked(self):
        pend = self._pending
        if pend is None:
            return
        self._pending = None
        rec, mark = pend["rec"], pend["mark"]
        now = self._counters()
        rec["compile_events"] = now["count"] - mark["count"]
        rec["compile_s"] = round(now["seconds"] - mark["seconds"], 4)
        rec["compile_cache_hits"] = now["cache_hits"] - mark["cache_hits"]
        self.records.append(rec)
        if self._fh is not None and not self._fh.closed:
            self._fh.write_line(json.dumps(rec))
        from . import spans

        spans.emit(
            f"forensics/{rec['event']}", pend["t0"], max(rec["compile_s"], 1e-6),
            {"fn": rec["fn"], "cause": rec["cause"]}, cat="forensics",
        )

    def flush(self):
        """Finalize any pending event (attributes its compile delta)."""
        with self._lock:
            self._finalize_locked()

    def recompiles(self) -> list:
        """Diagnosed ``recompile`` events (first compiles excluded). A
        still-pending event is included read-only — its cause is already
        diagnosed, only the compile-delta attribution is outstanding, and
        finalizing it here would let a consumer thread (the Prometheus
        scrape) stamp it with a partial delta."""
        out = [r for r in self.records if r.get("event") == "recompile"]
        pend = self._pending
        if pend is not None and pend["rec"].get("event") == "recompile":
            out.append(pend["rec"])
        return out

    def close(self):
        self.flush()
        with self._lock:
            if self._fh is not None and not self._fh.closed:
                self._fh.close()


# -- module-level producer API (mirrors telemetry.spans) ---------------------

def arm(recorder: "ForensicsRecorder") -> "ForensicsRecorder":
    """Install the process-global recorder (engines reach it without
    holding the session)."""
    global _ACTIVE
    if _ACTIVE is not None and _ACTIVE is not recorder:
        _ACTIVE.close()
    _ACTIVE = recorder
    return recorder


def disarm():
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.close()
        _ACTIVE = None


def recorder() -> Optional["ForensicsRecorder"]:
    return _ACTIVE


def note_call(fn: str, tree):
    """Fingerprint one jitted call when forensics is armed; a single
    global read when it is not — cheap enough for every step path."""
    rec = _ACTIVE
    if rec is not None:
        rec.note_call(fn, tree)


def register(fn: str, **meta):
    rec = _ACTIVE
    if rec is not None:
        rec.register(fn, **meta)


def registered_entrypoints() -> dict:
    """The armed recorder's entry-point enumeration (empty when forensics
    is off) — what ``accelerate-tpu audit`` uses for coverage."""
    rec = _ACTIVE
    return rec.registered_entrypoints() if rec is not None else {}
