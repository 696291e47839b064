"""Per-step metrics accounting: rolling windows, flops/MFU math, device
memory and fp8 amax health probes.

This module owns the flops accounting (peak-flops table + the decoder
FLOPs/token formula) by which a live training run reports its MFU.

Everything here is host-side arithmetic; the only device interaction is
``device_memory_stats()`` (a stats query, not a computation) and
``fp8_amax_health()`` (one ``device_get`` of the tiny amax histories),
both called at *flush* cadence, never per step.
"""

from __future__ import annotations

import statistics
from collections import deque
from typing import Callable, Optional

import numpy as np

# bf16 peak FLOP/s per chip by device kind (public spec sheets)
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5": 459e12,  # v5p
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
    "TPU v7": 2307e12,  # Ironwood (bf16)
}


def peak_flops(device) -> Optional[float]:
    """Peak bf16 FLOP/s for a jax device; None for a ``device_kind`` not in
    the table (the CPU included) — MFU is then not reported rather than
    reported against a made-up peak, the ``flops_per_token_fn`` idiom."""
    kind = getattr(device, "device_kind", "cpu").lower()
    # most-specific (longest) name first: "TPU v5 lite" must win over "TPU v5"
    for name, flops in sorted(PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])):
        if name.lower() in kind:
            return flops
    return None


def decoder_flops_per_token(num_params: int, num_layers: int, seq_len: int,
                            embed_dim: int) -> float:
    """Training FLOPs per token for a causal decoder: 6N weight FLOPs +
    causal attention 6*L*S*E."""
    return 6 * num_params + 6 * num_layers * seq_len * embed_dim


def flops_per_token_fn(model_config) -> Optional[Callable[[int], float]]:
    """seq_len -> FLOPs/token for a model config that carries the decoder
    accounting fields (num_params/num_layers/embed_dim); None otherwise —
    MFU is then simply not reported rather than reported wrong."""
    try:
        n = int(model_config.num_params)
        layers = int(model_config.num_layers)
        embed = int(model_config.embed_dim)
    except (AttributeError, TypeError, ValueError):
        return None
    return lambda seq_len: decoder_flops_per_token(n, layers, int(seq_len), embed)


def batch_token_count(batch) -> tuple:
    """Best-effort (tokens, samples, seq_len) for a batch pytree.

    Token-shaped inputs (``input_ids``/``labels``/``decoder_input_ids``)
    give exact counts; anything else falls back to samples-only (leading
    dim of the first array leaf), with tokens/seq_len None so downstream
    consumers omit tokens/s and MFU instead of fabricating them.
    """
    leaf = None
    if isinstance(batch, dict):
        for key in ("input_ids", "labels", "decoder_input_ids"):
            v = batch.get(key)
            if v is not None and getattr(v, "ndim", 0) >= 1:
                shape = tuple(v.shape)
                return int(np.prod(shape)), int(np.prod(shape[:-1])), int(shape[-1])
        for v in batch.values():
            if getattr(v, "ndim", 0) >= 1:
                leaf = v
                break
    elif isinstance(batch, (tuple, list)):
        for v in batch:
            if getattr(v, "ndim", 0) >= 1:
                leaf = v
                break
    elif getattr(batch, "ndim", 0) >= 1:
        leaf = batch
    if leaf is None:
        return None, None, None
    return None, int(leaf.shape[0]), None


class MetricsWindow:
    """Rolling window of per-step records with a pure-python ``rollup()``.

    Records are plain dicts; recognized keys: ``wall_s`` (required for a
    record to count), ``steps`` (optimizer steps covered, default 1),
    ``tokens``, ``samples``, ``flops``, ``data_wait_s``, ``compile_events``,
    ``compile_s``, ``compile_cache_hits``. Unknown keys ride along
    untouched (the session stashes lazy device scalars under ``_``-keys).
    """

    def __init__(self, size: int = 32):
        self.records: deque = deque(maxlen=max(1, int(size)))
        self.total_steps = 0

    def add(self, record: dict):
        self.records.append(record)
        self.total_steps += int(record.get("steps", 1))

    def last(self) -> Optional[dict]:
        return self.records[-1] if self.records else None

    def rollup(self, peak: Optional[float] = None) -> dict:
        """Aggregate the window into flat scalars (``sys/`` namespace)."""
        recs = [r for r in self.records if r.get("wall_s")]
        if not recs:
            return {}
        # normalize to per-optimizer-step walls (a fused steps_per_call=K
        # record covers K steps in one wall measurement)
        per_step = [float(r["wall_s"]) / max(int(r.get("steps", 1)), 1) for r in recs]
        steps = sum(int(r.get("steps", 1)) for r in recs)
        wall_total = sum(float(r["wall_s"]) for r in recs)
        out = {
            "sys/window_steps": steps,
            "sys/step_time_s": wall_total / max(steps, 1),
            "sys/step_time_p50_s": statistics.median(per_step),
            "sys/step_time_max_s": max(per_step),
        }
        tokens = sum(int(r["tokens"]) for r in recs if r.get("tokens"))
        if tokens:
            out["sys/tokens_per_s"] = tokens / wall_total
        samples = sum(int(r["samples"]) for r in recs if r.get("samples"))
        if samples:
            out["sys/samples_per_s"] = samples / wall_total
        data_wait = sum(float(r.get("data_wait_s") or 0.0) for r in recs)
        out["sys/data_wait_s"] = data_wait
        out["sys/data_wait_frac"] = min(data_wait / wall_total, 1.0)
        flops = sum(float(r["flops"]) for r in recs if r.get("flops"))
        if flops:
            out["sys/model_flops_per_s"] = flops / wall_total
            if peak:
                out["sys/mfu_pct"] = 100.0 * flops / wall_total / peak
        for key in ("compile_events", "compile_s", "compile_cache_hits"):
            total = sum(r.get(key) or 0 for r in recs)
            if total:
                out[f"sys/{key}"] = round(total, 4) if key == "compile_s" else total
        return out


# last-seen peak-HBM per device (keyed by device id), so successive
# flight-recorder snapshots report the watermark DELTA — "which incident
# grew the peak". Only ``per_device=True`` (the bundle path) reads or
# advances these marks: routine rollups/flushes/scrapes call with the
# default and must not reset the bundle's baseline out from under it.
_PEAK_MARKS: dict = {}


def device_memory_stats(per_device: bool = False, devices=None) -> dict:
    """Live/peak device memory, when the backend exposes it.

    Tolerates backends whose ``memory_stats()`` returns ``None``, raises,
    or carries only some keys (each key is emitted only when present and
    numeric). Device 0 provides the stable ``sys/mem_*`` gauges;
    ``per_device=True`` (the flight-recorder bundle) additionally reports
    every device's peak-HBM watermark and its growth since the previous
    bundle snapshot (``sys/mem_peak_delta_bytes`` + ``_d<i>`` keys)."""
    if devices is None:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:
            return {}
    out = {}
    deltas = []
    for i, dev in enumerate(devices):
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        if i == 0:
            for src, dst in (
                ("bytes_in_use", "sys/mem_bytes_in_use"),
                ("peak_bytes_in_use", "sys/mem_peak_bytes"),
                ("bytes_limit", "sys/mem_bytes_limit"),
            ):
                v = stats.get(src)
                if isinstance(v, (int, float)):
                    out[dst] = int(v)
        if not per_device:
            continue
        peak = stats.get("peak_bytes_in_use")
        if not isinstance(peak, (int, float)):
            continue
        key = getattr(dev, "id", i)
        last = _PEAK_MARKS.get(key)
        delta = int(peak - last) if last is not None else 0
        _PEAK_MARKS[key] = peak
        deltas.append(delta)
        out[f"sys/mem_peak_bytes_d{i}"] = int(peak)
        out[f"sys/mem_peak_delta_bytes_d{i}"] = delta
    if deltas:
        out["sys/mem_peak_delta_bytes"] = max(deltas)
    return out


def fp8_amax_health(stats_tree) -> dict:
    """Delayed-fp8 amax-history health: the max amax in any history and the
    fraction of histories whose LAST COMPLETED slot is zero (a stale slot
    after warmup means some contraction never records — the classic symptom
    of a custom loop that forgot ``roll_amax_histories``). Slot 0 is the
    in-progress accumulator and the engine zeroes it at every optimizer-step
    roll — flushes happen right after that roll, so slot 1 (what slot 0 just
    became) is the youngest slot with a full step's amaxes in it. One host
    transfer of a few KB; call at flush cadence."""
    import jax

    leaves = [l for l in jax.tree_util.tree_leaves(stats_tree)
              if getattr(l, "ndim", 0) >= 2]
    if not leaves:
        return {}
    host = [np.asarray(jax.device_get(l), np.float32) for l in leaves]
    # history leaves are [..., 2, H] (operand rows x history slots)
    slot = 1 if all(h.shape[-1] > 1 for h in host) else 0
    done = np.concatenate([h[..., slot].reshape(-1) for h in host])
    return {
        "sys/fp8_amax_max": float(max(h.max() for h in host)),
        "sys/fp8_amax_stale_frac": float(np.mean(done == 0.0)),
    }
