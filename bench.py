"""Benchmark suite. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "extra": {...sub-benchmarks...}}.

Headline: training MFU on the flagship decoder (the reference publishes no
training-throughput numbers — BASELINE.md — so the driver's north star is
>=45% MFU and vs_baseline = MFU / 0.45). ``extra`` carries the sub-suite
that exercises the hard paths the headline config doesn't: GQA attention,
long-context training, and dispatch-to-first-token latency (the BASELINE
big-model-inference analog).

On a TPU chip this trains a ~390M-param LLaMA-style model in bf16 (pallas
flash attention, fused-CE loss, remat+scan). A run that finds no chip FAILS:
only an explicit ``JAX_PLATFORMS=cpu`` selects the tiny CPU smoke sizes, whose
rows are counts and control flow, never device speed (ROADMAP S0 replaces
this file with ledgered cells).

One process for each chip: the fresh-process TTFT workers and the pipeline
memory probe are children, and a chip belongs to one process at a time, so
``main()`` runs every child BEFORE the parent opens its own backend (a
parent that has touched jax holds the chip and the child then fails or
hangs). ``_run_child`` asserts that order.

Measurement notes:
- Every timed quantity is forced with a ``device_get`` of a value that
  transitively depends on the full computation (equivalent to
  ``block_until_ready`` on the result, plus the copy of one scalar).
- TTFT attempts for the bf16/int8/int4 variants run INTERLEAVED round-robin
  (adjacent attempts see the same host conditions) and decode latency is
  measured differentially (two loop lengths) to cancel per-call costs.
- The per-phase TTFT breakdown (dispatch_ttft_*_phases) separates the
  framework's own cost (startup + abstract-init/auto-map + stream CPU +
  first-call execute) from the physical ``transfer_flush`` of weight bytes
  host->device. Quantize-on-load (int8/int4 via the native csrc kernel)
  halves/quarters exactly that term. Device placements are submitted in
  ~64 MB batched device_put calls, and the AOT program persists as a
  jax.export artifact + XLA-cache entry, so repeat attempts skip the model
  trace entirely. The phases CONTEND for host CPU — each phase's wall
  includes the others' share; dispatch_total is the meaningful
  framework-owned number. None of these rows has been re-measured on a
  locally attached chip (the BENCH_r04/r05 figures come from an earlier
  set-up).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def __getattr__(name):
    # The flops accounting (peak table + decoder FLOPs/token) lives in
    # telemetry.metrics so a LIVE training run reports the same MFU this
    # benchmark computes offline — one definition, two consumers. The lazy
    # aliases keep external users unchanged WITHOUT billing the TTFT worker
    # subprocess for the accelerate_tpu package import at startup
    # (proc_startup_imports is a phase of record; the worker only needs
    # jax + the decoder family).
    if name in ("PEAK_FLOPS", "decoder_flops_per_token", "peak_flops"):
        from accelerate_tpu.telemetry import metrics

        return getattr(metrics, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _named_configs():
    """TTFT worker configs addressable by name across processes."""
    from accelerate_tpu.models import DecoderConfig

    return {
        "ttft_390m": DecoderConfig(  # the chip
            vocab_size=32_000, num_layers=12, embed_dim=1536, num_heads=12,
            num_kv_heads=12, mlp_dim=4096, max_seq_len=2048,
            dtype=jnp.bfloat16, remat=False, scan_layers=True,
        ),
        "ttft_tiny": DecoderConfig.tiny(),  # the explicit CPU smoke
    }


def _timed_steps(step, batch, steps, windows: int = 1):
    """Run warmup + `windows` timed windows of `steps` steps; return
    (final loss, best window's seconds). Short windows (sub-second) are
    hypersensitive to transient host stalls — one 200 ms hiccup reads as
    -20% MFU — so the fast per-sample benches take the best of several
    windows. The timed region ends in a device_get of the loss, which
    transitively depends on every timed step."""
    for _ in range(2):
        metrics = step(batch)
    float(jax.device_get(metrics["loss"]))
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            metrics = step(batch)
        loss = float(jax.device_get(metrics["loss"]))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    assert np.isfinite(loss), f"non-finite loss {loss}"
    return loss, best


def _train_bench(cfg, batch_size, seq_len, steps, mixed_precision, telemetry_out=None):
    """Train `steps` steps, return (tokens/sec, MFU, final loss).

    ``telemetry_out`` arms the runtime telemetry session with a per-step
    metrics JSONL at that exact path (step wall time, tokens/s, live MFU
    — the same records a production run gets), written by the engine as
    the bench runs; the headline numbers below stay measured by
    ``_timed_steps``'s forced-device_get windows (dispatch returns before
    the device finishes, so the window must end in a forced value)."""
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state(reset_partial_state=False)
    telemetry = None
    if telemetry_out:
        from accelerate_tpu.telemetry import TelemetryConfig

        telemetry = TelemetryConfig(metrics_path=telemetry_out, spans=False,
                                    window=max(64, steps))
    accelerator = Accelerator(mixed_precision=mixed_precision, telemetry=telemetry)
    model_def = DecoderLM(cfg, mesh=accelerator.mesh)
    variables = model_def.init_variables(jax.random.PRNGKey(0), batch_size=batch_size, seq_len=seq_len)
    model, optimizer = accelerator.prepare(
        Model(model_def, variables),
        optax.adamw(optax.warmup_cosine_decay_schedule(0.0, 3e-4, 100, 1000)),
    )
    step = accelerator.build_train_step()

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch_size, seq_len))
    batch = accelerator.prepare_for_eval({"input_ids": ids, "labels": ids})

    final_loss, dt = _timed_steps(step, batch, steps)
    tokens_per_sec = batch_size * seq_len * steps / dt
    from accelerate_tpu.telemetry.metrics import decoder_flops_per_token, peak_flops

    # FLOPs/token: 6N weight FLOPs + causal attention 6*L*S*E
    flops_per_token = decoder_flops_per_token(
        cfg.num_params, cfg.num_layers, seq_len, cfg.embed_dim
    )
    # no peak for this device kind (the CPU smoke) -> no MFU
    peak = peak_flops(jax.devices()[0])
    mfu = tokens_per_sec * flops_per_token / peak if peak else None
    if accelerator.telemetry is not None:
        accelerator.telemetry.close()
    return tokens_per_sec, mfu, final_loss, dt / steps


def _train_goodput_bench(cfg, batch_size, seq_len, steps, mixed_precision,
                         trace_dir, untraced_tok_s):
    """The explanatory-telemetry wave: the same train config with the FULL
    session armed (goodput ledger, recompile forensics, cost registry,
    spans) — the instrumentation that is designed to stay on in
    production.

    Three numbers of record come out: ``train_goodput_frac`` (the compute
    share of session wall from the goodput ledger), ``train_step_mfu_model``
    (cost-model MFU of the train-step executable: XLA's own flops over the
    measured wall vs the device peak), and the zero-overhead witness — the
    traced wave must hold >= 0.7x the untraced headline throughput
    (asserted; same contract the PR 4 serving witness enforces). A
    deliberately shape-varied step runs AFTER the timed window so the
    telemetry dir always carries one diagnosed recompile record with the
    exact argument/aval cause (`accelerate-tpu report` renders it)."""
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.telemetry import TelemetryConfig

    AcceleratorState._reset_state(reset_partial_state=False)
    accelerator = Accelerator(
        mixed_precision=mixed_precision,
        telemetry=TelemetryConfig(trace_dir=trace_dir, watchdog=False,
                                  flight_hooks=False, metrics_jsonl=True),
    )
    model_def = DecoderLM(cfg, mesh=accelerator.mesh)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=batch_size, seq_len=seq_len
    )
    model, optimizer = accelerator.prepare(
        Model(model_def, variables), optax.adamw(3e-4)
    )
    step = accelerator.build_train_step()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch_size, seq_len))
    batch = accelerator.prepare_for_eval({"input_ids": ids, "labels": ids})
    _, dt = _timed_steps(step, batch, steps)
    tok_s = batch_size * seq_len * steps / dt
    overhead_pct = (
        round(100 * (1 - tok_s / untraced_tok_s), 2) if untraced_tok_s else None
    )
    assert tok_s >= 0.7 * untraced_tok_s, (
        f"explanatory telemetry cost {100 * (1 - tok_s / untraced_tok_s):.1f}% "
        f"of train throughput ({tok_s:,.0f} vs {untraced_tok_s:,.0f} tok/s) — "
        "the always-on observability contract broke"
    )
    # the deliberately shape-varied step (half batch): the forensics layer
    # must diagnose the recompile this pays, naming the argument
    half = max(batch_size // 2, 1)
    varied = accelerator.prepare_for_eval(
        {"input_ids": ids[:half], "labels": ids[:half]}
    )
    metrics = step(varied)
    float(jax.device_get(metrics["loss"]))
    session = accelerator.telemetry
    # the continuous ops plane rides the same session (timeline sampler,
    # alert rules, usage meters are on by default): force one sample so
    # even a sub-second wave leaves a timeline artifact behind, then
    # publish how much history the wave accrued — the recompile-storm
    # rule sees the deliberate half-batch recompile above as data
    session.sample_timeline()
    rollup = session.rollup()
    out = {
        "tokens_per_sec_traced": round(tok_s, 1),
        "goodput_frac": rollup.get("goodput/goodput_frac"),
        "mfu_model_pct": rollup.get("exe/train_step_mfu_model_pct"),
        "recompiles_diagnosed": rollup.get("sys/recompiles_diagnosed"),
        "overhead_pct": overhead_pct,
        "timeline_samples": (
            session.timeline.sample_count if session.timeline is not None
            else None
        ),
        "alert_rules": (
            len(session.alerts.rules) if session.alerts is not None else 0
        ),
        "alerts_firing": (
            session.alerts.firing() if session.alerts is not None else []
        ),
    }
    session.close()
    return out


def _publish_goodput_rows(extra, cfg, batch_size, seq_len, steps,
                          mixed_precision, telemetry_out, untraced_tok_s,
                          prefix="train_"):
    """Run the traced wave and publish its rows. With ``--telemetry-out``
    the artifact dir (goodput/costs/forensics JSON) persists next to the
    metrics JSONL for `accelerate-tpu report`; otherwise a tempdir is
    used and discarded after the rollup is read. ``prefix`` names the
    row family — the fp8 forensics pass reuses this wave verbatim under
    ``fp8_train_*`` (ROADMAP 5b: the same recompile-forensics +
    per-executable-roofline instrumentation, pointed at the fp8 step)."""
    import tempfile

    if telemetry_out:
        gp_dir, ctx = os.path.dirname(os.path.abspath(telemetry_out)), None
    else:
        ctx = tempfile.TemporaryDirectory(prefix="att_bench_goodput_")
        gp_dir = ctx.name
    try:
        gp = _train_goodput_bench(cfg, batch_size, seq_len, steps,
                                  mixed_precision, gp_dir, untraced_tok_s)
    finally:
        if ctx is not None:
            ctx.cleanup()
    extra[f"{prefix}goodput_frac"] = gp["goodput_frac"]
    extra[f"{prefix}step_mfu_model"] = gp["mfu_model_pct"]
    extra[f"{prefix}telemetry_overhead_pct"] = gp["overhead_pct"]
    extra[f"{prefix}recompiles_diagnosed"] = gp["recompiles_diagnosed"]
    extra[f"{prefix}timeline_samples"] = gp["timeline_samples"]
    extra[f"{prefix}alert_rules"] = gp["alert_rules"]
    extra[f"{prefix}alerts_firing"] = gp["alerts_firing"]


def _encoder_bench(batch_size, seq_len, steps):
    """BERT-base fine-tune throughput (the BASELINE nlp_example row:
    samples/sec/chip + MFU)."""
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import EncoderClassifier, EncoderConfig
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state(reset_partial_state=False)
    accelerator = Accelerator(mixed_precision="bf16")
    cfg = EncoderConfig.bert_base()
    model_def = EncoderClassifier(cfg, mesh=accelerator.mesh)
    variables = model_def.init_variables(jax.random.PRNGKey(0), batch_size=batch_size, seq_len=seq_len)
    model, optimizer = accelerator.prepare(Model(model_def, variables), optax.adamw(2e-5))

    def loss_fn(apply_fn, params, batch):
        # dropout ACTIVE, like the reference's MRPC fine-tune
        return apply_fn(
            params,
            batch["input_ids"],
            attention_mask=batch["attention_mask"],
            labels=batch["labels"],
            deterministic=False,
        )["loss"]

    # steps_per_call: 10 full optimizer steps per dispatch. At ~40 ms/step
    # the per-dispatch host latency is a visible share of wall time —
    # fusing the loop makes the row measure the chip, not the dispatch.
    K = 10
    step = accelerator.build_train_step(loss_fn=loss_fn, steps_per_call=K)
    rng = np.random.RandomState(0)
    batch = accelerator.prepare_for_eval({
        "input_ids": rng.randint(0, cfg.vocab_size, (K, batch_size, seq_len)),
        "attention_mask": np.ones((K, batch_size, seq_len), np.int32),
        "labels": rng.randint(0, cfg.num_labels, (K, batch_size)),
    }, batch_dim=1)
    assert steps % K == 0, "steps must be a multiple of steps_per_call"
    _, dt = _timed_steps(step, batch, steps // K, windows=3)
    samples_per_sec = batch_size * steps / dt
    # matmul params only: embedding/position/type tables are gathers, not
    # matmuls (unlike the decoder, whose tied embedding IS the lm-head
    # matmul); attention term is 2x the causal convention (bidirectional)
    from accelerate_tpu.utils.serialization import flatten_pytree

    n_matmul = sum(
        int(np.prod(l.shape))
        for p, l in flatten_pytree(variables["params"]).items()
        if "embedding" not in p.lower()
    )
    from accelerate_tpu.telemetry.metrics import peak_flops

    flops_per_sample = (6 * n_matmul + 12 * cfg.num_layers * seq_len * cfg.embed_dim) * seq_len
    mfu = samples_per_sec * flops_per_sample / peak_flops(jax.devices()[0])
    return samples_per_sec, mfu


def _resnet_bench(batch_size, image_size, steps):
    """ResNet-50 training throughput (the BASELINE cv_example row:
    samples/sec/chip)."""
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import ResNet, VisionConfig
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state(reset_partial_state=False)
    accelerator = Accelerator(mixed_precision="bf16")
    cfg = VisionConfig.resnet50(image_size=image_size)
    model_def = ResNet(cfg)
    variables = model_def.init_variables(jax.random.PRNGKey(0), batch_size=batch_size, image_size=image_size)
    model, optimizer = accelerator.prepare(
        Model(model_def, variables), optax.sgd(0.1, momentum=0.9)
    )

    def loss_fn(apply_fn, params, batch):
        return apply_fn(params, batch["images"], labels=batch["labels"], train=True)["loss"]

    # fused 4-step loop (see _encoder_bench): ~33 ms steps are dispatch-
    # latency-sensitive. The K batch copies are tiled ON DEVICE — shipping
    # K full image batches host->device would dominate bench wall time,
    # and the per-step path reused one batch too.
    K = 4
    assert steps % K == 0, "steps must be a multiple of steps_per_call"
    step = accelerator.build_train_step(loss_fn=loss_fn, steps_per_call=K)
    rng = np.random.RandomState(0)
    batch = accelerator.prepare_for_eval({
        "images": rng.standard_normal((batch_size, image_size, image_size, 3)).astype(np.float32),
        "labels": rng.randint(0, cfg.num_classes, (batch_size,)),
    })
    batch = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), batch
    )
    _, dt = _timed_steps(step, batch, steps // K, windows=3)
    return batch_size * steps / dt


def _proc_age_seconds():
    """Seconds since this process exec'd (Linux) — the python-startup +
    import share of a fresh-process TTFT attempt."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().split()[21])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start_ticks / os.sysconf("SC_CLK_TCK")
    except Exception:
        return None


def _write_host_checkpoint(cfg, prompt_len, tmpdir):
    """Build a random checkpoint entirely host-side (shapes via eval_shape,
    numpy fill — no device traffic) and save it in the serving dtype. The
    BASELINE table's fp16 rows load half-precision checkpoints; bf16 is the
    TPU-native analog."""
    import ml_dtypes

    from accelerate_tpu.big_modeling import init_empty_weights
    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.utils.serialization import (
        flatten_pytree,
        save_pytree,
        unflatten_to_like,
    )

    model_def = DecoderLM(cfg)
    abstract = init_empty_weights(model_def, jnp.zeros((1, prompt_len), jnp.int32))
    abstract = abstract["params"] if "params" in abstract else abstract
    rng = np.random.RandomState(0)
    dt = np.dtype(ml_dtypes.bfloat16)
    flat = {
        k: (rng.standard_normal(v.shape) * 0.02).astype(dt)
        for k, v in flatten_pytree(abstract).items()
    }
    ckpt = os.path.join(tmpdir, "model.safetensors")
    save_pytree(unflatten_to_like(flat, abstract), ckpt, max_shard_size=1 << 30)
    return ckpt


def _ttft_once(cfg, ckpt, prompt_len, quant=None, max_memory=None):
    """One dispatch-to-first-token attempt in THIS process: checkpoint on
    disk -> auto device map (AOT compile overlapped with the weight stream)
    -> last-position logits on host (BASELINE big_model_inference rows: load
    time + first step). Only the [1, vocab] slice crosses device->host —
    fetching full [1, S, vocab] logits would time the copy, not the
    model. ``quant`` ("int8"/"int4") quantizes on the host as weights stream
    (the reference's load_in_8bit/4bit rows) via the native csrc kernel,
    halving/quartering the bytes that cross host->device (the phase
    breakdown shows how much of TTFT the transfer flush is).

    Returns (ttft_seconds, phases dict, dispatched model): phases say where
    the time went — ckpt_read / host_quantize / transfer_submit inside the
    stream (now CONCURRENT pipeline stages, so their sum exceeding
    dispatch_total is the measured overlap), the overlapped AOT thread's own
    wall, the post-stream join wait, and the first call (residual compile +
    transfer flush + execute). ``max_memory`` forces tier budgets (the
    host-streamed bench row caps "device" below the model size)."""
    from accelerate_tpu.big_modeling import load_checkpoint_and_dispatch
    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.utils.phases import add_phase, collect_phases, phase

    qc = None
    if quant:
        from accelerate_tpu.utils.quantization import QuantizationConfig

        qc = QuantizationConfig(
            load_in_8bit=quant == "int8", load_in_4bit=quant == "int4"
        )
    model_def = DecoderLM(cfg)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (1, prompt_len))
    timings = collect_phases()
    age = _proc_age_seconds()
    if age is not None:
        add_phase("proc_startup_imports", age)
    t0 = time.perf_counter()
    with phase("dispatch_total"):
        dispatched = load_checkpoint_and_dispatch(
            model_def, ckpt, jnp.zeros((1, prompt_len), jnp.int32),
            device_map="auto", max_memory=max_memory, quantization_config=qc,
        )
    # the per-batch link stalls are now measured INSIDE the stream
    # (_stream_device_leaves awaits each chunk before the next submit and
    # bills the wait to "transfer_flush"), so dispatch_total already
    # contains the real flush wall. The terminal whole-tree probe survives
    # only as a correctness witness + residual meter: anything it still
    # waits on ("flush_residual", ~0 when the in-stream accounting is
    # complete) is transfer work the stream failed to attribute — the old
    # single terminal probe also absorbed AOT-compile overlap, which is
    # how BENCH_r05 printed a 13-22 s "transfer_flush" nobody could pin.
    leaves = [
        l for l in jax.tree_util.tree_leaves(dispatched.params)
        if isinstance(l, jax.Array)
    ]
    probe = jax.jit(
        lambda ls: sum(jnp.sum(jnp.ravel(l)[:1].astype(jnp.float32)) for l in ls)
    )
    with phase("flush_probe_compile"):
        compiled_probe = probe.lower(leaves).compile()
    with phase("flush_residual"):
        float(jax.device_get(compiled_probe(leaves)))
    with phase("first_call"):
        out = dispatched(jnp.asarray(ids))
        first_logits = np.asarray(jax.device_get(out["logits"][:, -1]))
    ttft = time.perf_counter() - t0
    assert np.all(np.isfinite(first_logits))
    return ttft, dict(timings), dispatched


def _framework_ttft(phases: dict) -> float:
    """The framework-owned share of one TTFT attempt: what dispatch itself
    costs (startup excluded, byte movement excluded). ``transfer_flush`` is
    the physical host->device copy of the weights; this sum is the number
    the repo's own code can regress on. The flush is measured per-batch INSIDE
    the stream, so it lands inside ``dispatch_total`` and is subtracted
    back out here (plus any terminal residual the stream missed)."""
    fw = sum(
        phases.get(k, 0.0)
        for k in ("dispatch_total", "flush_probe_compile", "first_call")
    )
    return max(0.0, fw - phases.get("transfer_flush", 0.0)
               - phases.get("flush_residual", 0.0))


def _streamed_stats(dispatched, device_budget: int) -> dict:
    """Placement accounting + the peak-HBM invariant for a host-streamed
    dispatch: HBM holds the device-placed bytes plus the compiled program's
    temps (one streamed layer + activations) — NOT the model. Asserts the
    invariant; returns the numbers for the bench row."""
    from accelerate_tpu.utils.modeling import placement_of
    from accelerate_tpu.utils.serialization import flatten_pytree

    placed = host_bytes = 0
    for path, leaf in flatten_pytree(dispatched.params).items():
        n = int(getattr(leaf, "nbytes", 0) or 0)
        if placement_of(path, dispatched.device_map) == "device":
            placed += n
        else:
            host_bytes += n
    total = placed + host_bytes
    temp = out_bytes = None
    for compiled in dispatched._aot.values():
        try:
            ma = compiled.memory_analysis()
            temp = int(ma.temp_size_in_bytes)
            out_bytes = int(ma.output_size_in_bytes)
        except Exception:
            pass
        break
    peak_hbm = placed + (temp or 0) + (out_bytes or 0)
    # The invariant of record (reference big_model_inference README:43-45:
    # offloaded runs peak at a fraction of model size): weights actually
    # stayed off-device, and what HBM holds is the placed bytes + working
    # set, far below the full model.
    assert host_bytes > 0, "streamed dispatch placed everything on device"
    assert placed <= device_budget * 1.05 + (1 << 20), (placed, device_budget)
    # the ratio form only means something when weights dominate the working
    # set (on the tiny CPU-sim model the activations are bigger than the
    # whole checkpoint); the real bench row is hundreds of MB
    if temp is not None and total > (64 << 20):
        assert peak_hbm < total * 0.8, (
            f"peak HBM {peak_hbm} not < 80% of model {total}: streaming "
            "did not keep the bulk of the weights out of HBM"
        )
    return {
        "device_placed_mb": round(placed / 1e6, 1),
        "host_streamed_mb": round(host_bytes / 1e6, 1),
        "model_total_mb": round(total / 1e6, 1),
        "peak_hbm_mb": round(peak_hbm / 1e6, 1) if temp is not None else None,
        "compiled_temp_mb": round(temp / 1e6, 1) if temp is not None else None,
        "hbm_invariant_ok": True,
    }


def _ttft_streamed_once(cfg, ckpt, prompt_len, decode_tokens=(8, 40)):
    """One host-streamed TTFT + decode attempt in THIS process: the device
    budget is capped at ~35% of the checkpoint so the layer stack spills to
    pinned host and the model streams it per layer inside the jit (the
    bigger-than-HBM posture of the reference's offloaded rows, forced on a
    model that would otherwise fit). Returns (ttft, phases, stats,
    decode_s_per_token)."""
    from accelerate_tpu.generation import generate_dispatched
    from accelerate_tpu.utils.serialization import peek_flat_structs

    peeked = peek_flat_structs(ckpt) or {}
    total = sum(
        int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize for s in peeked.values()
    )
    budget = max(int(total * 0.35), 1 << 16)
    max_memory = {"device": budget, "cpu": 1 << 62}
    ttft, phases, dispatched = _ttft_once(cfg, ckpt, prompt_len, max_memory=max_memory)
    stats = _streamed_stats(dispatched, budget)

    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (1, prompt_len))
    base, extra = decode_tokens

    def run(n):
        out = generate_dispatched(dispatched, jnp.asarray(ids), max_new_tokens=n)
        return int(jax.device_get(out[0, -1]))  # forces the whole loop

    run(base)  # compile both loop lengths
    run(base + extra)
    timings = []
    for _ in range(2):
        t0 = time.perf_counter(); run(base); t_base = time.perf_counter() - t0
        t0 = time.perf_counter(); run(base + extra); t_full = time.perf_counter() - t0
        timings.append((t_full - t_base) / extra)
    return ttft, phases, stats, float(np.median(timings))


def _run_child(flags, cpu=False):
    """Run this file again as a child with internal ``flags``; returns its
    stdout, raises with the stderr tail on a non-zero exit. A chip belongs
    to one process at a time, so a child that needs it (``cpu=False`` on a
    TPU run) may only start while this parent has not opened a backend;
    ``cpu=True`` pins the child to the 8-device CPU sim instead."""
    import subprocess

    from jax._src import xla_bridge

    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        )
    elif env.get("JAX_PLATFORMS") != "cpu" and xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"bench child {flags} needs the chip, but this parent already "
            "holds it: run chip children before the parent touches jax"
        )
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *flags],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench child {flags} exited {out.returncode}: {out.stderr[-2000:]}"
        )
    return out.stdout


def _ttft_attempt(cfg_name, prompt_len, tmpdir, quant=None, stream=False):
    """One fresh-process TTFT attempt; returns (seconds, phases[, extras])."""
    flags = ["--_ttft_worker", cfg_name, str(prompt_len), tmpdir]
    if quant:
        flags += ["--_ttft_quant", quant]
    if stream:
        flags += ["--_ttft_stream"]
    stdout = _run_child(flags)
    lines = [l for l in stdout.splitlines() if l.startswith("TTFT ")]
    if not lines:
        raise RuntimeError(f"ttft worker printed no TTFT line: {stdout[-2000:]}")
    t = float(lines[0].split()[1])

    def _json_line(prefix):
        hits = [l for l in stdout.splitlines() if l.startswith(prefix)]
        return json.loads(hits[0][len(prefix):]) if hits else {}

    phases = _json_line("TTFT_PHASES ")
    if stream:
        return t, phases, _json_line("TTFT_STREAM ")
    return t, phases


def _ttft_bench_matrix(cfg_name, prompt_len, tmpdir, variants=("bf16", "int8", "int4"), rounds=3):
    """TTFT attempts for all variants, INTERLEAVED round-robin, so
    back-to-back variant runs see (nearly) the same host conditions and the
    bf16-vs-quantized comparison is like-for-like. Three rounds (VERDICT r5
    weak #6: best-of-2 was a noisy statistic for the metric of record) and,
    per attempt, the FRAMEWORK-OWNED TTFT (dispatch_total +
    flush_probe_compile + first_call) — the transfer-free companion number.
    Returns {variant: {"attempts": [...], "best", "p50", "fw_attempts":
    [...], "fw_best", "fw_p50", "phases": best attempt's breakdown}}."""
    out = {v: {"attempts": [], "fw_attempts": [], "phases": {},
               "flush_attempts": []} for v in variants}
    raw = {v: [] for v in variants}
    for _ in range(rounds):
        for v in variants:
            t, ph = _ttft_attempt(
                cfg_name, prompt_len, tmpdir, quant=None if v == "bf16" else v
            )
            raw[v].append(t)
            out[v]["attempts"].append(round(t, 2))
            out[v]["fw_attempts"].append(round(_framework_ttft(ph), 2))
            out[v]["flush_attempts"].append(round(ph.get("transfer_flush", 0.0), 2))
            if t <= min(raw[v]):
                out[v]["phases"] = ph
    for v in variants:
        ts = out[v]["attempts"]
        out[v]["best"] = min(ts)
        out[v]["p50"] = round(float(np.median(ts)), 2)
        fw = out[v]["fw_attempts"]
        out[v]["fw_best"] = min(fw)
        out[v]["fw_p50"] = round(float(np.median(fw)), 2)
        # transfer_flush is the physical copy and swung ~3x across rounds in
        # the r05 record: publish the MEDIAN of the >=3 attempts as the row
        # of record — like the TTFT rows — and tag the spread so a reader
        # can tell noise from a real regression
        fl = out[v]["flush_attempts"]
        out[v]["flush_median"] = round(float(np.median(fl)), 2)
        out[v]["flush_spread"] = [min(fl), max(fl)]
    return out


def _child_rows(on_tpu: bool) -> dict:
    """Every row a CHILD process measures: the fresh-process TTFT matrix,
    the host-streamed TTFT attempts and the pipeline memory probe. Called
    by ``main()`` before the parent opens its own backend — the TTFT
    children need the chip the parent would otherwise hold. The random
    checkpoint is written by a CPU-pinned child for the same reason."""
    import tempfile

    name, prompt = ("ttft_390m", 128) if on_tpu else ("ttft_tiny", 32)
    rows = {}
    with tempfile.TemporaryDirectory() as td:
        _run_child(["--_write_ckpt", name, str(prompt), td], cpu=True)
        if on_tpu:
            matrix = _ttft_bench_matrix(name, prompt, td)
            rows["dispatch_ttft_s"] = matrix["bf16"]["p50"]
            rows["dispatch_ttft_best_s"] = matrix["bf16"]["best"]
            rows["dispatch_ttft_median_s"] = matrix["bf16"]["p50"]
            rows["dispatch_ttft_attempts"] = matrix["bf16"]["attempts"]
            rows["dispatch_ttft_framework_s"] = matrix["bf16"]["fw_p50"]
            rows["dispatch_ttft_framework_attempts"] = matrix["bf16"]["fw_attempts"]
            for v in ("int8", "int4"):
                rows[f"dispatch_ttft_{v}_best_s"] = matrix[v]["best"]
                rows[f"dispatch_ttft_{v}_median_s"] = matrix[v]["p50"]
                rows[f"dispatch_ttft_{v}_attempts"] = matrix[v]["attempts"]
                rows[f"dispatch_ttft_{v}_framework_s"] = matrix[v]["fw_p50"]
                rows[f"dispatch_ttft_{v}_framework_attempts"] = matrix[v]["fw_attempts"]
            for v in ("bf16", "int8", "int4"):
                key = "dispatch_ttft_phases" if v == "bf16" else f"dispatch_ttft_{v}_phases"
                rows[key] = matrix[v]["phases"]
                # the transfer_flush noise rows (median-of-rounds + spread;
                # the best-attempt phase breakdown keeps the old shape)
                rows[f"dispatch_transfer_flush_{v}_median_s"] = matrix[v]["flush_median"]
                rows[f"dispatch_transfer_flush_{v}_spread_s"] = matrix[v]["flush_spread"]
        else:
            t, phases = _ttft_attempt(name, prompt, td)
            rows["dispatch_ttft_s"] = round(t, 2)
            rows["dispatch_ttft_framework_s"] = round(_framework_ttft(phases), 2)
        # host-streamed row (VERDICT r5 missing #1: the flagship subsystem
        # proven with the host tier actually in the serving path): device
        # budget forced below the model, layer stack streams from pinned
        # host per decode step, peak-HBM invariant asserted in the worker
        s_attempts, s_fw, s_stats = [], [], {}
        for _ in range(2 if on_tpu else 1):
            t, ph, stats = _ttft_attempt(name, prompt, td, stream=True)
            s_attempts.append(round(t, 2))
            s_fw.append(round(_framework_ttft(ph), 2))
            s_stats = stats or s_stats
    rows["dispatch_ttft_streamed"] = round(float(np.median(s_attempts)), 2)
    if on_tpu:
        rows["dispatch_ttft_streamed_attempts"] = s_attempts
        rows["dispatch_ttft_streamed_framework_s"] = round(float(np.median(s_fw)), 2)
    rows["decode_ms_per_token_streamed"] = s_stats.get("decode_ms_per_token")
    rows["streamed_hbm"] = {
        k: s_stats.get(k)
        for k in ("device_placed_mb", "host_streamed_mb", "model_total_mb",
                  "peak_hbm_mb", "compiled_temp_mb", "hbm_invariant_ok")
    }
    if on_tpu:
        mem = _pipeline_mem_bench()
        rows["pipeline_gpipe_temp_mb"] = round(mem["gpipe"] / 1e6, 1)
        rows["pipeline_1f1b_temp_mb"] = round(mem["1f1b"] / 1e6, 1)
    return rows


def _decode_bench(cfg, prompt_len, base_tokens=16, extra_tokens=256):
    """Greedy generation s/token on device-resident bf16 weights (the
    BASELINE big_model_inference generation metric). Differential timing —
    (t[base+extra] - t[base]) / extra — cancels prefill, dispatch overhead,
    and the host round trip, none of which are per-token costs. Each timed
    value is forced with a scalar device_get."""
    import dataclasses

    from accelerate_tpu.generation import generate
    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params

    # one explicit cache size for BOTH loop lengths, so the differential
    # really cancels per-call costs instead of comparing two cache buckets
    cfg = dataclasses.replace(
        cfg, max_cache_len=min(cfg.max_seq_len, -(-(prompt_len + base_tokens + extra_tokens) // 256) * 256)
    )
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len)
    params, _ = unbox_params(variables["params"])
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)
    )
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (1, prompt_len))

    def run(n):
        out = generate(model_def, params, ids, max_new_tokens=n)
        return int(jax.device_get(out[0, -1]))  # forces the whole loop

    run(base_tokens)  # compile both loop lengths
    run(base_tokens + extra_tokens)
    timings = []
    for _ in range(2):
        t0 = time.perf_counter(); run(base_tokens); t_base = time.perf_counter() - t0
        t0 = time.perf_counter(); run(base_tokens + extra_tokens); t_full = time.perf_counter() - t0
        timings.append((t_full - t_base) / extra_tokens)
    return float(np.median(timings))


def _decode_batched_bench(cfg, prompt_len, batch_sizes=(8, 32), max_new=96,
                          steps_per_call=16, warm_new=16):
    """Continuous-batching decode throughput (serving/ServingEngine) on
    device-resident bf16 weights: aggregate tokens/s and per-token latency
    at each slot count, plus the recompile invariant of record.

    Method: one warmup wave compiles every program (prefill buckets, the
    single step, the burst), ``mark_steady()``, then a timed wave with
    every slot occupied and STAGGERED prompt lengths — so the number also
    witnesses that admissions at varying lengths trigger no new compiles
    (``serving_admission_recompiles == 0``, asserted). Decode runs in
    fused ``steps_per_call`` bursts, so per-token cost measures the chip,
    not the per-dispatch host round trip (same trick as the train benches'
    fused loop). Tokens are forced to host every burst by the engine itself.

    The first batch size additionally reruns its wave with full request
    tracing armed (per-request JSONL + spans + SLO histograms) — the
    zero-overhead witness: request-level observability is designed to stay
    on in production, so the traced row must hold the untraced row's
    throughput (asserted within run-to-run noise), and its histograms
    supply the serving_ttft/itl percentile rows.
    Returns {batch: {"tokens_per_sec", "ms_per_token", ...}, "recompiles"}.
    """
    import dataclasses
    import tempfile

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    cap = -(-(2 * prompt_len + max_new) // 256) * 256
    cfg = dataclasses.replace(cfg, max_cache_len=min(cfg.max_seq_len, cap))
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len)
    params, _ = unbox_params(variables["params"])
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)
    )
    rng = np.random.RandomState(0)
    out = {}
    recompiles = {}
    for n in batch_sizes:
        engine = ServingEngine(
            model_def, params, num_slots=n,
            prefill_chunks=(prompt_len // 2, prompt_len),
            steps_per_call=steps_per_call,
        )
        # the baseline wave must be genuinely untraced even if some other
        # bench section left a global telemetry session live
        engine.telemetry = None
        # warmup: deterministically compile every program (prefill buckets,
        # admission scatter, single step, burst), then a tiny traffic wave
        # for the remaining eager host paths, then freeze the compile set
        engine.warmup()
        warm = [rng.randint(0, cfg.vocab_size, (l,))
                for l in (prompt_len, prompt_len // 2)]
        engine.generate_batched(warm, max_new_tokens=warm_new)
        engine.mark_steady()
        engine._step_samples.clear()
        engine._itl.clear()  # itl_p95 must measure the timed wave only
        # timed wave: full occupancy, staggered prompt lengths
        lengths = [prompt_len - (i % 4) * (prompt_len // 8) for i in range(n)]
        prompts = [rng.randint(0, cfg.vocab_size, (l,)) for l in lengths]
        t0 = time.perf_counter()
        engine.generate_batched(prompts, max_new_tokens=max_new)
        wall = time.perf_counter() - t0
        m = engine.metrics()
        rc = engine.admission_recompiles
        recompiles[n] = rc
        assert rc == 0, (
            f"continuous-batching admissions recompiled {rc} programs at "
            f"batch {n} — the slot arena's no-recompile invariant broke"
        )
        # decode-only rates from the engine's step samples (prefill chunks
        # excluded); e2e_wall covers the whole wave incl. admissions.
        # ms_per_token = mean device-step wall = each request's added
        # latency per token, the apples-to-apples of decode_ms_per_token.
        samples = list(engine._step_samples)
        wall_d = sum(w for w, _, _ in samples)
        toks = sum(t for _, t, _ in samples)
        steps = sum(s for _, _, s in samples)
        out[n] = {
            "tokens_per_sec": round(toks / wall_d, 1) if wall_d else None,
            "ms_per_token": round(1e3 * wall_d / steps, 3) if steps else None,
            "itl_p95_ms": round(m.get("serving/itl_p95_ms", 0.0), 3),
            "e2e_wall_s": round(wall, 2),
        }
        if n != batch_sizes[0]:
            continue
        # -- zero-overhead witness + SLO percentiles (first batch size) --
        from accelerate_tpu.telemetry import TelemetryConfig, TelemetrySession

        with tempfile.TemporaryDirectory(prefix="att_bench_trace_") as tdir:
            session = TelemetrySession(TelemetryConfig(
                trace_dir=tdir, watchdog=False, flight_hooks=False,
            ))
            engine.telemetry = session
            session.attach_serving(engine)
            engine._step_samples.clear()
            engine._itl.clear()
            prompts_t = [rng.randint(0, cfg.vocab_size, (l,)) for l in lengths]
            engine.generate_batched(prompts_t, max_new_tokens=max_new)
            t_samples = list(engine._step_samples)
            rollup = session.rollup()
            session.close()
            engine.telemetry = None
        wall_t = sum(w for w, _, _ in t_samples)
        toks_t = sum(t for _, t, _ in t_samples)
        tps, tps_t = toks / wall_d, toks_t / wall_t
        assert tps_t >= 0.7 * tps, (
            f"request tracing cost {100 * (1 - tps_t / tps):.1f}% of batched-"
            f"decode throughput at batch {n} ({tps_t:.1f} vs {tps:.1f} tok/s) "
            "— the always-on observability contract broke"
        )
        out[n]["tokens_per_sec_traced"] = round(tps_t, 1)
        out[n]["trace_overhead_pct"] = round(100 * (1 - tps_t / tps), 2)
        for key in ("ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms", "itl_p99_ms"):
            out[n][key] = rollup.get(f"serving/{key}")
    return out, recompiles


def _serving_slo_rows(batched: dict) -> dict:
    """The serving SLO rows from `_decode_batched_bench`'s traced wave —
    keyed off the FIRST batch size (the one the witness instruments)."""
    b = batched[next(iter(batched))]
    return {
        "serving_ttft_p50": b["ttft_p50_ms"],
        "serving_ttft_p99": b["ttft_p99_ms"],
        "serving_itl_p50": b["itl_p50_ms"],
        "serving_itl_p99": b["itl_p99_ms"],
        "serving_trace_overhead_pct": b["trace_overhead_pct"],
    }


class _ReplayDrafter:
    """Drafts from previously recorded output streams (prompt-lookup over
    known continuations): the controlled-accept-rate drafter the spec bench
    uses so `decode_spec_tokens_per_sec` measures the verify machinery, not
    the luck of an n-gram match on a random-weight model."""

    def __init__(self, streams):
        self._streams = [np.asarray(s, np.int64) for s in streams]

    def propose(self, context, k):
        context = np.asarray(context, np.int64)
        out = np.full((k,), int(context[-1]), np.int32)
        for ref in self._streams:
            if context.size <= ref.size and np.array_equal(
                ref[: context.size], context
            ):
                cont = ref[context.size : context.size + k]
                out[: cont.size] = cont
                break
        return out


def _serving_paged_bench(cfg, prompt_len, *, flat_slots=4, page_size=16,
                         max_new=16, spec_k=4, ttft_reqs=4):
    """Paged-arena serving rows: slots per HBM byte vs the flat arena,
    shared-prompt (prefix-cache) TTFT vs cold, and speculative-decode
    throughput at a controlled accept rate.

    - **slots/HBM**: a flat arena reserves ``max_cache_len`` of KV per slot;
      the paged arena only binds pages as requests grow, so at the SAME KV
      byte budget (flat_slots x pages_per_slot pages) it concurrently admits
      2x the slots when requests use <= half a slot's capacity — asserted,
      not assumed.
    - **prefix TTFT**: one cold request populates the cache, then identical
      templated prompts admit by mapping the shared pages and prefilling
      only the tail — `serving_prefix_ttft_p50` vs `serving_cold_ttft_p50`.
    - **spec decode**: the same engine shape with ``spec_draft_len`` on and
      a replay drafter (recorded streams -> accept rate ~1) measures the
      verify path's tokens/s vs the no-spec paged engine at matched batch;
      the model-free n-gram drafter's accept rate on this model is reported
      alongside as `spec_accept_rate_ngram`.
    """
    import dataclasses

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    need = prompt_len + max_new + spec_k
    slot_pages = -(-need // page_size)        # pages one request binds
    cap = 2 * slot_pages * page_size          # slot capacity = 2x a request
    assert cap <= cfg.max_seq_len, (cap, cfg.max_seq_len)
    cfg = dataclasses.replace(cfg, max_cache_len=cap)
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
    )
    params, _ = unbox_params(variables["params"])
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)
    )
    rng = np.random.RandomState(0)
    # a small bucket so a prefix-hit tail prefills a fraction of the cold
    # plan's tokens, not just fewer of the same-size chunks
    chunks = tuple(sorted({max(page_size, prompt_len // 4),
                           prompt_len // 2, prompt_len}))
    pages_per_slot = cap // page_size
    num_pages = flat_slots * pages_per_slot + 1  # flat-equivalent KV (+parking)

    def paged_engine(**kw):
        kw.setdefault("num_slots", flat_slots)
        kw.setdefault("max_cache_len", cap)
        kw.setdefault("prefill_chunks", chunks)
        kw.setdefault("page_size", page_size)
        engine = ServingEngine(model_def, params, **kw)
        engine.telemetry = None
        # compile the whole program set up front: the TTFT comparison and
        # the spec-vs-base tokens/s must measure steady-state dispatches,
        # not who happened to pay the first compile
        engine.warmup()
        return engine

    out = {"page_size": page_size, "max_cache_len": cap}

    # -- slots per HBM byte: flat vs paged at equal KV budget --------------
    flat = ServingEngine(model_def, params, num_slots=flat_slots,
                         max_cache_len=cap, prefill_chunks=chunks)
    flat.telemetry = None
    out["flat_slots"] = flat_slots
    out["arena_hbm_bytes_per_slot"] = {
        "flat": flat.arena_bytes // flat_slots,
    }
    del flat
    over = paged_engine(num_slots=2 * flat_slots, num_pages=num_pages,
                        prefix_cache=False)
    out["paged_slots"] = over.num_slots
    out["arena_hbm_bytes_per_slot"]["paged"] = over.arena_bytes // over.num_slots
    reqs = [
        over.submit(rng.randint(0, cfg.vocab_size, (prompt_len,)),
                    max_new_tokens=max_new, seed=i)
        for i in range(2 * flat_slots)
    ]
    peak = 0
    while over._queue or over._admitting is not None or over._slot_req:
        over.step()
        peak = max(peak, len(over._slot_req))
    assert all(r.done for r in reqs)
    out["paged_slots_admitted_at_flat_hbm"] = peak
    assert peak >= 2 * flat_slots, (
        f"paged arena admitted only {peak} concurrent slots at the flat "
        f"arena's KV budget (expected >= {2 * flat_slots})"
    )
    del over

    # -- prefix-cache TTFT: shared templated prompt vs cold ----------------
    engine = paged_engine(num_slots=1, num_pages=4 * pages_per_slot + 1)
    template = rng.randint(0, cfg.vocab_size, (prompt_len,))

    def ttft_of(prompt, seed):
        req = engine.submit(prompt, max_new_tokens=2, seed=seed)
        engine.run()
        return 1e3 * (req.first_token_t - req.submit_t), req

    ttft_of(rng.randint(0, cfg.vocab_size, (prompt_len,)), 999)  # host warm
    cold_ms = [ttft_of(rng.randint(0, cfg.vocab_size, (prompt_len,)), i)[0]
               for i in range(ttft_reqs)]
    ttft_of(template, 100)  # populate the cache with the template
    shared = [ttft_of(template, 101 + i) for i in range(ttft_reqs)]
    shared_ms = [t for t, _ in shared]
    assert all(r.prefix_hit > 0 for _, r in shared)
    out["serving_cold_ttft_p50_ms"] = round(float(np.median(cold_ms)), 3)
    out["serving_prefix_ttft_p50_ms"] = round(float(np.median(shared_ms)), 3)
    assert out["serving_prefix_ttft_p50_ms"] < out["serving_cold_ttft_p50_ms"], (
        "prefix-cache hit did not beat cold prefill TTFT: "
        f"{out['serving_prefix_ttft_p50_ms']} vs {out['serving_cold_ttft_p50_ms']} ms"
    )
    out["prefix_ttft_speedup"] = round(
        out["serving_cold_ttft_p50_ms"] / out["serving_prefix_ttft_p50_ms"], 2
    )
    del engine

    # -- speculative decode throughput at matched batch --------------------
    prompts = [rng.randint(0, cfg.vocab_size, (prompt_len,))
               for _ in range(flat_slots)]

    def decode_rate(engine):
        got = engine.generate_batched(prompts, max_new_tokens=max_new,
                                      seeds=range(flat_slots))
        samples = list(engine._step_samples)
        wall = sum(w for w, _, _ in samples)
        toks = sum(t for _, t, _ in samples)
        return (toks / wall if wall else None), got

    base = paged_engine(prefix_cache=False)
    base_tps, streams = decode_rate(base)
    out["decode_paged_tokens_per_sec"] = round(base_tps, 1) if base_tps else None
    del base
    spec = paged_engine(prefix_cache=False, spec_draft_len=spec_k,
                        drafter=_ReplayDrafter(streams))
    spec_tps, spec_streams = decode_rate(spec)
    for a, b in zip(streams, spec_streams):
        np.testing.assert_array_equal(a, b)  # spec output is token-exact
    m = spec.metrics()
    out["decode_spec_tokens_per_sec"] = round(spec_tps, 1) if spec_tps else None
    out["spec_accept_rate"] = round(m["serving/spec_accept_rate"], 4)
    if m["serving/spec_accept_rate"] > 0.5 and base_tps and spec_tps:
        assert spec_tps > base_tps, (
            f"speculative decode ({spec_tps:.1f} tok/s) did not beat the "
            f"plain paged engine ({base_tps:.1f} tok/s) at accept rate "
            f"{m['serving/spec_accept_rate']:.2f}"
        )
        out["spec_speedup"] = round(spec_tps / base_tps, 2)
    del spec
    # the model-free n-gram drafter's accept rate on THIS model/traffic
    ngram = paged_engine(prefix_cache=False, spec_draft_len=spec_k)
    ngram.generate_batched(prompts, max_new_tokens=max_new,
                           seeds=range(flat_slots))
    out["spec_accept_rate_ngram"] = round(
        ngram.metrics()["serving/spec_accept_rate"], 4
    )
    return out


def _serving_ragged_bench(cfg, prompt_len, *, num_slots=8, page_size=16,
                          max_new=48, steps_per_call=8, short_frac=0.75):
    """Occupancy/raggedness sweep for the pallas paged decode kernel
    (ops/attention): batched decode tokens/s at FULL occupancy with mixed
    lengths — 75% short slots (prompt_len/8) / 25% long (prompt_len) — the
    regime where the masked-dense read wastes the most bandwidth (every
    slot streams its whole arena reservation regardless of live length).

    TPU branch: runs the identical wave with the kernel (default dispatch)
    and with ``decode_kernel='dense'`` forced, publishing
    `decode_paged_kernel_speedup` (asserted >= 1.0) plus the kernel wave's
    `decode_ragged_tokens_per_sec`. CPU branch: the compiled kernel cannot
    run, so it publishes the dense wave's throughput and an
    interpret-mode PARITY witness instead (`decode_paged_kernel_parity`:
    kernel tokens == dense tokens on a tiny model, greedy and exact).
    """
    import dataclasses

    from accelerate_tpu.models import DecoderConfig, DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    on_tpu = jax.default_backend() == "tpu"
    cap = -(-(prompt_len + max_new) // page_size) * page_size
    assert cap <= cfg.max_seq_len, (cap, cfg.max_seq_len)
    if on_tpu and ((cfg.head_dim or 0) % 128 or page_size % 8):
        # the compiled kernel's shape gate (head_dim % 128, page % 8):
        # promote the sweep model so the row measures kernel-vs-dense,
        # not dense-vs-dense noise — published so the provenance is clear
        cfg = dataclasses.replace(cfg, head_dim=128)
        page_size = max(page_size, 8)
    rng = np.random.RandomState(0)
    n_long = max(1, int(round(num_slots * (1 - short_frac))))
    lengths = [prompt_len if i < n_long else max(page_size, prompt_len // 8)
               for i in range(num_slots)]
    prompts = [rng.randint(0, cfg.vocab_size, (l,)) for l in lengths]
    out = {
        "num_slots": num_slots, "page_size": page_size,
        "short_frac": round(1 - n_long / num_slots, 3),
        "short_len": min(lengths), "long_len": max(lengths),
    }

    def wave_tps(base_cfg, decode_kernel):
        wcfg = dataclasses.replace(base_cfg, max_cache_len=cap,
                                   decode_kernel=decode_kernel)
        model_def = DecoderLM(wcfg)
        variables = model_def.init_variables(
            jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
        )
        params, _ = unbox_params(variables["params"])
        params = jax.device_put(
            jax.tree_util.tree_map(lambda x: x.astype(wcfg.dtype), params)
        )
        engine = ServingEngine(
            model_def, params, num_slots=num_slots, max_cache_len=cap,
            prefill_chunks=(max(16, prompt_len // 4), prompt_len),
            page_size=page_size, prefix_cache=False,
            steps_per_call=steps_per_call,
        )
        engine.telemetry = None
        engine.warmup()
        engine.generate_batched(prompts[:2], max_new_tokens=4)  # host warm
        engine.mark_steady()
        engine._step_samples.clear()
        streams = engine.generate_batched(prompts, max_new_tokens=max_new)
        assert engine.admission_recompiles == 0
        samples = list(engine._step_samples)
        wall = sum(w for w, _, _ in samples)
        toks = sum(t for _, t, _ in samples)
        return (toks / wall if wall else None), streams, engine._kernel_costed

    if on_tpu:
        kernel_tps, kernel_streams, kernel_on = wave_tps(cfg, None)
        dense_tps, dense_streams, _ = wave_tps(cfg, "dense")
        # NOTE: no token-equality assert between the waves — kernel and
        # dense logits agree to reassociation-level noise, not bitwise,
        # so a near-tie argmax may legitimately flip on real hardware.
        # Exactness is the op/serving test suite's contract (interpret
        # mode, structurally matched walks); the bench's contract is the
        # speedup. Same generated LENGTH is still required (greedy, no
        # eos): a mismatch means a scheduling bug, not numerics.
        assert [len(s) for s in kernel_streams] == [len(s) for s in dense_streams]
        out["decode_ragged_tokens_per_sec"] = round(kernel_tps, 1)
        out["decode_ragged_tokens_per_sec_dense"] = round(dense_tps, 1)
        if not kernel_on:
            # pallas missing from this TPU build: both waves ran dense —
            # a speedup row here would be noise masquerading as signal
            out["decode_paged_kernel_speedup"] = None
            out["decode_paged_kernel_active"] = False
            return out
        speedup = kernel_tps / dense_tps
        assert speedup >= 1.0, (
            f"paged decode kernel ({kernel_tps:.1f} tok/s) lost to the "
            f"gathered masked-dense path ({dense_tps:.1f} tok/s) on the "
            "ragged-occupancy wave — the live-token walk must not regress"
        )
        out["decode_paged_kernel_speedup"] = round(speedup, 2)
    else:
        dense_tps, _, _ = wave_tps(cfg, "dense")
        out["decode_ragged_tokens_per_sec"] = (
            round(dense_tps, 1) if dense_tps else None
        )
        out["decode_paged_kernel_speedup"] = None  # compiled kernel is TPU-only
        # interpret-mode parity witness on a tiny model: the kernel wave's
        # greedy tokens must equal the dense wave's, token for token
        tiny = DecoderConfig.tiny(max_seq_len=64)
        t_rng = np.random.RandomState(1)
        t_prompts = [t_rng.randint(3, tiny.vocab_size, (l,)) for l in (12, 4, 9)]
        tiny_waves = {}
        for mode in ("interpret", "dense"):
            tcfg = dataclasses.replace(tiny, decode_kernel=mode,
                                       decode_kernel_block=8)
            t_model = DecoderLM(tcfg)
            t_vars = t_model.init_variables(
                jax.random.PRNGKey(0), batch_size=1, seq_len=12
            )
            t_params, _ = unbox_params(t_vars["params"])
            t_engine = ServingEngine(
                t_model, t_params, num_slots=2, max_cache_len=64,
                prefill_chunks=(32,), page_size=8, prefix_cache=False,
            )
            t_engine.telemetry = None
            tiny_waves[mode] = t_engine.generate_batched(
                t_prompts, max_new_tokens=6
            )
        for a, b in zip(tiny_waves["interpret"], tiny_waves["dense"]):
            np.testing.assert_array_equal(a, b)
        out["decode_paged_kernel_parity"] = True
    return out


def _serving_prefill_bench(cfg, prompt_len, *, num_slots=8, page_size=16,
                           max_new=8, short_frac=0.75):
    """TTFT rows for the ragged flash prefill kernel (PR 18): a mixed
    admission burst — 75% short prompts (prompt_len/8), 25% long — against
    one COARSE prefill bucket, the regime where the bucketed chunk path
    pays the most padding and per-request dispatches.

    TPU branch: the identical burst with the kernel (default dispatch) and
    with ``prefill_kernel='dense'`` forced, publishing
    `prefill_kernel_speedup` (admission->first-token p50 ratio, asserted
    >= 1.0 when the kernel engages) and both waves' pad waste (ragged
    asserted strictly below bucketed). CPU branch: the compiled kernel
    cannot run, so it publishes an interpret-vs-dense token-PARITY witness
    (`prefill_kernel_parity`) plus the same pad-waste comparison — the
    packer runs identically under the interpreter."""
    import dataclasses

    from accelerate_tpu.models import DecoderConfig, DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and ((cfg.head_dim or 0) % 64 or page_size % 8):
        # the prefill kernel's shape gate (head_dim % 64, page % 8):
        # promote so the row measures kernel-vs-dense, not dense-vs-dense
        cfg = dataclasses.replace(cfg, head_dim=64)
        page_size = max(page_size, 8)
    if not on_tpu:
        # CPU: the tiny model (the interpreter pays per-element Python
        # cost, so the witness must stay small); prompt lengths shrink
        # with it but the shape of the burst is identical
        cfg = DecoderConfig.tiny(max_seq_len=256)
        prompt_len = min(prompt_len, 32)
        page_size = min(page_size, 8)
        num_slots = min(num_slots, 4)
    cap = -(-(prompt_len + max_new + 1) // page_size) * page_size
    assert cap <= cfg.max_seq_len, (cap, cfg.max_seq_len)
    rng = np.random.RandomState(0)
    n_long = max(1, int(round(num_slots * (1 - short_frac))))
    short_len = max(page_size, prompt_len // 8)
    lengths = [prompt_len if i < n_long else short_len
               for i in range(num_slots)]
    prompts = [rng.randint(0, cfg.vocab_size, (l,)) for l in lengths]
    out = {
        "num_slots": num_slots, "page_size": page_size,
        "short_frac": round(1 - n_long / num_slots, 3),
        "short_len": short_len, "long_len": prompt_len,
        "prefill_bucket": prompt_len,
    }

    def wave(prefill_kernel):
        wcfg = dataclasses.replace(cfg, max_cache_len=cap,
                                   prefill_kernel=prefill_kernel)
        model_def = DecoderLM(wcfg)
        variables = model_def.init_variables(
            jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
        )
        params, _ = unbox_params(variables["params"])
        params = jax.device_put(
            jax.tree_util.tree_map(lambda x: x.astype(wcfg.dtype), params)
        )
        # ONE coarse bucket: the bucketed path pays prompt_len rows per
        # admission; the ragged packer pays token blocks per tail
        engine = ServingEngine(
            model_def, params, num_slots=num_slots, max_cache_len=cap,
            prefill_chunks=(prompt_len,), page_size=page_size,
            prefix_cache=False,
        )
        engine.telemetry = None
        engine.warmup()
        engine.mark_steady()
        reqs = [engine.submit(p, max_new_tokens=max_new, seed=i)
                for i, p in enumerate(prompts)]
        engine.run()
        assert all(r.outcome == "finished" for r in reqs)
        assert engine.admission_recompiles == 0, (
            "ragged prefill recompiled post-steady — the packed grid "
            "capacities must all be compiled at warmup()"
        )
        ttfts = [(r.first_token_t - r.submit_t) * 1e3 for r in reqs]
        m = engine.metrics()
        streams = [r.result() for r in reqs]
        return {
            "ttft_p50_ms": round(float(np.median(ttfts)), 2),
            "pad_waste": round(m.get("serving/prefill_pad_waste_frac", 0.0), 4),
            "packed_tokens": m.get("serving/prefill_packed_tokens", 0),
            "kernel_active": bool(m.get("serving/prefill_kernel_active")),
            "paths": {r.prefill_kernel for r in reqs},
            "streams": streams,
        }

    if on_tpu:
        kernel_wave = wave(None)          # default dispatch -> ragged
        dense_wave = wave("dense")
        out["prefill_ttft_p50_ms"] = kernel_wave["ttft_p50_ms"]
        out["prefill_ttft_p50_ms_dense"] = dense_wave["ttft_p50_ms"]
        out["prefill_packed_tokens"] = kernel_wave["packed_tokens"]
        out["prefill_pad_waste_frac"] = kernel_wave["pad_waste"]
        out["prefill_pad_waste_frac_dense"] = dense_wave["pad_waste"]
        # same generated LENGTH (greedy, no eos); token equality is the
        # interpret-mode test suite's contract, not real-HW numerics'
        assert ([len(s) for s in kernel_wave["streams"]]
                == [len(s) for s in dense_wave["streams"]])
        if not kernel_wave["kernel_active"]:
            # pallas missing from this TPU build: both waves ran bucketed
            out["prefill_kernel_speedup"] = None
            out["prefill_kernel_active"] = False
            return out
        assert kernel_wave["paths"] == {"ragged"}, kernel_wave["paths"]
        out["prefill_kernel_active"] = True
        speedup = dense_wave["ttft_p50_ms"] / kernel_wave["ttft_p50_ms"]
        assert speedup >= 1.0, (
            f"ragged prefill kernel TTFT p50 {kernel_wave['ttft_p50_ms']}ms "
            f"lost to the bucketed chunk path {dense_wave['ttft_p50_ms']}ms "
            "on the mixed burst — the packed dispatch must not regress TTFT"
        )
        out["prefill_kernel_speedup"] = round(speedup, 2)
        assert kernel_wave["pad_waste"] < dense_wave["pad_waste"], (
            kernel_wave["pad_waste"], dense_wave["pad_waste"]
        )
    else:
        kernel_wave = wave("interpret")   # the IDENTICAL kernel, interpreted
        dense_wave = wave("dense")
        out["prefill_ttft_p50_ms"] = dense_wave["ttft_p50_ms"]
        out["prefill_packed_tokens"] = kernel_wave["packed_tokens"]
        out["prefill_pad_waste_frac"] = kernel_wave["pad_waste"]
        out["prefill_pad_waste_frac_dense"] = dense_wave["pad_waste"]
        out["prefill_kernel_speedup"] = None  # compiled kernel is TPU-only
        assert kernel_wave["kernel_active"] and kernel_wave["paths"] == {"ragged"}
        # parity witness: the packed interpret wave's tokens must equal
        # the bucketed dense wave's, token for token (greedy + exact)
        for a, b in zip(kernel_wave["streams"], dense_wave["streams"]):
            np.testing.assert_array_equal(a, b)
        out["prefill_kernel_parity"] = True
        assert kernel_wave["pad_waste"] < dense_wave["pad_waste"], (
            kernel_wave["pad_waste"], dense_wave["pad_waste"]
        )
    return out


def _serving_kv_quant_bench(cfg, prompt_len, *, page_size=16, flat_slots=4,
                            max_new=16, steps_per_call=4):
    """Quantized KV-arena rows (serving/drift.py harness + the int8 paged
    engine): capacity, throughput, and quality in one section.

    - **capacity**: `arena_hbm_bytes_per_slot_int8` / `_int4` beside the
      bf16 row, with the slots-per-chip multiplier ASSERTED: an int8 arena
      holding >= 1.8x the slots must fit the bf16 arena's KV byte budget,
      and a full-occupancy wave at that slot count must actually run
      (every slot concurrently live, every request finished).
    - **throughput**: `decode_int8_kv_tokens_per_sec` from the timed wave
      on the int8 engine (fused bursts, same method as the batched rows).
    - **quality**: the drift harness's `kv_quant_token_match_rate` (int8,
      greedy, fixed seeds — asserted >= 0.98) and teacher-forced
      `kv_quant_logit_mse_int8`/`_int4`, so `report --diff` guards both
      capacity AND quality from this round on.
    """
    import dataclasses

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine
    from accelerate_tpu.serving.drift import kv_quant_drift

    cap = -(-(prompt_len + max_new) // page_size) * page_size
    assert cap <= cfg.max_seq_len, (cap, cfg.max_seq_len)
    cfg = dataclasses.replace(cfg, max_cache_len=cap)
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
    )
    params, _ = unbox_params(variables["params"])
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)
    )
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (prompt_len,))
               for _ in range(flat_slots)]
    chunks = tuple(sorted({max(page_size, prompt_len // 2), prompt_len}))
    out = {"page_size": page_size, "max_cache_len": cap}

    # -- drift harness: quality + per-slot bytes per precision. int4
    # reuses int8's bf16 baseline (same prompts/seeds/engine shape) so
    # the section pays for ONE bf16 wave, not two.
    drift = {}
    baseline = None
    for kvq in ("int8", "int4"):
        drift[kvq] = kv_quant_drift(
            model_def, params, prompts, kv_cache_dtype=kvq,
            max_new_tokens=max_new, page_size=page_size,
            num_slots=flat_slots, max_cache_len=cap, prefill_chunks=chunks,
            seeds=range(flat_slots), baseline=baseline,
        )
        baseline = drift[kvq]["baseline"]
    d8 = drift["int8"]
    out["arena_hbm_bytes_per_slot"] = d8["arena_bytes_per_slot_bf16"]
    out["arena_hbm_bytes_per_slot_int8"] = d8["arena_bytes_per_slot_quant"]
    out["arena_hbm_bytes_per_slot_int4"] = (
        drift["int4"]["arena_bytes_per_slot_quant"]
    )
    out["kv_quant_token_match_rate"] = round(d8["token_match_rate"], 4)
    out["kv_quant_token_match_rate_int4"] = round(
        drift["int4"]["token_match_rate"], 4
    )
    out["kv_quant_logit_mse_int8"] = d8["logit_mse"]
    out["kv_quant_logit_mse_int4"] = drift["int4"]["logit_mse"]
    assert d8["token_match_rate"] >= 0.98, (
        f"int8 KV arena greedy token-match rate {d8['token_match_rate']:.4f}"
        " < 0.98 on fixed seeds — storage quantization is perturbing "
        "generations past the shippable bound (run serving.drift."
        "kv_quant_drift on this model for the logit breakdown)"
    )

    # -- >= 1.8x concurrent slots at the bf16 arena's KV byte budget -------
    ratio = d8["arena_bytes_ratio"]
    assert ratio >= 1.8, (
        f"int8 arena shrank KV bytes only {ratio:.2f}x vs bf16 — the "
        ">=1.8x slots-per-chip contract cannot hold (scale arena too fat?)"
    )
    slots_q = int(ratio * flat_slots)
    quant = ServingEngine(
        model_def, params, num_slots=slots_q, max_cache_len=cap,
        prefill_chunks=chunks, page_size=page_size, prefix_cache=False,
        kv_cache_dtype="int8", steps_per_call=steps_per_call,
    )
    quant.telemetry = None
    assert quant.arena_bytes <= d8["arena_bytes_bf16"] * 1.02, (
        quant.arena_bytes, d8["arena_bytes_bf16"]
    )
    quant.warmup()
    quant.generate_batched(prompts[:2], max_new_tokens=4)  # host warm
    quant.mark_steady()
    quant._step_samples.clear()
    wave = [rng.randint(0, cfg.vocab_size, (prompt_len,))
            for _ in range(slots_q)]
    reqs = [quant.submit(p, max_new_tokens=max_new, seed=i)
            for i, p in enumerate(wave)]
    peak = 0
    while quant._pending():
        quant.step()
        peak = max(peak, len(quant._slot_req))
    assert all(r.outcome == "finished" for r in reqs)
    assert quant.admission_recompiles == 0, (
        "int8 arena recompiled post-steady — quantization must be a cache "
        "dtype, not a program shape"
    )
    out["kv_quant_slots_at_bf16_hbm"] = peak
    out["kv_quant_slots_ratio"] = round(ratio, 2)
    floor_slots = int(np.ceil(1.8 * flat_slots))
    assert peak >= slots_q >= floor_slots, (
        f"int8 arena ran only {peak} concurrent slots at the bf16 budget "
        f"(needed >= {slots_q}, contract floor {floor_slots})"
    )
    samples = list(quant._step_samples)
    wall = sum(w for w, _, _ in samples)
    toks = sum(t for _, t, _ in samples)
    out["decode_int8_kv_tokens_per_sec"] = (
        round(toks / wall, 1) if wall else None
    )
    return out


def _decode_block_autotune(cfg, *, length=None, iters=30):
    """`--tune-decode-block`: sweep the dense-arena decode kernel's
    ``decode_kernel_block`` over the divisors of the cache length and
    publish per-block walls + the winner, so real-TPU runs can pin
    ``DecoderConfig.decode_kernel_block`` from measured data (the PR 8
    follow-up: block retune was deferred to hardware). On TPU the sweep
    times the COMPILED kernel; off-TPU it runs the interpreter — the
    machinery and the published shape are identical, but interpret-mode
    walls measure the interpreter, so `best_block` is only meaningful on
    hardware (tagged via `compiled`). head_dim configs failing the
    kernel's 64-multiple shape gate report `gated: true` and sweep
    nothing (PR 18 widened the gate from 128-multiples: the lane dim
    pads 64→128 in VMEM, trading ~2x pad for kernel arithmetic)."""
    import dataclasses

    from accelerate_tpu.ops.attention import decode_attention

    on_tpu = jax.default_backend() == "tpu"
    d = int(cfg.head_dim or (cfg.embed_dim // cfg.num_heads))
    L = int(length or min(cfg.max_seq_len, 2048 if on_tpu else 128))
    out = {"head_dim": d, "length": L, "compiled": bool(on_tpu)}
    if on_tpu and d % 64:
        out["gated"] = True
        out["gate_reason"] = (
            f"head_dim {d} is not a 64-multiple; the compiled kernel "
            "falls back dense (retune on a 64-multiple config)"
        )
        return out
    kvh = int(cfg.num_kv_heads or cfg.num_heads)
    b, h = 8, int(cfg.num_heads)
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), dt)
    k = jnp.asarray(rng.standard_normal((b, kvh, L, d)), dt)
    v = jnp.asarray(rng.standard_normal((b, kvh, L, d)), dt)
    # 75/25 ragged occupancy, like the serving sweep the block serves
    pos = jnp.asarray(
        [[L - 1 if i % 4 == 0 else L // 8] for i in range(b)], jnp.int32
    )
    impl = None if on_tpu else "interpret"
    cands = [blk for blk in (16, 32, 64, 128, 256, 512)
             if blk <= L and L % blk == 0]
    walls = {}
    for blk in cands:
        fn = jax.jit(functools.partial(
            decode_attention, impl=impl, block_kv=blk
        ))

        def force(r):
            # device_get of a scalar slice ends the timed region
            float(jax.device_get(r[0, 0, 0, 0]))

        force(fn(q, k, v, q_positions=pos))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(q, k, v, q_positions=pos)
        force(r)
        walls[str(blk)] = round(1e3 * (time.perf_counter() - t0) / iters, 4)
    out["block_ms"] = walls
    out["best_block"] = int(min(walls, key=walls.get)) if walls else None
    return out


def _prefill_block_autotune(cfg, *, iters=20):
    """`--tune-kernel-blocks`: sweep the ragged prefill kernel's
    ``prefill_kernel_block`` (the packed token-block — one grid row-tile
    per block) against the arena page size (the kv-block the prefix
    sweep walks) and publish the wall grid plus the winners
    (`best_prefill_block`, `best_prefill_kv_page`), the prefill twin of
    `_decode_block_autotune`'s `best_block`. Same caveats: on TPU the
    sweep times the COMPILED kernel; off-TPU it times the interpreter,
    so the winners only mean anything on hardware (tagged `compiled`).
    The workload is two packed admissions splitting the grid — one
    resuming a prefix-cache hit (so the page-block skip phase sweeps
    real pages), one cold — the mixed shape the serving packer emits."""
    from accelerate_tpu.ops.attention import ragged_prefill_attention

    on_tpu = jax.default_backend() == "tpu"
    d = int(cfg.head_dim or (cfg.embed_dim // cfg.num_heads))
    out = {"head_dim": d, "compiled": bool(on_tpu)}
    if on_tpu and d % 64:
        out["gated"] = True
        out["gate_reason"] = (
            f"head_dim {d} is not a 64-multiple; the prefill kernel "
            "falls back to bucketed chunks (retune on a 64-multiple config)"
        )
        return out
    h = int(cfg.num_heads)
    kvh = int(cfg.num_kv_heads or cfg.num_heads)
    cap = 512 if on_tpu else 32
    iters = iters if on_tpu else 2
    bt_cands = (8, 16, 32, 64, 128) if on_tpu else (8, 16)
    ps_cands = (8, 16, 32) if on_tpu else (8,)
    impl = None if on_tpu else "interpret"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.standard_normal((1, h, cap, d)), dt)
    k_new = jnp.asarray(rng.standard_normal((1, kvh, cap, d)), dt)
    v_new = jnp.asarray(rng.standard_normal((1, kvh, cap, d)), dt)
    half = hist = cap // 2
    row_slot = jnp.asarray([0] * half + [1] * half, jnp.int32)
    row_pos = jnp.asarray(
        list(range(hist, hist + half)) + list(range(half)), jnp.int32
    )
    slot_hist = jnp.asarray([hist, 0], jnp.int32)
    out["length"] = cap
    walls = {}
    for ps in ps_cands:
        per = -(-(hist + half) // ps)
        table = jnp.asarray(np.arange(2 * per, dtype=np.int32).reshape(2, per))
        k_pages = jnp.asarray(rng.standard_normal((2 * per + 1, kvh, ps, d)), dt)
        v_pages = jnp.asarray(rng.standard_normal((2 * per + 1, kvh, ps, d)), dt)
        for bt in bt_cands:
            fn = jax.jit(functools.partial(
                ragged_prefill_attention, impl=impl, token_block=bt
            ))

            def force(r):
                # same device_get discipline as the decode sweep
                float(jax.device_get(r[0][0, 0, 0, 0]))

            kw = dict(page_table=table, row_slot=row_slot, row_pos=row_pos,
                      slot_hist=slot_hist)
            force(fn(q, k_new, v_new, k_pages, v_pages, **kw))  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                r = fn(q, k_new, v_new, k_pages, v_pages, **kw)
            force(r)
            walls[f"tb{bt}/page{ps}"] = round(
                1e3 * (time.perf_counter() - t0) / iters, 4
            )
    out["block_ms"] = walls
    if walls:
        best = min(walls, key=walls.get)
        tb, ps = best.split("/")
        out["best_prefill_block"] = int(tb[2:])
        out["best_prefill_kv_page"] = int(ps[4:])
    return out


def _serving_isolation_bench(cfg, prompt_len, *, page_size=16, num_slots=2,
                             storm_reqs=4, b_reqs=4, max_new=12,
                             chunk_delay_s=0.004):
    """Multi-tenant isolation rows (scheduler.py wired into the engine):
    a seeded tenant-A prefill storm lands mid-flight while tenant B
    ('interactive', priority 5) decodes short prompts — published as the
    clean vs under-storm ITL p99 of B and their ratio, plus the scheduling
    actions (preemptions, sheds, final ITL budget) the run took.

    Injected per-chunk prefill delays (FaultInjector, seeded) make chunk
    cost deterministic, so the degradation factor measures *scheduling*
    interference — how many storm chunks the ITL-budget controller lets
    between B's tokens — not host noise. The definite-outcome contract is
    asserted: every request in both waves terminates finished/shed.
    """
    import dataclasses

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import (
        FaultInjector,
        SchedulerConfig,
        ServingEngine,
    )

    cap = -(-(2 * prompt_len + max_new) // page_size) * page_size
    cfg = dataclasses.replace(cfg, max_cache_len=min(cfg.max_seq_len, cap))
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
    )
    params, _ = unbox_params(variables["params"])
    params = jax.device_put(
        jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)
    )
    chunk = max(page_size, prompt_len // 4)
    slo_ms = 1e3 * chunk_delay_s + 10.0

    def wave(storm: bool):
        rng = np.random.RandomState(42)
        stamps = {}

        def stamp(tok, req):
            stamps.setdefault(req.id, []).append(time.perf_counter())

        faults = FaultInjector(seed=1).delay_prefill(
            every=1, delay_s=chunk_delay_s
        )
        a_prompts = [rng.randint(0, cfg.vocab_size, (2 * prompt_len,))
                     for _ in range(storm_reqs)]
        reqs = []
        if storm:
            faults.storm(at_step=2, fire=lambda eng: reqs.extend(
                eng.submit(p, max_new_tokens=3, seed=100 + i,
                           tenant="batch", priority=0)
                for i, p in enumerate(a_prompts)
            ))
        engine = ServingEngine(
            model_def, params, num_slots=num_slots,
            max_cache_len=cfg.max_cache_len, prefill_chunks=(chunk,),
            page_size=page_size,
            scheduler=SchedulerConfig(itl_slo_ms=slo_ms), faults=faults,
        )
        engine.telemetry = None
        engine.warmup()
        engine.mark_steady()
        b_prompts = [rng.randint(0, cfg.vocab_size, (prompt_len // 2,))
                     for _ in range(b_reqs)]
        reqs += [
            engine.submit(p, max_new_tokens=max_new, seed=i,
                          tenant="interactive", priority=5, on_token=stamp)
            for i, p in enumerate(b_prompts)
        ]
        engine.run()
        assert all(r.done and r.outcome in ("finished", "shed")
                   for r in reqs), "a burst request never terminated"
        assert engine.admission_recompiles == 0, (
            "storm scheduling recompiled post-steady"
        )
        gaps = [
            1e3 * (b - a)
            for req in reqs if req.tenant == "interactive"
            for a, b in zip(stamps.get(req.id, []), stamps.get(req.id, [])[1:])
        ]
        return float(np.percentile(gaps, 99)), reqs, engine

    p99_base, _, _ = wave(storm=False)
    p99_storm, reqs, engine = wave(storm=True)
    m = engine.metrics()
    return {
        "itl_slo_ms": round(slo_ms, 2),
        "itl_p99_clean_ms": round(p99_base, 3),
        "itl_p99_storm_ms": round(p99_storm, 3),
        "storm_degradation_x": round(p99_storm / max(1e-9, p99_base), 2),
        "interactive_finished": sum(
            r.outcome == "finished" for r in reqs if r.tenant == "interactive"
        ),
        "storm_finished": sum(
            r.outcome == "finished" for r in reqs if r.tenant == "batch"
        ),
        "storm_shed": sum(
            r.outcome == "shed" for r in reqs if r.tenant == "batch"
        ),
        "preemptions": engine.preemptions,
        "itl_budget_final": m.get("serving/itl_budget"),
    }


def _router_failover_bench(cfg, prompt_len, *, page_size=16, num_slots=2,
                           n_requests=6, max_new=8):
    """Multi-replica failover rows (serving/router.py + replica_server):
    two in-process replicas behind the router, one hard-failed mid-burst.

    - ``router_failover_extra_ttft_ms`` — added first-token latency of a
      re-queued request (router-side TTFT) vs the undisturbed wave's
      median: what one replica death costs the requests it interrupts
      (re-queue backoff + full replay on the survivor).
    - ``router_requeue_success_rate`` — re-queued requests that still
      finished / re-queued requests. Asserted 1.0: the robustness
      headline (kill any replica mid-burst, every request completes) is
      a regression the `report --diff` sentry must catch, not a vibe.
    """
    import dataclasses
    import threading

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving.engine import ServingEngine
    from accelerate_tpu.serving.replica_server import ReplicaServer
    from accelerate_tpu.serving.router import Router, RouterConfig

    cap = -(-(prompt_len + max_new + page_size) // page_size) * page_size
    cfg = dataclasses.replace(cfg, max_cache_len=min(cfg.max_seq_len, cap))
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
    )
    params, _ = unbox_params(variables["params"])
    chunk = max(page_size, prompt_len // 2)

    def mk(name):
        engine = ServingEngine(
            model_def, params, num_slots=num_slots,
            max_cache_len=cfg.max_cache_len, prefill_chunks=(chunk,),
            page_size=page_size, replica=name,
        )
        engine.telemetry = None
        engine.warmup()
        return engine

    engines = {n: mk(n) for n in ("A", "B")}
    for engine in engines.values():
        # AFTER both warmups: the compile counters are process-global,
        # so B's warmup must not read as recompiles on steady-marked A
        engine.mark_steady()
    servers = {
        n: ReplicaServer(e, name=n).start() for n, e in engines.items()
    }
    router = Router(
        {n: s.url for n, s in servers.items()},
        config=RouterConfig(backoff_base_s=0.01, backoff_cap_s=0.05,
                            max_retries=6, poll_interval_s=0.1,
                            migrate_session_kv=False),
    )
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (prompt_len,))
               for _ in range(n_requests)]

    def wave(kill: bool):
        router.collector.poll_once()
        results = [None] * n_requests
        ttfts = [None] * n_requests
        first_token = threading.Event()

        def one(i):
            t0 = time.perf_counter()

            def on_tok(tok, req, _i=i, _t0=t0):
                if ttfts[_i] is None:
                    ttfts[_i] = time.perf_counter() - _t0
                    first_token.set()

            results[i] = router.submit(
                [int(t) for t in prompts[i]], max_new_tokens=max_new,
                seed=i, on_token=on_tok,
            )

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(n_requests)]
        for t in threads:
            t.start()
        victim = None
        if kill:
            assert first_token.wait(timeout=120), "burst never started"
            # kill whichever replica the burst actually landed on (the
            # router's least-loaded placement decides, not this bench)
            victim = "A" if (
                servers["A"].engine._slot_req or servers["A"].engine._pending()
            ) else "B"
            servers[victim].kill()
        for t in threads:
            t.join(timeout=300)
        assert all(r is not None and r.done for r in results), (
            "a routed request never reached a definite outcome"
        )
        return results, ttfts, victim

    try:
        clean, clean_ttfts, _ = wave(False)
        assert all(r.outcome == "finished" for r in clean)
        base_ms = 1e3 * float(np.median([t for t in clean_ttfts if t]))
        # edge golden signals off the clean wave: the client-observed
        # router TTFT p99 (the router's own streaming histogram — what
        # `report --diff` watches as the edge-latency regression row)
        ttft_hist = router.hists.get("router/ttft")
        snap = ttft_hist.snapshot() if ttft_hist is not None else {}
        e2e_ttft_p99_ms = (
            round(snap["p99_s"] * 1e3, 2) if snap else None
        )
        # ...and a short synthetic-canary run through the router: the
        # first probe records the golden tokens, the rest must reproduce
        # them token-exactly (correctness sentinel: any drop below 1.0
        # trips `report --diff --fail` regardless of threshold)
        from accelerate_tpu.telemetry.canary import CanaryProber, via_router

        prober = CanaryProber(
            via_router(router),
            [{"prompt": [int(t) for t in prompts[0]], "seed": 1234,
              "max_new_tokens": max_new}],
            interval_s=60.0,
        )
        for _ in range(3):
            prober.probe_once()
        canary_pass_ratio = prober.pass_ratio()
        prober.close()
        killed, kill_ttfts, victim = wave(True)
        requeued = [
            (r, t) for r, t in zip(killed, kill_ttfts)
            if any("error" in h for h in r.hops)
        ]
        survivor = servers["B" if victim == "A" else "A"].engine
        out = {
            "requests": n_requests,
            "requeued": len(requeued),
            "ttft_clean_ms": round(base_ms, 2),
            # vacuously 1.0 when the kill interrupted nothing (all
            # requests beat the kill on a fast machine): "no request was
            # lost" still holds and the sentry must not spuriously trip
            "router_requeue_success_rate": (
                sum(r.outcome == "finished" for r, _ in requeued)
                / len(requeued) if requeued else 1.0
            ),
            "survivor_recompiles": survivor.admission_recompiles,
            "canary_pass_ratio": canary_pass_ratio,
        }
        if e2e_ttft_p99_ms is not None:
            out["router_e2e_ttft_p99_ms"] = e2e_ttft_p99_ms
        assert canary_pass_ratio == 1.0, (
            "the synthetic canary failed token-exactness on a healthy "
            "2-replica fleet — determinism regression"
        )
        if requeued:
            rq_ms = 1e3 * float(np.median(
                [t for _, t in requeued if t is not None]
            ))
            out["router_failover_extra_ttft_ms"] = round(rq_ms - base_ms, 2)
        assert out["router_requeue_success_rate"] == 1.0, (
            "a re-queued request failed to complete on the survivor"
        )
        assert all(r.outcome == "finished" for r in killed)
        assert survivor.admission_recompiles == 0, (
            "the survivor recompiled post-steady while absorbing re-queues"
        )
        return out
    finally:
        router.close()
        for s in servers.values():
            s.close()


def _loadtest_bench(cfg, *, page_size=16, num_slots=2):
    """Replay the canonical workload spec (tests/workload_canonical.json)
    against a fresh engine and grade it — the SLO-scorecard rows:

    - ``loadtest_slo_attainment`` — fraction of finished requests meeting
      the spec's TTFT/ITL targets (asserted conserved first: every
      offered request reached a definite outcome);
    - ``loadtest_goodput_tokens_per_chip`` — finished tokens/s per chip;
    - ``ghost_hit_ratio_4x`` — the simulated prefix-cache hit ratio at
      4x capacity from the same drill (cache-economics telemetry: the
      gap vs ``serving/prefix_hit_ratio`` is the KV-tiering headroom).

    The spec is seeded and closed-loop, so the schedule — and with it
    the ghost ratio — is deterministic; only the timing rows breathe.
    """
    import dataclasses

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import loadgen
    from accelerate_tpu.serving.engine import ServingEngine
    from accelerate_tpu.telemetry import scorecard as sc

    spec = loadgen.WorkloadSpec.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tests", "workload_canonical.json",
    ))
    need = spec.prompt_cap + 16  # prompt cap + output + spec margin
    cap = -(-min(cfg.max_seq_len, need) // page_size) * page_size
    cfg = dataclasses.replace(cfg, max_cache_len=cap)
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=spec.prompt_cap
    )
    params, _ = unbox_params(variables["params"])
    engine = ServingEngine(
        model_def, params, num_slots=num_slots, max_cache_len=cap,
        prefill_chunks=(page_size, 2 * page_size), page_size=page_size,
        prefix_max_entries=6,  # small on purpose: the ghost shadows need
                               # real evictions to have economics to report
    )
    engine.telemetry = None
    engine.warmup()
    engine.mark_steady()
    result = loadgen.run(spec, engine, time_scale=0.0, timeout_s=120)
    card = sc.build_scorecard(result, chips=max(1, jax.device_count()))
    counts = card["counts"]
    assert card["conserved"] and counts["in_flight"] == 0, (
        f"canonical drill did not conserve/drain: {counts}"
    )
    assert engine.admission_recompiles == 0, (
        "the canonical workload recompiled post-steady"
    )
    metrics = engine.metrics()
    return {
        "loadtest_slo_attainment": round(
            card["fleet"]["slo_attainment_frac"], 4
        ),
        "loadtest_goodput_tokens_per_chip": (
            card["fleet"]["goodput_tokens_per_chip_s"]
        ),
        "loadtest_finished": counts["finished"],
        "loadtest_schedule_digest": result.digest,
        "ghost_hit_ratio_4x": round(
            metrics.get("serving/ghost_hit_ratio_4x", 0.0), 4
        ),
        "prefix_hit_ratio": round(
            metrics.get("serving/prefix_hit_ratio", 0.0), 4
        ),
    }


def _kv_tier_bench(cfg, *, page_size=16, num_slots=2, baseline=None):
    """The KV-tiering economics rows (docs/serving.md "Hierarchical KV
    tiering"): the ghost shadows priced the headroom, this drill cashes
    it in.

    Phase A replays the same canonical workload as ``_loadtest_bench``
    on an engine whose evictions demote into a host+disk tier 4x the
    HBM prefix cache (12 host + 12 disk entries over the 6-entry HBM
    cache), publishing ``kv_tier_hit_ratio_{hbm,host,disk,peer}`` and
    ``kv_restore_overlap_frac``. Against the untiered ``baseline`` row
    it asserts the tiers close at least half the gap between the real
    hit ratio and the 4x ghost ratio — the headroom the economics
    telemetry promised must actually be collectable.

    Phase B is the session-resume drill: warm a long prompt, evict it
    into a host tier 10x the HBM cache, resubmit, and time first-token
    wall vs a cold prefill of the same length — ``session_resume_ttft_
    p50`` must beat ``session_cold_ttft_p50`` (restoring pages is
    cheaper than recomputing them, or the tiers are pointless).
    """
    import dataclasses
    import tempfile

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import loadgen
    from accelerate_tpu.serving.engine import ServingEngine
    from accelerate_tpu.serving.tiers import TierConfig
    from accelerate_tpu.telemetry import scorecard as sc

    spec = loadgen.WorkloadSpec.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tests", "workload_canonical.json",
    ))
    need = spec.prompt_cap + 16
    cap = -(-min(cfg.max_seq_len, need) // page_size) * page_size
    model_def = DecoderLM(dataclasses.replace(cfg, max_cache_len=cap))
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=spec.prompt_cap
    )
    params, _ = unbox_params(variables["params"])
    out = {}
    with tempfile.TemporaryDirectory() as td:
        engine = ServingEngine(
            model_def, params, num_slots=num_slots, max_cache_len=cap,
            prefill_chunks=(page_size, 2 * page_size), page_size=page_size,
            prefix_max_entries=6,  # same HBM cache the baseline ran with
            kv_tiers=TierConfig(host_entries=12, disk_entries=12,
                                disk_dir=td),
        )
        engine.telemetry = None
        engine.warmup()
        engine.mark_steady()
        result = loadgen.run(spec, engine, time_scale=0.0, timeout_s=120)
        card = sc.build_scorecard(result, chips=max(1, jax.device_count()))
        counts = card["counts"]
        assert card["conserved"] and counts["in_flight"] == 0, (
            f"tiered canonical drill did not conserve/drain: {counts}"
        )
        assert engine.admission_recompiles == 0, (
            "KV tiering recompiled post-steady (the gather/install "
            "programs must be warmup-compiled)"
        )
        m = engine.metrics()
        hit = m.get("serving/prefix_hit_ratio", 0.0)
        out["kv_tier_prefix_hit_ratio"] = round(hit, 4)
        for tier in ("hbm", "host", "disk", "peer"):
            out[f"kv_tier_hit_ratio_{tier}"] = round(
                m.get(f"serving/kv_tier_hit_ratio_{tier}", 0.0), 4
            )
        out["kv_restores"] = int(m.get("serving/kv_restores", 0))
        out["kv_restore_overlap_frac"] = round(
            m.get("serving/kv_restore_overlap_frac", 0.0), 4
        )
    if baseline:
        base = float(baseline.get("prefix_hit_ratio", 0.0))
        ghost = float(baseline.get("ghost_hit_ratio_4x", base))
        if ghost > base:
            out["kv_tier_gap_closed_frac"] = round(
                (hit - base) / (ghost - base), 4
            )
            assert hit >= base + 0.5 * (ghost - base) - 1e-9, (
                f"host+disk tiers at 4x capacity closed less than half "
                f"the ghost gap: hit={hit:.4f} base={base:.4f} "
                f"ghost_4x={ghost:.4f}"
            )

    # phase B: session resume vs cold prefill, host tier 10x the arena
    cap_b = min(8 * page_size, (cfg.max_seq_len // page_size) * page_size)
    prompt_len = cap_b - page_size
    model_b = DecoderLM(dataclasses.replace(cfg, max_cache_len=cap_b))
    engine = ServingEngine(
        model_b, params, num_slots=num_slots, max_cache_len=cap_b,
        prefill_chunks=(page_size, 2 * page_size), page_size=page_size,
        prefix_max_entries=6,
        # insert registers every page-aligned prefix as its own entry, so
        # entry counts scale with pages; 60 host entries comfortably holds
        # every demotion this drill produces — 10x the HBM entry cache
        kv_tiers=TierConfig(host_entries=60),
    )
    engine.telemetry = None
    engine.warmup()
    engine.mark_steady()
    rng = np.random.default_rng(20260807)
    trials = 5
    prompts = [rng.integers(1, cfg.vocab_size, size=prompt_len,
                            dtype=np.int64).tolist() for _ in range(2 * trials)]

    def _ttft(prompt):
        t0 = time.perf_counter()
        req = engine.submit(prompt, max_new_tokens=1, seed=7)
        engine.run()
        assert req.outcome == "finished"
        return 1e3 * (time.perf_counter() - t0), req

    # cold first (nothing cached yet), then warm the resume prompts and
    # push them out of HBM into the host tier so the resubmits below must
    # restore, not just re-hit
    cold = [_ttft(p)[0] for p in prompts[trials:]]
    for p in prompts[:trials]:
        engine.submit(p, max_new_tokens=1, seed=7)
    engine.run()
    while engine._prefix.evict_lru():
        pass
    resumed = []
    for p in prompts[:trials]:
        ms, req = _ttft(p)
        assert req.kv_restore_tier == "host", (
            f"session resume did not restore from the host tier "
            f"(kv_restore_tier={req.kv_restore_tier!r})"
        )
        resumed.append(ms)
    out["session_cold_ttft_p50"] = round(float(np.median(cold)), 2)
    out["session_resume_ttft_p50"] = round(float(np.median(resumed)), 2)
    assert out["session_resume_ttft_p50"] < out["session_cold_ttft_p50"], (
        f"restoring {prompt_len}-token KV from host RAM did not beat the "
        f"cold prefill it replaces: resume={out['session_resume_ttft_p50']}"
        f"ms cold={out['session_cold_ttft_p50']}ms"
    )
    assert engine.admission_recompiles == 0, (
        "the session-resume drill recompiled post-steady"
    )
    return out


def _autoscale_bench(cfg, prompt_len, *, page_size=16, num_slots=2,
                     n_requests=6, max_new=8):
    """Closed-loop autoscaling rows (serving/autoscaler.py +
    telemetry/capacity.py): one in-process replica behind the router,
    then the real actuation path — the policy floor forces a scale-out,
    the new replica passes the token-exact canary gate before
    registration, and the collector must scrape it placeable.

    - ``autoscale_reaction_s`` — decision to first verified token out of
      the new replica (spawn is an in-process engine here, so this is
      the canary-gate + registration floor, not subprocess warmup);
    - ``fleet_capacity_tokens_per_s`` / ``fleet_headroom_frac`` — the
      capacity model's sustainable-rate estimate summed over the live
      fleet after the wave, against the offered rate it saw.
    """
    import dataclasses

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving.autoscaler import Autoscaler, SpawnedReplica
    from accelerate_tpu.serving.engine import ServingEngine
    from accelerate_tpu.serving.replica_server import ReplicaServer
    from accelerate_tpu.serving.router import Router, RouterConfig
    from accelerate_tpu.telemetry.capacity import AutoscalePolicy, fleet_capacity

    cap = -(-(prompt_len + max_new + page_size) // page_size) * page_size
    cfg = dataclasses.replace(cfg, max_cache_len=min(cfg.max_seq_len, cap))
    model_def = DecoderLM(cfg)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=1, seq_len=prompt_len
    )
    params, _ = unbox_params(variables["params"])
    chunk = max(page_size, prompt_len // 2)
    servers = []

    def mk(name):
        engine = ServingEngine(
            model_def, params, num_slots=num_slots,
            max_cache_len=cfg.max_cache_len, prefill_chunks=(chunk,),
            page_size=page_size, replica=name,
        )
        engine.telemetry = None
        engine.warmup()
        engine.mark_steady()
        server = ReplicaServer(engine, name=name).start()
        servers.append(server)
        return server

    def spawn_fn(name):
        server = mk(name)
        return SpawnedReplica(name, server.url, server=server)

    first = mk("A")
    router = Router(
        {"A": first.url},
        config=RouterConfig(poll_interval_s=0.1),
    )
    autoscaler = Autoscaler(
        router,
        policy=AutoscalePolicy(min_replicas=2, max_replicas=2,
                               cooldown_s=0.0, confirm_evals=1),
        spawn_fn=spawn_fn,
        goldens=[{"prompt": list(range(3, 3 + prompt_len)),
                  "seed": 1234, "max_new_tokens": max_new}],
        canary_probes=2,
    )
    router.attach_autoscaler(autoscaler)
    rng = np.random.RandomState(5)
    try:
        router.collector.poll_once()
        # below the policy floor: the first evaluation must actuate the
        # whole scale-out path (spawn -> canary gate -> register ->
        # placeable within a poll)
        record = autoscaler.evaluate_once()
        assert record["action"] == "scale_out" and (
            record["outcome"] == "scaled_out"
        ), f"autoscale drill did not scale out: {record}"
        # a wave across the now-2-replica fleet gives the capacity model
        # decode walls + occupancy to estimate from
        for i in range(n_requests):
            res = router.submit(
                [int(t) for t in rng.randint(0, cfg.vocab_size, (prompt_len,))],
                max_new_tokens=max_new, seed=i,
            )
            assert res.done and res.outcome == "finished"
        router.collector.poll_once()
        gauges = router.collector.fleet_gauges()
        capacity = fleet_capacity(gauges)
        ledger = autoscaler.conservation()
        assert ledger["conserved"], f"autoscale wave lost requests: {ledger}"
        out = {
            "autoscale_reaction_s": record.get("autoscale_reaction_s"),
            "autoscale_stages": record.get("stages"),
            "autoscale_replicas": autoscaler.fleet_size(),
        }
        if capacity is not None:
            out["fleet_capacity_tokens_per_s"] = capacity[
                "capacity_tokens_per_s"
            ]
            out["fleet_headroom_frac"] = capacity["headroom_frac"]
        return out
    finally:
        router.close()
        for s in servers:
            s.close()


def _pipeline_mem_worker():
    """Compiled temp-memory (stash + belts) for gpipe-under-AD vs the manual
    1F1B schedule at M=4S, on the 8-device CPU sim (the schedule's win is a
    memory asymptotic — O(S) vs O(M) per-stage activation stash — which is
    measurable without stage hardware). Prints one JSON line."""
    import dataclasses

    from accelerate_tpu.models import DecoderConfig, DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params

    M = 32
    cfg = DecoderConfig(
        vocab_size=256, num_layers=4, embed_dim=128, num_heads=4,
        max_seq_len=256, dtype=jnp.float32, remat=True, scan_layers=True,
        pipeline_stages=4, pipeline_microbatches=M,
    )
    model = DecoderLM(cfg)
    ids = jnp.zeros((M * 2, 256), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids[:1])
    params, _ = unbox_params(variables["params"])

    def gpipe_vag(p, i, l):
        return jax.value_and_grad(
            lambda pp: model.apply({"params": pp}, i, labels=l)["loss"]
        )(p)

    vag = DecoderLM(
        dataclasses.replace(cfg, pipeline_schedule="1f1b")
    ).pipeline_value_and_grad()
    out = {}
    for name, fn in (("gpipe", gpipe_vag), ("1f1b", vag)):
        ma = jax.jit(fn).lower(params, ids, ids).compile().memory_analysis()
        out[name] = ma.temp_size_in_bytes
    print(json.dumps(out))


def _pipeline_mem_bench() -> dict:
    """Run _pipeline_mem_worker in a CPU-sim child (the memory comparison
    neither needs nor should occupy the chip). A failed child raises."""
    return json.loads(_run_child(["--_pipeline_mem"], cpu=True).strip().splitlines()[-1])


def _incident_bench(n_incidents=3, n_requests=400, n_decisions=600,
                    observe_n=20_000):
    """Observability economics rows (telemetry/incidents.py + the exemplar
    reservoir), jax-free so the numbers mean the same thing on both
    branches:

    - ``exemplar_trace_ratio`` — request-tracker event throughput (the
      full submit→admit→token×N→finish lifecycle, JSONL record and SLO
      histograms armed) with the exemplar reservoir ON vs OFF — the
      zero-overhead witness at the production observation site (>= 0.7x
      asserted: exemplars are designed to stay on, same contract as the
      serving/train tracing witnesses);
    - ``incident_reconstruct_ms`` — wall time of ``reconstruct_incidents``
      over a synthetic artifact dir sized like a real drill (alert
      windows + request records + placement decisions + health flaps),
      with the exemplar join asserted to land on the right stage.
    """
    import tempfile

    from accelerate_tpu.telemetry.artifacts import ArtifactWriter
    from accelerate_tpu.telemetry.histograms import StreamingHistogram
    from accelerate_tpu.telemetry.incidents import reconstruct_incidents
    from accelerate_tpu.telemetry.requests import RequestTracer

    # -- exemplar zero-overhead witness ------------------------------------
    class _Session:  # the tracer's session surface, histograms only
        recorder = None
        flight = None

        def __init__(self, exemplars):
            self._hists = {}
            self._exemplars = exemplars

        def histogram(self, name):
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = StreamingHistogram()
                h.exemplars_enabled = self._exemplars
            return h

    class _Req:
        def __init__(self, i, tokens):
            self.id = f"req-{i}"
            self.prompt = np.zeros((64,), np.int32)
            self.max_new_tokens = tokens
            self.submit_t = time.perf_counter()
            self.finish_t = None
            self.replica = "r0"
            self.outcome = "finished"

    tokens = 64
    n_req = max(1, observe_n // tokens)

    def wave(exemplars, path):
        tracer = RequestTracer(_Session(exemplars), path=path)
        t0 = time.perf_counter()
        for i in range(n_req):
            req = _Req(i, tokens)
            tracer.on_submit(req)
            tracer.on_admission(req, 0, 0.002)
            tracer.on_first_token(req, 0.02)
            for k in range(1, tokens):
                tracer.on_token(req, 0.004, k)
            req.finish_t = time.perf_counter()
            tracer.on_finish(req, "eos")
        dt = time.perf_counter() - t0
        tracer.close()
        return n_req * tokens / dt

    with tempfile.TemporaryDirectory(prefix="att_bench_exemplar_") as tdir:
        def path(tag):
            return os.path.join(tdir, f"requests-{tag}.jsonl")

        wave(True, path("w0")), wave(False, path("w1"))  # warm both paths
        rate_on = max(wave(True, path(f"on{i}")) for i in range(3))
        rate_off = max(wave(False, path(f"off{i}")) for i in range(3))
    ratio = rate_on / rate_off
    assert ratio >= 0.7, (
        f"exemplar reservoir cost {100 * (1 - ratio):.1f}% of request-"
        f"tracing throughput ({rate_on:,.0f} vs {rate_off:,.0f} events/s) "
        "— the always-on exemplar contract broke"
    )

    # -- incident reconstruction wall --------------------------------------
    base = 1_700_000_000.0
    with tempfile.TemporaryDirectory(prefix="att_bench_incident_") as tdir:
        def writer(name):
            return ArtifactWriter(os.path.join(tdir, name))

        culprits = [f"cul-{k}" for k in range(n_incidents)]
        fh = writer("alerts-host0.jsonl")
        for k in range(n_incidents):
            t = base + 120.0 * k
            for state, dt, kv in (
                ("pending", 0.0, {}),
                ("firing", 6.0, {"exemplars": [culprits[k]]}),
                ("resolved", 30.0, {}),
            ):
                fh.write_line(json.dumps({
                    "t_unix_s": t + dt, "rule": "itl_burn_rate",
                    "state": state, "value": 2.0 + k, "severity": "page",
                    "description": "bench synthetic", **kv,
                }))
        fh.close()
        fh = writer("requests-host0.jsonl")
        for i in range(n_requests):
            rid = culprits[i] if i < n_incidents else f"req-{i}"
            t = base + 120.0 * (i % n_incidents) + 8.0
            fh.write_line(json.dumps({
                "request_id": rid, "replica": "r0",
                "queue_wait_ms": 2.0, "kv_restore_ms": 1.0,
                "ttft_ms": 20.0, "total_ms": 520.0, "tokens": 32,
                "submit_unix_s": t, "finish_unix_s": t + 0.52,
            }))
        fh.close()
        fh = writer("router-decisions.jsonl")
        for i in range(n_decisions):
            fh.write_line(json.dumps({
                "t_unix_s": base + 120.0 * (i % n_incidents) + 7.0,
                "request_id": f"req-{i}", "hop": 0, "chosen": "r0",
                "reason": "least_loaded",
            }))
        fh.close()
        fh = writer("fleet-events.jsonl")
        for k in range(n_incidents):
            fh.write_line(json.dumps({
                "t_unix_s": base + 120.0 * k + 5.0, "replica": "r0",
                "from": "healthy", "to": "degraded", "reason": "itl breach",
            }))
        fh.close()

        for _ in range(2):  # warm the import + OS cache
            incidents = reconstruct_incidents(tdir)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            incidents = reconstruct_incidents(tdir)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
    assert len(incidents) == n_incidents, incidents
    joined = [r for i in incidents for r in i["exemplar_requests"]
              if not r.get("missing")]
    assert joined and all(r["top_stage"] == "decode" for r in joined), (
        "incident exemplar join did not attribute the synthetic decode "
        f"stall to the decode stage: {joined}"
    )
    return {
        "incident_reconstruct_ms": round(best * 1e3, 2),
        "incident_exemplars_joined": len(joined),
        "exemplar_trace_ratio": round(ratio, 3),
        "exemplar_trace_overhead_pct": round(100 * (1 - ratio), 2),
        "exemplar_trace_events_per_sec": round(rate_on),
    }


def _audit_rows():
    """Post-warmup static-audit pass (`accelerate-tpu audit` in-process):
    host lint + import hygiene + the program auditor over a warmed tiny
    serving engine and the fused train step, counted modulo the repo's
    checked-in ``audit-baseline.json``. Published as bench rows so
    `report --diff` treats a new P1 finding exactly like a perf
    regression (the per-fingerprint keys ride the telemetry-dir path)."""
    try:
        from accelerate_tpu.analysis import host_lint, hygiene, program_audit
        from accelerate_tpu.analysis.findings import Baseline, summarize

        findings = host_lint.lint_paths()
        findings += hygiene.hygiene_findings()
        findings += program_audit.self_audit(warmup=True)
        baseline = Baseline.load(
            os.path.join(hygiene.repo_root(), "audit-baseline.json")
        )
        active, suppressed = baseline.split(findings)
        s = summarize(active)
        return {
            "audit_findings_p1": s["findings_p1"],
            "audit_findings_total": s["findings_total"],
            "audit_findings_baselined": len(suppressed),
        }
    except Exception as e:  # the audit must never sink the bench
        return {"audit_error": repr(e)[:200]}


def main():
    import argparse

    from accelerate_tpu.models import DecoderConfig

    parser = argparse.ArgumentParser()
    parser.add_argument("--_ttft_worker", nargs=3, metavar=("CFG", "PROMPT", "DIR"),
                        help="internal: run one TTFT attempt and print it")
    parser.add_argument("--_ttft_quant", default=None, choices=["int8", "int4"],
                        help="internal: quantize-on-load for the TTFT attempt")
    parser.add_argument("--_ttft_stream", action="store_true",
                        help="internal: force the host-streaming tier (device "
                             "budget < model) and report decode + HBM stats")
    parser.add_argument("--_pipeline_mem", action="store_true",
                        help="internal: print gpipe-vs-1f1b compiled temp bytes")
    parser.add_argument("--_write_ckpt", nargs=3, metavar=("CFG", "PROMPT", "DIR"),
                        help="internal: write the random TTFT checkpoint (CPU child)")
    parser.add_argument("--tune-decode-block", action="store_true",
                        help="sweep decode_kernel_block for the dense-arena "
                             "decode kernel and publish per-block walls + the "
                             "winner (meaningful on real TPU; CPU runs the "
                             "interpreter to prove the machinery)")
    parser.add_argument("--tune-kernel-blocks", action="store_true",
                        help="superset of --tune-decode-block: also sweep the "
                             "ragged prefill kernel's token-block x kv-page "
                             "grid and publish best_prefill_block beside "
                             "best_block (same real-TPU caveat)")
    parser.add_argument("--telemetry-out", default=None, metavar="PATH",
                        help="write the headline train bench's per-step runtime-"
                             "telemetry records (step wall, tokens/s, live MFU) "
                             "as JSONL at PATH — drop it next to BENCH_*.json")
    args, _ = parser.parse_known_args()

    # only an explicit JAX_PLATFORMS=cpu selects the CPU smoke sizes
    cpu_run = os.environ.get("JAX_PLATFORMS") == "cpu"

    def require_platform() -> bool:
        """Open the backend; fail unless it is the TPU or the CPU was asked for."""
        backend = jax.default_backend()
        if backend != "tpu" and not cpu_run:
            sys.exit(
                f"bench.py found no TPU (backend={backend!r}) and JAX_PLATFORMS=cpu "
                "was not given: refusing to print CPU numbers under device metric names"
            )
        return backend == "tpu"

    if args._pipeline_mem:
        _pipeline_mem_worker()
        return

    if args._write_ckpt:
        name, prompt, tmpdir = args._write_ckpt
        _write_host_checkpoint(_named_configs()[name], int(prompt), tmpdir)
        return

    if args._ttft_worker:
        name, prompt, tmpdir = args._ttft_worker
        require_platform()
        cfg = _named_configs()[name]
        ckpt = os.path.join(tmpdir, "model.safetensors")
        if args._ttft_stream:
            ttft, phases, stats, decode_s = _ttft_streamed_once(cfg, ckpt, int(prompt))
            stats["decode_ms_per_token"] = round(decode_s * 1e3, 2)
            print(f"TTFT {ttft:.3f}")
            print("TTFT_PHASES " + json.dumps({k: round(v, 3) for k, v in phases.items()}))
            print("TTFT_STREAM " + json.dumps(stats))
            return
        ttft, phases, _ = _ttft_once(cfg, ckpt, int(prompt), quant=args._ttft_quant)
        print(f"TTFT {ttft:.3f}")
        print("TTFT_PHASES " + json.dumps({k: round(v, 3) for k, v in phases.items()}))
        return

    # children first: a chip belongs to one process at a time, and from
    # require_platform() on this parent holds it
    extra = _child_rows(on_tpu=not cpu_run)
    on_tpu = require_platform()

    if on_tpu:
        # TPU-native PRNG for the dropout streams (utils/random.KeyChain):
        # threefry costs ~25% of a dropout-0.1 BERT step on v5e
        os.environ.setdefault("ATT_PRNG_IMPL", "rbg")

        # save_dots: keep matmul outputs, recompute only elementwise in the
        # backward — measured +3.8pp MFU over save_attention at S=2048
        # (long-context rows below keep save_attention: at 16k+/chip the
        # flash recompute is the win and save_dots goes bandwidth-bound)
        flagship = DecoderConfig(
            vocab_size=32_000, num_layers=12, embed_dim=1536, num_heads=12,
            num_kv_heads=12, mlp_dim=4096, max_seq_len=2048,
            dtype=jnp.bfloat16, remat=True, remat_policy="save_dots",
            scan_layers=True,
        )
        tok_s, mfu, _, step_ms = _train_bench(
            flagship, 8, 2048, 20, "bf16", telemetry_out=args.telemetry_out
        )

        # explanatory-telemetry wave: goodput ledger + forensics + cost
        # registry armed, 0.7x zero-overhead witness vs the headline row
        _publish_goodput_rows(extra, flagship, 8, 2048, 10, "bf16",
                              args.telemetry_out, tok_s)

        # the BASELINE nlp_example / cv_example rows (samples/sec/chip).
        # These run EARLY: their sub-second steps make them the rows most
        # sensitive to host noise (the r03-r05 record shows the same config
        # reading differently early and late in one run).
        enc_sps, enc_mfu = _encoder_bench(64, 128, 20)
        extra["bert_base_samples_per_sec"] = round(enc_sps)
        extra["bert_base_train_mfu_pct"] = round(enc_mfu * 100, 2)
        extra["resnet50_samples_per_sec"] = round(_resnet_bench(64, 224, 12))

        # GQA config: 4x fewer KV heads — the kernel path the headline MHA
        # config never exercises
        gqa = DecoderConfig(
            vocab_size=32_000, num_layers=12, embed_dim=1536, num_heads=12,
            num_kv_heads=4, mlp_dim=4096, max_seq_len=2048,
            dtype=jnp.bfloat16, remat=True, remat_policy="save_dots",
            scan_layers=True,
        )
        gqa_tok_s, gqa_mfu, _, _ = _train_bench(gqa, 8, 2048, 10, "bf16")
        extra["gqa_train_mfu_pct"] = round(gqa_mfu * 100, 2)
        extra["gqa_tokens_per_sec"] = round(gqa_tok_s)

        # long-context: 16k and 32k tokens single chip (ring attention
        # exercises the sequence axis only multi-chip; single-chip this
        # stresses the flash kernel's long-S path + remat)
        longctx = DecoderConfig(
            vocab_size=32_000, num_layers=8, embed_dim=1024, num_heads=8,
            num_kv_heads=8, mlp_dim=2816, max_seq_len=16_384,
            dtype=jnp.bfloat16, remat=True, scan_layers=True,
        )
        # batch 2: the [2, 16k] shapes tile the MXU better than [1, 16k]
        # (+1.5pp MFU) and smooth run-to-run variance
        lc_tok_s, lc_mfu, _, _ = _train_bench(longctx, 2, 16_384, 4, "bf16")
        extra["long16k_train_mfu_pct"] = round(lc_mfu * 100, 2)
        extra["long16k_tokens_per_sec"] = round(lc_tok_s)

        long32k = DecoderConfig(
            vocab_size=32_000, num_layers=8, embed_dim=1024, num_heads=8,
            num_kv_heads=8, mlp_dim=2816, max_seq_len=32_768,
            dtype=jnp.bfloat16, remat=True, scan_layers=True,
        )
        lc32_tok_s, lc32_mfu, _, _ = _train_bench(long32k, 1, 32_768, 3, "bf16")
        extra["long32k_train_mfu_pct"] = round(lc32_mfu * 100, 2)
        extra["long32k_tokens_per_sec"] = round(lc32_tok_s)

        # fp8-vs-bf16 row (always on; reference benchmarks/fp8/* analog).
        # v5e has no fp8 MXU — XLA emulates via convert — so this row
        # QUANTIFIES the recipe's overhead on this generation; the speedup
        # arrives on v6e+/Ironwood with the same code path.
        fp8_tok_s, fp8_mfu, _, _ = _train_bench(flagship, 8, 2048, 10, "fp8")
        extra["fp8_train_mfu_pct"] = round(fp8_mfu * 100, 2)
        extra["fp8_tokens_per_sec"] = round(fp8_tok_s)
        # fp8 forensics pass (ROADMAP 5b): the SAME recompile-forensics +
        # per-executable-roofline wave the bf16 leg runs, pointed at the
        # fp8 step — fp8_train_recompiles_diagnosed localizes any
        # retracing, fp8_train_step_mfu_model is XLA's own cost model over
        # the measured wall (vs the bf16 row above, the gap IS the
        # emulation tax docs/fp8.md quantifies on pre-fp8-MXU silicon)
        _publish_goodput_rows(extra, flagship, 8, 2048, 6, "fp8",
                              None, fp8_tok_s, prefix="fp8_train_")
        extra["fp8_vs_bf16_mfu_ratio"] = round(fp8_mfu / mfu, 3) if mfu else None

        ttft_cfg = _named_configs()["ttft_390m"]
        extra["decode_ms_per_token"] = round(_decode_bench(ttft_cfg, 128) * 1e3, 2)

        # continuous-batching decode (serving/): the single-stream row
        # above is the baseline this must beat ≥3x aggregate at batch 8
        batched, rcs = _decode_batched_bench(ttft_cfg, 128, batch_sizes=(8, 32))
        extra["decode_batched_tokens_per_sec"] = {
            f"batch{n}": v["tokens_per_sec"] for n, v in batched.items()
        }
        extra["decode_batched_ms_per_token"] = {
            f"batch{n}": v["ms_per_token"] for n, v in batched.items()
        }
        extra["decode_batched_detail"] = {f"batch{n}": v for n, v in batched.items()}
        extra["serving_admission_recompiles"] = max(rcs.values())
        # SLO percentiles from the traced (request-tracing-on) wave, plus
        # the zero-overhead witness ratio it was measured under
        extra.update(_serving_slo_rows(batched))
        single_tps = 1e3 / extra["decode_ms_per_token"]
        extra["decode_batched_speedup_b8"] = round(
            extra["decode_batched_tokens_per_sec"]["batch8"] / single_tps, 2
        )

        # paged arena + prefix cache + speculative decode (serving/pages.py):
        # 2x slots at the flat arena's KV budget, near-zero TTFT for shared
        # templated prompts, and the verify path's tokens/s — all asserted
        extra["serving_paged"] = _serving_paged_bench(
            ttft_cfg, 128, flat_slots=8, page_size=64, max_new=32, spec_k=4,
        )
        extra["serving_prefix_ttft_p50"] = extra["serving_paged"]["serving_prefix_ttft_p50_ms"]
        extra["decode_spec_tokens_per_sec"] = extra["serving_paged"]["decode_spec_tokens_per_sec"]
        extra["spec_accept_rate"] = extra["serving_paged"]["spec_accept_rate"]
        extra["arena_hbm_bytes_per_slot"] = extra["serving_paged"]["arena_hbm_bytes_per_slot"]

        # quantized KV arena (serving/drift.py): >=1.8x slots at the bf16
        # KV budget, int8 decode throughput, and the drift-quality bound —
        # all asserted, all regression-guarded via report --diff
        extra["serving_kv_quant"] = _serving_kv_quant_bench(
            ttft_cfg, 128, page_size=64, flat_slots=8, max_new=32,
        )
        for key in ("arena_hbm_bytes_per_slot_int8",
                    "arena_hbm_bytes_per_slot_int4",
                    "kv_quant_token_match_rate",
                    "decode_int8_kv_tokens_per_sec"):
            extra[key] = extra["serving_kv_quant"][key]

        if args.tune_decode_block or args.tune_kernel_blocks:
            extra["decode_block_autotune"] = _decode_block_autotune(ttft_cfg)
        if args.tune_kernel_blocks:
            extra["prefill_block_autotune"] = _prefill_block_autotune(ttft_cfg)
            extra["best_prefill_block"] = (
                extra["prefill_block_autotune"].get("best_prefill_block")
            )

        # ragged-occupancy decode: the pallas paged kernel vs the gathered
        # masked-dense read at 75% short / 25% long slots (asserted >= 1x)
        extra["serving_ragged"] = _serving_ragged_bench(
            ttft_cfg, 128, num_slots=8, page_size=64, max_new=48,
        )
        extra["decode_ragged_tokens_per_sec"] = (
            extra["serving_ragged"]["decode_ragged_tokens_per_sec"]
        )
        extra["decode_paged_kernel_speedup"] = (
            extra["serving_ragged"]["decode_paged_kernel_speedup"]
        )

        # ragged prefill: the packed flash prefill kernel vs bucketed
        # chunks on a mixed admission burst — TTFT speedup (asserted
        # >= 1x when the kernel engages) + pad-waste comparison
        extra["serving_prefill"] = _serving_prefill_bench(
            ttft_cfg, 128, num_slots=8, page_size=64,
        )
        for key in ("prefill_kernel_speedup", "prefill_pad_waste_frac",
                    "prefill_ttft_p50_ms"):
            extra[key] = extra["serving_prefill"].get(key)

        # multi-tenant isolation under a seeded prefill storm (scheduler):
        # tenant B's ITL p99 clean vs under-storm, preempt/shed actions
        extra["serving_isolation"] = _serving_isolation_bench(
            ttft_cfg, 128, page_size=64, num_slots=4,
        )
        extra["serving_isolation_degradation_x"] = (
            extra["serving_isolation"]["storm_degradation_x"]
        )

        # multi-replica failover: kill a replica mid-burst behind the
        # router, publish the re-queue cost + asserted success rate
        extra["router_failover"] = _router_failover_bench(
            ttft_cfg, 128, page_size=64, num_slots=2,
        )
        extra["router_failover_extra_ttft_ms"] = (
            extra["router_failover"].get("router_failover_extra_ttft_ms")
        )
        extra["router_requeue_success_rate"] = (
            extra["router_failover"]["router_requeue_success_rate"]
        )
        # edge golden-signal rows: client-observed router TTFT p99 +
        # the synthetic-canary correctness sentinel (report --diff
        # flags ANY pass-ratio drop, threshold or not)
        extra["router_e2e_ttft_p99_ms"] = (
            extra["router_failover"].get("router_e2e_ttft_p99_ms")
        )
        extra["canary_pass_ratio"] = (
            extra["router_failover"]["canary_pass_ratio"]
        )
        # workload-replay rows: the canonical spec graded by the SLO
        # scorecard + the ghost-cache economics gauge (report --diff
        # grades attainment/goodput/ghost-ratio drift between rounds)
        extra["loadtest"] = _loadtest_bench(ttft_cfg, page_size=64)
        for key in ("loadtest_slo_attainment",
                    "loadtest_goodput_tokens_per_chip",
                    "ghost_hit_ratio_4x"):
            extra[key] = extra["loadtest"][key]
        # KV-tiering economics: the same canonical drill with the
        # host+disk tiers on (asserted to close >= half the ghost gap)
        # plus the session-resume-vs-cold-prefill TTFT race
        extra["kv_tiering"] = _kv_tier_bench(
            ttft_cfg, page_size=64, baseline=extra["loadtest"],
        )
        for key in ("session_resume_ttft_p50", "session_cold_ttft_p50",
                    "kv_restore_overlap_frac", "kv_tier_hit_ratio_hbm",
                    "kv_tier_hit_ratio_host", "kv_tier_hit_ratio_disk",
                    "kv_tier_hit_ratio_peer"):
            extra[key] = extra["kv_tiering"][key]
        # closed-loop autoscaling rows: forced scale-out through the
        # real actuation path (canary-gated registration) + the capacity
        # model's fleet estimate — report --diff watches the reaction
        extra["autoscale"] = _autoscale_bench(
            ttft_cfg, 128, page_size=64, num_slots=2,
        )
        for key in ("autoscale_reaction_s", "fleet_capacity_tokens_per_s",
                    "fleet_headroom_frac"):
            extra[key] = extra["autoscale"].get(key)
    else:
        cfg = DecoderConfig.tiny(max_seq_len=256)
        tok_s, mfu, _, step_ms = _train_bench(
            cfg, 4, 128, 5, "no", telemetry_out=args.telemetry_out
        )
        _publish_goodput_rows(extra, cfg, 4, 128, 5, "no",
                              args.telemetry_out, tok_s)

        extra["decode_ms_per_token"] = round(
            _decode_bench(DecoderConfig.tiny(max_seq_len=128), 32, base_tokens=4, extra_tokens=16) * 1e3, 2
        )
        batched, rcs = _decode_batched_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, batch_sizes=(8,),
            max_new=24, steps_per_call=4, warm_new=5,
        )
        extra["decode_batched_tokens_per_sec"] = {
            f"batch{n}": v["tokens_per_sec"] for n, v in batched.items()
        }
        extra["decode_batched_ms_per_token"] = {
            f"batch{n}": v["ms_per_token"] for n, v in batched.items()
        }
        extra["serving_admission_recompiles"] = max(rcs.values())
        extra.update(_serving_slo_rows(batched))
        extra["serving_paged"] = _serving_paged_bench(
            DecoderConfig.tiny(max_seq_len=256), 64, flat_slots=2,
            page_size=16, max_new=8, spec_k=3, ttft_reqs=3,
        )
        extra["serving_prefix_ttft_p50"] = extra["serving_paged"]["serving_prefix_ttft_p50_ms"]
        extra["decode_spec_tokens_per_sec"] = extra["serving_paged"]["decode_spec_tokens_per_sec"]
        extra["spec_accept_rate"] = extra["serving_paged"]["spec_accept_rate"]
        extra["arena_hbm_bytes_per_slot"] = extra["serving_paged"]["arena_hbm_bytes_per_slot"]
        extra["serving_kv_quant"] = _serving_kv_quant_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, page_size=16,
            flat_slots=2, max_new=16, steps_per_call=2,
        )
        for key in ("arena_hbm_bytes_per_slot_int8",
                    "arena_hbm_bytes_per_slot_int4",
                    "kv_quant_token_match_rate",
                    "decode_int8_kv_tokens_per_sec"):
            extra[key] = extra["serving_kv_quant"][key]
        if args.tune_decode_block or args.tune_kernel_blocks:
            extra["decode_block_autotune"] = _decode_block_autotune(
                DecoderConfig.tiny(max_seq_len=256)
            )
        if args.tune_kernel_blocks:
            extra["prefill_block_autotune"] = _prefill_block_autotune(
                DecoderConfig.tiny(max_seq_len=256)
            )
            extra["best_prefill_block"] = (
                extra["prefill_block_autotune"].get("best_prefill_block")
            )
        extra["serving_ragged"] = _serving_ragged_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, num_slots=4,
            page_size=16, max_new=12, steps_per_call=4,
        )
        extra["decode_ragged_tokens_per_sec"] = (
            extra["serving_ragged"]["decode_ragged_tokens_per_sec"]
        )
        extra["decode_paged_kernel_speedup"] = (
            extra["serving_ragged"]["decode_paged_kernel_speedup"]
        )
        # ragged prefill witness, CPU-sized: interpret-vs-dense token
        # parity + the pad-waste comparison (the packer runs identically
        # under the interpreter; the compiled speedup row is TPU-only)
        extra["serving_prefill"] = _serving_prefill_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, num_slots=4,
            page_size=8, max_new=8,
        )
        for key in ("prefill_kernel_speedup", "prefill_pad_waste_frac",
                    "prefill_kernel_parity", "prefill_ttft_p50_ms"):
            extra[key] = extra["serving_prefill"].get(key)
        extra["serving_isolation"] = _serving_isolation_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, page_size=16,
            num_slots=2, storm_reqs=3, b_reqs=3, max_new=8,
        )
        extra["serving_isolation_degradation_x"] = (
            extra["serving_isolation"]["storm_degradation_x"]
        )
        extra["router_failover"] = _router_failover_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, page_size=16,
            num_slots=2, n_requests=6, max_new=8,
        )
        extra["router_failover_extra_ttft_ms"] = (
            extra["router_failover"].get("router_failover_extra_ttft_ms")
        )
        extra["router_requeue_success_rate"] = (
            extra["router_failover"]["router_requeue_success_rate"]
        )
        extra["router_e2e_ttft_p99_ms"] = (
            extra["router_failover"].get("router_e2e_ttft_p99_ms")
        )
        extra["canary_pass_ratio"] = (
            extra["router_failover"]["canary_pass_ratio"]
        )
        # workload-replay rows, CPU-sized (same canonical spec + digest
        # as the TPU branch — the schedule is seed-determined, so the
        # attainment/ghost rows diff cleanly across backends and rounds)
        extra["loadtest"] = _loadtest_bench(
            DecoderConfig.tiny(max_seq_len=256), page_size=16,
        )
        for key in ("loadtest_slo_attainment",
                    "loadtest_goodput_tokens_per_chip",
                    "ghost_hit_ratio_4x"):
            extra[key] = extra["loadtest"][key]
        extra["kv_tiering"] = _kv_tier_bench(
            DecoderConfig.tiny(max_seq_len=256), page_size=16,
            baseline=extra["loadtest"],
        )
        for key in ("session_resume_ttft_p50", "session_cold_ttft_p50",
                    "kv_restore_overlap_frac", "kv_tier_hit_ratio_hbm",
                    "kv_tier_hit_ratio_host", "kv_tier_hit_ratio_disk",
                    "kv_tier_hit_ratio_peer"):
            extra[key] = extra["kv_tiering"][key]
        # closed-loop autoscaling rows, CPU-sized (same actuation path
        # as the TPU branch; the reaction floor diffs across rounds)
        extra["autoscale"] = _autoscale_bench(
            DecoderConfig.tiny(max_seq_len=256), 32, page_size=16,
            num_slots=2, n_requests=6, max_new=8,
        )
        for key in ("autoscale_reaction_s", "fleet_capacity_tokens_per_s",
                    "fleet_headroom_frac"):
            extra[key] = extra["autoscale"].get(key)

    # observability economics rows (both branches, jax-free): incident
    # reconstruction wall + the exemplar zero-overhead witness — report
    # --diff grades both like any other perf row
    extra.update(_incident_bench())

    # static-audit regression rows (both branches; post-warmup pass)
    extra.update(_audit_rows())

    print(
        f"[bench] backend={jax.default_backend()} tokens/s={tok_s:,.0f} "
        f"step_time={step_ms * 1e3:.1f}ms extra={extra}",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "decoder_train_mfu",
                "platform": jax.default_backend(),
                "value": round(mfu * 100, 2) if mfu is not None else None,
                "unit": "percent_of_peak_bf16",
                "vs_baseline": round(mfu / 0.45, 3) if mfu is not None else None,
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    main()
