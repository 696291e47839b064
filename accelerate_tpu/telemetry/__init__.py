"""Unified runtime telemetry: per-step metrics, span tracing, and the
heartbeat/straggler watchdog.

The reference Accelerate exposes observability as disconnected pieces
(trackers, a profiler wrapper, prints). Here one session object ties the
runtime together and the engine feeds it automatically:

    from accelerate_tpu import Accelerator
    from accelerate_tpu.telemetry import TelemetryConfig

    accelerator = Accelerator(
        log_with="jsonl", project_dir="runs/exp",
        telemetry=TelemetryConfig(watchdog=True, watchdog_deadline_s=600),
    )
    ...
    accelerator.log_system_metrics(step=step)   # rollup -> every tracker

- **metrics pipeline** — every optimizer step (eager or fused
  ``build_train_step``) records wall time, tokens, data-loader wait, and
  XLA compile activity into a rolling window; ``rollup()`` adds MFU
  (the flops accounting of ``telemetry.metrics``),
  grad-norm/loss, fp16 loss-scale, fp8 amax health, device memory and the
  PowerSGD wire-bytes estimate. Flushes ride the existing
  ``GeneralTracker`` plumbing, so JSONL/TensorBoard/W&B get system
  metrics for free (main-process gating included).
- **span tracing** — ``telemetry.spans`` streams nestable spans as a
  Chrome-trace-compatible JSONL per host (``utils/phases.py`` now rides
  the same rails for the TTFT path).
- **watchdog** — ``telemetry.watchdog`` monitors a shared-dict heartbeat
  and dumps per-host stacks + the last spans when a step stalls.
- **request tracing** — ``telemetry.requests`` records every serving
  request's lifecycle (queue wait → prefill chunks → per-token ITL →
  finish) as spans + one JSONL record per request, feeding the
  **SLO histograms** (``telemetry.histograms``) whose TTFT/ITL/queue-wait
  p50/p95/p99 ride every rollup and the Prometheus exposition
  (``telemetry.exporter``, optional scrape thread).
- **flight recorder** — ``telemetry.recorder`` keeps a bounded ring of
  recent events and dumps a debug bundle (in-flight requests, spans,
  memory, stacks) on unhandled exception, watchdog trip, or SIGTERM;
  trigger-based ``jax.profiler`` capture windows ride the same module.
- **recompile forensics** — ``telemetry.forensics`` fingerprints the
  abstract signature of every registered jitted entry point per call and
  diffs it when the compile counters move, emitting the *cause* ("arg
  batch['input_ids'] changed i32[8,128] -> i32[8,136]") as a JSONL record
  plus a tagged span.
- **goodput ledger + cost registry** — ``telemetry.goodput`` partitions
  session wall into compute/compile/checkpoint/data-wait/stall/idle
  (fractions sum to 1.0 in every rollup); ``telemetry.costs`` captures
  ``cost_analysis``/``memory_analysis`` per executable at first compile
  and classifies each against the device roofline, attributing measured
  wall into per-fn model-MFU rows. ``accelerate-tpu report`` renders all
  three offline.
- **continuous ops plane** — ``telemetry.timeline`` samples every rollup
  gauge (plus histogram p50/p95/p99) on a background cadence into a
  bounded multi-resolution ring with windowed queries;
  ``telemetry.alerts`` evaluates threshold and multi-window SLO
  burn-rate rules against it (pending→firing→resolved, event log,
  ``alert_firing`` exposition, actions that dump a flight bundle or arm
  a capture window); ``telemetry.usage`` meters per-tenant tokens, HBM
  page-seconds, compute-ms and outcome counts. ``accelerate-tpu watch``
  renders all three live; ``report`` renders them offline.

Everything is off unless a config is passed (or ``ATT_TELEMETRY=1``);
when off, the engine's only cost is one ``is None`` check per step.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Optional

from .canary import CanaryProber  # noqa: F401 (public API)
from .histograms import StreamingHistogram, percentile_keys  # noqa: F401
from .metrics import MetricsWindow, batch_token_count, flops_per_token_fn
from .spans import SpanRecorder, load_chrome_trace, span  # noqa: F401 (public API)
from .watchdog import HeartbeatWatchdog, build_stall_report  # noqa: F401

_ACTIVE_SESSION: Optional["TelemetrySession"] = None


def current_session() -> Optional["TelemetrySession"]:
    return _ACTIVE_SESSION


def note_data_wait(seconds: float):
    """Hook for data loaders: attribute host time spent producing/placing a
    batch to the *next* step's record. Near-free when telemetry is off."""
    s = _ACTIVE_SESSION
    if s is not None:
        s.note_data_wait(seconds)


@dataclass
class TelemetryConfig:
    """Knobs for the runtime telemetry session (see docs/telemetry.md).

    ``trace_dir`` is where per-host artifacts land (span JSONL, watchdog
    dumps, optional per-step metrics JSONL). When None it falls back to
    ``<logging_dir>/telemetry`` if the Accelerator has a project dir,
    else file-producing features quietly stay off (the metrics window and
    watchdog still run).
    """

    enabled: bool = True
    window: int = 32                       # rolling window, in step records
    flush_every: int = 0                   # auto-flush to trackers every N steps (0 = manual)
    trace_dir: Optional[str] = None
    spans: bool = True                     # stream engine/user spans to JSONL
    span_ring: int = 64                    # newest spans of the in-memory ring a watchdog dump prints
    metrics_jsonl: bool = False            # per-step records to metrics-host<i>.jsonl
    metrics_path: Optional[str] = None     # exact per-step JSONL path (overrides)
    device_memory: bool = True
    flops_per_token: Optional[float] = None  # override the model-derived accounting
    watchdog: bool = False
    watchdog_deadline_s: float = 300.0
    watchdog_poll_s: Optional[float] = None
    heartbeat_dir: Optional[str] = None    # shared dir for cross-host straggler naming
    # request-level tracing + SLO histograms (serving; docs/serving.md)
    request_log: bool = True               # per-request JSONL records (needs trace_dir)
    token_span_every: int = 0              # per-token decode spans for 1-in-N requests (0 = off)
    itl_series_max: int = 512              # ITL samples kept per request record
    exporter_port: Optional[int] = None    # Prometheus scrape thread (0 = ephemeral port)
    # exemplar reservoirs on the SLO histograms: sampled request ids ride
    # the exposition and name culprits at alert firing edges (off = the
    # histograms observe values only — the zero-overhead witness baseline)
    exemplars: bool = True
    # JSONL artifact retention (telemetry/artifacts.py): every family's
    # writer rotates at artifact_max_bytes keeping artifact_generations
    # rotated files per family
    artifact_max_bytes: int = 64 * 1024 * 1024
    artifact_generations: int = 3
    # explanatory layer (docs/telemetry.md: goodput + roofline; the
    # forensics JSONL needs trace_dir, the in-memory diffing does not)
    forensics: bool = True             # recompile cause diffing + JSONL
    goodput: bool = True               # wall-clock goodput ledger
    cost_registry: bool = True         # per-executable roofline rows
    # the continuous ops plane (docs/telemetry.md: timeline / alerting /
    # per-tenant usage). Sampling runs on a background daemon thread at
    # timeline_interval_s; 0 disables the thread (call
    # session.sample_timeline() manually — what deterministic tests do).
    timeline: bool = True
    timeline_interval_s: float = 1.0
    timeline_tiers: Optional[tuple] = None  # ((interval_s, capacity), ...)
    alerts: bool = True                     # evaluate rules per sample
    alert_rules: Optional[list] = None      # default: alerts.default_ruleset()
    alert_itl_slo_ms: Optional[float] = None  # ITL burn-rate rule SLO
    usage: bool = True                      # per-tenant usage accounting
    # flight recorder (docs/troubleshooting.md)
    flight_recorder: bool = True
    flight_events: int = 256               # bounded event ring capacity
    flight_hooks: bool = True              # dump on sys.excepthook / SIGTERM
    # SIGTERM additionally requests a serving drain: attached engines stop
    # admitting, shed their queues, and the live loop finishes in-flight
    # requests — shutdown mid-burst leaves every request with a definite
    # outcome instead of abandoning the queue (docs/serving.md)
    drain_on_sigterm: bool = True
    # trigger-based jax.profiler capture windows (docs/profiling.md)
    profile_steps: Optional[tuple] = None  # (start, stop) step window
    profile_window_steps: int = 16         # auto-armed window length, in steps
    profile_trigger_itl_p99_ms: Optional[float] = None  # SLO breach auto-arm
    profile_dir: Optional[str] = None      # default: <trace_dir>/profile

    @classmethod
    def from_env(cls) -> Optional["TelemetryConfig"]:
        """ATT_TELEMETRY=1 enables defaults; ATT_TELEMETRY_DIR sets
        trace_dir; ATT_TELEMETRY_WATCHDOG_S enables the watchdog with that
        deadline; ATT_TELEMETRY_PORT starts the Prometheus scrape thread;
        ATT_TELEMETRY_PROFILE_STEPS="N:M" arms a capture window for steps
        N..M. Returns None when the env asks for nothing."""
        flag = os.environ.get("ATT_TELEMETRY", "").strip().lower()
        wd = os.environ.get("ATT_TELEMETRY_WATCHDOG_S", "").strip()
        if flag in ("", "0", "false") and not wd:
            return None
        cfg = cls()
        d = os.environ.get("ATT_TELEMETRY_DIR", "").strip()
        if d:
            cfg.trace_dir = d
        if wd:
            cfg.watchdog = True
            cfg.watchdog_deadline_s = float(wd)
        port = os.environ.get("ATT_TELEMETRY_PORT", "").strip()
        if port:
            try:
                cfg.exporter_port = int(port)
            except ValueError:
                import logging

                logging.getLogger(__name__).warning(
                    "ignoring malformed ATT_TELEMETRY_PORT=%r (expected an "
                    "integer port; 0 = ephemeral)", port,
                )
        win = os.environ.get("ATT_TELEMETRY_PROFILE_STEPS", "").strip()
        if win:
            lo, _, hi = win.partition(":")
            try:
                cfg.profile_steps = (int(lo), int(hi))
            except ValueError:
                import logging

                logging.getLogger(__name__).warning(
                    "ignoring malformed ATT_TELEMETRY_PROFILE_STEPS=%r "
                    "(expected N:M, e.g. 100:120)", win,
                )
        return cfg


def resolve_config(telemetry) -> Optional[TelemetryConfig]:
    """Accelerator-arg resolution: None -> env, True -> defaults, config
    passthrough (honoring .enabled), anything falsy -> off."""
    if telemetry is None:
        return TelemetryConfig.from_env()
    if telemetry is True:
        return TelemetryConfig()
    if isinstance(telemetry, TelemetryConfig):
        return telemetry if telemetry.enabled else None
    if not telemetry:
        return None
    raise TypeError(
        f"telemetry= expects a TelemetryConfig, True/False or None; got {telemetry!r}"
    )


class TelemetrySession:
    """One live telemetry pipeline: engines feed it, trackers drain it.

    Created by the Accelerator (``telemetry=`` / ``ATT_TELEMETRY``) and
    installed as the process-global session so decoupled producers (data
    loaders, ``note_data_wait``) reach it without plumbing.
    """

    def __init__(self, config: TelemetryConfig, accelerator=None):
        global _ACTIVE_SESSION
        if _ACTIVE_SESSION is not None:
            # a replaced session must not leak its watchdog thread / fds
            _ACTIVE_SESSION.close()
        self.config = config
        self._accelerator = accelerator
        self.process_index = self._process_index()
        self.trace_dir = self._resolve_trace_dir()
        self.window = MetricsWindow(config.window)
        self._engines: list = []
        self._serving: list = []
        self._data_wait = 0.0
        self._pend_tokens = 0
        self._pend_samples = 0
        self._pend_seq_len = None
        self._last_opt_t: Optional[float] = None
        self._last_beat = None
        self._last_hb_file_t = 0.0
        self._flops_fn = None
        self._wire_bytes: Optional[int] = None
        self._peak: Optional[float] = None
        self._peak_bw: Optional[float] = None
        self._closed = False

        self.recorder: Optional[SpanRecorder] = None
        if config.spans and self.trace_dir:
            from . import spans as _spans

            self.recorder = _spans.arm(
                os.path.join(self.trace_dir, f"trace-host{self.process_index}.jsonl"),
                self.process_index,
            )

        self._metrics_fh = None
        path = config.metrics_path
        if path is None and config.metrics_jsonl and self.trace_dir:
            path = os.path.join(
                self.trace_dir, f"metrics-host{self.process_index}.jsonl"
            )
        if path:
            self._metrics_fh = self.artifact_writer(path)

        from ..utils.compile_cache import compile_event_counters, install_compile_listeners

        install_compile_listeners()
        self._compile_mark = compile_event_counters()

        # the explanatory layer: goodput ledger, recompile forensics, and
        # the per-executable cost registry (docs/telemetry.md)
        self.goodput = None
        if config.goodput:
            from . import goodput as _goodput

            self.goodput = _goodput.arm(_goodput.GoodputLedger())
        self.forensics = None
        if config.forensics:
            from . import forensics as _forensics

            fpath = None
            if self.trace_dir:
                fpath = os.path.join(
                    self.trace_dir, f"forensics-host{self.process_index}.jsonl"
                )
            self.forensics = _forensics.arm(_forensics.ForensicsRecorder(
                fpath, self.process_index,
            ))
        self.costs = None
        if config.cost_registry:
            from .costs import CostRegistry

            self.costs = CostRegistry(
                peak_flops_fn=self.peak_flops, peak_bw_fn=self.peak_hbm_bw,
            )

        # SLO histograms + the request tracer (serving engines feed both)
        self.hists: dict = {}
        from .requests import RequestTracer

        req_path = None
        if config.request_log and self.trace_dir:
            req_path = os.path.join(
                self.trace_dir, f"requests-host{self.process_index}.jsonl"
            )
        self.requests = RequestTracer(
            self, req_path, itl_series_max=config.itl_series_max,
            token_span_every=config.token_span_every,
        )

        self.flight = None
        if config.flight_recorder:
            from .recorder import FlightRecorder

            self.flight = FlightRecorder(
                self, dump_dir=self.trace_dir, capacity=config.flight_events,
                process_index=self.process_index,
                drain_serving=config.drain_on_sigterm,
            )
            if config.flight_hooks:
                self.flight.install_hooks()

        self.capture = None
        if config.profile_steps or config.profile_trigger_itl_p99_ms is not None:
            pdir = config.profile_dir or (
                os.path.join(self.trace_dir, "profile") if self.trace_dir else None
            )
            if pdir:
                from .recorder import CaptureWindow

                start, stop = config.profile_steps or (None, None)
                self.capture = CaptureWindow(
                    pdir, start_step=start, stop_step=stop,
                    window_steps=config.profile_window_steps,
                )

        # the continuous ops plane: per-tenant usage meters, the sampled
        # timeline, and the alert rules evaluated on its cadence — built
        # after flight/capture (alert actions reach both) and before the
        # exporter (which renders the alert_firing series)
        self.usage = None
        if config.usage:
            from .usage import UsageAccountant

            self.usage = UsageAccountant()
        # freshness clock for the exposition's att_scrape_age_seconds:
        # advanced by every sample_timeline() tick, so a fleet collector
        # can tell a frozen sampler from a frozen replica. None until the
        # first sample (and forever on a timeline-less session): exporting
        # an age no sampler will ever advance would read as a permanently
        # degrading replica
        self.last_sample_unix_s = None
        self.timeline = None
        self.alerts = None
        self._sampler = None
        if config.timeline:
            from .timeline import Timeline, TimelineSampler

            self.timeline = Timeline(tiers=config.timeline_tiers)
            if config.alerts:
                from . import alerts as _alerts

                rules = config.alert_rules
                if rules is None:
                    slo = (
                        config.alert_itl_slo_ms
                        if config.alert_itl_slo_ms is not None
                        else config.profile_trigger_itl_p99_ms
                    )
                    rules = _alerts.default_ruleset(itl_slo_ms=slo)
                apath = None
                if self.trace_dir:
                    apath = os.path.join(
                        self.trace_dir, f"alerts-host{self.process_index}.jsonl"
                    )
                self.alerts = _alerts.AlertManager(
                    self.timeline, rules, session=self, log_path=apath,
                    exemplar_source=self._alert_exemplars,
                )
            if config.timeline_interval_s and config.timeline_interval_s > 0:
                self._sampler = TimelineSampler(
                    self.sample_timeline, config.timeline_interval_s
                ).start()

        self.exporter = None
        if config.exporter_port is not None:
            from .exporter import ScrapeServer

            self.exporter = ScrapeServer(self, port=config.exporter_port)

        self.watchdog: Optional[HeartbeatWatchdog] = None
        if config.watchdog:
            self.watchdog = HeartbeatWatchdog(
                deadline_s=config.watchdog_deadline_s,
                poll_s=config.watchdog_poll_s,
                heartbeat_dir=config.heartbeat_dir,
                dump_dir=self.trace_dir,
                last_spans=config.span_ring,
                on_stall=self._on_stall,
            ).start()

        _ACTIVE_SESSION = self

    # -- setup helpers -----------------------------------------------------

    @staticmethod
    def _process_index() -> int:
        from ..state import PartialState

        return int(PartialState._shared_state.get("process_index", 0))

    def _resolve_trace_dir(self) -> Optional[str]:
        d = self.config.trace_dir
        if d is None and self._accelerator is not None:
            logging_dir = getattr(self._accelerator, "logging_dir", None)
            if logging_dir:
                d = os.path.join(str(logging_dir), "telemetry")
        if d:
            os.makedirs(d, exist_ok=True)
        return d

    def attach_engine(self, engine):
        """Wire a TrainEngine: step hooks + the static accounting (FLOPs/token
        from the model config, PowerSGD/dtype wire bytes from the sharding
        config) that a rollup reports without touching the device."""
        engine.telemetry = self
        self._engines.append(engine)
        if self.config.flops_per_token:
            fpt = float(self.config.flops_per_token)
            self._flops_fn = lambda seq_len: fpt
        elif self._flops_fn is None:
            cfg = getattr(engine.model.definition, "config", None)
            if cfg is not None:
                self._flops_fn = flops_per_token_fn(cfg)
        sc = engine.sharding_config
        if (
            (getattr(sc, "grad_compression_dtype", None)
             or getattr(sc, "grad_compression_rank", None))
            and engine.mesh is not None
            and engine.mesh.shape.get("replica", 1) > 1
        ):
            try:
                self._wire_bytes = int(engine.replica_wire_bytes(
                    engine.params,
                    getattr(sc, "grad_compression_dtype", None),
                    getattr(sc, "grad_compression_rank", None),
                )["bytes"])
            except Exception:
                self._wire_bytes = None

    def attach_serving(self, engine):
        """Wire a serving engine (serving/engine.py): its ``serving/``
        gauges — tokens/s, queue depth, slot occupancy, inter-token latency
        percentiles, admission recompiles — join every rollup/flush, and
        its decode steps feed the rolling window via ``on_step`` like a
        train engine's do. Held by WEAK reference: a dropped engine (and
        its multi-hundred-MB cache arena) must not be pinned for the
        session's lifetime."""
        import weakref

        if not any(ref() is engine for ref in self._serving):
            self._serving.append(weakref.ref(engine))

    def histogram(self, name: str) -> StreamingHistogram:
        """Get-or-create the named SLO histogram (e.g. ``serving/ttft``;
        values in seconds). Percentiles join every rollup as
        ``{name}_p50_ms``/``_p95_ms``/``_p99_ms`` and the Prometheus
        exposition as a native histogram."""
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = StreamingHistogram()
            h.exemplars_enabled = bool(self.config.exemplars)
        return h

    def artifact_writer(self, path: str):
        """A bounded-rotation JSONL appender for ``path`` honoring the
        session's retention config — the one append path every artifact
        family (metrics, requests, alerts, decisions) shares."""
        from .artifacts import ArtifactWriter

        return ArtifactWriter(
            path,
            max_bytes=self.config.artifact_max_bytes,
            max_generations=self.config.artifact_generations,
        )

    def _alert_exemplars(self, key: str) -> list:
        """Exemplar request descriptors for the histogram backing an
        alert-rule key — stamped onto firing-edge alert events so the
        event log names culprit requests, not just a breached number."""
        from .alerts import exemplars_for_key

        return exemplars_for_key(self.hists, key)

    def _on_stall(self, report: str):
        """Watchdog trip: dump a flight-recorder bundle and (when a
        profiler window is configured) arm a capture for the next steps."""
        if self.goodput is not None and self.watchdog is not None:
            age = getattr(self.watchdog, "last_stall_age_s", None)
            if age:
                self.goodput.note_stall(age)
        if self.flight is not None:
            self.flight.note("watchdog_stall")
            self.flight.dump("watchdog_stall", extra={"stall_report": report})
        if self.capture is not None:
            self.capture.arm("watchdog_stall")

    def sample_timeline(self, now: Optional[float] = None) -> dict:
        """One timeline tick: bring the usage integrals current, fold a
        device-free rollup (every gauge + histogram percentiles) into the
        timeline, and run one alert-evaluation pass. The background
        sampler calls this every ``timeline_interval_s``; with the thread
        off (interval 0) call it manually — ``now`` overrides the sample
        timestamp, which is what deterministic tests use."""
        tl = self.timeline
        if tl is None:
            return {}
        values = self.host_rollup()
        t = tl.add_sample(values, now=now)
        # wall clock, not `now`: deterministic tests drive `now` with a
        # fake clock, but the exposition's staleness gauge answers "when
        # did this session last actually sample" in real time
        self.last_sample_unix_s = time.time()
        if self.usage is not None:
            self.usage.mark()
        if self.alerts is not None:
            self.alerts.evaluate(now=t)
        return values

    def request_drain_serving(self):
        """Ask every attached serving engine to drain (flag-only: stop
        admitting, shed the queue; the loop already driving the engine
        finishes the in-flight requests). Called from the flight
        recorder's SIGTERM hook — pure host bookkeeping, safe from a
        signal handler."""
        for ref in list(self._serving):
            engine = ref()
            if engine is None:
                continue
            try:
                engine.request_drain()
            except Exception:
                pass

    def executable_memory(self) -> dict:
        """Live-executable ``memory_analysis`` from every attached serving
        engine (flight-recorder bundle section); {} when none exposes it.
        Cached-only: this runs on the watchdog thread against a possibly
        wedged backend, so it must never trigger a compile."""
        out = {}
        for ref in list(self._serving):
            engine = ref()
            if engine is None:
                continue
            try:
                stats = engine.executable_memory_stats(cached_only=True)
            except Exception:
                continue
            if stats:
                out[f"serving_engine_{len(out)}"] = stats
        return out

    # -- producers ---------------------------------------------------------

    def note_data_wait(self, seconds: float):
        self._data_wait += float(seconds)

    def note_batch(self, args, kwargs, argnames: tuple = ()):
        """Eager path: count the tokens of one model call (micro-steps
        accumulate until the optimizer-step boundary drains them).
        ``argnames`` is the model's positional parameter order, so
        ``model(input_ids, labels)`` counts the same as the kwargs form."""
        named = {argnames[i]: a for i, a in enumerate(args) if i < len(argnames)}
        named.update(kwargs)
        batch = named if named else (args[0] if len(args) == 1 else args)
        tokens, samples, seq_len = batch_token_count(batch)
        if tokens:
            self._pend_tokens += tokens
        if samples:
            self._pend_samples += samples
        if seq_len:
            self._pend_seq_len = seq_len

    def on_optimizer_step(self, engine):
        """Eager-loop boundary: wall time = time since the previous boundary
        (covers data + forward + update — the throughput-relevant number).
        The first boundary only starts the clock."""
        now = time.perf_counter()
        wall = None if self._last_opt_t is None else now - self._last_opt_t
        self._last_opt_t = now
        tokens, self._pend_tokens = self._pend_tokens, 0
        samples, self._pend_samples = self._pend_samples, 0
        seq_len, self._pend_seq_len = self._pend_seq_len, None
        if wall is None:
            self._heartbeat(engine.step_count)
            return
        loss = engine._pending_loss
        self.on_step(engine, wall, tokens=tokens or None, samples=samples or None,
                     seq_len=seq_len, metrics={"loss": loss} if loss is not None else None,
                     exe="train_fwd_bwd")

    def on_step(self, engine, wall_s: float, tokens=None, samples=None,
                seq_len=None, steps: int = 1, metrics: Optional[dict] = None,
                exe: Optional[str] = None):
        """Record one completed step (or one fused K-step dispatch).
        ``exe`` names the executable that ran (``train_step``,
        ``decode_step``, ...) so the cost registry can attribute the wall
        to its roofline row."""
        step = engine.step_count
        data_wait, self._data_wait = self._data_wait, 0.0
        comp = self._drain_compile()
        if self.goodput is not None:
            self.goodput.on_step(wall_s, compile_s=comp.get("compile_s") or 0.0,
                                 data_wait_s=data_wait)
        if self.costs is not None and exe:
            # one dispatch of the named executable — NOT `steps`: a fused
            # K-step program's flops_per_call already covers the K steps,
            # so billing K calls would inflate its model MFU K-fold
            self.costs.note_wall(exe, wall_s)
        rec = {
            "step": step,
            "wall_s": float(wall_s),
            "steps": int(steps),
            "data_wait_s": data_wait,
            "tokens": tokens,
            "samples": samples,
            "seq_len": seq_len,
            **comp,
        }
        if tokens and seq_len and self._flops_fn is not None:
            rec["flops"] = tokens * self._flops_fn(seq_len)
        if metrics:
            # device scalars stay lazy until a flush resolves them — a
            # device_get here would serialize the async dispatch pipeline
            rec["_loss"] = metrics.get("loss")
            rec["_grad_norm"] = metrics.get("grad_norm")
        self.window.add(rec)
        self._heartbeat(step)
        if self.recorder is not None:
            from . import spans as _spans

            _spans.emit("engine/train_step", time.perf_counter() - wall_s, wall_s,
                        {"step": step, "steps": steps}, cat="engine")
        if self._metrics_fh is not None:
            self._write_step_record(rec)
        if self.flight is not None:
            self.flight.note("step", step=step, steps=steps,
                             wall_ms=round(wall_s * 1e3, 2), tokens=tokens)
        if self.capture is not None:
            thr = self.config.profile_trigger_itl_p99_ms
            if thr is not None and not self.capture.active:
                itl = self.hists.get("serving/itl")
                # a few samples must accrue before a p99 means anything
                if itl is not None and itl.count >= 16:
                    p99 = itl.quantile(0.99)
                    if p99 is not None and p99 * 1e3 > thr:
                        self.capture.arm("itl_p99_slo")
            self.capture.on_step(step)
        fe = self.config.flush_every
        if fe and len(self.window.records) and self.window.total_steps % fe == 0:
            self.flush(step=step)

    def _heartbeat(self, step: int):
        from ..state import PartialState

        # session-local beat: a serving-only process never constructs
        # PartialState, and the watchdog must still see progress there
        self._last_beat = (int(step), time.monotonic())
        if PartialState._shared_state:
            PartialState().publish_heartbeat(step)
        if self.config.heartbeat_dir:
            now = time.monotonic()
            if now - self._last_hb_file_t >= 1.0:
                self._last_hb_file_t = now
                try:
                    from .watchdog import publish_heartbeat_file

                    publish_heartbeat_file(
                        self.config.heartbeat_dir, self.process_index, step
                    )
                except OSError:
                    pass

    def _drain_compile(self) -> dict:
        from ..utils.compile_cache import compile_event_counters

        now = compile_event_counters()
        mark, self._compile_mark = self._compile_mark, now
        return {
            "compile_events": now["count"] - mark["count"],
            "compile_s": now["seconds"] - mark["seconds"],
            "compile_cache_hits": now["cache_hits"] - mark["cache_hits"],
        }

    # -- consumers ---------------------------------------------------------

    def _resolve(self, value):
        if value is None:
            return None
        try:
            import jax

            return float(jax.device_get(value))
        except Exception:
            try:
                return float(value)
            except (TypeError, ValueError):
                return None

    def _write_step_record(self, rec: dict):
        import json

        if self._metrics_fh is None or self._metrics_fh.closed:
            return
        out = {k: v for k, v in rec.items() if not k.startswith("_") and v is not None}
        out["time_unix_s"] = round(time.time(), 3)
        if rec.get("tokens") and rec.get("wall_s"):
            out["tokens_per_s"] = rec["tokens"] / rec["wall_s"]
        if rec.get("flops") and rec.get("wall_s") and self.peak_flops():
            out["mfu_pct"] = 100.0 * rec["flops"] / rec["wall_s"] / self.peak_flops()
        loss = self._resolve(rec.get("_loss"))
        if loss is not None:
            out["loss"] = loss
        gn = self._resolve(rec.get("_grad_norm"))
        if gn is not None:
            out["grad_norm"] = gn
        self._metrics_fh.write_line(json.dumps(out))

    def peak_flops(self) -> Optional[float]:
        """Peak bf16 FLOP/s of device 0; None for an unknown device kind
        (no MFU is reported then)."""
        if self._peak is None:
            import jax

            from .metrics import peak_flops

            self._peak = peak_flops(jax.devices()[0])
        return self._peak

    def peak_hbm_bw(self) -> Optional[float]:
        """Peak HBM bandwidth of device 0 (the roofline ridge's
        denominator); None for an unknown device kind."""
        if self._peak_bw is None:
            import jax

            from .costs import peak_hbm_bw

            self._peak_bw = peak_hbm_bw(jax.devices()[0])
        return self._peak_bw

    def rollup(self) -> dict:
        """Aggregate the rolling window plus the engine-state gauges into
        one flat dict of scalars (the ``log_system_metrics`` payload)."""
        out = self.window.rollup(peak=self.peak_flops())
        last = self.window.last()
        if last is not None:
            out["sys/step"] = last["step"]
            loss = self._resolve(last.get("_loss"))
            if loss is not None:
                out["sys/loss"] = loss
            gn = self._resolve(last.get("_grad_norm"))
            if gn is not None:
                out["sys/grad_norm"] = gn
        for engine in self._engines:
            if engine.scale_state is not None:
                scale = self._resolve(engine.scale_state.get("scale"))
                if scale is not None:
                    out["sys/loss_scale"] = scale
                out["sys/last_step_skipped"] = bool(engine.last_step_skipped())
            extra = engine.extra_state
            if isinstance(extra, dict) and "fp8_stats" in extra:
                from .metrics import fp8_amax_health

                out.update(fp8_amax_health(extra["fp8_stats"]))
        # lifetime SLO histograms first, then the serving-engine gauges:
        # where the keys overlap (serving/itl_p50/_p95_ms) the engine's
        # RECENT-window view must win, or a fresh latency regression would
        # be diluted by hours of healthy lifetime traffic; the histograms
        # keep exclusive ownership of _p99/_count/_mean/_max and the
        # ttft/queue_wait families
        for name, hist in list(self.hists.items()):
            out.update(percentile_keys(name, hist))
        self._serving = [ref for ref in self._serving if ref() is not None]
        for ref in self._serving:
            engine = ref()
            if engine is None:
                continue
            try:
                out.update(engine.metrics())
            except Exception:  # a dying engine must not take the flush down
                pass
        if self._wire_bytes is not None:
            out["sys/replica_wire_bytes_per_step"] = self._wire_bytes
        if self.goodput is not None:
            out.update(self.goodput.rollup_keys())
        if self.costs is not None:
            out.update(self.costs.rollup_keys())
        if self.forensics is not None:
            # no flush here: rollup() also runs on the Prometheus scrape
            # thread, and finalizing the producer's pending event from
            # there would stamp it with a partial compile delta. A pending
            # event counts once its own thread (or close()) finalizes it.
            out["sys/recompiles_diagnosed"] = len(self.forensics.recompiles())
        if self.usage is not None:
            out.update(self.usage.rollup_keys())
        if self.alerts is not None:
            out.update(self.alerts.rollup_keys())
        if self.config.device_memory:
            from .metrics import device_memory_stats

            out.update(device_memory_stats())
        return out

    def host_rollup(self) -> dict:
        """``rollup()`` minus every device interaction: no ``device_get``
        of pending loss/grad scalars, no peak-flops probe, no memory
        query. This is what the flight recorder snapshots from the
        watchdog thread — a full rollup would block forever on the very
        wedged backend the dump is diagnosing."""
        out = self.window.rollup(peak=self._peak)
        last = self.window.last()
        if last is not None:
            out["sys/step"] = last["step"]
        for name, hist in list(self.hists.items()):
            out.update(percentile_keys(name, hist))
        self._serving = [ref for ref in self._serving if ref() is not None]
        for ref in self._serving:
            engine = ref()
            if engine is None:
                continue
            try:
                out.update(engine.metrics())  # host-side deque/counter math
            except Exception:
                pass
        if self.goodput is not None:
            out.update(self.goodput.rollup_keys())
        if self.costs is not None:
            # probe=False: resolving the peak tables touches jax.devices(),
            # and this path runs on the watchdog thread against a possibly
            # wedged backend — use only already-resolved peaks
            out.update(self.costs.rollup_keys(probe=False))
        if self.forensics is not None:
            out["sys/recompiles_diagnosed"] = len(self.forensics.recompiles())
        if self.usage is not None:
            out.update(self.usage.rollup_keys())
        if self.alerts is not None:
            out.update(self.alerts.rollup_keys())
        return out

    def flush(self, step: Optional[int] = None) -> dict:
        """Rollup + push through the Accelerator's trackers (main-process
        gating happens inside each tracker, so calling this everywhere is
        safe). Returns the values."""
        values = self.rollup()
        if not values:
            return values
        acc = self._accelerator
        if acc is not None and getattr(acc, "trackers", None):
            if step is None:
                step = values.get("sys/step")
            acc.log(values, step=step)
        if self.flight is not None:
            self.flight.note_snapshot(values)
        self._write_artifacts()
        return values

    def _write_artifacts(self):
        """Refresh the offline snapshots ``accelerate-tpu report`` reads
        (cost registry + goodput ledger; forensics streams its own JSONL)."""
        if not self.trace_dir:
            return
        try:
            if self.costs is not None:
                self.costs.write_snapshot(os.path.join(
                    self.trace_dir, f"costs-host{self.process_index}.json"))
            if self.goodput is not None:
                self.goodput.write_snapshot(os.path.join(
                    self.trace_dir, f"goodput-host{self.process_index}.json"))
            if self.timeline is not None:
                self.timeline.flush_jsonl(os.path.join(
                    self.trace_dir,
                    f"timeline-host{self.process_index}.jsonl"))
            if self.usage is not None:
                self.usage.write_snapshot(os.path.join(
                    self.trace_dir, f"usage-host{self.process_index}.json"))
        except OSError:
            pass

    def close(self):
        global _ACTIVE_SESSION
        if self._closed:
            return
        self._closed = True
        for engine in self._engines:
            if getattr(engine, "telemetry", None) is self:
                engine.telemetry = None
        for ref in self._serving:
            engine = ref()
            if engine is not None and getattr(engine, "telemetry", None) is self:
                engine.telemetry = None  # a live server must not feed a closed session
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._sampler is not None:
            self._sampler.stop()
        if self.timeline is not None and self.timeline.sample_count == 0:
            # a session shorter than the sampling interval still leaves
            # one sample behind, so report/watch never see an empty file
            try:
                self.sample_timeline()
            except Exception:
                pass
        if self.capture is not None:
            self.capture.close()
        if self.exporter is not None:
            self.exporter.close()
        if self.flight is not None:
            self.flight.uninstall_hooks()
        self._write_artifacts()
        if self.alerts is not None:
            self.alerts.close()
        if self.forensics is not None:
            from . import forensics as _forensics

            if _forensics.recorder() is self.forensics:
                _forensics.disarm()
            else:
                self.forensics.close()
        if self.goodput is not None:
            from . import goodput as _goodput

            if _goodput.ledger() is self.goodput:
                _goodput.disarm()
        self.requests.close()
        if self.recorder is not None:
            from . import spans as _spans

            if _spans.recorder() is self.recorder:
                _spans.disarm()
            else:
                self.recorder.close()
        if self._metrics_fh is not None:
            self._metrics_fh.close()
        if _ACTIVE_SESSION is self:
            _ACTIVE_SESSION = None
