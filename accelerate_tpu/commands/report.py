"""`accelerate-tpu report` — the doctor's read of a telemetry dir.

`trace` answers "show me the timeline"; `report` answers "where did the
time go and why". It merges everything a session leaves behind —

    goodput-host<i>.json     wall-clock partition (the goodput ledger)
    costs-host<i>.json       per-executable roofline rows (cost registry)
    forensics-host<i>.jsonl  diagnosed recompiles with their causes
    metrics-host<i>.jsonl    per-step records (optional)
    requests-host<i>.jsonl   serving request log (optional)

    timeline-host<i>.jsonl   continuous gauge timeline (sampled rollups)
    alerts-host<i>.jsonl     alert lifecycle events (pending/firing/resolved)
    usage-host<i>.json       per-tenant usage accounting
    router-requests*.jsonl   router request log (waterfall's router half)
    canary-results.jsonl     synthetic canary probe outcomes
    audit.json               static-audit findings (`accelerate-tpu audit --out`)
    loadtest-scorecard.json  SLO scorecard (`accelerate-tpu loadtest --out`)

— into one explanation:

    accelerate-tpu report runs/exp/telemetry
    accelerate-tpu report runs/exp/telemetry --json

The text form prints the goodput breakdown (fractions sum to 1.0), the
top executables by measured wall with their roofline class and cost-model
MFU / bandwidth utilization, every recompile with the exact argument and
aval change that caused it, the timeline's headline series, the alert
history, and the per-tenant usage table. Pure stdlib + the telemetry
host modules: no jax import, so it runs anywhere the artifacts land.

``--diff A B`` is the regression sentry: it flattens two runs' metrics
(telemetry dirs, dirs holding ``BENCH_r*.json``, or bench JSON files
directly) and flags every shared metric that moved more than
``--threshold`` — turning the bench trajectory into a checkable
artifact (``--fail`` exits non-zero when anything is flagged).
"""

from __future__ import annotations

import glob
import json
import os
import sys

BAR_WIDTH = 24


def _host_files(target: str, pattern: str) -> list:
    if os.path.isdir(target):
        return sorted(glob.glob(os.path.join(target, pattern)))
    return []


def _host_of(path: str, prefix: str) -> str:
    name = os.path.basename(path)
    stem = name.split(".", 1)[0]
    return stem[len(prefix):] if stem.startswith(prefix) else "?"


def _load_json(path: str):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _load_jsonl(path: str) -> list:
    out = []
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    out.append(json.loads(line))
    except (OSError, ValueError):
        pass
    return out


def load_goodput(target: str) -> dict:
    """Merged goodput: per-host snapshots plus an aggregate over summed
    bucket seconds (an idle host dilutes fleet goodput — that is the
    point of fleet accounting)."""
    from ..telemetry.goodput import BUCKETS

    hosts = {}
    for path in _host_files(target, "goodput-host*.json"):
        data = _load_json(path)
        if data:
            hosts[_host_of(path, "goodput-host")] = data
    if not hosts:
        return {}
    seconds = {b: 0.0 for b in BUCKETS}
    elapsed = 0.0
    for data in hosts.values():
        elapsed += data.get("elapsed_s") or 0.0
        for b in BUCKETS:
            seconds[b] += (data.get("seconds") or {}).get(b) or 0.0
    total = sum(seconds.values())
    fractions = {b: (seconds[b] / total if total > 0 else 0.0) for b in BUCKETS}
    return {"hosts": hosts, "seconds": seconds, "fractions": fractions,
            "elapsed_s": elapsed}


def load_costs(target: str) -> dict:
    """Merged cost registry: rows keyed by executable name, wall/calls
    summed across hosts, static cost fields from the first host that
    captured them."""
    merged: dict = {}
    peaks = {}
    for path in _host_files(target, "costs-host*.json"):
        data = _load_json(path)
        if not data:
            continue
        for key in ("peak_flops", "peak_hbm_bw", "ridge_intensity"):
            if data.get(key) and key not in peaks:
                peaks[key] = data[key]
        for row in data.get("executables") or []:
            name = row.get("name")
            if name is None:
                continue
            cur = merged.get(name)
            if cur is None:
                merged[name] = dict(row)
            else:
                cur["wall_s"] = round(cur.get("wall_s", 0.0) + (row.get("wall_s") or 0.0), 4)
                cur["calls"] = cur.get("calls", 0) + (row.get("calls") or 0)
                for k, v in row.items():
                    cur.setdefault(k, v)
    rows = sorted(merged.values(), key=lambda r: -(r.get("wall_s") or 0.0))
    # re-derive the utilization numbers over the merged wall
    pf, pb = peaks.get("peak_flops"), peaks.get("peak_hbm_bw")
    for row in rows:
        wall, calls = row.get("wall_s") or 0.0, row.get("calls") or 0
        if wall > 0 and calls > 0:
            if row.get("flops_per_call") and pf:
                row["mfu_model_pct"] = round(
                    100.0 * row["flops_per_call"] * calls / wall / pf, 3)
            if row.get("hbm_bytes_per_call"):
                row["hbm_gbps"] = round(
                    row["hbm_bytes_per_call"] * calls / wall / 1e9, 3)
                if pb:
                    row["bw_util_pct"] = round(
                        100.0 * row["hbm_bytes_per_call"] * calls / wall / pb, 3)
    return {**peaks, "executables": rows}


def load_forensics(target: str) -> list:
    """Every forensics record (host-tagged, oldest first)."""
    out = []
    for path in _host_files(target, "forensics-host*.jsonl"):
        host = _host_of(path, "forensics-host")
        for rec in _load_jsonl(path):
            rec.setdefault("host", host)
            out.append(rec)
    out.sort(key=lambda r: r.get("time_unix_s", 0))
    return out


def load_steps(target: str) -> dict:
    """Aggregate of the per-step metrics JSONL (when the run wrote one)."""
    walls, tokens, compiles = [], 0, 0
    for path in _host_files(target, "metrics-host*.jsonl"):
        for rec in _load_jsonl(path):
            if rec.get("wall_s"):
                walls.append(float(rec["wall_s"]) / max(int(rec.get("steps", 1)), 1))
            tokens += rec.get("tokens") or 0
            compiles += rec.get("compile_events") or 0
    if not walls:
        return {}
    walls.sort()
    return {
        "steps": len(walls),
        "step_time_p50_s": round(walls[len(walls) // 2], 4),
        "step_time_max_s": round(walls[-1], 4),
        "tokens": tokens,
        "compile_events": compiles,
    }


# the series the text report (and `watch`) treat as headliners — shown
# first when present; every other sampled key stays in --json
NOTABLE_TIMELINE_KEYS = (
    "serving/tokens_per_s", "serving/itl_recent_p99_ms",
    "serving/ttft_p99_ms", "serving/queue_depth", "serving/slot_occupancy",
    "serving/pages_in_use", "serving/shed", "goodput/goodput_frac",
    "serving/capacity_tokens_per_s", "serving/headroom_frac",
    "sys/tokens_per_s", "sys/mfu_pct", "alerts/firing_count",
)


def load_timeline_summary(target: str) -> dict:
    """Full-span stats per sampled gauge out of ``timeline-host*.jsonl``
    (merged across hosts): {samples, span_s, keys: {key: {last, mean,
    min, max, n}}}."""
    if not _host_files(target, "timeline-host*.jsonl"):
        return {}
    from ..telemetry.timeline import load_timeline

    tl = load_timeline(target)
    if tl.sample_count == 0 or tl.last_t is None:
        return {}
    keys = {}
    span = 0.0
    for key in tl.keys():
        w = tl.window(key, float("inf"), now=tl.last_t)
        if not w:
            continue
        span = max(span, w["span_s"])
        keys[key] = {
            "last": round(w["last"], 4),
            "mean": round(w["mean"], 4) if w["mean"] is not None else None,
            "min": round(w["min"], 4),
            "max": round(w["max"], 4),
            "n": w["n"],
        }
    return {"samples": tl.sample_count, "span_s": round(span, 1), "keys": keys}


def load_alert_summary(target: str) -> dict:
    """Alert history out of ``alerts-host*.jsonl`` (and the fleet
    collector's ``alerts-fleet.jsonl``): per-rule final state +
    fired/resolved counts, plus the raw event list."""
    if not (_host_files(target, "alerts-host*.jsonl")
            or _host_files(target, "alerts-fleet.jsonl")):
        return {}
    from ..telemetry.alerts import load_alerts

    return load_alerts(target)


def load_usage_table(target: str) -> dict:
    if not _host_files(target, "usage-host*.json"):
        return {}
    from ..telemetry.usage import load_usage

    return load_usage(target)


def load_fleet_summary(target: str) -> dict:
    """Fleet-collector artifacts (``fleet.json`` snapshot +
    ``fleet-events.jsonl`` health transitions) under the telemetry dir —
    present when a :class:`~..telemetry.fleet.FleetCollector` ran with
    ``log_dir`` pointed here."""
    if not (_host_files(target, "fleet.json")
            or _host_files(target, "fleet-events.jsonl")):
        return {}
    from ..telemetry.fleet import load_fleet

    return load_fleet(target)


def load_waterfall_summary(target: str) -> dict:
    """Per-stage TTFT decomposition aggregate — present when a
    ``Router(log_dir=...)`` left ``router-requests*.jsonl`` here
    (replica request logs join in when they share the dir)."""
    if not _host_files(target, "router-requests*.jsonl"):
        return {}
    from ..telemetry.waterfall import summarize_waterfall
    from .trace import build_waterfall_rows

    rows = build_waterfall_rows(target)
    return summarize_waterfall(rows) if rows else {}


def load_canary_summary(target: str) -> dict:
    """Canary probe outcomes out of ``canary-results.jsonl``: totals,
    recent pass ratio, and the replicas that served failing probes."""
    if not _host_files(target, "canary-results.jsonl"):
        return {}
    from ..telemetry.canary import load_canary

    results = load_canary(target)
    if not results:
        return {}
    failed = [r for r in results if not r.get("passed")]
    by_replica: dict = {}
    for r in failed:
        # a probe that never reached a replica (router down, submit_fn
        # error) has no attribution — say so, don't render "None"
        name = r.get("replica") or "(unattributed)"
        by_replica[str(name)] = by_replica.get(str(name), 0) + 1
    recent = results[-32:]
    return {
        "probes": len(results),
        "passed": sum(1 for r in results if r.get("passed")),
        "failed": len(failed),
        "pass_ratio": round(
            sum(1 for r in recent if r.get("passed")) / len(recent), 4
        ),
        "failing_replicas": by_replica,
        "last_failure": failed[-1] if failed else None,
    }


def load_audit(target: str) -> dict:
    """The static-audit snapshot (``audit.json`` written by
    ``accelerate-tpu audit --out DIR``): active findings, baselined
    suppressions, and the severity summary."""
    for path in _host_files(target, "audit.json"):
        data = _load_json(path)
        if isinstance(data, dict):
            return data
    return {}


def load_autoscale_summary(target: str) -> dict:
    """Autoscaler decision history out of ``autoscale-decisions.jsonl``:
    counts by action and outcome, reaction times (burn-rule firing →
    first verified token on the new replica), the scale-in conservation
    verdicts, and the recent decisions with their stage decomposition."""
    if not _host_files(target, "autoscale-decisions.jsonl"):
        return {}
    from ..serving.autoscaler import load_autoscale_decisions

    records = load_autoscale_decisions(target)
    if not records:
        return {}
    actions: dict = {}
    outcomes: dict = {}
    for r in records:
        act = str(r.get("action"))
        actions[act] = actions.get(act, 0) + 1
        out = r.get("outcome")
        if out:
            outcomes[str(out)] = outcomes.get(str(out), 0) + 1
    reactions = [r["autoscale_reaction_s"] for r in records
                 if isinstance(r.get("autoscale_reaction_s"), (int, float))]
    not_conserved = sum(
        1 for r in records
        if (r.get("ledger") or {}).get("conserved") is False
    )
    return {
        "decisions": len(records),
        "actions": actions,
        "outcomes": outcomes,
        "reaction_s_last": round(reactions[-1], 4) if reactions else None,
        "reaction_s_max": round(max(reactions), 4) if reactions else None,
        "scale_ins_not_conserved": not_conserved,
        "recent": records[-8:],
    }


def load_incident_summary(target: str) -> dict:
    """Reconstructed incidents out of the alert logs: counts, still-open
    tally, mean resolved duration, and a one-line digest per incident —
    the teaser the full ``accelerate-tpu incident show`` expands."""
    if not (_host_files(target, "alerts-host*.jsonl")
            or _host_files(target, "alerts-fleet.jsonl")):
        return {}
    from ..telemetry.incidents import reconstruct_incidents, summarize_incidents

    incidents = reconstruct_incidents(target)
    if not incidents:
        return {}
    out = summarize_incidents(incidents)
    out["recent"] = [
        {
            "index": i["index"], "rule": i["rule"], "state": i["state"],
            "fired_t": i["fired_t"], "duration_s": i["duration_s"],
            "exemplars": i["exemplars"][:3],
            "top_stages": sorted(set(
                r["top_stage"] for r in i.get("exemplar_requests") or []
                if r.get("top_stage")
            )),
            "events": len(i.get("events") or []),
        }
        for i in incidents[-8:]
    ]
    return out


def load_loadtest_scorecard(target: str) -> dict:
    """The SLO scorecard (``loadtest-scorecard.json`` written by
    ``accelerate-tpu loadtest --out DIR``): attainment per tenant and
    fleet-wide, goodput tokens/s-per-chip, the conservation ledger."""
    if not _host_files(target, "loadtest-scorecard.json"):
        return {}
    from ..telemetry.scorecard import load_scorecard

    return load_scorecard(target) or {}


def load_report(target: str) -> dict:
    forensics = load_forensics(target)
    data = {
        "target": target,
        "goodput": load_goodput(target),
        "costs": load_costs(target),
        "recompiles": [r for r in forensics if r.get("event") == "recompile"],
        "first_compiles": [r for r in forensics
                           if r.get("event") == "first_compile"],
        "steps": load_steps(target),
        "timeline": load_timeline_summary(target),
        "alerts": load_alert_summary(target),
        "usage": load_usage_table(target),
        "fleet": load_fleet_summary(target),
        "waterfall": load_waterfall_summary(target),
        "canary": load_canary_summary(target),
        "autoscale": load_autoscale_summary(target),
        "incidents": load_incident_summary(target),
        "audit": load_audit(target),
        "loadtest": load_loadtest_scorecard(target),
    }
    req_files = _host_files(target, "requests-host*.jsonl")
    if req_files:
        from .trace import load_requests, summarize_requests

        data["requests"] = summarize_requests(load_requests(target))
    return data


def _bar(frac: float) -> str:
    n = int(round(max(0.0, min(frac, 1.0)) * BAR_WIDTH))
    return "#" * n + "." * (BAR_WIDTH - n)


def render_table(rows, indent: str = "  ") -> list:
    """Column-aligned text lines for a [header, *rows] tuple list (the
    one table renderer every section — and `watch` — shares)."""
    rows = [tuple(str(c) for c in r) for r in rows]
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    return [indent + "  ".join(c.ljust(w) for c, w in zip(r, widths))
            for r in rows]


def format_report(data: dict) -> str:
    lines = [f"== accelerate-tpu report: {data.get('target', '?')} =="]

    gp = data.get("goodput") or {}
    if gp:
        fr = gp["fractions"]
        lines.append("")
        lines.append(
            f"goodput breakdown ({len(gp.get('hosts') or {})} host(s), "
            f"{gp.get('elapsed_s', 0):.1f}s wall; fractions sum to "
            f"{sum(fr.values()):.2f}):"
        )
        order = ("compute", "compile", "checkpoint", "data_wait", "stall", "idle")
        for b in order:
            f = fr.get(b, 0.0)
            secs = (gp.get("seconds") or {}).get(b, 0.0)
            lines.append(f"  {b:<10} {100 * f:6.1f}%  {_bar(f)}  {secs:9.2f}s")
        lines.append(f"  goodput (productive compute) = {100 * fr.get('compute', 0.0):.1f}%")
    else:
        lines.append("")
        lines.append("goodput breakdown: no goodput-host*.json found "
                     "(run with telemetry enabled)")

    costs = data.get("costs") or {}
    rows = costs.get("executables") or []
    lines.append("")
    if rows:
        ridge = costs.get("ridge_intensity")
        ridge_txt = f"{ridge:.1f}" if isinstance(ridge, (int, float)) else "?"
        lines.append("top executables by measured wall (roofline vs "
                     f"ridge {ridge_txt} flops/byte):")
        header = ("executable", "wall_s", "calls", "class", "AI",
                  "MFU(model)", "BW util", "GB/s")
        table = [header]
        for row in rows[:10]:
            mfu = row.get("mfu_model_pct")
            bw = row.get("bw_util_pct")
            gbps = row.get("hbm_gbps")
            table.append((
                str(row.get("name")),
                f"{row.get('wall_s', 0.0):.3f}" if row.get("wall_s") is not None else "",
                str(row.get("calls", "")),
                row.get("roofline", "?"),
                f"{row['arith_intensity']:.2f}" if row.get("arith_intensity") is not None else "",
                f"{mfu:.2f}%" if mfu is not None else "",
                f"{bw:.2f}%" if bw is not None else "",
                f"{gbps:.1f}" if gbps is not None else "",
            ))
        lines.extend(render_table(table))
    else:
        lines.append("executables: no costs-host*.json found")

    recs = data.get("recompiles") or []
    firsts = data.get("first_compiles") or []
    lines.append("")
    lines.append(f"recompiles ({len(recs)} diagnosed, "
                 f"{len(firsts)} first compiles):")
    for rec in recs:
        t = rec.get("time_unix_s")
        comp = rec.get("compile_s")
        hits = rec.get("compile_cache_hits") or 0
        suffix = []
        if comp is not None:
            suffix.append(f"compile {comp:.2f}s")
        suffix.append(f"{rec.get('compile_events', '?')} events")
        if hits:
            suffix.append(f"{hits} cache hits")
        stamp = f"[host {rec.get('host', '?')}" + (
            f" @{t:.0f}] " if isinstance(t, (int, float)) else "] ")
        lines.append(f"  {stamp}{rec.get('cause')}  ({', '.join(suffix)})")
    if not recs:
        lines.append("  none — every entry point held its steady-state signature")

    steps = data.get("steps") or {}
    if steps:
        lines.append("")
        lines.append(
            f"steps: {steps['steps']} recorded, p50 {steps['step_time_p50_s']}s, "
            f"max {steps['step_time_max_s']}s, {steps['tokens']} tokens, "
            f"{steps['compile_events']} compile events"
        )
    req = data.get("requests") or {}
    if req.get("requests"):
        lines.append(
            f"serving: {req.get('requests')} requests, {req.get('tokens')} tokens"
            + (f", ttft p50/p99 = {req.get('ttft_p50_ms')}/{req.get('ttft_p99_ms')} ms"
               if req.get("ttft_p50_ms") is not None else "")
        )

    tl = data.get("timeline") or {}
    if tl.get("samples"):
        lines.append("")
        lines.append(
            f"timeline: {tl['samples']} samples over {tl.get('span_s', 0)}s "
            "(timeline-host*.jsonl)"
        )
        keys = tl.get("keys") or {}
        shown = [k for k in NOTABLE_TIMELINE_KEYS if k in keys]
        for key in shown:
            s = keys[key]
            lines.append(
                f"  {key:<32} last {s['last']:>10}  mean {s['mean']:>10}  "
                f"max {s['max']:>10}"
            )
        rest = len(keys) - len(shown)
        if rest > 0:
            lines.append(f"  (+{rest} more sampled series in --json)")

    alerts = data.get("alerts") or {}
    rules = alerts.get("rules") or {}
    if rules:
        firing = sorted(n for n, r in rules.items() if r.get("state") == "firing")
        fired_total = sum(r.get("fired_count", 0) for r in rules.values())
        lines.append("")
        lines.append(
            f"alerts: {len(firing)} firing, {fired_total} fired over the "
            f"session ({len(alerts.get('events') or [])} lifecycle events)"
        )
        for name in sorted(rules, key=lambda n: (rules[n].get("state") != "firing", n)):
            r = rules[name]
            lines.append(
                f"  [{r.get('state', '?'):>7}] {name}  fired {r.get('fired_count', 0)}x"
                + (f", last value {r.get('last_value')}"
                   if r.get("last_value") is not None else "")
            )

    fleet = data.get("fleet") or {}
    replicas = fleet.get("replicas") or {}
    if replicas:
        gauges = fleet.get("fleet") or {}
        down = gauges.get("fleet/replicas_down", 0)
        lines.append("")
        lines.append(
            f"fleet: {len(replicas)} replica(s), "
            f"{gauges.get('fleet/replicas_placeable', '?')} placeable, "
            f"{down} down ({fleet.get('polls', '?')} polls)"
        )
        header = ("replica", "state", "load_score", "scrapes_ok",
                  "scrapes_failed", "last_ok_age_s")
        table = [header]
        placement = fleet.get("placement") or []
        order = [p["replica"] for p in placement if p["replica"] in replicas]
        order += [n for n in sorted(replicas) if n not in order]
        for name in order:
            r = replicas[name]
            score = r.get("load_score")
            table.append((
                name, r.get("state", "?"),
                f"{score:.3f}" if isinstance(score, float) else str(score),
                str(r.get("scrapes_ok", "")), str(r.get("scrapes_failed", "")),
                str(r.get("last_ok_age_s", "")),
            ))
        lines.extend(render_table(table))
        events = fleet.get("events") or []
        if events:
            lines.append(f"  health transitions ({len(events)}):")
            for evt in events[-8:]:
                lines.append(
                    f"    @{evt.get('t_unix_s', 0):.0f} {evt.get('replica')}: "
                    f"{evt.get('from')} -> {evt.get('to')} ({evt.get('reason')})"
                )

    wf = data.get("waterfall") or {}
    if wf.get("requests"):
        from ..telemetry.waterfall import stage_table

        lines.append("")
        lines.append(
            f"request waterfall ({wf['requests']} request(s), "
            f"{wf.get('joined', 0)} joined with replica records"
            + (f"; e2e TTFT p50/p99 = {wf['e2e_ttft_p50_ms']}/"
               f"{wf['e2e_ttft_p99_ms']} ms"
               if wf.get("e2e_ttft_p99_ms") is not None else "")
            + "):"
        )
        lines.extend(render_table(stage_table(wf)))

    canary = data.get("canary") or {}
    if canary.get("probes"):
        lines.append("")
        lines.append(
            f"canary: {canary['probes']} probe(s), {canary['failed']} "
            f"failed, recent pass ratio {canary['pass_ratio']}"
        )
        for name, n in sorted((canary.get("failing_replicas") or {}).items(),
                              key=lambda kv: -kv[1]):
            lines.append(f"  failing probes served by {name}: {n}")
        last = canary.get("last_failure")
        if last:
            lines.append(
                f"  last failure: {last.get('request_id')} on "
                f"{last.get('replica')} ({last.get('reason', '?')})"
            )

    a = data.get("autoscale") or {}
    if a.get("decisions"):
        acts = a.get("actions") or {}
        lines.append("")
        lines.append(
            f"autoscale: {a['decisions']} decision(s) — "
            f"{acts.get('scale_out', 0)} out, {acts.get('scale_in', 0)} in, "
            f"{acts.get('hold', 0)} held"
            + (f"; reaction last/max = {a['reaction_s_last']}/"
               f"{a['reaction_s_max']} s"
               if a.get("reaction_s_last") is not None else "")
        )
        if a.get("scale_ins_not_conserved"):
            lines.append(
                f"  [NOT CONSERVED] {a['scale_ins_not_conserved']} "
                "scale-in(s) lost requests across the membership change"
            )
        for rec in (a.get("recent") or [])[-6:]:
            stages = rec.get("stages") or {}
            stage_txt = " ".join(
                f"{k.replace('_s', '')}={v:.2f}s" for k, v in stages.items()
                if isinstance(v, (int, float))
            )
            lines.append(
                f"  @{rec.get('t_unix_s', 0):.0f} {rec.get('action')}"
                + (f" {rec.get('replica')}" if rec.get("replica") else "")
                + f" [{rec.get('outcome') or rec.get('reason', '?')}]"
                + f" ({rec.get('reason', '?')})"
                + (f"  {stage_txt}" if stage_txt else "")
            )

    inc = data.get("incidents") or {}
    if inc.get("count"):
        dur = (f', mean duration {inc["mean_duration_s"]:.1f}s'
               if inc.get("mean_duration_s") is not None else "")
        lines.append("")
        lines.append(
            f'incidents: {inc["count"]} reconstructed, {inc["open"]} open'
            f'{dur} (`accelerate-tpu incident show <dir>` for the timeline)'
        )
        for row in inc.get("recent") or []:
            ex = ",".join(str(r) for r in row.get("exemplars") or []) or "-"
            top = "/".join(row.get("top_stages") or []) or "?"
            d = (f'{row["duration_s"]:.1f}s'
                 if row.get("duration_s") is not None else "open")
            lines.append(
                f'  #{row["index"]} {row["rule"]} [{row["state"]}] '
                f'dur={d} events={row.get("events", 0)} '
                f'exemplars={ex} dominant={top}'
            )

    card = data.get("loadtest") or {}
    if card:
        from ..telemetry.scorecard import format_scorecard

        lines.append("")
        lines.append("loadtest scorecard:")
        lines.extend("  " + ln for ln in format_scorecard(card))

    usage = data.get("usage") or {}
    tenants = usage.get("tenants") or {}
    if tenants:
        lines.append("")
        lines.append(f"tenant usage ({len(tenants)} tenant(s), "
                     f"{usage.get('hosts', 1)} host(s)):")
        cols = ("prefill_tokens", "decode_tokens", "prefix_hit_tokens",
                "page_seconds", "compute_ms", "finished", "shed",
                "preempted", "cancelled")
        header = ("tenant",) + tuple(c.replace("_tokens", "_tok") for c in cols)
        table = [header]
        order = sorted(tenants, key=lambda t: -(tenants[t].get("decode_tokens") or 0))
        for name in order:
            row = tenants[name]
            table.append((name,) + tuple(
                f"{row.get(c, 0):.1f}" if isinstance(row.get(c), float)
                else str(row.get(c, 0)) for c in cols
            ))
        lines.extend(render_table(table))

    audit = data.get("audit") or {}
    if audit:
        summ = audit.get("summary") or {}
        # severity-major before truncating: a P1 must never hide behind
        # twelve P2s in discovery order
        sev_rank = {"P1": 0, "P2": 1, "P3": 2}
        active = sorted(
            audit.get("findings") or [],
            key=lambda f: (sev_rank.get(f.get("severity"), 9),
                           str(f.get("target")), str(f.get("check"))),
        )
        suppressed = audit.get("suppressed") or []
        lines.append("")
        lines.append(
            f"static audit: {summ.get('findings_total', len(active))} active "
            f"finding(s) ({summ.get('findings_p1', 0)} P1), "
            f"{len(suppressed)} baselined"
        )
        for f in active[:12]:
            lines.append(
                f"  [{f.get('severity', '?')}] {f.get('check')}  "
                f"{f.get('target')}  ({f.get('fingerprint', '?')})"
            )
            lines.append(f"       {f.get('message', '')}")
        if len(active) > 12:
            lines.append(f"  (+{len(active) - 12} more in --json)")
        for f in suppressed[:6]:
            lines.append(
                f"  [baselined {f.get('severity', '?')}] {f.get('check')}  "
                f"{f.get('target')}: {f.get('justification', '?')}"
            )
    return "\n".join(lines)


# -- the regression sentry (`report --diff A B`) ----------------------------


def _flatten_numeric(obj, prefix: str, out: dict):
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten_numeric(v, f"{prefix}.{k}" if prefix else str(k), out)
    elif isinstance(obj, bool):
        return
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)


def _bench_metrics(path: str) -> dict:
    """Flat metrics from one BENCH_r*.json (the driver's shape: headline
    `parsed.metric/value` plus the `parsed.extra` tree) or any plain
    metric-tree JSON."""
    data = _load_json(path)
    if not isinstance(data, dict):
        return {}
    out: dict = {}
    parsed = data.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        if isinstance(parsed.get("value"), (int, float)):
            out[str(parsed["metric"])] = float(parsed["value"])
        _flatten_numeric(parsed.get("extra") or {}, "", out)
    else:
        _flatten_numeric(data, "", out)
    # per-attempt lists and wall-clock stamps are noise, not metrics
    return {k: v for k, v in out.items()
            if not k.endswith(("_attempts", "time_unix_s"))}


def collect_diff_metrics(target: str) -> dict:
    """One side of a diff, flattened to {metric: float}: a bench JSON
    file, a dir holding ``BENCH_r*.json`` (newest wins), or a telemetry
    artifact dir (goodput fractions, roofline rows, request/step
    summaries, timeline means, usage totals)."""
    if os.path.isfile(target):
        return _bench_metrics(target)
    bench = sorted(glob.glob(os.path.join(target, "BENCH_r*.json")))
    if bench:
        return _bench_metrics(bench[-1])
    data = load_report(target)
    out: dict = {}
    for b, f in (data["goodput"].get("fractions") or {}).items():
        out[f"goodput/{b}_frac"] = float(f)
    for row in data["costs"].get("executables") or []:
        name = row.get("name")
        for field in ("mfu_model_pct", "bw_util_pct", "hbm_gbps", "arith_intensity"):
            if isinstance(row.get(field), (int, float)):
                out[f"exe/{name}/{field}"] = float(row[field])
    _flatten_numeric(data.get("steps") or {}, "steps", out)
    _flatten_numeric(data.get("requests") or {}, "requests", out)
    for key, s in ((data.get("timeline") or {}).get("keys") or {}).items():
        if isinstance(s.get("mean"), (int, float)):
            out[f"timeline/{key}/mean"] = float(s["mean"])
    for tenant, row in ((data.get("usage") or {}).get("tenants") or {}).items():
        _flatten_numeric(row, f"usage/{tenant}", out)
    # the edge regression signals: per-stage waterfall percentiles (a p99
    # that moved names its stage) and the canary pass ratio (any drop is
    # a correctness regression — diff_metrics flags it past-threshold-or-not)
    wf = data.get("waterfall") or {}
    for stage, row in (wf.get("stages") or {}).items():
        for field in ("p50_ms", "p99_ms"):
            if isinstance(row.get(field), (int, float)):
                out[f"waterfall/{stage}/{field}"] = float(row[field])
    if isinstance(wf.get("e2e_ttft_p99_ms"), (int, float)):
        out["router_e2e_ttft_p99_ms"] = float(wf["e2e_ttft_p99_ms"])
    # which prefill path served the joined requests: a round where
    # `waterfall/prefill_kernel_dense` grows at `_ragged`'s expense is a
    # kernel-gate regression even if the p99 hasn't moved yet. (Bench-side
    # `prefill_kernel_speedup` / `prefill_pad_waste_frac` need no code
    # here — `_flatten_numeric` lifts every numeric in the bench extras.)
    for mode, count in (wf.get("prefill_kernel") or {}).items():
        if isinstance(count, (int, float)) and not isinstance(count, bool):
            out[f"waterfall/prefill_kernel_{mode}"] = float(count)
    canary = data.get("canary") or {}
    if isinstance(canary.get("pass_ratio"), (int, float)):
        out["canary_pass_ratio"] = float(canary["pass_ratio"])
    # the closed-loop signals: scale action counts and the reaction time
    # (burn firing -> first verified token on the new replica) — a round
    # where reaction_s grew names the actuation path, and any scale-in
    # that broke conservation is a correctness regression outright
    autoscale = data.get("autoscale") or {}
    if autoscale:
        acts = autoscale.get("actions") or {}
        out["autoscale/scale_outs"] = float(acts.get("scale_out", 0))
        out["autoscale/scale_ins"] = float(acts.get("scale_in", 0))
        for field in ("reaction_s_last", "reaction_s_max",
                      "scale_ins_not_conserved"):
            if isinstance(autoscale.get(field), (int, float)):
                out[f"autoscale/{field}"] = float(autoscale[field])
    # the replay-plane regression signals: fleet attainment/goodput plus
    # per-tenant attainment — a tenant whose SLO slipped between rounds
    # names itself even when the fleet number holds (mix shift)
    card = data.get("loadtest") or {}
    if card:
        fleet = (card.get("fleet") or {})
        for field in ("slo_attainment_frac", "goodput_tokens_per_s",
                      "goodput_tokens_per_chip_s", "ttft_p99_ms",
                      "itl_p99_ms"):
            if isinstance(fleet.get(field), (int, float)):
                out[f"loadtest/{field}"] = float(fleet[field])
        for name, row in (card.get("tenants") or {}).items():
            for field in ("slo_attainment_frac", "goodput_tokens_per_s"):
                if isinstance(row.get(field), (int, float)):
                    out[f"loadtest/{name}/{field}"] = float(row[field])
        # KV-tiering restore rows (only present when the joined server
        # records saw restores): a restore-latency regression between
        # rounds names the tier plumbing, not the model
        for field in ("kv_restores", "kv_restore_ms_p50"):
            if isinstance(card.get(field), (int, float)):
                out[f"loadtest/{field}"] = float(card[field])
    # incident totals diff like any metric: a round with more incidents
    # (or ones that stay open longer) regressed operationally even when
    # every latency percentile held
    inc = data.get("incidents") or {}
    if inc:
        out["incident/count"] = float(inc.get("count", 0))
        out["incident/open"] = float(inc.get("open", 0))
        if isinstance(inc.get("mean_duration_s"), (int, float)):
            out["incident/mean_duration_s"] = float(inc["mean_duration_s"])
    out["recompiles_diagnosed"] = float(len(data.get("recompiles") or []))
    audit = data.get("audit") or {}
    if audit:
        # audit findings are a regression signal: the counts diff like any
        # metric, and each active P1 additionally travels as its own
        # fingerprint key so a NEW P1 between two runs is flagged even
        # when the count happens to stay level (one fixed, one introduced)
        summ = audit.get("summary") or {}
        out["audit/findings_total"] = float(summ.get("findings_total", 0))
        out["audit/findings_p1"] = float(summ.get("findings_p1", 0))
        for f in audit.get("findings") or []:
            if f.get("severity") == "P1" and f.get("fingerprint"):
                out[f"audit/p1/{f['fingerprint']}"] = 1.0
    return out


# metrics where ANY drop — not just a past-threshold move — is a
# regression: a canary pass ratio below its baseline means the service
# returned wrong tokens, and correctness has no noise budget
_DROP_SENTINEL_MARKERS = ("canary_pass_ratio", "canary/pass_ratio")


def _is_sentinel_drop(key: str, va: float, vb: float,
                      min_abs: float) -> bool:
    return any(m in key for m in _DROP_SENTINEL_MARKERS) and vb < va - min_abs


def diff_metrics(a: dict, b: dict, threshold: float = 0.1,
                 min_abs: float = 1e-9) -> dict:
    """Shared-metric comparison: relative change per metric, the ones
    past ``threshold`` flagged (sorted, biggest mover first). Sentinel
    metrics (canary pass ratio) flag on any decrease."""
    shared = sorted(set(a) & set(b))
    rows = []
    for key in shared:
        va, vb = a[key], b[key]
        if abs(va - vb) <= min_abs:
            rel = 0.0
        elif abs(va) <= min_abs:
            # moved off zero: no finite relative change exists — flag it
            # as `from_zero` with rel_change None (json.dumps(inf) would
            # emit the non-spec `Infinity` token and break --json consumers)
            rel = None
        else:
            rel = (vb - va) / abs(va)
        rows.append({"metric": key, "a": va, "b": vb,
                     "rel_change": round(rel, 4) if rel is not None else None,
                     "from_zero": rel is None,
                     "sentinel": _is_sentinel_drop(key, va, vb, min_abs)})
    # a P1 audit finding that exists only in B is NEW regression evidence
    # even though unshared keys normally stay out of the flag list (the
    # count metrics can stay level when one P1 is fixed and another lands)
    for key in sorted(set(b) - set(a)):
        if key.startswith("audit/p1/"):
            rows.append({"metric": key, "a": 0.0, "b": b[key],
                         "rel_change": None, "from_zero": True,
                         "sentinel": False})
    flagged = [r for r in rows
               if r["from_zero"] or r["sentinel"]
               or abs(r["rel_change"]) > threshold]
    flagged.sort(key=lambda r: -(float("inf") if (r["from_zero"] or r["sentinel"])
                                 else abs(r["rel_change"])))
    return {
        "shared_metrics": len(shared),
        "only_a": sorted(set(a) - set(b)),
        "only_b": sorted(set(b) - set(a)),
        "threshold": threshold,
        "flagged": flagged,
        "rows": rows,
    }


def format_diff(diff: dict, a_name: str, b_name: str) -> str:
    lines = [f"== accelerate-tpu report --diff: {a_name} vs {b_name} =="]
    lines.append(
        f"{diff['shared_metrics']} shared metrics, threshold "
        f"{100 * diff['threshold']:.0f}% — {len(diff['flagged'])} flagged"
    )
    if diff["flagged"]:
        table = [("metric", "A", "B", "change")]
        for r in diff["flagged"][:40]:
            rel = r["rel_change"]
            change = "from zero" if r["from_zero"] else f"{100 * rel:+.1f}%"
            if r.get("sentinel"):
                change += " (correctness sentinel)"
            table.append((
                r["metric"], f"{r['a']:.4g}", f"{r['b']:.4g}", change,
            ))
        lines.extend(render_table(table))
    else:
        lines.append("  no shared metric moved past the threshold")
    if diff["only_a"] or diff["only_b"]:
        lines.append(
            f"  (unshared: {len(diff['only_a'])} only in A, "
            f"{len(diff['only_b'])} only in B)"
        )
    return "\n".join(lines)


def report_command(args) -> int:
    if args.diff:
        a_path, b_path = args.diff
        a, b = collect_diff_metrics(a_path), collect_diff_metrics(b_path)
        if not a or not b:
            missing = a_path if not a else b_path
            print(f"report --diff: no metrics found under {missing} — "
                  "expected BENCH_r*.json or telemetry artifacts",
                  file=sys.stderr)
            return 1
        diff = diff_metrics(a, b, threshold=args.threshold)
        if args.json:
            print(json.dumps(diff))
        else:
            print(format_diff(diff, a_path, b_path))
        return 1 if (args.fail and diff["flagged"]) else 0
    if not args.target:
        print("report: pass a telemetry dir (or --diff A B)", file=sys.stderr)
        return 1
    data = load_report(args.target)
    if not (data["goodput"] or data["costs"].get("executables")
            or data["recompiles"] or data["first_compiles"] or data["steps"]
            or data["timeline"] or data["usage"] or data["alerts"]
            or data["fleet"] or data["waterfall"] or data["canary"]
            or data["incidents"] or data["audit"] or data["loadtest"]):
        print(f"no telemetry artifacts found under {args.target} — expected "
              "goodput-host*.json / costs-host*.json / forensics-host*.jsonl "
              "/ fleet.json / audit.json (see docs/telemetry.md)", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(data))
    else:
        print(format_report(data))
    return 0


def register(subparsers):
    parser = subparsers.add_parser(
        "report",
        help="Explain a telemetry dir: goodput breakdown, per-executable "
             "roofline rows, diagnosed recompiles, timeline/alerts/usage "
             "(--diff A B = regression sentry)",
    )
    parser.add_argument("target", nargs="?", default=None,
                        help="telemetry dir (goodput/costs/forensics/"
                             "timeline/alerts/usage artifacts)")
    parser.add_argument("--json", action="store_true", help="machine-readable output")
    parser.add_argument("--diff", nargs=2, metavar=("A", "B"), default=None,
                        help="diff two runs (telemetry dirs, bench dirs, or "
                             "BENCH_r*.json files); flags moved metrics")
    parser.add_argument("--threshold", type=float, default=0.1,
                        help="relative change that flags a metric (default 0.10)")
    parser.add_argument("--fail", action="store_true",
                        help="exit 1 when --diff flags any metric (CI sentry)")
    parser.set_defaults(func=report_command)
    return parser
