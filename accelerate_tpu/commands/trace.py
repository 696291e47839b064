"""`accelerate-tpu trace` — inspect the telemetry dir's serving artifacts.

A multi-host run leaves one Chrome-trace span JSONL and one request-log
JSONL per host in its telemetry dir; this command turns them back into
answers without a notebook:

    accelerate-tpu trace merge runs/exp/telemetry -o merged.json
    accelerate-tpu trace merge runs/exp/telemetry --request-id 42
    accelerate-tpu trace summary runs/exp/telemetry
    accelerate-tpu trace summary runs/exp/telemetry --request-id 42 --json
    accelerate-tpu trace summary runs/exp/telemetry --waterfall

``merge`` folds every host's span stream into ONE Perfetto-loadable
Chrome trace (hosts stay separate rows via their pid; per-host clock
epochs are aligned through the ``epoch_unix_s`` metadata each recorder
writes), optionally filtered to the spans of a single request.
``summary`` renders the request-log JSONL as a latency table — one row
per request plus aggregate TTFT/ITL/queue-wait percentiles from the same
log-bucketed histograms the live session uses — or, with
``--request-id``, the full lifecycle of one request (prefill chunk plan,
ITL series, compile activity). ``summary --waterfall`` joins the
router's own request log (``router-requests*.jsonl``) with the replica
request logs and decomposes each request's client-observed TTFT into
router-queue → placement → retry-backoff → transport → replica-queue →
prefill stages that sum to the total (``telemetry/waterfall.py``;
docs/serving.md "Reading the request waterfall"). Pure stdlib + the
telemetry host modules: no jax import, so it runs anywhere the log
files land.
"""

from __future__ import annotations

import glob
import json
import os
import sys


def _span_files(target: str) -> list:
    if os.path.isdir(target):
        return sorted(glob.glob(os.path.join(target, "trace-host*.jsonl")))
    return [target]


def _request_files(target) -> list:
    """Request-log files for one target or a list of targets (each a
    telemetry dir or one ``requests-host*.jsonl``) — N replicas each own
    a telemetry dir, and stitching needs all of them at once."""
    targets = [target] if isinstance(target, str) else list(target)
    out = []
    for t in targets:
        if os.path.isdir(t):
            out.extend(sorted(glob.glob(os.path.join(t, "requests-host*.jsonl"))))
        else:
            out.append(t)
    return out


def _same_id(a, b) -> bool:
    """Request-id equality across int/str sources (the CLI arg is a
    string; engine-assigned ids are ints, router-supplied ids may be
    either)."""
    return a == b or str(a) == str(b)


def merge_traces(target: str, request_id=None) -> dict:
    """Merge per-host span JSONLs into one ``{"traceEvents": [...]}``.

    Each recorder rebases its ``ts`` clock to its own start; the
    ``process_name`` metadata line carries ``epoch_unix_s``, so hosts are
    shifted onto the earliest host's axis before merging. With
    ``request_id``, only that request's spans (events whose args carry the
    id) plus the metadata rows survive."""
    per_host = []
    for path in _span_files(target):
        events = []
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
        epoch = None
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                epoch = (e.get("args") or {}).get("epoch_unix_s")
                break
        per_host.append((epoch, events))
    if not per_host:
        return {"traceEvents": []}
    epochs = [ep for ep, _ in per_host if ep is not None]
    base = min(epochs) if epochs else None
    merged = []
    for epoch, events in per_host:
        shift_us = (epoch - base) * 1e6 if (epoch is not None and base is not None) else 0.0
        for e in events:
            if e.get("ph") == "M":
                merged.append(e)
                continue
            if request_id is not None:
                if not _same_id((e.get("args") or {}).get("request_id"),
                                request_id):
                    continue
            if shift_us and "ts" in e:
                e = dict(e, ts=round(e["ts"] + shift_us, 3))
            merged.append(e)
    merged.sort(key=lambda e: (e.get("ph") == "M" and -1) or e.get("ts", 0))
    return {"traceEvents": merged}


def load_requests(target) -> list:
    """Every request record in the dir(s)/file(s), tagged with its source
    host (``target`` may be a list of telemetry dirs — one per replica)."""
    out = []
    for path in _request_files(target):
        name = os.path.basename(path)
        host = name[len("requests-host"):-len(".jsonl")] if name.startswith("requests-host") else "?"
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    rec = json.loads(line)
                    rec.setdefault("host", host)
                    out.append(rec)
    out.sort(key=lambda r: (r.get("submit_unix_s", 0), r.get("request_id", 0)))
    return out


def summarize_requests(records: list) -> dict:
    """Aggregate latency stats over request records — the same
    ``StreamingHistogram`` percentiles the live session reports."""
    from ..telemetry.histograms import StreamingHistogram

    hists = {"queue_wait_ms": StreamingHistogram(), "ttft_ms": StreamingHistogram(),
             "total_ms": StreamingHistogram(), "itl_ms": StreamingHistogram()}
    tokens = 0
    reasons: dict = {}
    outcomes: dict = {}
    preemptions = 0
    prefix_hits = prefix_tokens = prompt_tokens = 0
    pages = 0
    for rec in records:
        for key in ("queue_wait_ms", "ttft_ms", "total_ms"):
            v = rec.get(key)
            if isinstance(v, (int, float)):
                hists[key].add(v / 1e3)
        for v in rec.get("itl_ms") or []:
            hists["itl_ms"].add(v / 1e3)
        tokens += rec.get("tokens") or 0
        reason = rec.get("finish_reason", "?")
        reasons[reason] = reasons.get(reason, 0) + 1
        outcome = rec.get("outcome")
        if outcome:
            outcomes[outcome] = outcomes.get(outcome, 0) + 1
        preemptions += rec.get("preemptions") or 0
        hit = rec.get("prefix_hit") or 0
        prefix_hits += 1 if hit else 0
        prefix_tokens += hit
        prompt_tokens += rec.get("prompt_len") or 0
        pages += rec.get("pages_allocated") or 0
    agg = {"requests": len(records), "tokens": tokens, "finish_reasons": reasons}
    if outcomes:
        # the definite-outcome contract: every submitted request landed as
        # finished / shed / cancelled (an "evicted" here means a request
        # was abandoned at close — the thing drain() exists to prevent)
        agg["outcomes"] = outcomes
    if preemptions:
        agg["preemptions"] = preemptions
    if prefix_tokens or pages:
        # paged-arena attribution: which share of requests (and of prompt
        # tokens) the prefix cache served
        agg["prefix_hit_requests"] = prefix_hits
        agg["prefix_hit_ratio"] = round(prefix_hits / len(records), 4) if records else 0.0
        if prompt_tokens:
            agg["prefix_hit_token_frac"] = round(prefix_tokens / prompt_tokens, 4)
        agg["pages_allocated"] = pages
    for key, hist in hists.items():
        snap = hist.snapshot()
        if snap:
            agg[f"{key[:-3]}_p50_ms"] = round(snap["p50_s"] * 1e3, 3)
            agg[f"{key[:-3]}_p95_ms"] = round(snap["p95_s"] * 1e3, 3)
            agg[f"{key[:-3]}_p99_ms"] = round(snap["p99_s"] * 1e3, 3)
    return agg


def stitch_request(records: list) -> dict:
    """Merge one logical request's records — one per replica hop — into
    a hop-by-hop timeline. A router re-queuing a request (replica died,
    preemptive re-placement) submits the SAME external ``request_id`` to
    each replica; each replica's log holds its own hop. Hops order by
    submit time; ``gap_ms`` is the hand-off latency between one hop's
    finish and the next hop's submit (the router's re-queue cost)."""
    hops = sorted(records, key=lambda r: r.get("submit_unix_s", 0))
    out_hops = []
    prev_finish = None
    for i, rec in enumerate(hops):
        hop = {
            "hop": i,
            "replica": rec.get("replica") or rec.get("host", "?"),
            "submit_unix_s": rec.get("submit_unix_s"),
            "queue_wait_ms": rec.get("queue_wait_ms"),
            "ttft_ms": rec.get("ttft_ms"),
            "tokens": rec.get("tokens", 0),
            "total_ms": rec.get("total_ms"),
            "outcome": rec.get("outcome"),
            "finish_reason": rec.get("finish_reason"),
            "preemptions": rec.get("preemptions", 0),
        }
        submit = rec.get("submit_unix_s")
        if prev_finish is not None and submit is not None:
            hop["gap_ms"] = round((submit - prev_finish) * 1e3, 3)
        prev_finish = rec.get("finish_unix_s")
        out_hops.append(hop)
    first = hops[0].get("submit_unix_s")
    last = hops[-1].get("finish_unix_s")
    return {
        "request_id": hops[0].get("request_id"),
        "hops": out_hops,
        "hop_count": len(out_hops),
        "tokens": sum(h["tokens"] or 0 for h in out_hops),
        "end_to_end_ms": (
            round((last - first) * 1e3, 3)
            if first is not None and last is not None else None
        ),
        "outcome": out_hops[-1].get("outcome"),
    }


def _format_stitched(stitched: dict) -> str:
    from .report import render_table  # the one shared table renderer

    rows = [("hop", "replica", "queue_ms", "ttft_ms", "tokens", "total_ms",
             "gap_ms", "outcome", "reason")]
    for h in stitched["hops"]:
        rows.append((
            h["hop"], h["replica"], h.get("queue_wait_ms", ""),
            h.get("ttft_ms", ""), h.get("tokens", ""),
            h.get("total_ms", ""), h.get("gap_ms", ""),
            h.get("outcome", ""), h.get("finish_reason", ""),
        ))
    lines = [f"request {stitched['request_id']}: {stitched['hop_count']} hop(s) "
             f"across replicas, {stitched['tokens']} tokens"
             + (f", end-to-end {stitched['end_to_end_ms']} ms"
                if stitched.get("end_to_end_ms") is not None else "")]
    lines.extend(render_table(rows, indent=""))
    return "\n".join(lines)


def build_waterfall_rows(target, router_records=None) -> list:
    """Join a telemetry dir's router request log with its replica
    request logs and decompose — the shared load half of
    ``summary --waterfall`` and ``report``'s waterfall section."""
    from ..telemetry.waterfall import build_waterfalls, load_router_requests

    if router_records is None:
        router_records = load_router_requests(target)
    if not router_records:
        return []
    replica_recs = load_requests(target) if _request_files(target) else []
    return build_waterfalls(router_records, replica_recs)


def _format_waterfall(rows: list, agg: dict) -> str:
    """The waterfall table: one row per request (stage columns in causal
    order), then the per-stage percentile aggregate — the 'which stage
    ate the p99' answer."""
    from ..telemetry.waterfall import STAGES, stage_table

    from .report import render_table  # the one shared table renderer

    table = [("id", "replica", "hops", "e2e_ttft_ms")
             + tuple(f"{s}_ms" for s in STAGES) + ("top",)]
    for row in rows:
        table.append((
            str(row.get("request_id")), str(row.get("replica")),
            str(1 + (row.get("requeues") or 0)),
            str(row.get("e2e_ttft_ms")),
        ) + tuple(str(row["stages"].get(s, "")) for s in STAGES)
          + (row.get("top_stage", ""),))
    lines = [
        f"{agg.get('requests', 0)} request(s) decomposed "
        f"({agg.get('joined', 0)} joined with replica-side records); "
        "stages sum to the client-observed TTFT"
    ]
    lines.extend(render_table(table, indent=""))
    st_table = stage_table(agg, include_mean=True)
    if len(st_table) > 1:
        lines.append("")
        lines.append("per-stage aggregate (where the fleet's TTFT goes):")
        lines.extend(render_table(st_table))
    if agg.get("top_stages"):
        lines.append("top stage by request: " + ", ".join(
            f"{s}={n}" for s, n in sorted(
                agg["top_stages"].items(), key=lambda kv: -kv[1]
            )
        ))
    return "\n".join(lines)


def _waterfall_summary(args) -> int:
    from ..telemetry.waterfall import load_router_requests, summarize_waterfall

    router_recs = load_router_requests(args.target)
    if not router_recs:
        print(
            f"no router-requests*.jsonl found under {args.target} — run the "
            "router with RouterConfig(log_dir=...) / `serve router "
            "--log-dir` to record the waterfall's router-side half",
            file=sys.stderr,
        )
        return 1
    if args.request_id is not None:
        router_recs = [r for r in router_recs
                       if _same_id(r.get("request_id"), args.request_id)]
        if not router_recs:
            print(f"request id {args.request_id} not in the router log",
                  file=sys.stderr)
            return 1
    rows = build_waterfall_rows(args.target, router_records=router_recs)
    if not rows:
        print("no request in the router log reached a first token — "
              "nothing to decompose", file=sys.stderr)
        return 1
    agg = summarize_waterfall(rows)
    if args.json:
        print(json.dumps({"waterfalls": rows, "aggregate": agg}))
    else:
        print(_format_waterfall(rows, agg))
    return 0


def _format_table(records: list, agg: dict) -> str:
    cols = ("id", "host", "slot", "prompt", "tokens", "queue_ms", "ttft_ms",
            "itl_p50_ms", "total_ms", "reason")
    rows = [cols]
    for rec in records:
        rows.append((
            str(rec.get("request_id")), str(rec.get("host", "?")),
            str(rec.get("slot")), str(rec.get("prompt_len")),
            str(rec.get("tokens")), str(rec.get("queue_wait_ms", "")),
            str(rec.get("ttft_ms", "")), str(rec.get("itl_p50_ms", "")),
            str(rec.get("total_ms", "")), str(rec.get("finish_reason", "")),
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    lines = ["  ".join(c.rjust(w) for c, w in zip(row, widths)) for row in rows]
    lines.append("")
    lines.append(
        f"{agg['requests']} requests, {agg['tokens']} tokens; "
        + ", ".join(
            f"{k[:-len('_p50_ms')]} p50/p95/p99 = "
            f"{agg[k]}/{agg[k.replace('p50', 'p95')]}/{agg[k.replace('p50', 'p99')]} ms"
            for k in ("queue_wait_p50_ms", "ttft_p50_ms", "itl_p50_ms")
            if k in agg
        )
    )
    if "outcomes" in agg:
        parts = [f"{k}={v}" for k, v in sorted(agg["outcomes"].items())]
        if agg.get("preemptions"):
            parts.append(f"preemptions={agg['preemptions']}")
        lines.append("outcomes: " + ", ".join(parts))
    return "\n".join(lines)


def trace_command(args) -> int:
    if args.trace_cmd == "merge":
        trace = merge_traces(args.target, request_id=args.request_id)
        spans = [e for e in trace["traceEvents"] if e.get("ph") != "M"]
        if not spans:
            what = (f"no spans for request id {args.request_id}"
                    if args.request_id is not None else "no span events")
            print(f"{what} found under {args.target}", file=sys.stderr)
            return 1
        body = json.dumps(trace)
        if args.output:
            with open(args.output, "w") as fh:
                fh.write(body)
            n = len(trace["traceEvents"])
            print(f"wrote {n} events -> {args.output} (load in Perfetto / chrome://tracing)")
        else:
            print(body)
        return 0
    if args.trace_cmd == "summary":
        if getattr(args, "waterfall", False):
            return _waterfall_summary(args)
        records = load_requests(args.target)
        if not records:
            print(f"no request records found under {args.target}", file=sys.stderr)
            return 1
        if args.request_id is not None:
            records = [r for r in records
                       if _same_id(r.get("request_id"), args.request_id)]
            if not records:
                print(f"request id {args.request_id} not in the log", file=sys.stderr)
                return 1
            if len(records) > 1:
                # one logical request, several replica hops: stitch them
                # into the hop-by-hop timeline instead of a record dump
                stitched = stitch_request(records)
                if args.json:
                    print(json.dumps({"stitched": stitched, "records": records}))
                else:
                    print(_format_stitched(stitched))
                return 0
            print(json.dumps(records[0], indent=2))
            return 0
        agg = summarize_requests(records)
        if args.json:
            print(json.dumps({"requests": records, "aggregate": agg}))
        else:
            print(_format_table(records, agg))
        return 0
    print("usage: accelerate-tpu trace {merge,summary} ...", file=sys.stderr)
    return 1


def register(subparsers):
    parser = subparsers.add_parser(
        "trace", help="Merge / inspect telemetry span traces and request logs"
    )
    sub = parser.add_subparsers(dest="trace_cmd")
    merge = sub.add_parser(
        "merge", help="Merge per-host Chrome-trace JSONLs into one trace JSON"
    )
    merge.add_argument("target", help="telemetry dir (or one trace-host*.jsonl)")
    merge.add_argument("-o", "--output", default=None, help="output path (default: stdout)")
    merge.add_argument("--request-id", default=None,
                       help="keep only this request's spans")
    summary = sub.add_parser(
        "summary", help="Summarize request-log JSONL(s) into a latency table"
    )
    summary.add_argument(
        "target", nargs="+",
        help="telemetry dir(s) (or requests-host*.jsonl files) — pass one "
             "dir per replica to merge a fleet's request logs",
    )
    summary.add_argument(
        "--request-id", default=None,
        help="print one request's full lifecycle record; with records "
             "from several replicas, stitch them into the hop-by-hop "
             "timeline",
    )
    summary.add_argument(
        "--waterfall", action="store_true",
        help="decompose each request's client-observed TTFT into stages "
             "(router-queue / placement / retry-backoff / transport / "
             "replica-queue / prefill) by joining router-requests*.jsonl "
             "with the replica request logs; prints per-stage "
             "p50/p95/p99 aggregates",
    )
    summary.add_argument("--json", action="store_true", help="machine-readable output")
    parser.set_defaults(func=trace_command)
    return parser
