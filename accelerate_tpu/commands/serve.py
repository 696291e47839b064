"""``accelerate-tpu serve`` — launch a serving replica or the router.

Two roles, one subcommand (docs/serving.md "Multi-replica serving &
failover"):

- ``accelerate-tpu serve router --replica NAME=URL [--replica ...]``
  runs the stdlib-HTTP/JSONL front door (``serving/router.py``):
  least-loaded + session-affinity placement, failover + re-queue,
  elastic ``/v1/register`` membership. **Jax-free end to end** — the
  router tier runs on boxes with no accelerator stack, and this module
  is in the declared jax-free set (``analysis/hygiene.py``).
- ``accelerate-tpu serve replica --config tiny --port 8900`` builds a
  randomly-initialized demo model and serves it through a
  :class:`~..serving.replica_server.ReplicaServer` — the CPU-sim /
  drill bring-up path (production embedders wrap their own engine in
  ``ReplicaServer`` directly). Everything jax-heavy imports lazily
  inside the launch function, so registering the subcommand costs the
  log-reading commands nothing (the PR 12 lazy-registration pattern).
"""

from __future__ import annotations

import json


def register(subparsers):
    parser = subparsers.add_parser(
        "serve",
        help="launch a serving replica server or the multi-replica router",
    )
    sub = parser.add_subparsers(dest="role")

    router = sub.add_parser(
        "router", help="stdlib-HTTP/JSONL front door over N replicas "
                       "(jax-free; failover + re-queue + elastic membership)"
    )
    router.add_argument("--replica", action="append", default=[],
                        metavar="[NAME=]URL",
                        help="replica base URL (repeatable); more can join "
                             "at runtime via POST /v1/register")
    router.add_argument("--host", default="127.0.0.1")
    router.add_argument("--port", type=int, default=8790)
    router.add_argument("--max-inflight", type=int, default=64,
                        help="bounded router queue; past it submits shed "
                             "with shed_reason=router_queue_full")
    router.add_argument("--max-retries", type=int, default=4)
    router.add_argument("--backoff-base", type=float, default=0.05,
                        metavar="S")
    router.add_argument("--backoff-cap", type=float, default=2.0, metavar="S")
    router.add_argument("--backoff-seed", type=int, default=0)
    router.add_argument("--request-timeout", type=float, default=None,
                        metavar="S")
    router.add_argument("--poll-interval", type=float, default=0.25,
                        metavar="S", help="replica health/placement scrape "
                                          "cadence")
    router.add_argument("--no-affinity", action="store_true",
                        help="disable session->replica stickiness")
    router.add_argument("--no-kv-migration", action="store_true",
                        help="disable the KV handoff when a session moves "
                             "off a draining replica")
    router.add_argument("--log-dir", default=None, metavar="DIR",
                        help="write router-requests.jsonl (the latency "
                             "waterfall's router half), "
                             "router-decisions.jsonl (placement-decision "
                             "log) and canary-results.jsonl here")
    router.add_argument("--no-instrument", action="store_true",
                        help="disable golden-signal histograms, hop "
                             "timing stamps and the decision log (the "
                             "zero-overhead witness baseline)")
    router.add_argument("--canary-interval", type=float, default=0.0,
                        metavar="S",
                        help="probe the fleet with a seeded golden prompt "
                             "every S seconds, verifying token-exactness "
                             "(0 = off); gauges land on /metrics as "
                             "canary/*")
    router.add_argument("--canary-prompt", default="1,2,3",
                        help="comma-separated golden prompt token ids "
                             "(the first finished probe records the "
                             "golden output every later probe must "
                             "reproduce)")
    router.add_argument("--canary-max-new-tokens", type=int, default=8)
    router.add_argument("--canary-seed", type=int, default=0)

    replica = sub.add_parser(
        "replica", help="one engine process behind HTTP (demo model; "
                        "production embeds ReplicaServer over its own engine)"
    )
    replica.add_argument("--config", default="tiny",
                        help="named DecoderConfig constructor (tiny)")
    replica.add_argument("--name", default=None,
                         help="replica identity (default ATT_REPLICA or "
                              "host:port); stamped into request records")
    replica.add_argument("--host", default="127.0.0.1")
    replica.add_argument("--port", type=int, default=0,
                         help="0 binds an ephemeral port (printed as JSON "
                              "on stdout at startup)")
    replica.add_argument("--num-slots", type=int, default=4)
    replica.add_argument("--max-cache-len", type=int, default=None)
    replica.add_argument("--prefill-chunks", default="16,64",
                         help="comma-separated prefill bucket sizes")
    replica.add_argument("--page-size", type=int, default=16,
                         help="tokens a KV page; must divide the cache length")
    replica.add_argument("--kv-cache-dtype", default=None,
                         choices=["bf16", "int8", "int4"])
    replica.add_argument("--kv-host-entries", type=int, default=0,
                         help="host-RAM KV tier capacity in prefix entries "
                              "(0 = tiering off; evictions drop as before)")
    replica.add_argument("--kv-disk-entries", type=int, default=0,
                         help="disk KV tier capacity in prefix entries "
                              "(needs --kv-disk-dir)")
    replica.add_argument("--kv-disk-dir", default=None, metavar="DIR",
                         help="directory for demoted KV blobs (durable "
                              "across restarts; torn/corrupt blobs are "
                              "rejected and deleted)")
    replica.add_argument("--kv-peers", action="append", default=[],
                         metavar="[NAME=]URL",
                         help="peer replica base URL for the fleet KV tier "
                              "(repeatable): a local miss pulls a warm "
                              "prefix over /v1/kv/export after checking "
                              "the peer's /v1/kv/directory")
    replica.add_argument("--temperature", type=float, default=0.0)
    replica.add_argument("--top-k", type=int, default=None)
    replica.add_argument("--init-seed", type=int, default=0,
                         help="model-init PRNG seed (two replicas launched "
                              "with the same config+seed serve the same "
                              "weights — what the drills rely on)")
    replica.add_argument("--max-seq-len", type=int, default=256)

    parser.set_defaults(func=serve_command)


def serve_command(args) -> int:
    role = getattr(args, "role", None)
    if role == "router":
        return _serve_router(args)
    if role == "replica":
        return _serve_replica(args)
    print("usage: accelerate-tpu serve {router|replica} [--help]")
    return 1


def _parse_replica_flags(values) -> list:
    pairs = []
    for i, item in enumerate(values):
        if "=" in item:
            name, url = item.split("=", 1)
        else:
            name, url = f"r{i}", item
        pairs.append((name.strip(), url.strip()))
    return pairs


def _serve_router(args) -> int:
    # jax-free by construction: router.py + telemetry.fleet only
    from ..serving.router import Router, RouterConfig, RouterServer

    cfg = RouterConfig(
        max_inflight=args.max_inflight,
        max_retries=args.max_retries,
        backoff_base_s=args.backoff_base,
        backoff_cap_s=args.backoff_cap,
        backoff_seed=args.backoff_seed,
        request_timeout_s=args.request_timeout,
        poll_interval_s=args.poll_interval,
        affinity=not args.no_affinity,
        migrate_session_kv=not args.no_kv_migration,
        instrument=not args.no_instrument,
        log_dir=args.log_dir,
    )
    router = Router(_parse_replica_flags(args.replica), config=cfg).start()
    if args.canary_interval and args.canary_interval > 0:
        from ..telemetry.canary import CanaryProber, flight_via_router, via_router

        prompt = [int(t) for t in str(args.canary_prompt).split(",") if t.strip()]
        prober = CanaryProber(
            via_router(router),
            [{"prompt": prompt, "seed": int(args.canary_seed),
              "max_new_tokens": int(args.canary_max_new_tokens)}],
            interval_s=float(args.canary_interval),
            log_dir=args.log_dir,
            flight_fn=flight_via_router(router),
        ).start()
        router.attach_canary(prober)
    server = RouterServer(router, host=args.host, port=args.port)
    print(json.dumps({"role": "router", "port": server.port,
                      "replicas": len(args.replica),
                      "canary": bool(args.canary_interval),
                      "log_dir": args.log_dir}), flush=True)
    try:
        import time

        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
        router.close()
    return 0


def build_replica_engine(args):
    """Build the demo engine the ``replica`` role serves (also what the
    multi-process drills import to construct a token-exact reference in
    the test process: same config + ``--init-seed`` => same weights).
    This is the jax-paying half — import it lazily."""
    import jax

    from ..models import DecoderConfig, DecoderLM
    from ..parallel.sharding import unbox_params
    from ..serving.engine import ServingEngine

    if args.config != "tiny":
        raise SystemExit(f"unknown --config {args.config!r} (have: tiny)")
    cfg = DecoderConfig.tiny(max_seq_len=int(args.max_seq_len))
    model = DecoderLM(cfg)
    variables = model.init_variables(
        jax.random.PRNGKey(int(args.init_seed)), batch_size=1, seq_len=16
    )
    params, _ = unbox_params(variables["params"])
    chunks = tuple(
        int(c) for c in str(args.prefill_chunks).split(",") if c.strip()
    )
    kv_tiers = None
    host_entries = int(getattr(args, "kv_host_entries", 0) or 0)
    disk_entries = int(getattr(args, "kv_disk_entries", 0) or 0)
    peers = _parse_replica_flags(getattr(args, "kv_peers", []) or [])
    if host_entries or disk_entries or peers:
        from ..serving.tiers import TierConfig

        kv_tiers = TierConfig(
            host_entries=max(host_entries, 1 if (disk_entries or peers) else 0),
            disk_entries=disk_entries,
            disk_dir=getattr(args, "kv_disk_dir", None),
            peers=tuple(peers),
        )
    return ServingEngine(
        model, params,
        num_slots=int(args.num_slots),
        max_cache_len=args.max_cache_len,
        prefill_chunks=chunks,
        page_size=int(args.page_size),
        temperature=float(args.temperature),
        top_k=args.top_k,
        kv_cache_dtype=args.kv_cache_dtype,
        replica=args.name,
        kv_tiers=kv_tiers,
    )


def _serve_replica(args) -> int:
    from ..serving.replica_server import ReplicaServer

    engine = build_replica_engine(args)
    engine.warmup()
    engine.mark_steady()
    server = ReplicaServer(
        engine, host=args.host, port=int(args.port), name=args.name,
        handle_signals=True,
    ).start()
    print(json.dumps({"role": "replica", "replica": server.name,
                      "port": server.port, "url": server.url}), flush=True)
    try:
        # SIGTERM drains (finish in-flight, flight-record) and unblocks
        # this wait; SIGKILL is what the drills practice surviving
        server.serve_until_drained()
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
    return 0
