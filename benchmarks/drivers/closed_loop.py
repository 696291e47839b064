"""Closed-loop serving: a fixed population of clients over a replayed trace.

Each client sends its next request when its last one ends; lengths and their
order come from the traffic file (``traffic_gen.expand``), token ids from
``--seed``. The loop calls ``ServingEngine.submit`` and ``step`` and reads
the public fields of ``Request`` and the engine's public counters; nothing
in it looks at the clock to decide anything, so the sequence of batch
compositions and prefill dispatches is a function of the traffic file.
"""

from __future__ import annotations

import dataclasses
import gc
import re
import statistics
import time

import numpy as np

import costs
import traffic_gen
import weights


class Rec:
    """One request as the loop sees it."""

    __slots__ = ("session", "ask", "client", "req", "output_len", "submit_iter",
                 "dispatch_iter", "dispatch_t", "first_iter", "stamps", "seen")

    def __init__(self, session, ask, client, output_len, submit_iter):
        self.session, self.ask, self.client = session, ask, client
        self.output_len, self.submit_iter = output_len, submit_iter
        self.req = None
        self.dispatch_iter = self.first_iter = None
        self.dispatch_t = None
        self.stamps = []   # host clock at each token, as a streaming client sees them
        self.seen = 0      # tokens counted up to the previous iteration

    def on_token(self, _token, _req):
        self.stamps.append(time.perf_counter())


class ClosedLoop:
    def __init__(self, engine, traffic: dict, seed: int, arch, config: dict, spans):
        self.engine, self.traffic, self.seed, self.spans = engine, traffic, seed, spans
        self.arch, self.config = arch, config
        self.vocab, self.page_size = arch.vocab(config), config["serving"]["page_size"]
        self.trace = traffic_gen.expand(traffic)
        self.clients = int(traffic["clients"])
        self.cursor = 0                       # next session of the trace
        self.current = [None] * self.clients  # (session, next ask index, document ids)
        self.live = [None] * self.clients     # Rec in flight
        self.recs = []
        self.iters = []                       # one dict an iteration
        self.poll_pages = False               # engine.metrics() each iteration (traced run)
        # how the population starts, so that the warm-in can be short: client i
        # first submits at iteration i * every_iterations, and/or has its first
        # session cut (traffic_gen.stagger)
        self.start = traffic.get("start", {})

    def _next_ask(self, client: int):
        cur = self.current[client]
        if cur is None or cur[1] >= len(cur[0].asks):
            session = self.trace[self.cursor % len(self.trace)]
            if self.cursor >= len(self.trace):  # a second lap: new documents
                session = dataclasses.replace(session, index=self.cursor)
            if self.cursor < self.clients and self.start.get("cut_first_session", False):
                session = traffic_gen.stagger(session, client, self.clients)
            self.cursor += 1
            doc = traffic_gen.document_tokens(self.seed, session, self.vocab)
            cur = [session, 0, doc]
            self.current[client] = cur
        session, k, doc = cur
        cur[1] = k + 1
        prompt = np.concatenate([doc, traffic_gen.ask_tokens(self.seed, session, k, self.vocab)])
        return session, k, prompt

    def _refill(self):
        for client in range(self.clients):
            rec = self.live[client]
            if rec is not None and not rec.req.done:
                continue
            if rec is None and len(self.iters) < client * int(self.start.get("every_iterations", 0)):
                continue
            session, k, prompt = self._next_ask(client)
            rec = Rec(session.index, k, client, session.asks[k].output_len, len(self.iters))
            rec.req = self.engine.submit(prompt, max_new_tokens=rec.output_len, on_token=rec.on_token)
            self.live[client] = rec
            self.recs.append(rec)

    def iterate(self):
        eng = self.engine
        with self.spans.span("bench/submit"):
            self._refill()
        packed0, gen0, t0 = eng.prefill_packed_tokens, eng.generated_tokens, time.perf_counter()
        with self.spans.span("bench/step"):
            eng.step()
        t1 = time.perf_counter()
        with self.spans.span("bench/emit"):
            i = len(self.iters)
            prefill = eng.prefill_packed_tokens > packed0
            walked = kv_bytes = decoded = 0
            comp = []
            for rec in self.live:
                if rec is None:
                    continue
                req = rec.req
                n = len(req.tokens)
                if rec.dispatch_iter is None and (req.pages_allocated or req.prefix_hit or n):
                    rec.dispatch_iter, rec.dispatch_t = i, t0
                if n and rec.first_iter is None:
                    rec.first_iter = i
                if n - max(rec.seen, 1) >= 1:  # a decode step wrote at prompt + n - 2
                    write_pos = len(req.prompt) + n - 2
                    walked += costs.page_rounded(write_pos, self.page_size)
                    kv_bytes += self.arch.decode_kv_bytes(self.config, write_pos, self.page_size)
                    decoded += 1
                if n:
                    comp.append((rec.session, rec.ask, n))
                rec.seen = n
            it = {"t0": t0, "t1": t1, "prefill": prefill, "emitted": eng.generated_tokens - gen0,
                  "decoded": decoded, "walked_tokens": walked, "kv_bytes": kv_bytes, "live": len(comp),
                  "comp": tuple(comp)}
            if self.poll_pages:
                it["pages_in_use"] = eng.metrics().get("serving/pages_in_use")
            self.iters.append(it)


def build_engine(ctx):
    """Weights from the seed on the device, then the engine with the
    configuration's deployment settings and the engine's defaults."""
    import jax.numpy as jnp

    from accelerate_tpu.serving import ServingEngine

    arch, c, s = ctx.arch, ctx.settings, ctx.settings["serving"]
    kernel = "interpret" if ctx.rehearsal else None
    cfg = arch.decoder_config(
        c, max_seq_len=s["max_cache_len"], remat=False, decode_kernel=kernel, prefill_kernel=kernel)
    params = weights.make_jit(arch.reference, c, ctx.seed, jnp.bfloat16, adapt=arch.to_program_tree(c))
    engine = ServingEngine(
        arch.module(cfg), params, page_size=s["page_size"], num_slots=s["num_slots"],
        max_cache_len=s["max_cache_len"], num_pages=s["num_pages"],
        **s.get("engine_kwargs", {}))
    del params
    engine.warmup()
    engine.mark_steady()
    return engine


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def run(ctx) -> dict:
    import jax

    from accelerate_tpu.utils.compile_cache import compile_event_counters

    traffic, c, s = ctx.traffic, ctx.settings, ctx.settings["serving"]
    ctx.say(f"traffic {traffic['name']}: {traffic['clients']} clients, longest request "
            f"{traffic_gen.longest_request(traffic, s['max_cache_len'])} tokens")
    engine = build_engine(ctx)
    ctx.say(f"engine warm: arena {engine.arena_bytes / 2**30:.2f} GiB in {engine.num_pages} pages, "
            f"{time.perf_counter() - ctx.t_start:.1f}s since start")
    loop = ClosedLoop(engine, traffic, ctx.seed, ctx.arch, c, ctx.spans)
    loop.poll_pages = ctx.trace
    warm_in = int(traffic["warm_in_iterations"] if not ctx.rehearsal else traffic.get("rehearsal_warm_in", 8))
    for _ in range(warm_in):
        loop.iterate()
    ctx.setup_done()

    compiles0 = compile_event_counters()["count"]
    i0 = len(loop.iters)
    t0 = time.perf_counter()
    traced_from = None
    untraced = ctx.seconds - (min(ctx.trace_seconds, ctx.seconds / 2) if ctx.trace else 0.0)
    if ctx.iterations:  # a rehearsal counted in iterations: the window closes with the last of them
        for _ in range(ctx.iterations):
            loop.iterate()
        untraced = loop.iters[-1]["t1"] - t0
    while time.perf_counter() - t0 < untraced:
        loop.iterate()
    i1 = len(loop.iters)
    if ctx.trace:
        ctx.start_trace()
        traced_from = len(loop.iters)
        t_tr = time.perf_counter()
        while time.perf_counter() - t_tr < ctx.seconds - untraced:
            loop.iterate()
        ctx.stop_trace()
    compiles = compile_event_counters()["count"] - compiles0
    # The window is [t0, t0 + untraced] exactly. The iteration that crosses its
    # end counts by the share of its time that lies inside: a rate over whole
    # iterations only would jump by the last one's tokens whenever a run
    # fits one iteration more or less (0.7% between 80 and 81, PR 23).
    window = loop.iters[i0:i1]
    w0, w1 = t0, t0 + untraced
    last = window[-1]
    inside = min(1.0, max(0.0, (w1 - last["t0"]) / (last["t1"] - last["t0"]))) if last["t1"] > w1 else 1.0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())

    in_window = lambda t: t is not None and w0 <= t <= w1
    firsts = [r for r in loop.recs if in_window(r.req.first_token_t)]
    ttft = [1e3 * (r.req.first_token_t - r.req.submit_t) for r in firsts]
    gaps = [1e3 * (b - a) for r in loop.recs for a, b in zip(r.stamps, r.stamps[1:]) if w0 <= b <= w1]
    submitted = [r for r in loop.recs if i0 <= r.submit_iter < i1]
    failed = [r for r in submitted if r.req.done and
              (r.req.outcome != "finished" or len(r.req.tokens) != r.output_len)]
    shed_any = [r for r in loop.recs if r.req.done and r.req.outcome != "finished"]
    emitted = sum(it["emitted"] for it in window[:-1]) + inside * last["emitted"]
    ctx.say(f"window {w1 - w0:.3f}s, {len(window) - 1} iterations and {inside:.3f} of one, {emitted:.1f} tokens, "
            f"{len(firsts)} first tokens, {len(gaps)} gaps, {len(submitted)} submitted, "
            f"{len(failed)} failed, {compiles} compiles in the window")

    values = {"out_tokens_per_s": emitted / (w1 - w0)}
    if ttft:
        values["ttft_p50_ms"] = statistics.median(ttft)
    if gaps:
        values["itl_p95_ms"] = _percentile(gaps, 95)
    per_req_gap = [1e3 * (r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)
                   for r in loop.recs if r.req.done and in_window(r.req.finish_t) and len(r.stamps) > 1]
    if per_req_gap:
        values["tpot_p50_ms"] = statistics.median(per_req_gap)

    dispatches = [sum(it["prefill"] for it in loop.iters[r.submit_iter:r.first_iter + 1]) for r in firsts]
    counters = {
        "kind": "serve", "window_s": w1 - w0, "iterations": len(window),
        "ttft_ms": ttft, "itl_gaps_ms_count": len(gaps), "itl_p50_ms": _percentile(gaps, 50),
        "itl_p99_ms": _percentile(gaps, 99),
        "prefill_dispatches": dispatches,
        "admit_wait_ms": [1e3 * (r.dispatch_t - r.req.submit_t) for r in firsts if r.dispatch_t],
        "prefill_iteration_share": sum(it["prefill"] for it in window) / len(window),
        "occupancy": [it["live"] / s["num_slots"] for it in window],
        "prompt_tokens": sum(len(r.req.prompt) for r in firsts),
        "prefix_hit_tokens": sum(r.req.prefix_hit for r in firsts),
        "pages_in_use_peak": max((it.get("pages_in_use") or 0 for it in loop.iters), default=0),
        "num_pages": engine.num_pages,
        "live_tokens_page_rounded_peak": max(it["walked_tokens"] for it in loop.iters),
        "compiles_in_window": compiles,
    }
    if traced_from is not None:
        traced = loop.iters[traced_from:]
        counters["traced"] = {
            "t0": traced[0]["t0"], "t1": traced[-1]["t1"],
            "decode_steps": sum(1 for it in traced if it["decoded"]),
            "decode_kv_bytes": sum(it["kv_bytes"] for it in traced),
            "prefill_dispatches": sum(it["prefill"] for it in traced),
        }

    # what is compared: of the first requests submitted in the window, among those it finished, the longest and a
    # draw from the seed; a control run reads every finished candidate, since limits are set from all of them
    sample, others = pick_sample(loop.recs, int(traffic["check_requests"]), ctx.seed, i0,
                                 lambda r: r.req.done and r.req.outcome == "finished" and in_window(r.req.finish_t))
    cases = [{"session": r.session, "ask": r.ask, "client": r.client, "held": r in sample,
              "submit_iter": r.submit_iter, "first_iter": r.first_iter,
              "prompt": np.asarray(r.req.prompt), "served": np.asarray(r.req.tokens, np.int32),
              "prefix_hit": int(r.req.prefix_hit)} for r in sample + (others if ctx.control else [])]
    iteration_log = {"warm_in": i0, "live": [it["live"] for it in loop.iters],
                     "prefill": [int(it["prefill"]) for it in loop.iters],
                     "ms": [round(1e3 * (it["t1"] - it["t0"]), 3) for it in loop.iters],
                     # (client, submitted at, first token at, prompt tokens found cached): where a mix's
                     # warm-in ends is read from these, once
                     "requests": [(r.client, r.submit_iter, r.first_iter, int(r.req.prefix_hit)) for r in loop.recs]}
    n_attempted, n_failed, n_bad = len(submitted), len(failed), len(failed) + len(shed_any)
    del loop, engine, firsts, submitted, failed, shed_any, sample, others
    gc.collect()
    jax.clear_caches()

    check = compare(ctx, cases)
    ok = check["ok"] and compiles == 0 and n_bad == 0
    compared = dict(check["numbers"], nothing_compared=(int(not cases), 0), compiles_in_window=(compiles, 0),
                    requests_not_finished=(n_bad, 0))
    return {"values": values, "counters": counters, "attempted": n_attempted, "failed": n_failed,
            "correct": ok, "memory_peak_bytes": int(peak), "check": check, "compared": compared,
            "iterations": iteration_log}


CANDIDATES = 8  # candidates for each compared request


def pick_sample(recs: list, n: int, seed: int, i0: int, finished) -> tuple:
    """``(sample, the other finished candidates)``. The candidates are the
    first ``CANDIDATES * n`` requests submitted from iteration ``i0`` on, the
    window's first: the loop never looks at the clock and warm-in is counted
    in iterations, so they are the same requests at every pace, and every
    token of theirs, the prefill too, is the window's work. Those of them
    that ``finished`` in the window are compared: the longest (prompt plus
    served tokens), then the others in an order of the candidates' places
    drawn from the seed, up to ``n``. A slower run finishes fewer of them and
    misses those; the rest keep their order. Where the finished candidates
    hold both, the sample's last gives way to one that hit the prefix cache
    or one that did not."""
    head = [r for r in recs if r.submit_iter >= i0][: CANDIDATES * n]
    order = np.random.default_rng([int(seed), 3]).permutation(CANDIDATES * n)
    ready = [head[i] for i in order if i < len(head) and finished(head[i])]
    if not ready:
        return [], []
    longest = max(ready, key=lambda r: len(r.req.prompt) + len(r.req.tokens))
    rest = [r for r in ready if r is not longest]
    picked = ([longest] + rest)[:n]
    for want_hit in (True, False):
        if not any(bool(r.req.prefix_hit) == want_hit for r in picked):
            extra = [r for r in rest if bool(r.req.prefix_hit) == want_hit]
            if extra:
                picked[-1] = extra[0]
    return picked, [r for r in rest if r not in picked]


def statistic(name: str):
    """The statistic of the pooled gaps (how far each served token's logit
    lies under the reference's best) that a key of ``limits`` names.
    ``served_logit_gap`` is the maximum: a fault in few tokens reads there, a
    wrong page, a mask off by one, a token altered where it is produced.
    ``served_logit_gap_p<q>`` is the q-th percentile: a model degraded
    everywhere misses the reference's choice in far more tokens than a sound
    one does, which one token whose last expert flipped cannot mimic."""
    if name == "served_logit_gap":
        return lambda gaps: float(gaps.max())
    m = re.fullmatch(r"served_logit_gap_p(\d+)", name)
    if not m or not 0 < int(m[1]) < 100:
        raise KeyError(f"limit {name!r} is no statistic the comparison knows: served_logit_gap or "
                       "served_logit_gap_p<q>, the q-th percentile")
    return lambda gaps: float(np.percentile(gaps, int(m[1])))


def judge(gaps, limits: dict) -> tuple:
    """``({name: (value, limit)}, whether every limit is held)`` for every key
    of ``limits``. A key that names no statistic is an error, and no gaps at
    all hold nothing (their statistics read 0.0)."""
    gaps, of = np.asarray(gaps, np.float32), {k: statistic(k) for k in limits}
    numbers = {k: (of[k](gaps) if gaps.size else 0.0, float(limits[k])) for k in limits}
    return numbers, bool(gaps.size) and all(value <= limit for value, limit in numbers.values())


def _numbers_text(numbers: dict) -> str:
    return ", ".join(f"{k} {v:.6f} limit {lim}" for k, (v, lim) in numbers.items())


def compare(ctx, cases: list) -> dict:
    """The reference once over each case's prompt with its served tokens, and
    every statistic the configuration's limits name over the pooled gaps of
    the cases that are held. ``--control`` is the set-up of a limit: beside
    each case's gaps it reads those of the tokens the lower precision puts
    first and of a random id in each served token's place, and the same
    statistics of the control's; ``--dump`` keeps every case's gaps."""
    import jax.numpy as jnp

    c, reference = ctx.settings, ctx.arch.reference
    t0 = time.perf_counter()
    w = weights.make_jit(reference, c, ctx.seed, jnp.bfloat16)
    record = []
    for i, case in enumerate(cases):
        prompt, served = case["prompt"], case["served"]
        ids = np.concatenate([prompt, served[:-1]])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = np.asarray(reference.logits_at(c, w, ids, rows, "float32"))
        under_best = lambda tokens: (ref.max(axis=-1) - ref[np.arange(len(served)), tokens]).astype(np.float32)
        rec = {k: v for k, v in case.items() if k not in ("prompt", "served")}
        rec.update(prompt_tokens=len(prompt), gaps=under_best(served))
        if ctx.control:
            low = np.asarray(reference.logits_at(c, w, ids, rows, ctx.control)).argmax(axis=-1)
            other = np.random.default_rng([int(ctx.seed), 4, i]).integers(0, ref.shape[-1], len(served))
            rec.update(control_gaps=under_best(low), altered_gaps=under_best(other))
        record.append(rec)
    took = time.perf_counter() - t0
    held = [r for r in record if r["held"]]
    pooled = lambda key: np.concatenate([r[key] for r in held]) if held else np.zeros(0, np.float32)
    gaps = pooled("gaps")
    numbers, ok = judge(gaps, ctx.limits)
    ctx.say(f"compared: {_numbers_text(numbers)} ({'ok' if ok else 'NOT CORRECT'}); "
            f"{len(held)} requests submitted and finished in the window, {gaps.size} served tokens, "
            f"{int((gaps == 0).sum())} the reference's own first choice, the longest prompt "
            f"{max((r['prompt_tokens'] for r in held), default=0)}, {sum(1 for r in held if r['prefix_hit'])} "
            f"behind a cached prefix; reference took {took:.1f}s over {len(record)} requests")
    out = {"ok": ok, "numbers": numbers, "control": None, "reference_s": took,
           "cases": [{k: (np.round(v.astype(float), 6).tolist() if isinstance(v, np.ndarray) else v)
                      for k, v in r.items()} for r in record]}
    if ctx.control and held:
        out["control"], _ = judge(pooled("control_gaps"), ctx.limits)
        fails = [k for k, (v, lim) in out["control"].items() if v > lim]
        ctx.say(f"control ({ctx.control} reference in the program's place): {_numbers_text(out['control'])} "
                f"({'fails ' + ', '.join(fails) + ', as it must' if fails else 'PASSES: the limits are too loose'}); "
                f"one served token replaced by a random id reads {np.percentile(pooled('altered_gaps'), 1):.3f} or "
                f"more at 99 positions in 100")
    return out
