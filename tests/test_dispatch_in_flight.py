"""One dispatch in flight (docs/serving.md, "One dispatch in flight"):
``ServingEngine.step()`` enqueues the next decode step before it reads the
last one's tokens, and a pack's first tokens reach their slots on the device.

The deferred order is held against the settled order, which is the same
code with ``_settle()`` called after every ``step()`` (every result read
before the next dispatch: the serial iteration of before): the same tokens,
finish reasons, prefix hits and pages, request by request, whatever the
path. Then what the deferred order alone has: a token dropped by a cancel
or a timeout, a row discarded after a late eos, a slot and its pages used
again by the very next pack, no wait for the device outside the two fetch
spans, and no compile once steady.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.sharding import unbox_params
from accelerate_tpu.serving import SchedulerConfig, ServingEngine
from accelerate_tpu.telemetry import spans as spans_mod

FETCHES = ("serving/token_fetch", "serving/prefill_fetch")


def _params(model):
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    return unbox_params(variables["params"])[0]


@pytest.fixture(scope="module")
def models():
    """``dense``: one kind. ``window``: a full and a window kind (pages
    released behind the window). ``state``: state-space layers beside an
    attention layer (a recurrent state a slot, zeroed inside the pack);
    ``heads``: the same with the mixer that has heads. ``latent``: latent
    attention (one entry a token for all heads). ``closing``: a closing
    window of 16 in chunks of 4 (a step that fills a page pools it, a close
    gives the window's pages back). ``experts``: routed experts (both
    programs return their load)."""
    common = dict(vocab_size=128, embed_dim=64, mlp_dim=128, num_heads=4, max_seq_len=96, dtype=jnp.float32,
                  scan_layers=True, remat=False)
    window = DecoderLM(DecoderConfig(
        num_layers=3, head_dim=16, layer_pattern=(0, 1, 1),
        layer_kinds=(("full", dict(num_kv_heads=1)), ("window", dict(num_kv_heads=2, attn_window=16))), **common))
    state = DecoderLM(DecoderConfig(
        num_layers=3, num_kv_heads=1, head_dim=16, rope_dim=0, layer_pattern=(0, 1, 0),
        layer_kinds=(("state_space", dict(mixer="ssm", ssm_state_dim=8, ssm_dt_rank=8)),
                     ("attention", dict(mixer="attention"))), **common))
    heads = DecoderLM(DecoderConfig(
        num_layers=3, num_kv_heads=1, head_dim=16, rope_dim=0, layer_pattern=(0, 1, 0),
        layer_kinds=(("state_space", dict(mixer="ssd", ssm_num_heads=4, ssm_head_dim=16, ssm_state_dim=8)),
                     ("attention", dict(mixer="attention"))), **common))
    latent = DecoderLM(DecoderConfig(
        num_layers=2, head_dim=24, v_head_dim=16, kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, **common))
    closing = DecoderLM(DecoderConfig(num_layers=2, num_kv_heads=2, head_dim=16, eva_window=16, eva_chunk=4, **common))
    experts = DecoderLM(DecoderConfig(num_layers=2, num_kv_heads=2, head_dim=16, moe_num_experts=4, moe_top_k=2,
                                      **common))
    dense = DecoderLM(DecoderConfig.tiny(max_seq_len=96))
    found = dict(dense=dense, window=window, state=state, heads=heads, latent=latent, closing=closing,
                 experts=experts)
    return {name: (model, _params(model)) for name, model in found.items()}


BASE = dict(num_slots=2, max_cache_len=96, page_size=8, prefill_chunks=(8, 16))
# (prompt tokens, new tokens): five requests through two slots, so slots are
# used again, one prompt takes two packs, and two requests end at their first token
REQUESTS = ((20, 6), (5, 1), (12, 9), (3, 4), (9, 1), (7, 12))
# path: (model, engine arguments); the pages of every path are compared too
PATHS = {
    "greedy": ("dense", {}),
    "sampled": ("dense", dict(temperature=0.8, top_k=5)),
    "donated": ("dense", dict(donate=True)),
    "scheduler": ("dense", dict(scheduler=SchedulerConfig())),
    "one_slot": ("dense", dict(num_slots=1)),
    "window": ("window", dict(prefix_cache=False, num_pages=1 + 2 * 12, kind_pages={"window16": 1 + 2 * 5})),
    "state": ("state", dict(prefix_cache=False)),
    "heads": ("heads", dict(prefix_cache=False)),
    "latent": ("latent", dict(prefix_cache=False)),
    "closing": ("closing", dict(prefix_cache=False, page_size=4)),
    "experts": ("experts", dict(prefix_cache=False)),
}
# the paths of a model by kind: a late eos frees a slot that the next pack takes
BY_KIND = ("window", "state", "heads", "latent", "closing", "experts")


def _base(kind):
    """The engine's arguments for a model by its name (a path by kind has its model's name)."""
    return {**BASE, **(PATHS[kind][1] if kind in BY_KIND else {})}


def _mark():
    spans_mod.emit("mark", 0.0, 0.0)
    return spans_mod.snapshot()[-1][0]


def _since(mark, *names):
    return [s for s in spans_mod.snapshot() if s[0] > mark and (not names or s[2] in names)]


def _drive(eng, settled, each=None):
    """Run to the end; ``settled`` reads every result before the next
    dispatch. ``each(eng)`` is called after every iteration."""
    while eng._pending():
        eng.step()
        if settled:
            eng._settle()
        if each is not None:
            each(eng)
    assert eng._flight is None and not eng._flight_packs and eng.metrics()["serving/dispatch_depth"] == 0


def _serve(models, path, settled, requests=REQUESTS, each=None, **over):
    model, params = models[PATHS[path][0]]
    eng = ServingEngine(model, params, **{**BASE, **PATHS[path][1], **over})
    rng = np.random.RandomState(3)
    reqs = [eng.submit(rng.randint(3, 120, (n,)), max_new_tokens=new, seed=i)
            for i, (n, new) in enumerate(requests)]
    _drive(eng, settled, each)
    return eng, reqs


def _facts(eng, reqs, pages=True):
    """What both orders must agree on, request by request and in sum."""
    per = [(list(r.tokens), r.outcome, r.finish_reason, r.prefix_hit, r.prefill_dispatches,
            r.pages_allocated if pages else None) for r in reqs]
    total = (eng.generated_tokens, eng.requests_completed, eng.pages_released, eng._allocator.in_use,
             [k.allocator.in_use for k in eng._kinds], eng.pages_allocated if pages else None)
    return per, total


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_deferred_order_serves_what_the_settled_order_serves(models, path):
    mark = _mark()
    deferred = _serve(models, path, settled=False)
    fetches = _since(mark, "serving/token_fetch")
    settled = _serve(models, path, settled=True)
    assert _facts(*deferred) == _facts(*settled)
    assert all(r.outcome == "finished" and len(r.tokens) == new for r, (_, new) in zip(deferred[1], REQUESTS))
    assert deferred[0].rows_discarded == 0  # no eos: no row is computed for nothing
    overlapped = sum(f[5]["in_flight"] for f in fetches)
    # all but the reads that found nothing left to enqueue
    assert overlapped >= len(fetches) / 2 and len(fetches) - overlapped <= len(REQUESTS)


@pytest.mark.parametrize("path", ["greedy", "sampled", "one_slot", *BY_KIND])
def test_a_late_eos_discards_one_row_and_nothing_follows_it(models, path):
    """The end of a request by eos is learnt one dispatch late: the step
    already enqueued computed a row for it, which is counted and thrown
    away. Its slot and pages go to the very next pack while that step is
    still in flight (a later dispatch on the same stream writes them after
    it), and that request is served what the settled order serves it."""
    requests = ((20, 12), (5, 12), (12, 12), (3, 12), (9, 12))
    _, free = _serve(models, path, settled=True, requests=requests)
    # a token that the first request emits mid-stream, and others may too, but none as its first (that one's
    # row is computed in either order: a pack's slots ride the decode step enqueued before its firsts are read)
    eos = next(t for t in (free[0].tokens[4], *free[0].tokens[1:]) if all(t != r.tokens[0] for r in free))
    stale, reused = set(), []

    def each(eng):
        reused.extend(slot for slot in stale if slot in eng._slot_req)
        stale.clear()
        if eng._flight is not None:
            stale.update(slot for slot, req in eng._flight.roster if req.finish_reason == "eos")

    mark = _mark()
    eng, reqs = _serve(models, path, settled=False, requests=requests, each=each, eos_token_id=eos)
    emits = _since(mark, "serving/emit")
    want_eng, want = _serve(models, path, settled=True, requests=requests, eos_token_id=eos)
    assert _facts(eng, reqs, pages=False) == _facts(want_eng, want, pages=False)
    ended = [r for r in reqs if r.finish_reason == "eos"]
    assert reqs[0] in ended and len(reqs[0].tokens) == free[0].tokens.index(eos) + 1 < 12
    for r in ended:  # nothing emitted after the eos
        assert r.tokens[-1] == eos and eos not in r.tokens[:-1]
    late = [r for r in ended if len(r.tokens) < r.max_new_tokens and len(r.tokens) > 1]
    assert sum(s[5]["discarded"] for s in emits) == eng.rows_discarded == len(late) > 0
    assert want_eng.rows_discarded == 0
    # a discarded row may have taken the page after the request's last
    assert 0 <= eng.pages_allocated - want_eng.pages_allocated <= len(late)
    if path == "one_slot" or path in BY_KIND:
        assert reused  # a slot freed by the late eos, taken while the step that wrote into it was in flight


@pytest.mark.parametrize("kind", ["dense", "heads", "closing"])
@pytest.mark.parametrize("how", ["cancel", "timeout"])
def test_a_request_ended_by_reap_drops_its_token_in_flight(models, how, kind):
    """... and what the dropped row left behind (a state advanced once more,
    a page of a closing kind filled and pooled) is nothing to the request
    that stays, nor to whoever takes the slot next."""
    model, params = models[kind]
    base = _base(kind)
    eng = ServingEngine(model, params, **base)
    rng = np.random.RandomState(5)
    victim = eng.submit(rng.randint(3, 120, (9,)), max_new_tokens=20, seed=0)
    other = eng.submit(rng.randint(3, 120, (6,)), max_new_tokens=10, seed=1)
    while len(victim.tokens) < 3:
        eng.step()
    assert eng._flight is not None and victim in [r for _, r in eng._flight.roster]
    assert victim._dispatched == len(victim.tokens) + 1  # one token computed, not read
    if how == "cancel":
        victim.cancel()
    else:
        victim.timeout_s = 1e-9
    held = len(victim.tokens)
    mark = _mark()
    eng.step()
    assert victim.outcome == "cancelled" and victim.finish_reason == ("cancelled" if how == "cancel" else "timeout")
    assert len(victim.tokens) == held and victim.slot is None
    (emit,) = _since(mark, "serving/emit")
    assert emit[5]["emitted"] == 1 and emit[5]["discarded"] == 0  # the other's token; a drop is no late eos
    after = eng.submit(rng.randint(3, 120, (7,)), max_new_tokens=6, seed=2)  # takes the victim's slot
    _drive(eng, settled=False)
    want = ServingEngine(model, params, **base).generate_batched([other.prompt, after.prompt], max_new_tokens=10,
                                                                 seeds=[1, 2])
    np.testing.assert_array_equal(other.result(), want[0])
    np.testing.assert_array_equal(after.result(), want[1][:after.prompt.size + 6])


@pytest.mark.parametrize("relief", ["preempts", "sheds"])
def test_page_pressure_reads_the_tokens_first(models, relief):
    """A live slot cannot grow: the engine settles (the victim's chain is
    saved behind every dispatched step, so its tokens must all be read),
    pages the victim out, and both requests end as in the settled order.
    With no scheduler to name a victim, the request that cannot grow is shed
    with every token it was served read, and the other is served to its end."""
    model, params = models["dense"]

    def run(settled):
        eng = ServingEngine(model, params, num_slots=2, max_cache_len=24, prefill_chunks=(4, 8), page_size=8,
                            num_pages=6, prefix_cache=False,
                            scheduler=SchedulerConfig() if relief == "preempts" else None)
        rng = np.random.RandomState(7)
        low = eng.submit(rng.randint(3, 120, (8,)), max_new_tokens=16, seed=1, priority=0)
        high = eng.submit(rng.randint(3, 120, (3,)), max_new_tokens=20, seed=2, priority=5)
        _drive(eng, settled)
        return eng, [low, high]

    eng, reqs = run(False)
    want_eng, want = run(True)
    assert eng.preemptions == want_eng.preemptions == int(relief == "preempts")
    assert eng.resumptions == want_eng.resumptions
    assert [(list(r.tokens), r.outcome, r.shed_reason, r.preemptions) for r in reqs] == \
        [(list(r.tokens), r.outcome, r.shed_reason, r.preemptions) for r in want]
    if relief == "preempts":
        assert reqs[1].outcome == "finished" and len(reqs[1].tokens) == 20
    else:
        assert sorted(r.outcome for r in reqs) == ["finished", "shed"] and eng.requests_shed == 1
        shed = next(r for r in reqs if r.outcome == "shed")
        assert shed.shed_reason == "page_exhausted" and 0 < len(shed.tokens) == shed._dispatched < shed.max_new_tokens
        assert eng._allocator.in_use == 0 and eng._flight is None


def test_drain_delivers_what_was_computed_and_leaves_nothing_unread(models):
    model, params = models["dense"]
    eng = ServingEngine(model, params, **BASE)
    rng = np.random.RandomState(9)
    reqs = [eng.submit(rng.randint(3, 120, (n,)), max_new_tokens=8, seed=i) for i, n in enumerate((6, 11, 4))]
    for _ in range(4):
        eng.step()
    assert eng._flight is not None and eng.metrics()["serving/dispatch_depth"] == 1
    summary = eng.drain()
    assert eng._flight is None and not eng._pending()
    assert [r.outcome for r in reqs] == ["finished", "finished", "shed"] and summary["completed"] == 2
    want = ServingEngine(model, params, **BASE).generate_batched([r.prompt for r in reqs[:2]], max_new_tokens=8)
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(r.result(), w)
    # a drain that runs out of time reads what is in flight before it cancels
    eng = ServingEngine(model, params, **BASE)
    req = eng.submit(rng.randint(3, 120, (6,)), max_new_tokens=30, seed=0)
    for _ in range(3):
        eng.step()
    dispatched = req._dispatched
    eng.drain(timeout_s=0.0)
    assert req.outcome == "cancelled" and req.finish_reason == "drain_timeout"
    assert len(req.tokens) == dispatched and eng._flight is None


def test_first_and_second_token_are_never_read_together(models):
    """A first token is stamped when the host reads it, in the iteration of
    its pack and behind that iteration's decode dispatch; the request's
    second token is the next iteration's read."""
    model, params = models["dense"]
    eng = ServingEngine(model, params, **BASE)
    stamps = {}
    rng = np.random.RandomState(11)
    reqs = [eng.submit(rng.randint(3, 120, (n,)), max_new_tokens=5, seed=i,
                       on_token=lambda tok, r: stamps.setdefault(r.id, []).append(eng.iterations))
            for i, n in enumerate((6, 11, 4, 9))]
    mark = _mark()
    _drive(eng, settled=False)
    for r in reqs:
        seen = stamps[r.id]
        assert len(seen) == 5 and seen[1] == seen[0] + 1  # iterations counts the finished ones: the read is in the next
        assert all(b > a for a, b in zip(seen, seen[1:]))  # one token of a request an iteration
        assert r.first_token_t <= r._last_token_t
    firsts = _since(mark, "serving/prefill_fetch")
    assert firsts and all("in_flight" in f[5] for f in firsts)
    assert any(f[5]["in_flight"] == 1 for f in firsts)


@pytest.mark.parametrize("kind", ["dense", *BY_KIND])
def test_step_waits_for_the_device_only_under_the_two_fetch_spans(models, monkeypatch, kind):
    """Every ``jax.device_get`` and ``block_until_ready`` of a run lies inside
    a ``serving/token_fetch`` or ``serving/prefill_fetch`` span, and nothing
    compiles after ``mark_steady()`` across admissions, finishes and slots
    used again: whatever the kinds keep (pages released behind a window or
    at a close, a state a slot, the experts' load read with the tokens)."""
    calls = []

    def timed(fn):
        def wrapper(*a, **k):
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                calls.append((t0, time.perf_counter()))
        return wrapper

    model, params = models[kind]
    eng = ServingEngine(model, params, **_base(kind), temperature=0.7, top_k=8, eos_token_id=5)
    eng.warmup()
    eng.mark_steady()
    monkeypatch.setattr(jax, "device_get", timed(jax.device_get))
    monkeypatch.setattr(jax, "block_until_ready", timed(jax.block_until_ready))
    mark = _mark()
    rng = np.random.RandomState(13)
    reqs = [eng.submit(rng.randint(3, 120, (n,)), max_new_tokens=new, seed=i)
            for i, (n, new) in enumerate(REQUESTS * 2)]
    _drive(eng, settled=False)
    assert all(r.outcome == "finished" for r in reqs) and eng.admission_recompiles == 0
    fetches = [(s[3], s[4]) for s in _since(mark, *FETCHES)]
    assert calls and len(calls) == len(fetches)
    for t0, t1 in calls:
        assert any(f0 <= t0 and t1 <= f1 for f0, f1 in fetches)
    programs = {spec["name"] for spec in eng.audit_entrypoints()}
    assert {"admit_state", "stage_keys", "decode_step"} <= programs


def test_the_engine_counts_ahead_of_what_it_has_read_by_one_dispatch(models):
    """``serving/decode_grow`` counts the tokens the step being enqueued will
    walk; an outside observer (the benchmark's driver) infers a step from
    the growth of ``len(req.tokens)``, which it sees one iteration later.
    The two agree iteration by iteration, shifted by that one (what
    ``tests/benchmark/test_bench_program_spans.py`` held unshifted for the
    serial order)."""
    model, params = models["dense"]
    eng = ServingEngine(model, params, **BASE)
    rng = np.random.RandomState(15)
    reqs = [eng.submit(rng.randint(3, 120, (n,)), max_new_tokens=new, seed=i)
            for i, (n, new) in enumerate(REQUESTS)]
    seen = {id(r): 0 for r in reqs}
    inside, outside = [], []
    ps = eng.page_size
    while eng._pending():
        mark = _mark()
        eng.step()
        grown = _since(mark, "serving/decode_grow")
        inside.append(grown[0][5]["walked_tokens"] if grown else 0)
        walked = 0
        for r in reqs:
            n = len(r.tokens)
            if n - max(seen[id(r)], 1) >= 1:  # a decode step wrote at prompt + n - 2
                walked += ((r.prompt.size + n - 2) // ps + 1) * ps
            seen[id(r)] = n
        outside.append(walked)
    assert sum(inside) > 0 and inside[:-1] == outside[1:] and outside[0] == 0 and inside[-1] == 0
