"""EVA attention (a closing window with pooled summaries) against the plain
reference of the architecture that brought it (``benchmarks/reference/
evabyte.py``, which imports nothing of the program), at a small size on the
CPU with seeded weights and float32 activations: logits of prefill and then
decode through the paged cache, the pooling kernel interpreted against
``jax.numpy``, the cache kind's bookkeeping at every position of three
windows, the entries a slot holds against the reference's own, and what
``ServingEngine`` refuses for the kind.

Tolerances: the program runs in float32 here, as the reference does, so what
is left is the order of float32 sums: logits agree within 2e-4 (they are of
order 1-3) and cache entries within 2e-5. bfloat16 in the pooling's softmax
moves a pooled entry by 1e-3 to 1e-2 and bfloat16 logits move by up to 2^-9
of a logit, 4e-3 at 2: each fails its tolerance (held below).
"""

import dataclasses
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import manifest  # noqa: E402
import weights  # noqa: E402

from accelerate_tpu.models import DecoderConfig, DecoderLM  # noqa: E402
from accelerate_tpu.ops import eva as E  # noqa: E402
from accelerate_tpu.serving import SchedulerConfig, ServingEngine  # noqa: E402
from accelerate_tpu.serving.pages import CacheKind  # noqa: E402
from accelerate_tpu.serving.tiers import TierConfig  # noqa: E402
from accelerate_tpu.telemetry import spans as program_spans  # noqa: E402

ARCH = manifest.load_arch("evabyte")
REF = ARCH.reference
W, C, PS = 32, 4, 4          # window, chunk, page: a window is 8 pages, its summaries 2
LOGIT_TOL, ENTRY_TOL = 2e-4, 2e-5


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; a whole engine
    with interpreted kernels is then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def tiny(**over) -> dict:
    """The benchmark's configuration at the issue's small size: window 32,
    chunks and pages of 4, 3 layers, hidden 64, a head of 2 x 320 rows."""
    with open(os.path.join(BENCH, "configs", "evabyte-6.5b-serve-8l.json")) as f:
        c = json.load(f)
    c.pop("rehearsal")
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=3, window_size=W, chunk_size=C, num_pred_heads=2)
    c.update(over)
    return c


def program(c, seed=11, dtype=jnp.float32, **cfg_over):
    cfg = dataclasses.replace(ARCH.decoder_config(c, max_seq_len=256, remat=False, **cfg_over), dtype=dtype)
    params = weights.make_jit(REF, c, seed, dtype, adapt=ARCH.to_program_tree(c))
    return DecoderLM(cfg), params


def ref_logits(c, ids, seed=11, precision="float32"):
    w = weights.make_jit(REF, c, seed, jnp.float32)
    return np.asarray(REF.logits_at(c, w, ids, np.arange(len(ids)), precision, pad_to=8))


def engine(c, kernel=None, seed=11, **kw):
    model, params = program(c, seed, decode_kernel=kernel, prefill_kernel=kernel)
    kw = {"num_slots": 3, "max_cache_len": 256, "page_size": PS, "prefill_chunks": (8, 16),
          "prefix_cache": False, **kw}
    return ServingEngine(model, params, **kw)


class Through:
    """Prefill and decode of whole sequences through an engine's own cache,
    tables and bookkeeping (``_ensure_writable``, ``_release_behind_window``),
    dispatch by dispatch, with the logits of every row read back: what the
    engine's programs compute, before the sampling."""

    def __init__(self, eng):
        self.eng, self.defn = eng, eng._paged_def
        self.req = types.SimpleNamespace(pages_allocated=0, tenant=None)
        self.length = {}

        def pack(arena, ids, row_slot, row_pos, hist, tables):
            out, mut = self.defn.apply(
                {"params": eng.params, "cache": arena}, ids[None], positions=jnp.maximum(row_pos, 0)[None],
                use_cache=True, decode=True, cache_positions=row_pos[None], page_table=tables,
                ragged_slots=row_slot, slot_hist=hist, mutable=["cache"])
            return mut["cache"], out["logits"][0]

        def step(arena, tokens, lengths, active, tables):
            pos = jnp.where(active, lengths, eng.max_cache_len - 1)
            out, mut = self.defn.apply(
                {"params": eng.params, "cache": arena}, tokens[:, None], positions=pos[:, None],
                use_cache=True, decode=True, cache_positions=pos, page_table=tables,
                kv_lengths=jnp.where(active, lengths + 1, 0), mutable=["cache"])
            return mut["cache"], out["logits"][:, 0]

        self._pack, self._step = jax.jit(pack), jax.jit(step)

    def prefill(self, shares: list) -> dict:
        """One pack: ``shares`` is [(slot, ids of the slot's next positions)],
        each within one window. Returns {slot: logits of its rows}."""
        eng, bt = self.eng, self.eng._ragged_bt
        cap = max(c for c in eng._ragged_caps)
        ids, row_slot, row_pos = np.zeros(cap, np.int32), np.full(cap, -1, np.int32), np.full(cap, -1, np.int32)
        hist, r, where = np.zeros(eng.num_slots, np.int32), 0, {}
        for slot, new in shares:
            s0 = self.length.get(slot, 0)
            assert s0 // W == (s0 + len(new) - 1) // W, "a share lies in one window"
            eng._ensure_writable(self.req, slot, s0, s0 + len(new) - 1)
            nb = -(-len(new) // bt)
            ids[r:r + len(new)] = new
            row_slot[r:r + nb * bt] = slot
            row_pos[r:r + len(new)] = np.arange(s0, s0 + len(new))
            hist[slot], where[slot] = s0, (r, len(new))
            r += nb * bt
        eng._arena, logits = self._pack(eng._arena, jnp.asarray(ids), jnp.asarray(row_slot), jnp.asarray(row_pos),
                                        jnp.asarray(hist), eng._tables_arg())
        for slot, new in shares:
            self.length[slot] = self.length.get(slot, 0) + len(new)
            eng._release_behind_window(self.req, slot, self.length[slot])
        return {slot: np.asarray(logits[r0:r0 + n]) for slot, (r0, n) in where.items()}

    def decode(self, tokens: dict) -> dict:
        """One step: ``tokens`` {slot: the token at the slot's next position}."""
        eng, n = self.eng, self.eng.num_slots
        tok, lengths, active = np.zeros(n, np.int32), np.zeros(n, np.int32), np.zeros(n, bool)
        for slot, t in tokens.items():
            pos = self.length[slot]
            eng._release_behind_window(self.req, slot, pos)
            eng._ensure_writable(self.req, slot, pos, pos)
            tok[slot], lengths[slot], active[slot] = t, pos, True
        eng._arena, logits = self._step(eng._arena, jnp.asarray(tok), jnp.asarray(lengths), jnp.asarray(active),
                                        eng._tables_arg())
        for slot in tokens:
            self.length[slot] += 1
        return {slot: np.asarray(logits[slot]) for slot in tokens}

    def free(self, slot: int):
        self.eng._release_slot_pages(slot)
        del self.length[slot]

    def sequence(self, slot: int, ids, prompt_len: int, share: int = 16) -> np.ndarray:
        """Logits of every position of ``ids``: the first ``prompt_len`` in
        packs of up to ``share`` rows that end at a close, the rest a step each."""
        out, pos = [], 0
        while pos < prompt_len:
            n = min(share, prompt_len - pos, W - pos % W)
            out.append(self.prefill([(slot, ids[pos:pos + n])])[slot])
            pos += n
        for t in ids[prompt_len:]:
            out.append(self.decode({slot: int(t)})[slot][None])
        return np.concatenate(out)


IDS = np.random.default_rng(0).integers(0, 320, 3 * W + 17)


@pytest.mark.parametrize("kernel", [None, "interpret"], ids=["dense", "interpret"])
def test_prefill_then_decode_logits_are_the_references_across_three_closes(kernel):
    """A sequence whose prompt closes two windows and whose decode closes a
    third (prompt 2 W + 9, then W + 8 steps): the logits at every position,
    packs and steps alike, against the reference's whole forward."""
    c = tiny()
    thr = Through(engine(c, kernel))
    got = thr.sequence(0, IDS, prompt_len=2 * W + 9)
    want = ref_logits(c, IDS)
    assert got.shape == want.shape == (len(IDS), 320)
    assert thr.eng.windows_closed == 3
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # ... and the tolerance is one bfloat16 logits would fail (order of the logits 2^-9)
    assert np.abs(want.astype(jnp.bfloat16).astype(np.float32) - want).max() > 5 * LOGIT_TOL


def test_a_pack_whose_slots_sit_in_different_windows_and_a_slot_taken_again():
    """One pack carries rows of a slot in its third window and of a fresh
    slot in its first; a slot is freed and taken again by another sequence
    (its stale pages, table and summaries must not show)."""
    c = tiny()
    thr = Through(engine(c, "interpret"))
    a, b = IDS[:2 * W + 20], np.random.default_rng(1).integers(0, 320, W + 12)
    want_a, want_b = ref_logits(c, a), ref_logits(c, b)
    got_a = [thr.sequence(0, a[:2 * W + 4], prompt_len=2 * W + 4)]
    both = thr.prefill([(0, a[2 * W + 4:2 * W + 12]), (1, b[:8])])  # windows 2 and 0 in one pack
    got_a.append(both[0])
    got_b = [both[1]]
    for i in range(8):  # decode both, one in its third window and one in its first
        step = thr.decode({0: int(a[2 * W + 12 + i]), 1: int(b[8 + i])})
        got_a.append(step[0][None]); got_b.append(step[1][None])
    np.testing.assert_allclose(np.concatenate(got_a), want_a, atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(np.concatenate(got_b), want_b[:16], atol=LOGIT_TOL, rtol=0)
    in_use = thr.eng._allocator.in_use
    thr.free(0)
    assert thr.eng._allocator.in_use < in_use
    again = thr.sequence(0, b, prompt_len=W + 3)  # the freed slot, another sequence, across a close
    np.testing.assert_allclose(again, want_b, atol=LOGIT_TOL, rtol=0)


def test_the_engine_serves_the_references_tokens_across_closes_with_one_dispatch_in_flight():
    """The normal path whole: submit, packs, steps, one dispatch in flight.
    Three requests in windows of their own; every served token's logit lies
    within the tolerance of the reference's best, in prompts and decodes that
    cross closes; closes, pooled pages and released pages are counted."""
    c = tiny()
    eng = engine(c, "interpret")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 320, n) for n in (2 * W + 9, 5, W - 2, W + 1)]
    mark = program_spans.snapshot()[-1][0] if program_spans.snapshot() else 0
    reqs = [eng.submit(p, max_new_tokens=W + 8) for p in prompts]  # four requests over three slots
    eng.run()
    for p, r in zip(prompts, reqs):
        served = np.asarray(r.tokens)
        assert r.outcome == "finished" and len(served) == W + 8
        ref = ref_logits(c, np.concatenate([p, served[:-1]]))[len(p) - 1:]
        gap = ref.max(-1) - ref[np.arange(len(served)), served]
        assert gap.max() <= LOGIT_TOL, gap.max()
    lengths = [len(p) + W + 8 - 1 for p in prompts]  # positions written
    assert eng.windows_closed == sum(n // W for n in lengths)
    assert eng.pages_pooled == sum(n // PS for n in lengths)
    assert eng.pages_released == eng.windows_closed * (W // PS - W // C // PS)
    assert eng._allocator.in_use == 0 and not eng._slot_req
    steps = [s[5] for s in program_spans.snapshot() if s[0] > mark and s[2] == "serving/step"]
    assert sum(a["windows_closed"] for a in steps) == eng.windows_closed
    assert sum(a["pages_pooled"] for a in steps) == eng.pages_pooled
    held = [a for a in steps if a.get("live_tokens")]
    assert held and all(0 < a["entries_held"] <= a["live_tokens"] for a in held)
    assert min(a["entries_held"] / a["live_tokens"] for a in held) < 0.5  # closed windows stand as summaries
    m = eng.metrics()
    assert m["serving/windows_closed"] == eng.windows_closed and m["serving/pages_pooled"] == eng.pages_pooled


def test_the_entries_a_slot_holds_are_the_references_summaries_then_its_open_window():
    """Every window of a sequence closed by the engine's own bookkeeping: the
    slot's entries, read back from its pages through its table, are the
    reference's kbar, vbar of the closed windows followed by the open
    window's rotated k, v, in that order, in every layer."""
    c = tiny()
    eng = engine(c, "interpret")
    prompt = np.random.default_rng(3).integers(0, 320, 2 * W + 5)
    req = eng.submit(prompt, max_new_tokens=W)
    while eng._tables_host.closed[0] < 3:  # until the third window has closed
        eng.step()
    eng._settle()
    length = len(prompt) + req._dispatched - 1  # positions in the cache
    assert length // W == 3 and eng._kinds[0].entries(length) == 3 * (W // C) + length % W
    ids = np.concatenate([prompt, np.asarray(req.tokens)])[:length]
    w = weights.make_jit(REF, c, 11, jnp.float32)
    th = eng._tables_host
    n_entries = eng._kinds[0].entries(length)
    pages = th.rows[0, :-(-n_entries // PS)]
    assert th.alloc_count[0] == len(pages) and 0 not in pages
    for layer in range(c["num_hidden_layers"]):
        kbar, vbar, k, v = (np.asarray(x) for x in REF.closed_entries(c, w, ids, layer))
        want_k = np.concatenate([kbar[:3 * W // C], k[3 * W:]])
        want_v = np.concatenate([vbar[:3 * W // C], v[3 * W:]])
        leaves = eng._arena["layers"]["block"]["attn"]
        for name, want in (("cached_key", want_k), ("cached_value", want_v)):
            got = np.asarray(leaves[name][layer])[pages]            # [pages, KVH, page, D]
            got = got.transpose(0, 2, 1, 3).reshape(-1, *got.shape[1:2], got.shape[3])[:n_entries]
            np.testing.assert_allclose(got, want, atol=ENTRY_TOL, rtol=0, err_msg=f"{name} layer {layer}")


# -- the cache kind's bookkeeping --------------------------------------------


def test_the_tables_entry_count_at_every_position_of_three_windows_is_the_formula():
    """Position by position through three windows and into a fourth, by the
    engine's own growth and close: the entries a slot holds are ``L - W w +
    (W / C) w``, its table holds the pages of those entries and the pages
    aside, and the pages a close releases are back in the pool."""
    c = tiny()
    eng = engine(c)
    kind, th = eng._kinds[0], eng._tables_host
    assert kind.closes == (W, C) and kind.name == f"closing{W}" and th.aside == W // C // PS
    req = types.SimpleNamespace(pages_allocated=0, tenant=None)
    free0 = kind.allocator.free_count
    for pos in range(3 * W + 9):
        released = eng._release_behind_window(req, 0, pos)
        eng._ensure_writable(req, 0, pos, pos)
        w = pos // W
        held = pos + 1  # positions in the cache once ``pos`` is written
        assert kind.entries(held) == held - W * (held // W) + (W // C) * (held // W)
        assert released == (W // PS - th.aside if pos and pos % W == 0 else 0)
        assert th.closed[0] == w
        assert th.alloc_count[0] == kind.entries(pos) // PS + 1
        pages = th.slot_pages(0)
        assert len(pages) == th.alloc_count[0] + th.aside and len(set(pages)) == len(pages) and 0 not in pages
        assert kind.allocator.free_count == free0 - len(pages)  # what a close released is back in the pool
        assert ARCH.entries_held(c, held) == kind.entries(held)
    eng._release_slot_pages(0)
    assert kind.allocator.free_count == free0 and th.closed[0] == 0 and not th.aside_held[0]


def test_a_kind_whose_table_cannot_hold_its_entries_is_refused():
    with pytest.raises(ValueError, match="at least two windows"):
        CacheKind("closing32", None, 64, 2, pages_per_slot=W // PS, page_size=PS, layers=1, token_bytes=8,
                  closes=(W, C))
    with pytest.raises(ValueError, match="must be a chunk"):
        CacheKind("closing32", None, 64, 2, pages_per_slot=64, page_size=2 * PS, layers=1, token_bytes=8,
                  closes=(W, C))


# -- the pooling -------------------------------------------------------------


def _pool_case(seed=0, layers=3, pages=12, kvh=4, d=16, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (layers, pages, kvh, PS, d)).astype(dtype),
            jax.random.normal(k[1], (layers, pages, kvh, PS, d)).astype(dtype),
            jax.random.normal(k[2], (kvh, d)), jax.random.normal(k[3], (kvh, d)))


def test_the_pooling_kernel_interpreted_is_its_jax_numpy_form():
    """Three pages pooled in one layer of the stack, two into rows of one
    page, and a step of nothing (the parking page); everything no step names
    is bit for bit what it was, the other layers too."""
    kp, vp, mu, phi = _pool_case()
    src, dst, off = (jnp.asarray(x, jnp.int32) for x in ([3, 0, 5, 7], [9, 0, 9, 10], [1, 0, 2, 3]))
    layer = 1
    want_k, want_v = E.eva_pool_reference(kp[layer], vp[layer], mu, phi, src, dst, off, 0.25)
    got_k, got_v = jax.jit(lambda k, v: E.eva_pool_pages(
        k, v, mu, phi, src, dst, off, sm_scale=0.25, layer=layer, interpret=True))(kp, vp)
    for other in (0, 2):
        assert np.array_equal(np.asarray(got_k[other]), np.asarray(kp[other]))
        assert np.array_equal(np.asarray(got_v[other]), np.asarray(vp[other]))
    for got, want, before in ((got_k[layer], want_k, kp[layer]), (got_v[layer], want_v, vp[layer])):
        got, want, before = np.asarray(got), np.asarray(want), np.asarray(before)
        np.testing.assert_allclose(got[1:], want[1:], atol=1e-6, rtol=0)  # (the parking page holds anything)
        changed = {(int(p), int(o)) for s, p, o in zip(src, dst, off) if s}
        for page in range(1, got.shape[0]):
            for row in range(PS):
                if (page, row) not in changed:
                    assert np.array_equal(got[page, :, row], before[page, :, row]), (page, row)


def test_bfloat16_in_the_poolings_softmax_fails_the_entries_tolerance(monkeypatch):
    """The pooling's logits are float32 (``mixedp_attn``): taken in bfloat16
    they move a pooled entry by far more than the tolerance the cache is held
    to against the reference."""
    kp, vp, mu, phi = _pool_case(seed=5)
    sound = E.pool_chunks(kp[0], vp[0], mu, phi, 0.25)
    low = E.pool_chunks(kp[0].astype(jnp.bfloat16).astype(jnp.float32), vp[0], mu.astype(jnp.bfloat16),
                        phi.astype(jnp.bfloat16), 0.25)
    softmax = jax.nn.softmax
    monkeypatch.setattr(jax.nn, "softmax", lambda x, axis=-1: softmax(
        x.astype(jnp.bfloat16), axis=axis).astype(jnp.float32))
    lower = E.pool_chunks(kp[0], vp[0], mu, phi, 0.25)
    for a, b in zip(sound, lower):
        assert float(jnp.abs(a - b).max()) > 20 * ENTRY_TOL
    assert float(jnp.abs(sound[1] - low[1]).max()) > 20 * ENTRY_TOL


def test_whole_sequence_attention_is_the_references_layer():
    """``eva_attention`` (a forward pass without a cache) against the model's
    whole forward in the reference: the same logits."""
    c = tiny()
    model, params = program(c)
    ids = IDS[:2 * W + 11]
    got = np.asarray(model.apply({"params": params}, jnp.asarray(ids)[None])["logits"][0])
    np.testing.assert_allclose(got, ref_logits(c, ids), atol=LOGIT_TOL, rtol=0)


def test_the_head_multiplies_every_block_and_its_logits_leave_in_float32():
    """``num_pred_heads`` blocks of rows, block 0 returned; with ``fp32_logits``
    the product is not rounded to bfloat16 on its way out (some logit is no
    bfloat16 number), without it every logit is one; the norms scale by 1 + w."""
    c = tiny()
    model, params = program(c, dtype=jnp.bfloat16)
    assert params["lm_head"].shape == (64, 320 * 2) and params["layers"]["block"]["attn"]["eva_mu"].dtype == jnp.float32
    ids = jnp.asarray(IDS[:24])[None]
    is_bf16 = lambda x: np.array_equal(np.asarray(x), np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    full = model.apply({"params": params}, ids)["logits"]
    assert full.shape == (1, 24, 320) and full.dtype == jnp.float32 and not is_bf16(full)
    rounded = DecoderLM(dataclasses.replace(model.config, fp32_logits=False)).apply({"params": params}, ids)["logits"]
    assert is_bf16(rounded) and float(jnp.abs(rounded - full).max()) > 0
    plain = DecoderLM(dataclasses.replace(model.config, norm_unit_offset=False)).apply({"params": params}, ids)["logits"]
    assert float(jnp.abs(plain - full).max()) > 0.1  # norms of weight 0.1 N without their unit offset


# -- what the engine refuses -------------------------------------------------

REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_tiers": dict(kv_tiers=TierConfig(host_bytes=1 << 20)),
    "preemption": dict(scheduler=SchedulerConfig(preemption=True)),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_the_closing_kind_cannot_do_is_refused_by_name(feature):
    with pytest.raises(NotImplementedError, match=feature):
        engine(tiny(), **REFUSED[feature])


def test_quantized_pages_a_page_that_is_no_chunk_and_a_dense_cache_are_refused():
    c = tiny()
    model, params = program(c)
    with pytest.raises(NotImplementedError, match="unquantized"):
        engine(c, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="page_size 4"):
        ServingEngine(model, params, num_slots=2, max_cache_len=256, page_size=8, prefix_cache=False)
    with pytest.raises(ValueError, match="multiple of eva_chunk squared"):
        DecoderConfig(eva_window=24, eva_chunk=4)
    with pytest.raises(ValueError, match="no sliding window"):
        DecoderConfig(eva_window=32, eva_chunk=4, attn_window=8)
    with pytest.raises(NotImplementedError, match="keeps its cache in pages"):  # generate()'s dense cache
        model.apply({"params": params}, jnp.zeros((1, 8), jnp.int32), use_cache=True, mutable=["cache"])
