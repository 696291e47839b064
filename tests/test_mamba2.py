"""A model of half-blocks, Mamba-2 mixers (heads, a scalar decay a head, B and
C by group, a gated grouped norm), attention without rotation and LatentMoE
layers, against the plain reference of the architecture that brought them
(``benchmarks/reference/nemotron_h.py``, which imports nothing of the program),
at a small size on the CPU with seeded weights: the ``ssd_scan`` kernel against
its ``jax.numpy`` form, each layer kind and a whole model against the
reference, and the 4 MB-a-layer kind of state a slot in ``ServingEngine``."""

import dataclasses
import json
import os
import sys

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
from accelerate_tpu.ops import ssm as S  # noqa: E402
from accelerate_tpu.serving import ServingEngine, pages  # noqa: E402
from accelerate_tpu.telemetry import spans as program_spans  # noqa: E402

ARCH = manifest.load_arch("nemotron_h")
REF = ARCH.reference
CONFIG = os.path.join(BENCH, "configs", "nemotron3-super-120b-serve-11l-ep4.json")


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; a whole engine
    with interpreted kernels is then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def tiny(**over) -> dict:
    """The benchmark's configuration at its rehearsal's widths: published
    layers ``MEM*E``, the program's blocks ``ME | M | *E``; 8 of 32
    experts held, 4 a token."""
    with open(CONFIG) as f:
        c = json.load(f)
    rehearsal = c.pop("rehearsal")
    rehearsal.pop("limits")
    for group, values in rehearsal.items():
        if isinstance(c.get(group), dict):
            c[group].update(values)
        else:
            c[group] = values
    c.update(over)
    return c


def program(c, dtype, seed=11, **cfg_over):
    cfg = dataclasses.replace(ARCH.decoder_config(c, max_seq_len=256, remat=False, **cfg_over), dtype=dtype)
    params = weights.make_jit(REF, c, seed, dtype, adapt=ARCH.to_program_tree(c))
    return DecoderLM(cfg), params


def ref_logits(c, seed, dtype, ids, precision, rows=None):
    w = weights.make_jit(REF, c, seed, dtype)
    return np.asarray(REF.logits_at(c, w, ids, np.arange(len(ids)) if rows is None else rows, precision, pad_to=8))


IDS = np.random.default_rng(0).integers(0, 512, 56)


def _ring_mark() -> int:
    ring = program_spans.snapshot()
    return ring[-1][0] if ring else 0


def _args_since(mark: int, name: str) -> list:
    return [s[5] for s in program_spans.snapshot() if s[0] > mark and s[2] == name]


# -- the kernel against its jax.numpy form ---------------------------------


def _scan_case(slot, rows, fresh, layer, bt, heads=8, head_dim=8, groups=2, n=16, layers=3, slots=4, seed=0):
    """Blocks as the mixer hands them over: the step, the decay and the skip
    a channel (a head's scalar repeated over its channels)."""
    k = jax.random.split(jax.random.key(seed), 7)
    nb, width = len(slot), heads * head_dim
    a_head = lambda v: jnp.repeat(v, head_dim, axis=-1)
    args = (jax.random.normal(k[0], (nb, bt, width)),
            a_head(jax.nn.softplus(jax.random.normal(k[1], (nb, bt, heads)) - 3.0)),
            jax.random.normal(k[2], (nb, bt, groups, n)), jax.random.normal(k[3], (nb, bt, groups, n)),
            a_head(-jnp.exp(0.5 * jax.random.normal(k[4], (heads,)))), a_head(jax.random.normal(k[5], (heads,))),
            jax.random.normal(k[6], (layers, slots, *S.ssd_state_shape(width, groups, n))))
    kw = dict(block_slot=jnp.asarray(slot, jnp.int32), block_rows=jnp.asarray(rows, jnp.int32),
              block_fresh=jnp.asarray(fresh, jnp.int32), layer=layer)
    return args, kw


SCAN_CASES = {
    # a fresh slot over two blocks, a resumed slot whose last block is partial, a block of no rows that keeps its
    # slot, and a pack's padding
    "pack": dict(slot=[2, 2, 0, 0, -1, -1], rows=[8, 5, 3, 0, 0, 0], fresh=[1, 0, 0, 0, 0, 0], layer=1, bt=8),
    "pack_of_two_row_groups": dict(slot=[1, 3, 3], rows=[16, 16, 9], fresh=[0, 1, 0], layer=0, bt=16),
    "decode_step_with_a_dead_slot": dict(slot=[0, 1, 2, 3], rows=[1, 0, 1, 1], fresh=[0] * 4, layer=0, bt=1),
    "all_padding": dict(slot=[-1, -1], rows=[0, 0], fresh=[0, 0], layer=2, bt=8),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_kernel_interpreted_is_its_jax_numpy_form_in_place_in_the_layers_stack(case):
    spec = SCAN_CASES[case]
    args, kw = _scan_case(**spec)
    y0, s0 = S.ssd_scan(*args, impl="reference", **kw)
    y1, s1 = jax.jit(lambda *a: S.ssd_scan(*a, impl="interpret", **kw))(*args)
    for j, (slot, n) in enumerate(zip(spec["slot"], spec["rows"])):
        if slot >= 0 and n:
            np.testing.assert_allclose(np.asarray(y0[j, :n]), np.asarray(y1[j, :n]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(y1)).all()
    # what no block advances is bit for bit what it was: other layers, other slots, a dead slot, padding
    before, after = np.asarray(args[-1]), np.asarray(s1)
    touched = {s for s, n, f in zip(spec["slot"], spec["rows"], spec["fresh"]) if s >= 0 and (n or f)}
    for layer in range(before.shape[0]):
        for slot in range(before.shape[1]):
            if layer != spec["layer"] or slot not in touched:
                assert np.array_equal(before[layer, slot], after[layer, slot]), (layer, slot)


def _natural(state, heads, head_dim):
    """A slot's state [chunks, N, lane] as the equations have it, [H, P, N]."""
    chunks, n, lane = state.shape
    return np.moveaxis(np.asarray(state), 1, 2).reshape(heads, head_dim, n)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_the_recurrence_is_the_references_one_token_at_a_time(impl):
    """One slot, from zero, against ``reference/nemotron_h.recurrence`` (heads,
    B and C by group, a scalar decay a head) and, for the state it leaves,
    against the update written out in numpy."""
    heads, head_dim, groups, n, t = 8, 8, 2, 16, 16
    (x, dt, b, c, a, d, st), kw = _scan_case([0], [t], [1], 0, t, heads, head_dim, groups, n, layers=1, slots=1)
    y, s = S.ssd_scan(x, dt, b, c, a, d, st, impl=impl, **kw)
    per_head = lambda v: v[..., ::head_dim]
    want = REF.recurrence(x[0].reshape(t, heads, head_dim), per_head(dt[0]), b[0], c[0], per_head(a), per_head(d))
    np.testing.assert_allclose(np.asarray(y[0]).reshape(t, heads, head_dim), np.asarray(want), rtol=1e-4, atol=1e-4)
    x64, dt64, b64, a64 = (np.asarray(v, np.float64) for v in (x[0], per_head(dt[0]), b[0], per_head(a)))
    state = np.zeros((heads, head_dim, n))
    for i in range(t):
        b_h = np.repeat(b64[i], heads // groups, axis=0)
        state = np.exp(dt64[i] * a64)[:, None, None] * state \
            + (dt64[i][:, None] * x64[i].reshape(heads, head_dim))[:, :, None] * b_h[:, None, :]
    np.testing.assert_allclose(_natural(s[0, 0], heads, head_dim), state, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("what", ["state", "step"])
def test_bfloat16_in_the_state_or_the_step_fails_the_float32_tolerance(what):
    """The tolerance the kernel is held to above (2e-5) is float32's: the
    same recurrence with its state rounded to bfloat16 between the rows, or
    with the step rounded, misses it by two orders, so neither is what runs;
    and the kernel refuses a state that is not float32."""
    (x, dt, b, c, a, d, st), kw = _scan_case([0], [8], [1], 0, 8, layers=1, slots=1)
    y0, _ = S.ssd_scan(x, dt, b, c, a, d, st, impl="reference", **kw)
    low = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    if what == "step":
        y1, _ = S.ssd_scan(x, low(dt), b, c, a, d, st, impl="reference", **kw)
    else:
        rows, state = [], st
        for i in range(8):  # a row a call, the state rounded between
            y_i, state = S.ssd_scan(x[:, i:i + 1], dt[:, i:i + 1], b[:, i:i + 1], c[:, i:i + 1], a, d, low(state),
                                    impl="reference", **dict(kw, block_rows=jnp.ones(1, jnp.int32),
                                                             block_fresh=jnp.asarray([int(i == 0)])))
            rows.append(y_i)
        y1 = jnp.concatenate(rows, axis=1)
    assert float(jnp.abs(y1 - y0).max()) > 2e-3
    with pytest.raises(ValueError, match="float32"):
        S.ssd_scan(x, dt, b, c, a, d, st.astype(jnp.bfloat16), impl="reference", **kw)


def test_the_jax_numpy_form_differentiates():
    (x, dt, b, c, a, d, st), kw = _scan_case([0, 1], [8, 8], [1, 1], 0, 8, layers=1, slots=2)
    loss = lambda x, dt: jnp.sum(S.ssd_scan_reference(x, dt, b, c, a, d, st, **kw)[0] ** 2)
    gx, gdt = jax.grad(loss, argnums=(0, 1))(x, dt)
    assert np.isfinite(np.asarray(gx)).all() and float(jnp.abs(gdt).max()) > 0


# -- the model against the reference ----------------------------------------


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_each_half_block_is_the_references_layer(kind):
    """One published layer of each kind, which the program runs as a block
    that is a mixer alone or a feed-forward part alone: the program's whole
    forward pass in float32 against ``layer`` + ``head_logits`` of the
    reference (2e-4: float32 both sides, another order of summation)."""
    c = tiny(num_hidden_layers=1, hybrid_override_pattern=kind)
    assert ARCH.blocks(c) == [(kind, 0)]
    model, params = program(c, jnp.float32)
    block = params["layers_0"]["block"]
    assert set(block) == {"M": {"ln_attn", "ssm"}, "*": {"ln_attn", "attn"}, "E": {"ln_mlp", "moe_mlp"}}[kind]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        w = weights.make_jit(REF, c, 11, jnp.float32)
        h = jnp.take(w["embed"], jnp.asarray(IDS), axis=0).astype(jnp.float32)
        h = REF.layer(c, "float32", h, REF.layer_weights(c, w, 0), kind)
        want = np.asarray(REF.head_logits(c, "float32", {k: w[k] for k in REF.HEAD_LEAVES}, h))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_whole_mixer_shows_in_the_result():
    """Each piece of the mixer moves the logits when it is left out: the
    convolution's bias, the skip, the step's bias, A, the grouped norm's
    weight."""
    c = tiny(num_hidden_layers=1, hybrid_override_pattern="M")
    model, params = program(c, jnp.float32)
    run = lambda p: np.asarray(model.apply({"params": p}, jnp.asarray(IDS)[None])["logits"][0])
    want = run(params)
    for leaf, value in (("conv_b", 0.0), ("d_skip", 0.0), ("b_dt", 0.0), ("a_log", 0.0), ("norm_w", 1.0)):
        altered = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.full_like(x, value) if pages.leaf_name(path) == leaf else x, params)
        assert np.abs(run(altered) - want).max() > 1e-2, leaf


def test_a_whole_model_is_the_references_logits():
    """Published layers ``MEM*EME`` as blocks ``ME | M | *E | ME`` (four
    scans for seven half-blocks), float32; and the counts of parameters agree
    three ways."""
    c = tiny(hybrid_override_pattern="MEM*EME", num_hidden_layers=7)
    model, params = program(c, jnp.float32)
    assert ARCH.runs(c) == [("ME", 0, 1), ("M", 2, 1), ("*E", 3, 1), ("ME", 5, 1)]
    assert [n for _, n in model.config.kind_runs()] == [1, 1, 1, 1]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=3e-4)
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert held == model.config.num_params == ARCH.total_params(c)


def test_runs_of_a_kind_scan_together():
    """``MEMEME`` is one scan of three blocks, its experts one stack."""
    c = tiny(num_hidden_layers=7, hybrid_override_pattern="MEMEME*")
    model, params = program(c, jnp.float32)
    assert ARCH.runs(c) == [("ME", 0, 3), ("*", 6, 1)]
    assert params["layers_0"]["block"]["moe_mlp"]["w_up"].shape[:2] == (3, 8)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=3e-4)


def test_the_published_cut_counts_4648_million_parameters():
    """The configuration file whole: ``DecoderConfig.num_params`` counts the
    mixer with heads and the two-matrix experts in their latent, and agrees
    with the architecture's count from shapes and with ISSUE 44's table."""
    with open(CONFIG) as f:
        c = json.load(f)
    cfg = ARCH.decoder_config(c, max_seq_len=9216)
    assert cfg.num_params == ARCH.total_params(c) == 4_648_163_712
    assert [(cfg.layer_kinds[k][0], n) for k, n in cfg.kind_runs()] == [("ME", 3), ("M", 1), ("*E", 1), ("ME", 1)]
    kinds = dict(cfg.layer_kinds)
    m_kind = cfg.kind_config([n for n, _ in cfg.layer_kinds].index("M"))
    assert m_kind.state_slot_bytes == 4_194_304 + 3 * 10_240 * 4 and m_kind.ssm_conv_dim == 10_240
    assert kinds["ME"]["moe_experts_held"] == (0, 128) and kinds["ME"]["moe_router_outputs"] == 512
    assert S.ssd_state_shape(8192, 8, 128) == (64, 128, 128)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bfloat16_is_within_its_rounding_and_the_fp8_control_is_not(seed):
    """The program in bfloat16 (weights and activations; the recurrence and
    the residual stream float32) against the float32 reference on the same
    bfloat16 weights: the share of tokens whose first choice is the
    reference's and the median gap by which the program's first choice lies
    below the reference's best. The reference computed in fp8 in the
    program's place has to be far worse on both."""
    c = tiny()
    model, params = program(c, jnp.bfloat16, seed=seed)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    want = ref_logits(c, seed, jnp.bfloat16, IDS, "float32")
    low = ref_logits(c, seed, jnp.bfloat16, IDS, "fp8")
    rms = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
    agree = lambda x: float((x.argmax(-1) == want.argmax(-1)).mean())
    print("bfloat16 program", rms(got), agree(got), "fp8 reference", rms(low), agree(low))
    assert rms(got) < 0.5 * rms(low) and agree(got) >= 0.85 > agree(low)


# -- the state a slot in the serving engine ---------------------------------


def _engine(model, params, kernel=None, **kw):
    model = model.clone(config=dataclasses.replace(
        model.config, decode_kernel=kernel, prefill_kernel=kernel, ssm_kernel=kernel))
    args = dict(num_slots=4, max_cache_len=256, page_size=8, prefill_chunks=(16, 32), prefix_cache=False,
                num_pages=1 + 4 * 32)
    args.update(kw)
    return ServingEngine(model, params, **args)


def _served_gap(c, seed, dtype, prompts, reqs):
    """The widest gap by which a served token's logit lies below the float32
    reference's best (what the benchmark's ``served_logit_gap`` compares)."""
    w = weights.make_jit(REF, c, seed, dtype)
    worst = 0.0
    for prompt, req in zip(prompts, reqs):
        served = np.asarray(req.tokens)
        ids = np.concatenate([prompt, served[:-1]])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = np.asarray(REF.logits_at(c, w, ids, rows, "float32", pad_to=8))
        worst = max(worst, float((ref.max(-1) - ref[np.arange(len(served)), served]).max()))
    return worst


@pytest.mark.parametrize("kernel", ["interpret", None], ids=["kernels_interpreted", "jax_numpy"])
def test_prefill_in_packs_then_decode_through_state_and_pages_is_the_full_forward_pass(kernel):
    """Packed prefill, then decoding through the state and the pages, float32,
    against the reference's full forward pass over prompt + served tokens, on
    logits. The prompts are split across chunk boundaries that are no
    multiples of the token block (8): 70 = 32 + 32 + 6, 41 = 32 + 9, 90 = 32 +
    32 + 26; the short ones are co-admitted in one pack (5, 11 and 3 together,
    each padded to its block); seven requests over four slots, so slots sit
    at different depths and a slot is used again by a request that must start
    from zero. 8 of 32 experts are held, so the served logits leave out the
    same pairs the reference leaves out."""
    c = tiny()
    model, params = program(c, jnp.float32)
    eng = _engine(model, params, kernel)
    eng.warmup().mark_steady()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n) for n in (5, 11, 3, 70, 41, 23, 90)]
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    mark = _ring_mark()
    eng.run()
    assert eng.admission_recompiles == 0 and all(r.outcome == "finished" for r in reqs)
    assert _served_gap(c, 11, jnp.float32, prompts, reqs) <= 1e-3
    assert eng._allocator.in_use == 0
    m = eng.metrics()
    assert m["serving/ssd_kernel_active"] == int(kernel == "interpret") and m["serving/ssm_kernel_active"] == 0
    assert m["serving/state_in_place"] == 1 and m["serving/experts_from_stack"] == int(kernel == "interpret")
    packs = _args_since(mark, "serving/prefill_dispatch")
    assert max(p["ssm_slots"] for p in packs) >= 3
    assert sum(p["ssm_fresh_slots"] for p in packs) == len(prompts)
    assert sum(p["ssm_rows"] for p in packs) == sum(len(p) for p in prompts)


def test_a_slot_used_again_starts_from_zero_and_a_dead_slot_stays_as_it_is():
    c = tiny()
    model, params = program(c, jnp.float32)
    eng = _engine(model, params, "interpret", num_slots=2)
    eng.warmup().mark_steady()
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, 512, 21), rng.integers(0, 512, 37)
    first = eng.submit(a, max_new_tokens=8)
    eng.run()
    other = eng.submit(b, max_new_tokens=8)  # takes the slot the first one left, over its state
    eng.run()
    state = lambda: {jax.tree_util.keystr(p): np.asarray(l) for p, l in
                     jax.tree_util.tree_flatten_with_path(eng._arena)[0] if pages.is_state_leaf(p)}
    before = state()
    assert len(before) == 4 and all(np.abs(v).max() > 0 for v in before.values())  # 2 runs x (state, conv inputs)
    again = eng.submit(a, max_new_tokens=8)
    slot = None
    while not again.done:
        eng.step()
        slot = again.slot if again.slot is not None else slot
    assert again.tokens == first.tokens and other.tokens != first.tokens
    after = state()
    for name in before:
        dead = 1 - slot
        assert np.array_equal(before[name][:, dead], after[name][:, dead]), name
        assert not np.array_equal(before[name][:, slot], after[name][:, slot]), name


def test_the_arena_counts_the_state_and_a_half_block_without_a_mixer_keeps_nothing():
    c = tiny(hybrid_override_pattern="MEM*EEM", num_hidden_layers=7)  # ... | *E | E | M: a block that is E alone
    model, params = program(c, jnp.float32)
    assert [name for name, _ in ARCH.blocks(c)] == ["ME", "M", "*E", "E", "M"]
    eng = _engine(model, params)
    d, cd, n, k = 64, 64 + 2 * 2 * 16, 16, c["conv_kernel"]
    slot_bytes = 3 * (d * n * 4 + (k - 1) * cd * 4)  # three Mamba-2 layers; float32 activations here
    assert eng._state_kind.slot_bytes == slot_bytes and not eng._state_kind.paged
    assert eng.state_bytes == pages.state_nbytes(eng._arena) == 4 * slot_bytes
    assert [kind.name for kind in eng._kinds] == ["full"] and eng._kinds[0].layers == 1
    m = eng.metrics()
    assert (m["serving/state_bytes"], m["serving/state_bytes_per_slot"]) == (4 * slot_bytes, slot_bytes)
    mark = _ring_mark()
    prompts = [np.arange(9) + i for i in range(2)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.run()
    assert _served_gap(c, 11, jnp.float32, prompts, reqs) <= 1e-3
    decodes = _args_since(mark, "serving/decode_dispatch")
    assert decodes and all(d["ssm_slots"] == d["ssm_rows"] == d["slots"] for d in decodes)


def test_several_tokens_a_slot_in_one_step_are_refused_by_the_mixer():
    c = tiny(num_hidden_layers=1, hybrid_override_pattern="M")
    model, params = program(c, jnp.float32)
    cfg = dataclasses.replace(model.config, kv_page_size=8, kv_num_pages=9)
    with pytest.raises(NotImplementedError, match="rolled back"):
        jax.eval_shape(lambda p: DecoderLM(cfg).apply(
            {"params": p}, jnp.zeros((2, 3), jnp.int32), use_cache=True, decode=True,
            cache_positions=jnp.zeros((2, 3), jnp.int32), page_table=jnp.zeros((2, 4), jnp.int32),
            mutable=["cache"]), params)


@pytest.mark.parametrize("over", [dict(ssm_num_heads=None), dict(ssm_n_groups=3), dict(ssm_state_dim=0),
                                  dict(mixer="none", mlp_kind="none"), dict(mlp_kind="gelu")],
                         ids=["no_heads", "groups_that_do_not_divide_the_heads", "no_state", "nothing_at_all",
                              "an_unknown_feed_forward"])
def test_the_config_refuses_what_is_no_layer(over):
    fields = dict(mixer="ssd", ssm_num_heads=8, ssm_head_dim=8, ssm_n_groups=2, ssm_state_dim=16)
    fields.update(over)
    with pytest.raises(ValueError, match="ssd|state-space|mixer|mlp_kind"):
        DecoderConfig.tiny(**fields)
