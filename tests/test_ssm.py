"""A model with selective state-space layers beside attention layers without
rotation, against the plain reference of the architecture that brought them
(``benchmarks/reference/jamba.py``, which imports nothing of the program), at
a small size on the CPU with seeded weights: the ``ssm_scan`` kernel against
its ``jax.numpy`` form, each layer kind and a whole model against the
reference, and the recurrent state a slot in ``ServingEngine``."""

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

ARCH = manifest.load_arch("jamba")
REF = ARCH.reference


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; a whole engine
    with interpreted kernels is then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def tiny(**over) -> dict:
    """The benchmark's configuration at its rehearsal's widths: 5 layers,
    attention at 1 and 4, so state-space runs of 1 and of 2 layers."""
    with open(os.path.join(BENCH, "configs", "jamba2-3b-serve-28l.json")) as f:
        c = json.load(f)
    for group, values in c.pop("rehearsal").items():
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
    """The newest span's id: the ring is bounded, so a position in it says
    nothing once it has wrapped (as it has, late in the suite)."""
    ring = program_spans.snapshot()
    return ring[-1][0] if ring else 0


def _args_since(mark: int, name: str) -> list:
    return [s[5] for s in program_spans.snapshot() if s[0] > mark and s[2] == name]
RMS_LIMITS, GAP_LIMITS = (0.03, 0.06), (0.05, 0.1)  # bfloat16 under the first, the fp8 control over the second


# -- the kernel against its jax.numpy form ---------------------------------


def _scan_case(slot, rows, fresh, layer, bt, width=256, n=4, layers=3, slots=4, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    nb = len(slot)
    args = (jax.random.normal(k[0], (nb, bt, width)).astype(jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(k[1], (nb, bt, width)) - 3.0),
            jax.random.normal(k[2], (nb, bt, n)), jax.random.normal(k[3], (nb, bt, n)),
            -jnp.exp(0.5 * jax.random.normal(k[4], (n, width))), jax.random.normal(k[5], (width,)),
            jax.random.normal(k[6], (layers, slots, n, width)))
    kw = dict(block_slot=jnp.asarray(slot, jnp.int32), block_rows=jnp.asarray(rows, jnp.int32),
              block_fresh=jnp.asarray(fresh, jnp.int32), layer=layer)
    return args, kw


SCAN_CASES = {
    # a fresh slot over two blocks, a resumed slot whose last block is partial, a block of no rows that keeps its
    # slot, and a pack's padding
    "pack": dict(slot=[2, 2, 0, 0, -1, -1], rows=[8, 5, 3, 0, 0, 0], fresh=[1, 0, 0, 0, 0, 0], layer=1, bt=8),
    "decode_step_with_an_idle_slot": dict(slot=[0, 1, 2, 3], rows=[1, 0, 1, 1], fresh=[0] * 4, layer=0, bt=1),
    "all_padding": dict(slot=[-1, -1], rows=[0, 0], fresh=[0, 0], layer=2, bt=8),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_kernel_interpreted_is_its_jax_numpy_form(case):
    spec = SCAN_CASES[case]
    args, kw = _scan_case(**spec)
    y0, s0 = S.ssm_scan(*args, impl="reference", **kw)
    y1, s1 = jax.jit(lambda *a: S.ssm_scan(*a, impl="interpret", **kw))(*args)
    for j, (slot, n) in enumerate(zip(spec["slot"], spec["rows"])):
        if slot >= 0 and n:
            np.testing.assert_allclose(np.asarray(y0[j, :n]), np.asarray(y1[j, :n]), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(y1)).all()
    # what no block advances is bit for bit what it was: other layers, other slots, an idle slot, padding
    before, after = np.asarray(args[-1]), np.asarray(s1)
    touched = {s for s, n, f in zip(spec["slot"], spec["rows"], spec["fresh"]) if s >= 0 and (n or f)}
    for layer in range(before.shape[0]):
        for slot in range(before.shape[1]):
            if layer != spec["layer"] or slot not in touched:
                assert np.array_equal(before[layer, slot], after[layer, slot]), (layer, slot)


def test_the_recurrence_is_the_textbook_loop():
    """One slot, from zero, against the recurrence written out in numpy."""
    (u, dt, b, c, a, d, st), kw = _scan_case([0], [8], [1], 0, 8, width=32, layers=1, slots=1)
    y, s = S.selective_scan_reference(u, dt, b, c, a, d, st, **kw)
    u, dt, b, c, a, d = (np.asarray(x, np.float64) for x in (u.astype(jnp.float32), dt, b, c, a, d))
    state = np.zeros_like(a)
    for t in range(8):
        state = np.exp(dt[0, t][None] * a) * state + (dt[0, t] * u[0, t])[None] * b[0, t][:, None]
        np.testing.assert_allclose(np.asarray(y[0, t]), state.T @ c[0, t] + d * u[0, t], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s[0, 0]), state, rtol=1e-4, atol=1e-5)


def test_the_jax_numpy_form_differentiates():
    (u, dt, b, c, a, d, st), kw = _scan_case([0, 1], [8, 8], [1, 1], 0, 8, width=32, layers=1, slots=2)
    loss = lambda u, dt: jnp.sum(S.selective_scan_reference(u.astype(jnp.float32), dt, b, c, a, d, st, **kw)[0] ** 2)
    gu, gdt = jax.grad(loss, argnums=(0, 1))(u.astype(jnp.float32), dt)
    assert np.isfinite(np.asarray(gu)).all() and float(jnp.abs(gdt).max()) > 0


# -- the model against the reference ----------------------------------------


@pytest.mark.parametrize("attention_layer", [False, True], ids=["state_space", "attention_without_rotation"])
def test_each_layer_kind_is_the_references_layer(attention_layer):
    """One layer of each kind: the program's whole forward pass in float32
    against ``layer`` + ``head_logits`` of the reference (2e-4: float32 both
    sides, another order of summation)."""
    c = tiny(num_hidden_layers=1, attn_layer_period=1 if attention_layer else 2, attn_layer_offset=0 if attention_layer else 1)
    assert REF.layer_kinds(c) == [attention_layer]
    model, params = program(c, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        w = weights.make_jit(REF, c, 11, jnp.float32)
        h = jnp.take(w["embed"], jnp.asarray(IDS), axis=0).astype(jnp.float32)
        h = REF.layer(c, "float32", h, REF.layer_weights(c, w, 0), 0)
        want = np.asarray(REF.head_logits(c, "float32", {k: w[k] for k in REF.HEAD_LEAVES}, h))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_whole_mixer_shows_in_the_result():
    """Each piece of the mixer moves the logits when it is left out: the
    three inner norms, the convolution's bias, the skip, the gate (its input
    zeroed makes silu(z) 0), the step's bias and A."""
    c = tiny()
    model, params = program(c, jnp.float32)
    run = lambda p: np.asarray(model.apply({"params": p}, jnp.asarray(IDS)[None])["logits"][0])
    want = run(params)
    for leaf, value in (("norm_dt", 1.0), ("norm_b", 1.0), ("norm_c", 1.0), ("conv_b", 0.0), ("d_skip", 0.0),
                        ("b_dt", 0.0), ("a_log", 0.0)):
        altered = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.full_like(x, value) if pages.leaf_name(path) == leaf else x, params)
        assert np.abs(run(altered) - want).max() > 1e-2, leaf


def test_a_whole_model_is_the_references_logits():
    """5 layers, attention at 1 and 4 (state-space runs of 1 and 2 layers),
    float32; and the counts of parameters agree three ways."""
    c = tiny()
    model, params = program(c, jnp.float32)
    assert [n for _, n in model.config.kind_runs()] == [1, 1, 2, 1]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=3e-4)
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert held == model.config.num_params == ARCH.total_params(c)


def test_the_published_model_counts_three_billion_parameters():
    with open(os.path.join(BENCH, "configs", "jamba2-3b-serve-28l.json")) as f:
        c = json.load(f)
    cfg = ARCH.decoder_config(c, max_seq_len=8192)
    assert cfg.num_params == ARCH.total_params(c) == 3_029_337_472
    assert [n for _, n in cfg.kind_runs()] == [7, 1, 13, 1, 6]
    assert ARCH.ssm_state_bytes(c) == 26 * 358_400 and ARCH.kv_bytes_per_token(c) == 1024


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bfloat16_is_within_its_rounding_and_the_fp8_control_is_not(seed):
    """The program in bfloat16 (weights and activations; the recurrence and
    the residual stream float32) against the float32 reference on the same
    bfloat16 weights, over all logits: the root mean square error and the
    gap the benchmark compares, by which the program's first choice lies
    below the reference's best. The reference computed in fp8 in the
    program's place has to read beyond both limits."""
    c = tiny()
    model, params = program(c, jnp.bfloat16, seed=seed)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    want = ref_logits(c, seed, jnp.bfloat16, IDS, "float32")
    low = ref_logits(c, seed, jnp.bfloat16, IDS, "fp8")
    rms = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
    gap = lambda x: float((want.max(-1) - want[np.arange(len(want)), x.argmax(-1)]).max())
    print("bfloat16 program", rms(got), gap(got), "fp8 reference", rms(low), gap(low))
    assert rms(got) <= RMS_LIMITS[0] < RMS_LIMITS[1] <= rms(low)
    assert gap(got) <= GAP_LIMITS[0] < GAP_LIMITS[1] <= gap(low)


def test_generate_through_the_state_is_the_full_forward_pass():
    """``generate()``: the prompt in one call from a zero state, then one
    token a call from the cache's state, greedy, against the reference's
    first choices over prompt + generated."""
    from accelerate_tpu.generation import generate

    c = tiny()
    model, params = program(c, jnp.float32)
    out = np.asarray(generate(model, params, jnp.asarray(IDS[:20])[None], max_new_tokens=12))[0]
    ids, new = out[:-1], out[20:]
    ref = ref_logits(c, 11, jnp.float32, ids, "float32", rows=np.arange(19, 19 + len(new)))
    assert float((ref.max(-1) - ref[np.arange(len(new)), new]).max()) <= 1e-3


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


@pytest.mark.parametrize("dtype,kernel,limit", [(jnp.float32, "interpret", 1e-3), (jnp.float32, None, 1e-3),
                                                (jnp.bfloat16, None, 0.06)],
                         ids=["float32_kernels_interpreted", "float32_jax_numpy", "bfloat16_jax_numpy"])
def test_prefill_then_decode_through_the_state_is_the_full_forward_pass(dtype, kernel, limit):
    """Packed prefill, then decoding through the state and the pages,
    against the reference's full forward pass over prompt + served tokens.
    The prompts are split across chunk boundaries that are no multiples of
    the token block (8): 70 = 32 + 32 + 6, 41 = 32 + 9, 90 = 32 + 32 + 26; the
    short ones are co-admitted in one pack (5, 11 and 3 together, each
    padded to its block); six requests over four slots, so a slot is used
    again by a request that must start from zero."""
    c = tiny()
    model, params = program(c, dtype)
    eng = _engine(model, params, kernel)
    eng.warmup().mark_steady()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n) for n in (5, 11, 3, 70, 41, 23, 90)]
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    mark = _ring_mark()
    eng.run()
    assert eng.admission_recompiles == 0 and all(r.outcome == "finished" for r in reqs)
    assert _served_gap(c, 11, dtype, prompts, reqs) <= limit
    assert eng._allocator.in_use == 0
    m = eng.metrics()
    assert m["serving/ssm_kernel_active"] == int(kernel == "interpret") and m["serving/state_in_place"] == 1
    packs = _args_since(mark, "serving/prefill_dispatch")
    # several requests in one pack, and every request zeroed once, in a pack, with no dispatch of its own
    assert max(p["ssm_slots"] for p in packs) >= 3
    assert sum(p["ssm_fresh_slots"] for p in packs) == len(prompts)
    assert sum(p["ssm_rows"] for p in packs) == sum(len(p) for p in prompts)


def test_a_slot_used_again_starts_from_zero_and_an_idle_slot_stays_as_it_is():
    """The same prompt served twice through one slot, the second time over
    the first request's leftover state, gives the same tokens; while it
    decodes, the other slots' states do not move."""
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
    for name in before:  # the slot that sat idle through prefill packs and decode steps
        idle = 1 - slot
        assert np.array_equal(before[name][:, idle], after[name][:, idle]), name
        assert not np.array_equal(before[name][:, slot], after[name][:, slot]), name


def test_the_arena_counts_the_state_and_the_step_says_how_much_is_held():
    c = tiny()
    model, params = program(c, jnp.float32)
    eng = _engine(model, params)
    d, n, k = 2 * c["hidden_size"], c["mamba_d_state"], c["mamba_d_conv"]
    slot_bytes = 3 * d * (n * 4 + (k - 1) * 4)  # three state-space layers; float32 activations here
    assert eng._state_kind.slot_bytes == slot_bytes and not eng._state_kind.paged
    assert eng._state_kind.allocator is None and eng._state_kind.token_bytes == 0
    assert eng.state_bytes == pages.state_nbytes(eng._arena) == 4 * slot_bytes
    assert [k.name for k in eng._kinds] == ["full"] and eng._kinds[0].layers == 2
    assert eng.arena_bytes == pages.arena_nbytes(eng._arena) == eng.state_bytes + sum(
        int(l.nbytes) for l in pages.paged_leaves(eng._arena)) + 2 * 4  # and the attention layers' cache_index
    m = eng.metrics()
    assert (m["serving/state_bytes"], m["serving/state_bytes_per_slot"]) == (4 * slot_bytes, slot_bytes)
    mark = _ring_mark()
    reqs = [eng.submit(np.arange(9) + i, max_new_tokens=3) for i in range(2)]
    eng.run()
    steps = _args_since(mark, "serving/step")
    assert max(s["state_bytes_in_use"] for s in steps) == 2 * slot_bytes and steps[-1]["state_bytes_in_use"] == 0
    decodes = _args_since(mark, "serving/decode_dispatch")
    assert decodes and all(d["ssm_slots"] == d["slots"] for d in decodes)
    # the page operations leave the state alone: by name, though ssm_state has a page leaf's rank
    forked = pages.fork_page(eng._arena, 1, 2)
    for (p, x), y in zip(jax.tree_util.tree_flatten_with_path(eng._arena)[0], jax.tree_util.tree_leaves(forked)):
        assert pages.is_paged_leaf(p) or x is y
        assert pages.is_state_leaf(p) == (pages.leaf_name(p) in ("ssm_state", "conv_state"))
    assert len(pages.gather_page(eng._arena, 1)) == len(pages.paged_leaves(eng._arena)) == 4


def test_a_model_without_a_state_says_nothing_of_one():
    model = DecoderLM(DecoderConfig.tiny(num_layers=2))
    from accelerate_tpu.parallel.sharding import unbox_params

    params, _ = unbox_params(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    eng = ServingEngine(model, params, num_slots=2, max_cache_len=64, page_size=8, prefill_chunks=(8, 16))
    assert eng._state_kind is None and eng.state_bytes == 0
    assert not any("state" in k or "ssm" in k for k in eng.metrics())


def test_several_tokens_a_slot_in_one_step_are_refused_by_the_mixer():
    c = tiny(num_hidden_layers=1, attn_layer_period=2, attn_layer_offset=1)
    model, params = program(c, jnp.float32)
    cfg = dataclasses.replace(model.config, kv_page_size=8, kv_num_pages=9)
    with pytest.raises(NotImplementedError, match="rolled back"):
        jax.eval_shape(lambda p: DecoderLM(cfg).apply(
            {"params": p}, jnp.zeros((2, 3), jnp.int32), use_cache=True, decode=True,
            cache_positions=jnp.zeros((2, 3), jnp.int32), page_table=jnp.zeros((2, 4), jnp.int32),
            mutable=["cache"]), params)


@pytest.mark.parametrize("field,value", [("mixer", "mamba2"), ("ssm_kernel", "pallas"), ("ssm_state_dim", 0)])
def test_the_config_refuses_what_is_no_mixer(field, value):
    with pytest.raises(ValueError, match="mixer|ssm_kernel"):
        DecoderConfig.tiny(**{"mixer": "ssm", field: value})


def test_attention_without_rotation_is_no_rope_of_width_zero():
    cfg = DecoderConfig.tiny(rope_dim=0)
    assert cfg.rotary_dim == 0 and DecoderConfig.tiny().rotary_dim == cfg.head_dim
    model = DecoderLM(cfg)
    ids = jnp.asarray(IDS[:16] % 256)[None]
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    plain = model.apply({"params": params}, ids)["logits"]
    moved = model.apply({"params": params}, ids, positions=jnp.arange(16) + 40)["logits"]
    assert np.array_equal(np.asarray(plain), np.asarray(moved))  # positions reach nothing
    with pytest.raises(ValueError, match="rope_dim"):
        DecoderConfig.tiny(rope_dim=3)


def test_the_tiers_never_take_a_state_leaf_for_pages():
    """``ssm_state`` under a scanned stack has a page leaf's rank: the tiers
    find pages by the leaf's name, as ``pages.py`` does, whatever the shape."""
    import base64

    from accelerate_tpu.serving import tiers

    arr = np.zeros((2, 2, 4, 8), np.float32)  # [layers, slots = n_pages, N, D]: the rank and the count fit
    leaf = lambda path: {"path": path, "dtype": "float32", "shape": list(arr.shape),
                         "data": base64.b64encode(arr.tobytes()).decode("ascii")}
    doc = {"tokens": list(range(16)), "token_len": 16, "n_pages": 2, "leaves": [leaf("['layers']['block']['attn']['cached_key']")]}
    assert tiers.handoff_to_entry(doc).n_pages == 2
    doc["leaves"].append(leaf("['layers_0']['block']['ssm']['ssm_state']"))
    with pytest.raises(ValueError, match="ssm_state.*not a paged leaf"):
        tiers.handoff_to_entry(doc)
