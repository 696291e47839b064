"""A model that states layer kinds (window and full attention with their
own kv heads, widths and rotary base; dense and expert MLPs), against the
plain reference of the architecture that brought them
(``benchmarks/reference/mimo_v2_flash.py``, which imports nothing of the
program), at a small size on the CPU with seeded weights; the cache by
layer kind in ``ServingEngine``; and what such a model is refused."""

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
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.serving.scheduler import SchedulerConfig  # noqa: E402

ARCH = manifest.load_arch("mimo_v2_flash")
REF = ARCH.reference


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; a whole engine
    with interpreted kernels is then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def tiny(layers=7, **over) -> dict:
    """The benchmark's configuration at its rehearsal's widths."""
    with open(os.path.join(BENCH, "configs", "mimo-v2-flash-serve-7l-ep16.json")) as f:
        c = json.load(f)
    for group, values in c.pop("rehearsal").items():
        if isinstance(c.get(group), dict):
            c[group].update(values)
        else:
            c[group] = values
    c.update(num_hidden_layers=layers, **over)
    return c


def program(c, dtype, seed=11, **cfg_over):
    cfg = dataclasses.replace(ARCH.decoder_config(c, max_seq_len=256, remat=False, **cfg_over), dtype=dtype)
    params = weights.make_jit(REF, c, seed, dtype, adapt=ARCH.to_program_tree(c))
    return DecoderLM(cfg), params


def ref_logits(c, seed, dtype, ids, precision):
    w = weights.make_jit(REF, c, seed, dtype)
    return np.asarray(REF.logits_at(c, w, ids, np.arange(len(ids)), precision, pad_to=8))


IDS = np.random.default_rng(0).integers(0, 512, 56)  # crosses the 16-wide window three times


@pytest.mark.parametrize("window_layer,expert_layer", [(0, 0), (1, 0), (0, 1), (1, 1)],
                         ids=["full_dense", "window_dense", "full_experts", "window_experts"])
def test_each_layer_kind_is_the_references_layer(window_layer, expert_layer):
    """One layer of each kind: the program's whole forward pass in float32
    against ``layer`` + ``head_logits`` of the reference. 2e-4: float32 both
    sides, another order of summation (logits are of order 1)."""
    c = tiny(1, hybrid_layer_pattern=[window_layer], moe_layer_freq=[expert_layer])
    model, params = program(c, jnp.float32)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    w = weights.make_jit(REF, c, 11, jnp.float32)
    h = jnp.take(w["embed"], jnp.asarray(IDS), axis=0)
    h = REF.layer(c, "float32", h, REF.layer_weights(c, w, 0), 0)
    want = np.asarray(REF.head_logits(c, "float32", {k: w[k] for k in REF.HEAD_LEAVES}, h))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_three_periods_of_layers_are_the_references_logits():
    """1 + 3 x 6 layers in published order, float32: 5e-4 over 19 layers."""
    c = tiny(19)
    model, params = program(c, jnp.float32)
    assert [n for _, n in model.config.kind_runs()] == [1, 4, 1, 5, 1, 5, 1, 1]
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=5e-4)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bfloat16_is_within_its_rounding_and_the_fp8_control_is_not(seed):
    """The program in bfloat16 (weights and activations) against the float32
    reference on the same bfloat16 weights. Single logits move further than
    rounding alone where the 8th and 9th expert of a token change places, so
    the two readings are over all logits: their root mean square error
    (0.010-0.018 over these seeds; the reference computed in fp8 in the
    program's place 0.085-0.092) and the gap the benchmark compares, by
    which the program's first choice lies below the reference's best
    (0.001-0.020 against 0.13-0.14). Each limit has room on both sides."""
    c = tiny(7)
    model, params = program(c, jnp.bfloat16, seed=seed)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    want = ref_logits(c, seed, jnp.bfloat16, IDS, "float32")
    low = ref_logits(c, seed, jnp.bfloat16, IDS, "fp8")
    rms = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
    gap = lambda x: float((want.max(-1) - want[np.arange(len(want)), x.argmax(-1)]).max())
    print("bfloat16 program", rms(got), gap(got), "fp8 reference", rms(low), gap(low))
    assert rms(got) <= 0.03 < 0.06 <= rms(low)
    assert gap(got) <= 0.04 < 0.09 <= gap(low)


def _engine(model, params, kernel=None, **kw):
    model = model.clone(config=dataclasses.replace(model.config, decode_kernel=kernel, prefill_kernel=kernel))
    args = dict(num_slots=4, max_cache_len=256, page_size=8, prefill_chunks=(16, 32), prefix_cache=False,
                num_pages=1 + 4 * 32, kind_pages={"window16": 48})
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


@pytest.mark.parametrize("dtype,kernel,limit", [(jnp.float32, "interpret", 1e-3), (jnp.bfloat16, None, 0.05)],
                         ids=["float32_kernels_interpreted", "bfloat16_dense_paths"])
def test_prefill_then_decode_through_the_cache_by_kind_is_the_full_forward_pass(dtype, kernel, limit):
    """Packed prefill, then decoding through pages of two kinds with the
    window's pages released, against the reference's full forward pass over
    prompt + served tokens. Prompts cross the 16-wide window many times and
    a slot is used again. float32: the served token is the reference's own
    within 1e-3; bfloat16: within 0.05, bfloat16's rounding (the benchmark's
    rehearsal holds 0.05 at these widths over its seeds)."""
    c = tiny(7)
    model, params = program(c, dtype)
    eng = _engine(model, params, kernel)
    eng.warmup().mark_steady()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n) for n in (5, 70, 41, 19, 23, 90)]
    reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
    eng.run()
    assert eng.admission_recompiles == 0 and all(r.outcome == "finished" for r in reqs)
    assert _served_gap(c, 11, dtype, prompts, reqs) <= limit
    # every page is back, of both kinds, and pages were given back on the way
    assert [k.allocator.in_use for k in eng._kinds] == [0, 0] and eng.pages_released > 0


def test_the_shares_of_all_holders_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: 4 chips hold 4
    of 16 experts each; the program's expert layer on each computes its own
    experts' part, and the parts add up to what the reference gives for the
    whole layer with all 16 held."""
    from accelerate_tpu.models.moe import MoeMLP

    c = tiny(2, hybrid_layer_pattern=[0, 0], moe_layer_freq=[0, 1])
    whole = dict(c, n_routed_experts=16)
    w = weights.make_jit(REF, whole, 5, jnp.float32)
    lw = REF.layer_weights(whole, w, 1)
    y = jax.random.normal(jax.random.PRNGKey(0), (24, c["hidden_size"]))
    want = np.asarray(REF.experts(whole, "float32", y, lw))
    parts = 0
    for first in range(0, 16, 4):
        cfg = DecoderConfig.tiny(
            embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"], moe_num_experts=4,
            moe_router_outputs=16, moe_experts_held=(first, 4), moe_top_k=c["num_experts_per_tok"],
            moe_scoring="sigmoid", moe_selection_bias=True)
        held = {"router": lw["router"], "selection_bias": lw["router_bias"],
                "w_gate": lw["gate_exp"][first:first + 4], "w_up": lw["up_exp"][first:first + 4],
                "w_down": lw["down_exp"][first:first + 4]}
        part, _ = MoeMLP(cfg).apply({"params": held}, y[None])
        parts = parts + np.asarray(part[0])
    np.testing.assert_allclose(parts, want, atol=2e-5)
    assert np.abs(want).max() > 0.01  # the layer adds something to be right about


def test_window_pages_are_released_and_never_read_again():
    """A context that crosses many windows: the window kind never holds more
    than the window's pages and one write ahead, its released pages go to
    other slots while the request still decodes, and the served tokens stay
    the reference's (a page read after its release would show there)."""
    c = tiny(7)
    model, params = program(c, jnp.float32)
    eng = _engine(model, params)
    window_kind = eng._kinds[1]
    assert (window_kind.name, window_kind.window) == ("window16", 16)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, n) for n in (120, 33, 64, 17, 80, 9)]
    reqs = [eng.submit(p, max_new_tokens=60) for p in prompts]
    held = []
    while eng.step():
        th = window_kind.tables
        held.append(max(th.alloc_count[s] - th.released[s] for s in range(4)))
        for slot, req in eng._slot_req.items():
            live = th.slot_pages(slot)
            assert len(set(live)) == len(live) and 0 not in live
    # 16 positions span at most 3 pages of 8; a 32-row pack holds 4 more while it is written
    assert max(held) <= 3 + 4 + 1 and sorted(held)[len(held) // 2] <= 4
    assert eng.pages_released >= sum(len(p) + 60 - 16 for p in prompts) // 8 - 6
    assert _served_gap(c, 11, jnp.float32, prompts, reqs) <= 1e-3
    full, window = eng.metrics()["serving/pages_in_use"], eng.metrics()["serving/pages_in_use.window16"]
    assert (full, window) == (0, 0)


def test_a_model_of_one_kind_keeps_its_trees_tables_and_page_counts():
    """What every caller has today: parameters under ``layers``, one cache
    leaf pair stacked over all layers, one page table, the allocator's
    counts, no load vector and no per-kind gauge."""
    cfg = DecoderConfig.tiny(num_layers=3, num_kv_heads=2)
    model = DecoderLM(cfg)
    from accelerate_tpu.parallel.sharding import unbox_params

    params, _ = unbox_params(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert set(params) == {"embedding", "layers", "ln_final"}
    assert params["layers"]["block"]["attn"]["wv"].shape == (3, 64, 2, 16)
    assert cfg.kind_runs() == [(None, 3)] and cfg.cache_kind == "full"
    eng = ServingEngine(model, params, num_slots=2, max_cache_len=64, page_size=8, prefill_chunks=(8, 16))
    assert [k.name for k in eng._kinds] == ["full"] and eng._kinds[0].allocator is eng._allocator
    leaves = jax.tree_util.tree_flatten_with_path(eng._arena)[0]
    shapes = {jax.tree_util.keystr(p): l.shape for p, l in leaves}
    assert shapes["['layers']['block']['attn']['cached_key']"] == (3, 17, 2, 8, 16)
    assert eng._page_tables.shape == (2, 8) and eng._tables_arg() is eng._page_tables
    req = eng.submit(np.arange(20) % 256, max_new_tokens=6)
    eng.run()
    # 4 pages of 8 for 26 positions and one copy-on-write fork of the prompt's last page, as before
    assert req.outcome == "finished" and (eng.pages_allocated, eng.page_forks) == (5, 1) and eng.pages_released == 0
    m = eng.metrics()
    assert m["serving/pages_total"] == 17 and not any("." in k.split("/")[-1] and "pages" in k for k in m)
    with pytest.raises(ValueError, match="this model has one kind"):
        ServingEngine(model, params, num_slots=2, max_cache_len=64, page_size=8, kind_pages={"window8": 9})


REFUSALS = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_tiers": dict(kv_tiers=object()),
    "preemption by page-out": dict(scheduler=SchedulerConfig(preemption=True)),
    "quantized pages": dict(kv_cache_dtype="int8"),
}


def _state_model(attention_behind: bool = False):
    """A model of one kind whose mixer is a state-space block: what it keeps
    a slot is a recurrent state (cache kind "state"), and no pages at all.
    ``attention_behind``: an attention layer behind the state-space one, as
    the engine needs one to keep a slot's length by."""
    ssm = dict(mixer="ssm", ssm_state_dim=4, ssm_dt_rank=4)
    kinds = dict(layer_kinds=(("state_space", ssm), ("attention", {})), layer_pattern=(0, 1)) if attention_behind else ssm
    model = DecoderLM(DecoderConfig.tiny(num_layers=2, **kinds))
    return model, jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])


@pytest.mark.parametrize("kind", ["layer_kinds", "state"])
@pytest.mark.parametrize("feature", sorted(REFUSALS))
def test_what_cannot_be_right_for_layer_kinds_refuses_by_name(feature, kind):
    """... and for a recurrent state a slot: a cached prefix would need the
    state's snapshot at its page boundary, a page-out its copy."""
    if kind == "state":
        model, params = _state_model()
        assert model.config.cache_kind == "state" and not model.config.layer_kinds
        with pytest.raises(NotImplementedError, match=feature):
            _engine(model, params, **dict(REFUSALS[feature], kind_pages=None))
        return
    c = tiny(7)
    cfg = ARCH.decoder_config(c, max_seq_len=256, remat=False)
    params = jax.eval_shape(lambda: weights.make(REF, c, weights.seed_key(1), jnp.float32))
    params = jax.eval_shape(ARCH.to_program_tree(c), params)
    with pytest.raises(NotImplementedError, match=feature):
        _engine(DecoderLM(cfg), params, **REFUSALS[feature])


@pytest.mark.parametrize("page_size", [0, None])
def test_the_flat_slot_arena_is_gone(page_size):
    """A falsy page_size asked for the flat arena; it is refused by name,
    for a model by kind as for any other."""
    c = tiny(7)
    cfg = ARCH.decoder_config(c, max_seq_len=256, remat=False)
    params = jax.eval_shape(lambda: weights.make(REF, c, weights.seed_key(1), jnp.float32))
    params = jax.eval_shape(ARCH.to_program_tree(c), params)
    with pytest.raises(ValueError, match="flat slot arena is gone"):
        _engine(DecoderLM(cfg), params, page_size=page_size, kind_pages=None)


@pytest.mark.parametrize("kind", ["layer_kinds", "state"])
def test_kv_handoff_refuses_by_name(kind):
    if kind == "state":
        from accelerate_tpu.parallel.sharding import unbox_params

        model, _ = _state_model(attention_behind=True)
        params, _ = unbox_params(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        eng = _engine(model, params, kind_pages=None)
        assert [k.name for k in eng._kinds] == ["full"] and eng._kinds[0].layers == 1
        assert eng._state_kind.slot_bytes == 1 * 128 * (4 * 4 + 3 * 4)
        req = eng.submit(np.arange(20) % 256, max_new_tokens=4)
        eng.run()
        assert req.outcome == "finished" and len(req.tokens) == 4
    else:
        model, params = program(tiny(7), jnp.float32)
        eng = _engine(model, params)
    with pytest.raises(NotImplementedError, match="KV handoff"):
        eng.export_prefix_kv(np.arange(16))
    with pytest.raises(NotImplementedError, match="KV handoff"):
        eng.import_prefix_kv({})


def test_a_model_with_no_attention_layer_refuses_by_name():
    """A slot's length, admission and growth are kept by an attention kind's
    page tables; a model of state-space layers only is refused, not served
    over pages of no bytes."""
    model, params = _state_model()
    with pytest.raises(NotImplementedError, match="no attention layer"):
        _engine(model, params, kind_pages=None)


def test_parameters_held_and_active_a_token():
    """``num_params`` counts what is held (the experts here, each layer by
    its kind); ``num_active_params`` what a token passes through."""
    c = tiny(7)
    cfg = ARCH.decoder_config(c, max_seq_len=256)
    params = jax.eval_shape(ARCH.to_program_tree(c), jax.eval_shape(
        lambda: weights.make(REF, c, weights.seed_key(1), jnp.float32)))
    held = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    assert cfg.num_params == held == ARCH.total_params(c)
    e, m = c["hidden_size"], c["moe_intermediate_size"]
    assert cfg.num_params - cfg.num_active_params == 6 * (4 - 8) * 3 * e * m  # 8 a token against 4 held
    real = dict(c, n_routed_experts=16)
    assert (lambda k: k.num_params - k.num_active_params)(ARCH.decoder_config(real, max_seq_len=256)) \
        == 6 * (16 - 8) * 3 * e * m
