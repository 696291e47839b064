"""A model of Gated DeltaNet layers (a state the delta rule corrects: key
heads that serve two value heads each, a convolution over q, k and v, two
scalars a head a token, l2-normed queries and keys, a gated norm a head)
beside gated attention and many narrow experts, against the plain reference of
the architecture that brought them (``benchmarks/reference/qwen3_next.py``,
which imports nothing of the program), at a small size on the CPU with seeded
weights: the ``gdn_scan`` kernel and the chunked form against the row-by-row
rule, each layer kind and a whole model against the reference, and the
2 MB-a-layer kind of state a slot in ``ServingEngine``."""

import dataclasses
import functools
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

ARCH = manifest.load_arch("qwen3_next")
REF = ARCH.reference
CONFIG = os.path.join(BENCH, "configs", "qwen3-next-80b-serve-12l-ep8.json")


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; a whole engine
    with interpreted kernels is then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def tiny(**over) -> dict:
    """The benchmark's configuration at its rehearsal's widths: layers
    ``LLLF``, 4 key heads over 8 value heads of 8, 8 of 32 experts held, 4 a
    token."""
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


# -- the rule's forms against each other -------------------------------------


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _scan_case(slot, rows, fresh, layer, bt, hk=2, hv=4, dk=8, dv=8, layers=3, slots=4, seed=0):
    """Blocks as the mixer hands them over: q and k normed, g negative, beta
    in (0, 1), a state that is not zero."""
    k = jax.random.split(jax.random.key(seed), 6)
    nb = len(slot)
    args = (_unit(jax.random.normal(k[0], (nb, bt, hk, dk))) * dk ** -0.5, _unit(jax.random.normal(k[1], (nb, bt, hk, dk))),
            jax.random.normal(k[2], (nb, bt, hv, dv)), -jax.nn.softplus(jax.random.normal(k[3], (nb, bt, hv)) - 1.0),
            jax.nn.sigmoid(jax.random.normal(k[4], (nb, bt, hv))), jax.random.normal(k[5], (layers, slots, hv, dk, dv)))
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
    # a key head a value head (no head shares a turned tile), and a step in which every slot is live in the last layer
    "pack_with_a_key_head_a_value_head": dict(slot=[3, 0, 0], rows=[8, 8, 2], fresh=[0, 1, 0], layer=1, bt=8, hk=4),
    "decode_step_of_every_slot": dict(slot=[0, 1, 2, 3], rows=[1, 1, 1, 1], fresh=[0] * 4, layer=2, bt=1),
}


def _same_where_live(spec, o0, o1, s0, s1, tol):
    for j, (slot, n) in enumerate(zip(spec["slot"], spec["rows"])):
        if slot >= 0 and n:
            np.testing.assert_allclose(np.asarray(o0[j, :n]), np.asarray(o1[j, :n]), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=tol, atol=tol)
    assert np.isfinite(np.asarray(o1)).all()


def _untouched_is_bit_for_bit(spec, before, after):
    """What no block advances is what it was: other layers, other slots, a dead slot, padding."""
    before, after = np.asarray(before), np.asarray(after)
    touched = {s for s, n, f in zip(spec["slot"], spec["rows"], spec["fresh"]) if s >= 0 and (n or f)}
    for layer in range(before.shape[0]):
        for slot in range(before.shape[1]):
            if layer != spec["layer"] or slot not in touched:
                assert np.array_equal(before[layer, slot], after[layer, slot]), (layer, slot)


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_kernel_interpreted_is_the_row_rule_in_place_in_the_layers_stack(case):
    spec = SCAN_CASES[case]
    args, kw = _scan_case(**spec)
    o0, s0 = S.gdn_scan(*args, impl="reference", **kw)
    o1, s1 = jax.jit(lambda *a: S.gdn_scan(*a, impl="interpret", **kw))(*args)
    _same_where_live(spec, o0, o1, s0, s1, 2e-5)
    _untouched_is_bit_for_bit(spec, args[-1], s1)


def _near_repeated_keys_beta_one(args):
    """The triangular system at its worst: every row writes all of what it
    read (beta 1) and consecutive keys are all but the same, so ``k_t . k_j``
    is near 1 all over the chunk."""
    q, k, v, g, beta, st = args
    return q, _unit(k[:, :1] + 0.05 * k), v, g, jnp.ones_like(beta), st


def _a_run_of_fast_decay_then_slow(args):
    """``g`` of -50 a row for rows 5-28 (the configuration allows it: ``a_log``
    up to log 16), -0.01 after: the running sum stands near -1,200 where the
    ratios between rows 29-63 are differences of hundredths."""
    q, k, v, g, beta, st = args
    row = jnp.arange(g.shape[1])[None, :, None]
    return q, k, v, jnp.where((row >= 5) & (row < 29), -50.0, jnp.where(row >= 29, -0.01, g)), beta, st


# what the chunked form can get wrong and a walk could not: 64-row blocks (a chunk of 8, 16, 32 or 64 rows by the
# block's live rows, its system solved 32 rows at a time)
CHUNK_CASES = {
    "near_repeated_keys_at_beta_1": (dict(slot=[1, 1], rows=[64, 64], fresh=[0, 0], layer=0, bt=64), _near_repeated_keys_beta_one),
    "a_run_of_g_at_minus_50_then_minus_0.01": (
        dict(slot=[2, 2], rows=[64, 40], fresh=[0, 0], layer=1, bt=64), _a_run_of_fast_decay_then_slow),
    **{f"{n}_live_rows_of_64": (dict(slot=[3, 0], rows=[n, n], fresh=[0, 1], layer=2, bt=64), None) for n in (1, 15, 16, 17, 63)},
    "slots_over_three_blocks_with_a_fresh_one_between": (
        dict(slot=[2, 2, 2, 0, 3, 3, 3, -1], rows=[64, 64, 21, 30, 64, 64, 5, 0], fresh=[0, 0, 0, 1, 0, 0, 0, 0], layer=1,
             bt=64), None),
}


@pytest.mark.parametrize("form", ["interpret", "chunked"])
@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_a_64_row_block_in_chunked_form_is_the_row_rule(case, form):
    """The kernel's form for a block of more than one row (``interpret``) and
    its ``jax.numpy`` mirror, at the tolerance the row walk was held to."""
    spec, alter = CHUNK_CASES[case]
    args, kw = _scan_case(**spec)
    args = alter(args) if alter else args
    rule = functools.partial(S.gdn_scan, impl="interpret") if form == "interpret" else functools.partial(S.gdn_chunked, chunk=64)
    o0, s0 = S.gdn_scan(*args, impl="reference", **kw)
    o1, s1 = jax.jit(lambda *a: rule(*a, **kw))(*args)
    _same_where_live(spec, o0, o1, s0, s1, 2e-5)
    _untouched_is_bit_for_bit(spec, args[-1], s1)
    if form == "interpret":  # a padding row's o is zero, not what the state would have answered
        assert not any(np.asarray(o1[j, n:]).any() for j, n in enumerate(spec["rows"]))


@pytest.mark.parametrize("chunk", [16, 8, 4, 5, 3, 64], ids=lambda c: f"chunks_of_{c}")
@pytest.mark.parametrize("case", ["pack", "pack_of_two_row_groups"])
def test_the_chunked_form_is_the_row_form(case, chunk):
    """Chunk sizes that divide a block's rows (8 and 16 rows: 4, 8, 16), that
    do not (3, 5: the block is padded with rows that advance nothing) and one
    that is longer than the block (64: one chunk of the block's rows)."""
    spec = SCAN_CASES[case]
    args, kw = _scan_case(**spec)
    o0, s0 = S.gdn_scan(*args, impl="reference", **kw)
    o1, s1 = jax.jit(lambda *a: S.gdn_chunked(*a, chunk=chunk, **kw))(*args)
    _same_where_live(spec, o0, o1, s0, s1, 2e-5)
    _untouched_is_bit_for_bit(spec, args[-1], s1)


@pytest.mark.parametrize("form", ["reference", "interpret", "chunked"])
def test_the_rule_is_the_references_one_token_at_a_time(form):
    """One slot, from zero, against ``reference/qwen3_next.delta_rule`` (a key
    head's rows given to each value head it serves) and, for the state it
    leaves, against the update written out in numpy."""
    hk, hv, dk, dv, t = 2, 4, 8, 8, 16
    (q, k, v, g, beta, st), kw = _scan_case([0], [t], [1], 0, t, hk, hv, dk, dv, layers=1, slots=1)
    rule = functools.partial(S.gdn_chunked, chunk=8) if form == "chunked" else functools.partial(S.gdn_scan, impl=form)
    o, s = rule(q, k, v, g, beta, st, **kw)
    per = hv // hk
    want = REF.delta_rule(jnp.repeat(q[0], per, axis=1), jnp.repeat(k[0], per, axis=1), v[0], g[0], beta[0])
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want), rtol=1e-4, atol=1e-5)
    q64, k64, v64, g64, b64 = (np.asarray(x[0], np.float64) for x in (q, k, v, g, beta))
    state = np.zeros((hv, dk, dv))
    for i in range(t):
        k_h = np.repeat(k64[i], per, axis=0)
        state = np.exp(g64[i])[:, None, None] * state
        read = np.einsum("hkv,hk->hv", state, k_h)
        state = state + k_h[:, :, None] * (b64[i][:, None] * (v64[i] - read))[:, None, :]
    np.testing.assert_allclose(np.asarray(s[0, 0]), state, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("what", ["state", "decay"])
def test_bfloat16_in_the_state_or_the_decay_fails_the_float32_tolerance(what):
    """The tolerance the kernel is held to above (2e-5) is float32's: the same
    rule with its state rounded to bfloat16 between the rows, or with ``g``
    rounded, misses it by well over an order, so neither is what runs; and the rule
    refuses a state that is not float32."""
    (q, k, v, g, beta, st), kw = _scan_case([0], [8], [1], 0, 8, layers=1, slots=1)
    v = 4.0 * v
    o0, _ = S.gdn_scan(q, k, v, g, beta, st, impl="reference", **kw)
    low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if what == "decay":
        o1, _ = S.gdn_scan(q, k, v, low(g), beta, st, impl="reference", **kw)
    else:
        rows, state = [], st
        for i in range(8):  # a row a call, the state rounded between
            o_i, state = S.gdn_scan(q[:, i:i + 1], k[:, i:i + 1], v[:, i:i + 1], g[:, i:i + 1], beta[:, i:i + 1],
                                    low(state), impl="reference", **dict(kw, block_rows=jnp.ones(1, jnp.int32),
                                                                         block_fresh=jnp.asarray([int(i == 0)])))
            rows.append(o_i)
        o1 = jnp.concatenate(rows, axis=1)
    assert float(jnp.abs(o1 - o0).max()) > 5e-4
    with pytest.raises(ValueError, match="float32"):
        S.gdn_scan(q, k, v, g, beta, st.astype(jnp.bfloat16), impl="reference", **kw)


@pytest.mark.parametrize("form", ["rows", "chunked"])
def test_the_jax_numpy_forms_differentiate(form):
    (q, k, v, g, beta, st), kw = _scan_case([0, 1], [8, 8], [1, 1], 0, 8, layers=1, slots=2)
    rule = functools.partial(S.gdn_chunked, chunk=4) if form == "chunked" else functools.partial(S.gdn_scan, impl="reference")
    loss = lambda v, g: jnp.sum(rule(q, k, v, g, beta, st, **kw)[0] ** 2)
    gv, gg = jax.grad(loss, argnums=(0, 1))(v, g)
    assert np.isfinite(np.asarray(gv)).all() and float(jnp.abs(gg).max()) > 0


# -- the model against the reference ----------------------------------------


@pytest.mark.parametrize("kind", ["L", "F"])
def test_each_layer_kind_is_the_references_layer(kind):
    """One published layer of each kind, mixer and experts: the program's
    whole forward pass in float32 against ``layer`` + ``head_logits`` of the
    reference (3e-4: float32 both sides, another order of summation). The
    ``F`` layer is the reference's attention with its query and key norms, its
    output gate and its rotated quarter (``stage_first_layer`` 3 makes the one
    layer held the published layer 3, a full-attention one)."""
    c = tiny(num_hidden_layers=1, stage_first_layer=0 if kind == "L" else 3)
    assert ARCH.pattern(c) == REF.layer_pattern(c) == kind
    model, params = program(c, jnp.float32)
    block = params["layers_0"]["block"]
    assert set(block) == {"ln_attn", "ln_mlp", "moe_mlp", "ssm" if kind == "L" else "attn"}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        w = weights.make_jit(REF, c, 11, jnp.float32)
        h = jnp.take(w["embed"], jnp.asarray(IDS), axis=0).astype(jnp.float32)
        h = REF.layer(c, "float32", h, REF.layer_weights(c, w, 0), kind)
        want = np.asarray(REF.head_logits(c, "float32", {k: w[k] for k in REF.HEAD_LEAVES}, h))
    np.testing.assert_allclose(got, want, atol=3e-4)


def _altered(params, leaf, value):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, value) if pages.leaf_name(path) == leaf else x, params)


@pytest.mark.parametrize("kind,leaves", [
    ("L", (("b_dt", 0.0), ("a_log", 0.0), ("norm_w", 1.0), ("w_ba", 0.0), ("conv_w", 0.25))),
    ("F", (("q_norm", 0.0), ("k_norm", 0.0), ("wg", 0.0))),
], ids=["the_delta_net", "the_gated_attention"])
def test_the_whole_mixer_shows_in_the_result(kind, leaves):
    """Each piece of a mixer moves the logits when it is altered: the step's
    bias, A, the gated norm's weight, the two scalars' projection, the
    convolution; the query's and the key's norm and the output gate."""
    c = tiny(num_hidden_layers=1, stage_first_layer=0 if kind == "L" else 3)
    model, params = program(c, jnp.float32)
    run = lambda p: np.asarray(model.apply({"params": p}, jnp.asarray(IDS)[None])["logits"][0])
    want = run(params)
    for leaf, value in leaves:
        assert np.abs(run(_altered(params, leaf, value)) - want).max() > 1e-2, leaf


def test_the_rotated_quarter_is_a_quarter():
    """``rope_dim`` is ``partial_rotary_factor x head_dim``; rotating the whole
    head instead gives other logits, so the reference's agreement above holds
    the quarter."""
    c = tiny(num_hidden_layers=1, stage_first_layer=3)
    model, params = program(c, jnp.float32)
    assert model.config.rope_dim == 4 and model.config.head_dim == 16
    whole = DecoderLM(dataclasses.replace(model.config, rope_dim=None))
    run = lambda m: np.asarray(m.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    assert np.abs(run(model) - run(whole)).max() > 1e-2


def test_a_whole_model_is_the_references_logits():
    """Two periods ``LLLFLLLF`` (four scans), float32; and the counts of
    parameters agree three ways. The adapter is its own inverse."""
    c = tiny(num_hidden_layers=8)
    model, params = program(c, jnp.float32)
    assert ARCH.runs(c) == [("L", 0, 3), ("F", 3, 1), ("L", 4, 3), ("F", 7, 1)]
    assert [n for _, n in model.config.kind_runs()] == [3, 1, 3, 1]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=3e-4)
    held = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert held == model.config.num_params == ARCH.total_params(c)
    w = weights.make_jit(REF, c, 11, jnp.float32)
    back = ARCH.from_program_tree(c, params)
    assert set(back) == set(w) and all(np.array_equal(np.asarray(back[k]), np.asarray(w[k])) for k in w)


def test_a_stage_that_starts_inside_a_period_keeps_the_published_order():
    c = tiny(num_hidden_layers=5, stage_first_layer=2)  # published layers 2-6: L F L L L
    assert ARCH.pattern(c) == "LFLLL"
    model, params = program(c, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
        np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=3e-4)


def test_the_published_cut_counts_2929_million_parameters():
    """The configuration file whole: ``DecoderConfig.num_params`` counts the
    new mixer, the doubled query projection with the two head norms and the
    shared expert's gate, and agrees with the architecture's count from shapes
    and with ISSUE 48's table (2,929.4 M)."""
    with open(CONFIG) as f:
        c = json.load(f)
    cfg = ARCH.decoder_config(c, max_seq_len=18432)
    assert cfg.num_params == ARCH.total_params(c) == 9 * 239_245_504 + 3 * 232_790_528 + 77_793_280 == 2_929_374_400
    assert [(cfg.layer_kinds[k][0], n) for k, n in cfg.kind_runs()] == [("L", 3), ("F", 1)] * 3
    l_kind, f_kind = (cfg.kind_config([n for n, _ in cfg.layer_kinds].index(name)) for name in "LF")
    assert l_kind.state_slot_bytes == 2_097_152 + 3 * 8_192 * 4 and l_kind.ssm_conv_dim == 8_192
    assert l_kind._layer_params() == 33_718_464 + 4_200_448 + 64 * 3_145_728
    assert f_kind._layer_params() == 27_263_488 + 4_200_448 + 64 * 3_145_728
    assert cfg.moe_experts_held == (0, 64) and cfg.moe_router_outputs == 512 and cfg.moe_top_k == 10
    assert (f_kind.rope_dim, f_kind.head_dim, f_kind.num_kv_heads, f_kind.rope_theta) == (64, 256, 2, 1e7)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bfloat16_is_within_its_rounding_and_the_fp8_control_is_not(seed):
    """The program in bfloat16 (weights and activations; the rule, its state
    and the residual stream float32) against the float32 reference on the same
    bfloat16 weights. The reference computed in fp8 in the program's place has
    to be far worse."""
    c = tiny()
    model, params = program(c, jnp.bfloat16, seed=seed)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    want = ref_logits(c, seed, jnp.bfloat16, IDS, "float32")
    low = ref_logits(c, seed, jnp.bfloat16, IDS, "fp8")
    rms = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
    agree = lambda x: float((x.argmax(-1) == want.argmax(-1)).mean())
    print("bfloat16 program", rms(got), agree(got), "fp8 reference", rms(low), agree(low))
    assert rms(got) < 0.5 * rms(low) and agree(got) >= 0.85 > agree(low)


def test_the_references_bfloat16_state_control_rounds_the_delta_rules_state_and_nothing_else():
    """The control read once on the chip (PERF.md section 6, PR 48): float32
    but for the state of the ``L`` layers, so it moves the logits of ``LLLF``
    and is float32 to the bit where every layer is ``F``."""
    c = tiny()
    want, got = (ref_logits(c, 11, jnp.float32, IDS, p) for p in ("float32", "bfloat16_state"))
    low = ref_logits(c, 11, jnp.float32, IDS, "fp8")
    rms = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
    assert 0.0 < rms(got) < 0.2 * rms(low)
    only_f = tiny(full_attention_interval=1)
    assert np.array_equal(*(ref_logits(only_f, 11, jnp.float32, IDS, p) for p in ("float32", "bfloat16_state")))


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


@pytest.mark.parametrize("form", ["kernels_interpreted", "jax_numpy", "kernels_interpreted_packs_of_8_and_64"])
def test_prefill_in_packs_then_decode_through_state_and_pages_is_the_full_forward_pass(form):
    """Packed prefill, then decoding through the state and the pages, float32,
    against the reference's full forward pass over prompt + served tokens, on
    logits. The prompts are split across chunk boundaries that are no
    multiples of the token block (8): 70 = 32 + 32 + 6, 41 = 32 + 9, 90 = 32 +
    32 + 26; the short ones are co-admitted in one pack (5, 11 and 3 together,
    each padded to its block); seven requests over four slots, so slots sit
    at different depths and a slot is used again by a request that must start
    from zero. 8 of 32 experts are held, so the served logits leave out the
    same pairs the reference leaves out. ``packs_of_8_and_64``: the packs are
    one token block or eight (70 = 64 + 6, 90 = 64 + 26), so a slot's state
    crosses more blocks inside one call of the kernel and fewer calls."""
    c = tiny()
    kernel = None if form == "jax_numpy" else "interpret"
    model, params = program(c, jnp.float32)
    eng = _engine(model, params, kernel, **(dict(prefill_chunks=(8, 64)) if form.endswith("8_and_64") else {}))
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
    assert m["serving/gdn_kernel_active"] == int(kernel == "interpret")
    assert m["serving/ssd_kernel_active"] == m["serving/ssm_kernel_active"] == 0
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
    assert len(before) == 2 and all(np.abs(v).max() > 0 for v in before.values())  # one run of L x (state, conv inputs)
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


def test_the_arena_counts_the_state_beside_the_attention_layers_pages():
    c = tiny(num_hidden_layers=8)  # LLLF LLLF: six DeltaNet layers in two runs, two attention layers
    model, params = program(c, jnp.float32)
    eng = _engine(model, params)
    hv, dk, dv, cd, k = 8, 8, 8, 2 * 4 * 8 + 8 * 8, c["linear_conv_kernel_dim"]
    slot_bytes = 6 * (hv * dk * dv * 4 + (k - 1) * cd * 4)
    assert eng._state_kind.slot_bytes == slot_bytes == ARCH.slot_state_bytes(c) and not eng._state_kind.paged
    assert eng.state_bytes == pages.state_nbytes(eng._arena) == 4 * slot_bytes
    assert [kind.name for kind in eng._kinds] == ["full"] and eng._kinds[0].layers == 2
    m = eng.metrics()
    assert (m["serving/state_bytes"], m["serving/state_bytes_per_slot"]) == (4 * slot_bytes, slot_bytes)
    mark = _ring_mark()
    prompts = [np.arange(9) + i for i in range(2)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    eng.run()
    assert _served_gap(c, 11, jnp.float32, prompts, reqs) <= 1e-3
    decodes = _args_since(mark, "serving/decode_dispatch")
    assert decodes and all(d["ssm_slots"] == d["ssm_rows"] == d["slots"] for d in decodes)


def test_the_engine_refuses_a_prefix_cache_beside_the_state():
    model, params = program(tiny(), jnp.float32)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        _engine(model, params, prefix_cache=True)


def test_several_tokens_a_slot_in_one_step_are_refused_by_the_mixer():
    c = tiny(num_hidden_layers=1)
    model, params = program(c, jnp.float32)
    cfg = dataclasses.replace(model.config, kv_page_size=8, kv_num_pages=9)
    with pytest.raises(NotImplementedError, match="rolled back"):
        jax.eval_shape(lambda p: DecoderLM(cfg).apply(
            {"params": p}, jnp.zeros((2, 3), jnp.int32), use_cache=True, decode=True,
            cache_positions=jnp.zeros((2, 3), jnp.int32), page_table=jnp.zeros((2, 4), jnp.int32),
            mutable=["cache"]), params)


@pytest.mark.parametrize("over", [dict(ssm_num_heads=None), dict(ssm_n_groups=3), dict(ssm_state_dim=0),
                                  dict(ssm_conv_width=1), dict(attn_output_gate=True, v_head_dim=8),
                                  dict(moe_shared_gate=True)],
                         ids=["no_heads", "key_heads_that_do_not_divide_the_value_heads", "no_key_width",
                              "a_convolution_of_one_tap", "a_gate_of_another_width_than_the_values",
                              "a_gate_without_a_shared_expert"])
def test_the_config_refuses_what_is_no_layer(over):
    fields = dict(mixer="gdn", ssm_num_heads=8, ssm_head_dim=8, ssm_n_groups=4, ssm_state_dim=8)
    fields.update(over)
    with pytest.raises(ValueError, match="gdn|state-space|attn_output_gate|moe_shared_gate"):
        DecoderConfig.tiny(**fields)
