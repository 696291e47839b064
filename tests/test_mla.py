"""Latent attention (MLA) and the model that brought it, against the plain
reference of its architecture (``benchmarks/reference/deepseek_v3.py``, which
imports nothing of the program), at a small size on the CPU with seeded
weights: YaRN's frequencies against the closed form, the two forms of the
attention against each other and against the reference, the two kernels in
their latent mode against ``jax.numpy``, prefill in packs and decoding through
the latent cache in ``ServingEngine`` against the reference's full forward
pass, the precisions that have to fail, and what such a model is refused."""

import dataclasses
import json
import math
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

import accelerate_tpu.models.decoder as decoder  # noqa: E402
import accelerate_tpu.ops.attention as A  # noqa: E402
from accelerate_tpu.models import DecoderConfig, DecoderLM  # noqa: E402
from accelerate_tpu.ops.layers import yarn_inv_freq, yarn_mscale  # noqa: E402
from accelerate_tpu.serving import ServingEngine  # noqa: E402
from accelerate_tpu.serving.scheduler import SchedulerConfig  # noqa: E402

ARCH = manifest.load_arch("deepseek_v3")
REF = ARCH.reference
CONFIG = os.path.join(BENCH, "configs", "gigachat3.1-702b-serve-6l-ep32.json")


@pytest.fixture(autouse=True)
def optimized_xla():
    """The suite compiles with most XLA optimizations off; a whole engine
    with interpreted kernels is then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def published() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def tiny(layers=6, **over) -> dict:
    """The benchmark's configuration at its rehearsal's widths."""
    c = published()
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


IDS = np.random.default_rng(0).integers(0, 512, 56)


# -- YaRN ----------------------------------------------------------------------

def test_yarn_frequencies_are_the_closed_form_at_the_published_keys():
    """``rope_theta`` 1e5, 64 rotated dimensions, factor 64 over an original
    4,096: the dimension that turns 32 times over the original context is
    64 ln(4096 / 64 pi) / (2 ln 1e5) = 8.38 and the one that turns once
    18.01, so dimensions 0-8 keep their frequency, 19-31 are divided by 64
    and 9-18 are blended linearly over (j - 8) / 11."""
    c = published()
    rs, d, theta = c["rope_scaling"], c["qk_rope_head_dim"], float(c["rope_theta"])
    got = yarn_inv_freq(d, theta, rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"], rs["beta_slow"])
    cd = lambda b: d * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(theta))
    assert (math.floor(cd(32)), math.ceil(cd(1))) == (8, 19) and got.shape == (32,) and got.dtype == np.float32
    f = np.array([theta ** (-2 * j / 64) for j in range(32)])
    want = [f[j] if j <= 8 else f[j] / 64 if j >= 19 else f[j] / 64 * (j - 8) / 11 + f[j] * (1 - (j - 8) / 11)
            for j in range(32)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_array_equal(got, REF.yarn_inv_freq(c))  # the reference's own, written apart
    # the softmax scale carries mscale^2: 0.1 ln 64 + 1 = 1.4159, 192^-1/2 x 2.0047 = 0.14468
    assert yarn_mscale(64, 1) == pytest.approx(1.4158883)
    cfg = ARCH.decoder_config(c, max_seq_len=64)
    assert cfg.attn_sm_scale == pytest.approx(0.14468, rel=1e-4)
    assert cfg.attn_sm_scale == pytest.approx(REF.softmax_scale(c), rel=1e-12)
    # cos and sin are scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1 here
    sin, cos = decoder._rotary_tables(jnp.arange(3), cfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(cos)[2], np.cos(2 * got), rtol=1e-6)
    assert sin.shape == (3, 32)


def test_an_unstretched_rotation_is_plain_rope():
    np.testing.assert_allclose(yarn_inv_freq(8, 1e4, 1.0, 4096), [1e4 ** (-2 * j / 8) for j in range(4)], rtol=1e-6)
    assert yarn_mscale(1.0) == 1.0


# -- the layers against the reference --------------------------------------------

@pytest.mark.parametrize("stage_first_layer", [2, 3], ids=["dense", "experts"])
def test_each_layer_kind_is_the_references_layer(stage_first_layer):
    """One layer of each kind (the last leading dense layer; an expert
    layer with its group stage and its shared expert): the program's whole
    forward pass in float32, attention expanded, against ``layer`` +
    ``head_logits`` of the reference. 2e-4: float32 both sides, another order
    of summation (logits are of order 1)."""
    c = tiny(1, stage_first_layer=stage_first_layer)
    model, params = program(c, jnp.float32)
    assert model.config.cache_kind == "latent"
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    w = weights.make_jit(REF, c, 11, jnp.float32)
    h = jnp.take(w["embed"], jnp.asarray(IDS), axis=0)
    h = REF.layer(c, "float32", h, REF.layer_weights(c, w, 0), 0)
    want = np.asarray(REF.head_logits(c, "float32", {k: w[k] for k in REF.HEAD_LEAVES}, h))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_held_stage_is_the_references_logits():
    """Layers 2-7 of the published order (one dense, five with experts),
    float32: 5e-4 over 6 layers."""
    c = tiny(6)
    model, params = program(c, jnp.float32)
    assert [n for _, n in model.config.kind_runs()] == [1, 5]
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    np.testing.assert_allclose(got, ref_logits(c, 11, jnp.float32, IDS, "float32"), atol=5e-4)


DEGRADED = ["sound", "router_in_bfloat16", "inner_norms_in_bfloat16", "softmax_in_bfloat16"]


@pytest.mark.parametrize("case", DEGRADED)
def test_the_tolerance_fails_bfloat16_where_the_model_states_float32(case, monkeypatch):
    """The float32 program against the reference over two expert layers at
    3e-4 (float32 both sides; the sound program reads under 1e-4). The same
    program with bfloat16 in one place, where the model states float32, has
    to fail it: the router's logits (the choice of 8 of 32 flips for some
    token, and a whole expert's product comes or goes), the two norms inside
    the attention (2^-9 relative on every query and every latent), or the
    softmax's probabilities."""
    from accelerate_tpu.models import moe

    c = tiny(2, stage_first_layer=3)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if case == "router_in_bfloat16":
        scores = moe.router_scores
        monkeypatch.setattr(moe, "router_scores", lambda logits, scoring: scores(bf(logits), scoring))
    elif case == "inner_norms_in_bfloat16":
        norm = decoder._norm
        inner = (c["q_lora_rank"], c["kv_lora_rank"])
        monkeypatch.setattr(decoder, "_norm", lambda x, w, cfg: bf(norm(x, w, cfg)) if w.shape[-1] in inner
                            else norm(x, w, cfg))
    elif case == "softmax_in_bfloat16":
        softmax = jax.nn.softmax
        monkeypatch.setattr(jax.nn, "softmax", lambda x, axis=-1: bf(softmax(bf(x), axis=axis)))
    model, params = program(c, jnp.float32)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    err = float(np.abs(got - ref_logits(c, 11, jnp.float32, IDS, "float32")).max())
    print(case, err)
    assert err <= 3e-4 if case == "sound" else err > 3e-4, err


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bfloat16_is_within_its_rounding_and_the_fp8_control_is_not(seed):
    """The program in bfloat16 (weights and activations) against the float32
    reference on the same bfloat16 weights, over all logits of the held
    stage: their root mean square error and the gap the benchmark compares,
    by which the program's first choice lies below the reference's best;
    beside them the reference computed in fp8 in the program's place (CPU,
    these seeds: 0.03-0.06 against 0.2-0.25; a token whose last chosen
    expert flips moves every logit of its row by 2.5 times a normalised
    score's share, which is what the larger readings hold). Each limit has
    room on both sides (the readings are printed)."""
    c = tiny(6)
    model, params = program(c, jnp.bfloat16, seed=seed)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(IDS)[None])["logits"][0])
    want = ref_logits(c, seed, jnp.bfloat16, IDS, "float32")
    low = ref_logits(c, seed, jnp.bfloat16, IDS, "fp8")
    rms = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
    print("bfloat16 program", rms(got), "fp8 reference", rms(low))
    assert rms(got) <= 0.08 < 0.15 <= rms(low)


# -- the two forms ---------------------------------------------------------------

def _attention_layer(dtype=jnp.float32, kernel="dense", **over):
    """One ``LatentAttention`` at the rehearsal's widths with seeded weights,
    the reference's layer weights beside it, and a paged cache of 12 pages."""
    c = tiny(1, **over)
    cfg = dataclasses.replace(ARCH.decoder_config(c, max_seq_len=256, remat=False), dtype=dtype, kv_page_size=8,
                              kv_num_pages=12, decode_kernel=kernel, prefill_kernel=kernel, layer_kinds=(),
                              layer_pattern=())
    w = weights.make_jit(REF, c, 7, dtype)
    params = ARCH.to_program_tree(c)(w)["layers_0"]["block"]["attn"]
    params = jax.tree_util.tree_map(lambda x: x[0], params)
    return c, cfg, params, REF.layer_weights(c, w, 0)


@pytest.mark.parametrize("kernel", ["dense", "interpret"])
def test_absorbed_is_expanded_is_the_reference(kernel):
    """One layer's attention over 40 positions in float32, three ways: the
    reference (expanded, every head's keys and values made from the
    latents), the program expanded (no cache) and the program absorbed: 32
    rows as one pack over an empty cache, then 8 decode steps, each reading
    the latents the pack and the steps before it left in the pages and
    making no key or value. 2e-5: the same numbers in another order."""
    c, cfg, params, lw = _attention_layer(kernel=kernel)
    t, e = 40, c["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, t, e), jnp.float32)
    want = np.asarray(REF._mm(REF.attention(c, "float32", x[0], lw), lw["o"], "float32"))
    sin, cos = decoder._rotary_tables(jnp.arange(t), cfg, jnp.float32)
    expanded = decoder.LatentAttention(cfg).apply({"params": params}, x, sin, cos)
    np.testing.assert_allclose(np.asarray(expanded[0]), want, atol=2e-5)
    # absorbed: slot 1 of 2, its table rows pages 3.. in order
    absorbed = decoder.LatentAttention(cfg, use_cache=True, decode=True)
    table = jnp.asarray([[0] * 8, list(range(3, 11))], jnp.int32)
    lanes = A.cache_entry_widths(cfg)[1]
    cache = {"cached_latent": jnp.zeros((12, 1, 8, lanes), jnp.float32)}
    n = 32
    pos = jnp.arange(n)
    out, mutated = absorbed.apply(
        {"params": params, "cache": cache}, x[:, :n], sin[:n], cos[:n], cache_positions=pos[None],
        page_table=table, ragged_slots=jnp.ones((n,), jnp.int32), slot_hist=jnp.zeros((2,), jnp.int32),
        mutable=["cache"])
    np.testing.assert_allclose(np.asarray(out[0]), want[:n], atol=2e-5)
    cache = mutated["cache"]
    # the pages hold [latent | rotated key] after the norm and the rotation, and no key or value of a head
    assert set(cache) == {"cached_latent"} and cache["cached_latent"].shape == (12, 1, 8, lanes)
    for step in range(n, t):
        out, mutated = absorbed.apply(
            {"params": params, "cache": cache}, jnp.stack([jnp.zeros_like(x[0, step:step + 1]), x[0, step:step + 1]]),
            sin[None, step:step + 1].repeat(2, 0), cos[None, step:step + 1].repeat(2, 0),
            cache_positions=jnp.asarray([0, step]), page_table=table, kv_lengths=jnp.asarray([0, step + 1]),
            mutable=["cache"])
        cache = mutated["cache"]
        np.testing.assert_allclose(np.asarray(out[1, 0]), want[step], atol=2e-5)


def test_a_latent_cache_is_paged_only():
    c, cfg, params, _ = _attention_layer()
    x = jnp.zeros((1, 8, c["hidden_size"]))
    sin, cos = decoder._rotary_tables(jnp.arange(8), cfg, jnp.float32)
    with pytest.raises(NotImplementedError, match="keeps its cache in pages"):
        decoder.LatentAttention(dataclasses.replace(cfg, kv_page_size=None, kv_num_pages=None), use_cache=True).apply(
            {"params": params}, x, sin, cos, mutable=["cache"])


# -- the kernels in their latent mode ---------------------------------------------

def _entries(key, shape, latent, width):
    """Random entries whose lanes past ``width`` are zeros, as stored."""
    x = jax.random.normal(key, shape, jnp.float32)
    return jnp.where(jnp.arange(shape[-1]) < width, x, 0.0)


@pytest.mark.parametrize("in_place", [False, True], ids=["one_layer", "the_stack_written_in_place"])
def test_the_decode_kernel_in_latent_mode_is_the_dense_read(in_place):
    """4 slots at depths 0 (idle), 5, 16 and 37 of 8-token pages, 4 query
    heads against one 48-lane entry whose first 32 lanes are the value: the
    interpreted kernel against a gather of the pages and ``jax.numpy``.
    In place: the step's new entries are written by the kernel into layer 1
    of a stack of 3, and nothing else of the stack changes."""
    h, lanes, latent, ps, pages = 4, 48, 32, 8, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    arena = _entries(ks[0], (3, pages, 1, ps, lanes), latent, 40)
    q = _entries(ks[1], (4, h, 1, lanes), latent, 40)
    new = _entries(ks[2], (4, 1, 1, lanes), latent, 40)
    lengths = jnp.asarray([0, 5, 16, 37])
    pos = jnp.maximum(lengths - 1, 0)[:, None]
    table = jnp.asarray(np.random.default_rng(1).permutation(np.arange(1, pages))[:20].reshape(4, 5), jnp.int32)
    # the dense read of layer 1 with the new entries scattered first
    page = table[jnp.arange(4)[:, None], pos // ps]
    live = (lengths > 0)[:, None, None, None]
    scattered = arena[1].at[page, :, pos % ps].set(jnp.where(live, jnp.swapaxes(new, 1, 2), arena[1][page, :, pos % ps]))
    want = A.paged_latent_attention(q, scattered, page_table=table, q_positions=pos, latent=latent, sm_scale=0.2,
                                    kv_lengths=lengths, impl="dense")
    if in_place:
        got, stack = A.paged_latent_attention(q, arena, page_table=table, q_positions=pos, latent=latent, sm_scale=0.2,
                                              kv_lengths=lengths, impl="interpret", layer=jnp.int32(1), new=new)
        np.testing.assert_array_equal(np.asarray(stack[1]), np.asarray(scattered))
        np.testing.assert_array_equal(np.asarray(stack[0]), np.asarray(arena[0]))
        np.testing.assert_array_equal(np.asarray(stack[2]), np.asarray(arena[2]))
    else:
        got = A.paged_latent_attention(q, scattered, page_table=table, q_positions=pos, latent=latent, sm_scale=0.2,
                                       kv_lengths=lengths, impl="interpret")
    assert got.shape == (4, h, 1, latent)
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:], atol=2e-6)
    assert not np.asarray(got)[0].any()  # an idle slot: not walked, zeros


@pytest.mark.parametrize("in_place", [False, True], ids=["one_layer", "the_stack_written_in_place"])
@pytest.mark.parametrize("heads", [4, 64], ids=["4_heads_one_group", "64_heads_in_groups"])
def test_the_prefill_kernel_in_latent_mode_is_the_dense_reference(heads, in_place):
    """A pack of 32 rows: slot 2 brings 13 rows behind 21 cached entries,
    slot 0 brings 11 rows behind none; token blocks of 8, pads between. The
    interpreted kernel against ``_ragged_prefill_reference`` with the values
    cut from the entries; 64 heads are attended in groups of heads (8 rows x
    64 heads = 512 rows a group at most), 4 in one."""
    lanes, latent, ps, pages, cap = 48, 32, 8, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    arena = _entries(ks[0], (2, pages, 1, ps, lanes), latent, 40)
    q = _entries(ks[1], (1, heads, cap, lanes), latent, 40)
    new = _entries(ks[2], (1, 1, cap, lanes), latent, 40)
    row_slot = np.full(cap, -1, np.int32)
    row_pos = np.full(cap, -1, np.int32)
    row_slot[0:16], row_pos[0:13] = 2, np.arange(21, 34)
    row_slot[16:32], row_pos[16:27] = 0, np.arange(0, 11)
    hist = jnp.asarray([0, 0, 21], jnp.int32)
    table = jnp.asarray([[1, 2, 3, 4, 5], [0] * 5, [6, 7, 8, 9, 10]], jnp.int32)
    kw = dict(page_table=table, row_slot=row_slot, row_pos=row_pos, slot_hist=hist, latent=latent, sm_scale=0.2,
              token_block=8)
    want, payload = A.ragged_latent_attention(q, new, arena[1], impl="dense", **kw)
    if in_place:
        got, stack = A.ragged_latent_attention(q, new, arena, impl="interpret", layer=jnp.int32(1), **kw)
        valid = row_pos >= 0
        page = np.asarray(table)[np.maximum(row_slot, 0), np.maximum(row_pos, 0) // ps]
        written = np.asarray(arena[1]).copy()
        written[page[valid], :, row_pos[valid] % ps] = np.asarray(payload)[valid]
        np.testing.assert_array_equal(np.asarray(stack[1]), written)
        np.testing.assert_array_equal(np.asarray(stack[0]), np.asarray(arena[0]))
    else:
        got, same = A.ragged_latent_attention(q, new, arena[1], impl="interpret", **kw)
        np.testing.assert_array_equal(np.asarray(same), np.asarray(payload))
    assert got.shape == (1, heads, cap, latent)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def test_the_entry_is_stored_in_whole_lanes_and_counted_as_stored():
    """576 is no multiple of 128 lanes, and Mosaic takes no such slice of a
    page: the entry is stored in 640 (512 + 64 + 64 zeros), its first 512 the
    value, one kv head; the engine's kind counts 1,280 B a token a layer."""
    cfg = ARCH.decoder_config(published(), max_seq_len=64)
    assert cfg.latent_dim == 576 and A.cache_entry_widths(cfg) == (1, 640, 512)
    assert A.cache_entry_widths(ARCH.decoder_config(tiny(), max_seq_len=64)) == (1, 40, 32)
    assert A.cache_entry_widths(DecoderConfig.tiny(head_dim=192, v_head_dim=128, num_kv_heads=2)) == (2, 256, 128)


# -- the engine --------------------------------------------------------------------

def _engine(model, params, kernel=None, **kw):
    model = model.clone(config=dataclasses.replace(model.config, decode_kernel=kernel, prefill_kernel=kernel))
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
                                                (jnp.bfloat16, "interpret", 0.25)],
                         ids=["float32_kernels_interpreted", "float32_dense_paths", "bfloat16_kernels_interpreted"])
def test_prefill_then_decode_through_the_latent_cache_is_the_full_forward_pass(dtype, kernel, limit):
    """Packed prefill, then decoding through pages of latents, against the
    reference's full forward pass over prompt + served tokens. The prompts
    of 70 and 90 take three packs of 32 rows, so their later packs read
    cached latents behind the pack's own; the slots stand at different
    depths, and two of the six requests take a slot that was freed.
    float32: the served token is the reference's own within 1e-3. bfloat16:
    within 0.25: a routed weight is 2.5 times a normalised score here, so
    the last chosen expert of a token flipping under bfloat16's rounding
    moves a logit further than in a model without the factor (the benchmark's
    rehearsal, ``limits``)."""
    c = tiny(6)
    model, params = program(c, dtype)
    eng = _engine(model, params, kernel)
    eng.warmup().mark_steady()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n) for n in (5, 70, 41, 19, 23, 90)]
    reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
    eng.run()
    assert eng.admission_recompiles == 0 and all(r.outcome == "finished" for r in reqs)
    assert _served_gap(c, 11, dtype, prompts, reqs) <= limit
    # one kind of pages, every one of them back; the arena holds latents and nothing else
    (kind,) = eng._kinds
    assert (kind.name, kind.allocator.in_use, kind.layers) == ("latent", 0, 6)
    lanes = A.cache_entry_widths(eng._paged_def.config.run_configs()[0])[1]
    assert kind.token_bytes == lanes * jnp.dtype(dtype).itemsize
    flat, _ = jax.tree_util.tree_flatten_with_path(eng._arena)
    assert {p[-1].key for p, _ in flat} == {"cached_latent"}
    assert sorted(x.shape for _, x in flat) == [(1, 129, 1, 8, lanes), (5, 129, 1, 8, lanes)]
    m = eng.metrics()
    assert m["serving/latent_bytes_per_token"] == kind.token_bytes
    assert m["serving/mla_kernel_active"] == int(kernel == "interpret")
    assert m["serving/arena_in_place"] == m["serving/prefill_arena_in_place"] == int(kernel == "interpret")


REFUSALS = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_tiers": dict(kv_tiers=object()),
    "preemption by page-out": dict(scheduler=SchedulerConfig(preemption=True)),
    "quantized pages": dict(kv_cache_dtype="int8"),
}


def _latent_only_model():
    """Latent attention and nothing else by kind: one kind of layers, a dense MLP."""
    cfg = DecoderConfig.tiny(num_layers=2, num_heads=4, head_dim=24, v_head_dim=16, kv_lora_rank=32, q_lora_rank=24,
                             qk_nope_head_dim=16, qk_rope_head_dim=8)
    model = DecoderLM(cfg)
    return model, jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])


@pytest.mark.parametrize("model_kind", ["the_benchmarks_model", "latent_attention_alone"])
@pytest.mark.parametrize("feature", sorted(REFUSALS))
def test_what_cannot_be_right_for_a_latent_cache_refuses_by_name(feature, model_kind):
    """A latent kind is served on the normal path only, as every model by
    kind: the prefix cache (page sharing of latent pages waits for sharing
    behind ``CacheKind`` and S3's page references, ROADMAP R5), tiers,
    page-out and quantized pages refuse by the feature's name."""
    if model_kind == "latent_attention_alone":
        model, params = _latent_only_model()
        assert model.config.cache_kind == "latent" and not model.config.layer_kinds
    else:
        c = tiny(6)
        model = DecoderLM(ARCH.decoder_config(c, max_seq_len=256, remat=False))
        params = jax.eval_shape(lambda: weights.make(REF, c, weights.seed_key(1), jnp.float32))
        params = jax.eval_shape(ARCH.to_program_tree(c), params)
    with pytest.raises(NotImplementedError, match=feature):
        _engine(model, params, **REFUSALS[feature])


@pytest.mark.parametrize("fault,match", [
    (dict(qk_rope_head_dim=6), "head_dim = qk_nope_head_dim"),
    (dict(attn_window=16), "no window"),
    (dict(kv_cache_dtype="int8"), "unquantized latents"),
    (dict(kv_lora_rank=None), "latent attention's"),
    (dict(rope_yarn=(64, 4096, 32)), "rope_yarn is"),
])
def test_a_latent_config_that_cannot_be_right_is_refused(fault, match):
    base = dict(num_heads=4, head_dim=24, v_head_dim=16, kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16,
                qk_rope_head_dim=8)
    DecoderConfig.tiny(**base)
    with pytest.raises((ValueError, NotImplementedError), match=match):
        DecoderConfig.tiny(**dict(base, **fault))


def test_parameters_held_are_the_issues_arithmetic():
    """At the published widths: attention of one layer 132,595,712 with its
    four norms, a dense layer 528,957,440, an expert layer outside its routed
    experts 178,471,168 (the shared expert and the router's 256 outputs with
    their bias in it), 8 experts 352,321,536: 3,412,762,880 held, 6.36 GiB."""
    cfg = ARCH.decoder_config(published(), max_seq_len=64)
    dense, experts = cfg.run_configs()
    assert dense._layer_params() == 528_957_440 and experts._layer_params() == 530_792_704
    assert dense._layer_params() - 3 * 7168 * 18432 == 132_595_712
    assert experts._layer_params() - 8 * 3 * 7168 * 2048 == 178_471_168
    assert cfg.num_params == ARCH.total_params(published()) == 3_412_762_880
    assert cfg.num_active_params == cfg.num_params  # 8 a token, 8 held
