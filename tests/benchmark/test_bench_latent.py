"""Configurations whose architecture keeps a latent cache (one entry a token
a layer from which every head's keys and values are made), found in the
manifest by what their seam modules offer (``mla_decode_work``; no test here
names an architecture): the counts against a hand count, the adapter against
the program's own tree, the public-values file against altered configurations
that have to fail, the cell's fp8 control, and what its readers find."""

import json
import types

import numpy as np
import pytest

import cells
import manifest as M
import published_widths


def _latent():
    """(cell, configuration entry, configuration, seam module) of every cell
    whose architecture counts latent attention's work."""
    man, out = M.load_manifest(), []
    for w in man["workloads"]:
        cell = cells.find(w["name"])
        arch = M.load_arch(cell["config_values"]["model_type"])
        if hasattr(arch, "mla_decode_work"):
            out.append((w["name"], cell["config_entry"], cell["config_values"], arch))
    return out


LATENT = _latent()
CELLS = [name for name, *_ in LATENT]


def _of(name):
    (found,) = [b for b in LATENT if b[0] == name]
    return found


def test_the_manifest_has_a_cell_with_a_latent_cache():
    assert LATENT, "no cell's architecture keeps a latent cache"
    for name, entry, c, arch in LATENT:
        kinds = arch.mlp_kinds(c)
        assert len(kinds) == c["num_hidden_layers"] and kinds[0] is False and all(kinds[1:])
        assert entry["reduced"] == c["reduced"] and set(c["published"]) == set(c["reduced"])
        assert {"deployment", "assumed", "serving", "limits", "rehearsal", "stage_first_layer"} <= set(c)
        # the chip holds the last leading dense layer and the expert layers behind it
        assert c["stage_first_layer"] == c["first_k_dense_replace"] - 1
        assert c["serving"]["engine_kwargs"]["prefix_cache"] is False


@pytest.mark.parametrize("name", CELLS)
def test_cache_bytes_and_attention_work_are_a_hand_count(name):
    """At the published widths: an entry is 512 + 64 values, 1,152 B a token
    a layer in bf16 (42.7 times less than 64 heads' 192 + 192), 6,912 B over
    the 6 layers held; a decode step reads each page-rounded entry once and
    multiplies 2 x 64 x (576 + 512) = 139,264 FLOP with it; a pack's visible
    pair costs the expanded form's 2 x 64 x 384 = 49,152 FLOP a layer."""
    _, _, c, arch = _of(name)
    assert arch.latent_width(c) == 576 and arch.kv_token_bytes(c) == 1152
    assert 64 * (192 + 192) * 2 / arch.kv_token_bytes(c) == pytest.approx(42.67, abs=0.01)
    assert arch.kv_bytes_per_token(c) == 6 * 1152 == 6912
    assert arch.decode_kv_bytes(c, 100, 16) == 112 * 6912 and arch.decode_kv_bytes(c, 25599, 16) == 25600 * 6912
    assert arch.mla_decode_work(c, 16) == (16 * 6912, 16 * 6 * 139_264)
    moved, flops = arch.mla_decode_work(c, 1)
    assert flops / moved == pytest.approx(120.9, abs=0.1)  # against the chip's 240: memory-bound within a factor of two
    assert arch.mla_prefill_work(c, 10) == (0, 10 * 6 * 49_152)
    assert arch.mla_prefill_work(c, 10, rows=2, entries=5) == (6 * (2 * 64 * 384 * 2 + 5 * 1152), 10 * 6 * 49_152)
    # three matrices of 7168 x 2048 in bf16 an expert that got a token
    assert arch.expert_weight_bytes(c, 1) == 3 * 7168 * 2048 * 2 == 88_080_384


@pytest.mark.parametrize("name", CELLS)
def test_parameters_of_the_cut_are_the_issues_arithmetic(name):
    _, _, c, arch = _of(name)
    assert arch.vocab(c) == c["vocab_size"] == 16032 == c["published"]["vocab_size"] // 8
    assert arch.router_outputs(c) == 256 and c["n_routed_experts"] == 8
    assert arch.total_params(c) == 3_412_762_880
    assert 6.35 < 2 * arch.total_params(c) / 2**30 < 6.37
    # a token passes through 8 routed experts a layer where 8 are held: all of them are active
    assert arch.matmul_params(c) == arch.matmul_params(c, active=True)
    assert arch.runs(c) == [(0, 1, False), (1, 5, True)]
    assert arch.train_flops_per_token(c, 4096) > 6 * arch.matmul_params(c)


@pytest.mark.parametrize("name", CELLS)
def test_the_adapter_gives_the_programs_own_tree_and_back(name, optimized_xla):
    """At the rehearsal's widths: the tree ``to_program_tree`` makes has the
    leaves and shapes the program's module initialises, and
    ``from_program_tree`` takes it back leaf for leaf."""
    import jax
    import jax.numpy as jnp

    import run as R
    import weights
    from accelerate_tpu.parallel.sharding import unbox_params

    args = types.SimpleNamespace(seed=5, seconds=1.0, trace=0, cpu_rehearsal=True, control=None)
    ctx = R.Context(cells.find(name), args)
    c, arch = ctx.settings, ctx.arch
    model = arch.module(arch.decoder_config(c, max_seq_len=64, remat=False))
    want = jax.eval_shape(lambda: unbox_params(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])[0])
    published = weights.make_jit(arch.reference, c, 5, jnp.float32)
    tree = arch.to_program_tree(c)(published)
    shape_of = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
    assert shape_of(tree) == shape_of(want)
    back = arch.from_program_tree(c, tree)
    assert set(back) == set(published)
    for k in published:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(published[k]), err_msg=k)


def _public(name):
    _, entry, c, _ = _of(name)
    return dict(entry), json.loads(json.dumps(c)), published_widths.load_public(c["model_type"])


WIDTHS = sorted({k for name in CELLS for k, v in _public(name)[2]["values"].items()
                 if published_widths.WIDTH.search(k) and isinstance(v, int)})


@pytest.mark.parametrize("key", WIDTHS)
@pytest.mark.parametrize("name", CELLS)
def test_a_cut_width_fails_the_public_values(name, key):
    """Every width of the public file, one at a time (the two latent ranks,
    the three head widths, the hidden and both MLP widths, the heads):
    halved, it fails."""
    entry, c, public = _public(name)
    published_widths.check(c, entry, public)  # sound as committed
    c[key] = c[key] // 2
    with pytest.raises(AssertionError):
        published_widths.check(c, entry, public)


@pytest.mark.parametrize("case", ["seven_experts", "vocabulary_under_an_eighth", "three_layers_after_the_dense_one",
                                  "experts_held_not_listed_as_reduced", "a_dense_layer_more", "no_group_stage",
                                  "another_scaling_factor", "another_rope_scaling", "no_shared_expert"])
@pytest.mark.parametrize("name", CELLS)
def test_a_cut_under_the_floors_or_of_the_mathematics_fails_the_public_values(name, case):
    entry, c, public = _public(name)
    roles = public["roles"]
    if case == "seven_experts":
        c[roles["experts"]] = 7
    elif case == "vocabulary_under_an_eighth":
        c[roles["vocabulary"]] = public["values"][roles["vocabulary"]] // 8 - 1
    elif case == "three_layers_after_the_dense_one":
        c[roles["depth"]] = roles["leading_dense_layers"] + 3
    elif case == "experts_held_not_listed_as_reduced":
        for e in (c, entry):
            e["reduced"] = [k for k in e["reduced"] if k != roles["experts"]]
    elif case == "a_dense_layer_more":
        c["first_k_dense_replace"] += 1  # no role: it stays as published, the deployment says which layers are held
    elif case == "no_group_stage":
        c["n_group"] = c["topk_group"] = 1
    elif case == "another_scaling_factor":
        c["routed_scaling_factor"] = 1.0
    elif case == "another_rope_scaling":
        c["rope_scaling"] = dict(c["rope_scaling"], factor=32)
    elif case == "no_shared_expert":
        c["n_shared_experts"] = 0
    with pytest.raises(AssertionError):
        published_widths.check(c, entry, public)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_control_serving_latents_in_fp8_fails_the_limit(name, seed, optimized_xla):
    """The cell's rehearsal with its control: the program holds every limit
    of the rehearsal (the file's ``rehearsal_limits`` gives the readings the
    limits lie between), and the reference computed in fp8 in the program's
    place fails a percentile's by 1.5 times. The maximum is not asked to fail
    the control: one token whose last expert flipped reads as far as the
    control's worst. Nothing here moves with the machine's pace: the window
    is 120 iterations of the loop, not seconds, and the sample is drawn from
    the first requests submitted in it."""
    import run as R

    args = types.SimpleNamespace(seed=seed, seconds=1.0, iterations=120, trace=0, cpu_rehearsal=True, control="fp8")
    ctx = R.Context(cells.find(name), args)
    out = M.load_driver("closed_loop").run(ctx)
    assert out["correct"] is True and out["failed"] == 0
    assert out["iterations"]["warm_in"] + 120 == len(out["iterations"]["live"])
    held = [c for c in out["check"]["cases"] if c["held"]]
    assert len(held) == ctx.traffic["check_requests"]
    assert all(c["submit_iter"] >= out["iterations"]["warm_in"] for c in out["check"]["cases"])
    numbers, control = out["check"]["numbers"], out["check"]["control"]
    percentiles = [k for k in numbers if k != "served_logit_gap"]
    assert percentiles and set(numbers) == set(control) == set(ctx.limits)
    assert all(limit >= value for value, limit in numbers.values())
    assert any(value >= 1.5 * limit for value, limit in (control[k] for k in percentiles)), control


@pytest.mark.parametrize("name", CELLS)
def test_the_cells_readers_find_the_programs_counts(name, optimized_xla):
    """A rehearsed run leaves in the program's span ring what the cell's own
    readers take: the latent counts on the dispatch spans, load and chunks
    beside them, bytes and live tokens on the step spans (an entry as stored,
    over the layers held); a reader that finds none of it returns None."""
    import run as R

    args = types.SimpleNamespace(seed=7, seconds=2.0, trace=0, cpu_rehearsal=True, control=None)
    cell = cells.find(name)
    ctx = R.Context(cell, args)
    M.load_driver("closed_loop").run(ctx)
    from accelerate_tpu.telemetry import spans as program

    ring = program.snapshot()
    decode = [s[5] for s in ring if s[2] == "serving/decode_dispatch" and s[5] and "latent_tokens" in s[5]]
    prefill = [s[5] for s in ring if s[2] == "serving/prefill_dispatch" and s[5] and "latent_pairs" in s[5]]
    steps = [s[5] for s in ring if s[2] == "serving/step" and s[5] and "kv_bytes_in_use" in s[5]]
    assert decode and prefill and steps
    a = [d for d in decode if "expert_pairs" in d][-1]  # (the load is noted when the step is read, an iteration on)
    assert a["latent_tokens"] > 0 and a["arena_in_place"] == 1 and a["expert_chunks"] >= 0
    assert 0 <= a["expert_pairs"] <= a["expert_pairs_all"]
    p = prefill[-1]
    assert p["latent_pairs"] >= p["latent_entries"] > 0 and p["latent_expanded"] == 0 and p["arena_in_place"] == 1
    # pages of one kind: 40 lanes x 2 B x 6 layers a token at the rehearsal's widths, page-rounded
    c = ctx.settings
    stored = 6 * 2 * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
    peak = max(steps, key=lambda s: s["kv_bytes_in_use"])
    assert stored <= peak["kv_bytes_in_use"] / peak["live_tokens"] < 1.5 * stored
    new = [m["name"] for m in cell["per_layer"] if m["workloads"] == [name]]
    assert len(new) >= 4
    for metric in new:
        assert M.load_metric_reader(metric).read(None, None, {}, {"chips": 1, "peaks": {}}) is None
