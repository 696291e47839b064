"""Configurations whose architecture keeps a state that the delta rule
corrects (a recurrence whose work the seam counts as ``gdn_scan_bytes``), found
in the manifest by what their seam modules offer (no test here names an
architecture): the counts against a hand count, the layers' order, the adapter
against the program's own tree, the public-values file against altered
configurations that have to fail, the cell's fp8 control, and what its readers
find."""

import json
import types

import numpy as np
import pytest

import cells
import manifest as M
import published_widths


def _with_a_delta_rule():
    """(cell, configuration entry, configuration, seam module) of every cell
    whose architecture counts the delta rule."""
    man, out = M.load_manifest(), []
    for w in man["workloads"]:
        cell = cells.find(w["name"])
        arch = M.load_arch(cell["config_values"]["model_type"])
        if hasattr(arch, "gdn_scan_bytes"):
            out.append((w["name"], cell["config_entry"], cell["config_values"], arch))
    return out


FOUND = _with_a_delta_rule()
CELLS = [name for name, *_ in FOUND]


def _of(name):
    (found,) = [b for b in FOUND if b[0] == name]
    return found


def test_the_manifest_has_a_cell_with_a_delta_rule_state():
    assert FOUND, "no cell's architecture keeps a delta-rule state"
    for name, entry, c, arch in FOUND:
        assert entry["reduced"] == c["reduced"] and set(c["published"]) == set(c["reduced"])
        assert {"deployment", "assumed", "serving", "limits", "rehearsal", "stage_first_layer", "experts_first"} <= set(c)
        assert c["serving"]["engine_kwargs"]["prefix_cache"] is False
        # the seam offers none of the names by which other tests find other architectures' hand counts
        assert not {"layer_kinds", "entries_held", "window_pages", "mla_decode_work", "ssd_scan_bytes"} & set(dir(arch))
        # whole periods of the published pattern from a multiple of it: three DeltaNet layers and one of attention
        period = published_widths.load_public(c["model_type"])["roles"]["layer_period"]
        assert period == c["full_attention_interval"]
        assert c["stage_first_layer"] % period == 0 and c["num_hidden_layers"] % period == 0
        assert arch.pattern(c) == arch.reference.layer_pattern(c) == "LLLF" * (c["num_hidden_layers"] // period)


@pytest.mark.parametrize("name", CELLS)
def test_the_new_kernels_work_is_a_hand_count(name):
    """At the published widths. The mixer: a projection of 2,048 + 2,048 +
    4,096 + 4,096 = 12,288 columns, a convolution over 8,192 channels, a state
    of 32 x 128 x 128 float32 = 2,097,152 B a slot a layer, 19,759,104 B a slot
    over 9 layers with the convolution's 3 float32 rows. A decode step of 128
    slots moves each state once in and once out and a row of 49,408 B a slot (q
    and k 2,048 each, v and o 4,096 each, g and beta 32 each, float32), and
    makes 7 operations a state element. An expert is three matrices of 2,048 x
    512: 3,145,728 parameters, 6,291,456 B."""
    _, _, c, arch = _of(name)
    assert arch.qkvz_width(c) == 12_288 and arch.conv_dim(c) == 8_192 and arch.value_dim(c) == 4_096
    assert arch.state_bytes_per_layer(c) == 2_097_152
    assert arch.slot_state_bytes(c) == 9 * (2_097_152 + 98_304) == 19_759_104
    assert arch.gdn_scan_bytes(c, 128, 128) == 9 * 128 * (49_408 + 2 * 2_097_152)
    assert arch.gdn_scan_bytes(c, 256, 3) == 9 * (256 * 49_408 + 3 * 2 * 2_097_152)
    assert arch.gdn_scan_flops(c, 1) == 9 * 7 * 524_288
    assert arch.expert_params(c) == 3_145_728 and arch.expert_weight_bytes(c, 59 * 12) == 708 * 6_291_456
    assert arch.experts_held(c) == 12 * 64 and arch.expert_layers(c) == 12
    # pages: three attention layers of 2 kv heads x (256 + 256) x 2 B a token
    assert arch.kv_token_bytes(c) == 2_048 and arch.kv_bytes_per_token(c) == 6_144
    assert arch.decode_kv_bytes(c, 100, 16) == 112 * 6_144


@pytest.mark.parametrize("name", CELLS)
def test_parameters_of_the_cut_are_the_issues_arithmetic(name):
    _, _, c, arch = _of(name)
    assert arch.vocab(c) == c["vocab_size"] == 18_992 == c["published"]["vocab_size"] // 8
    assert arch.router_outputs(c) == 512 and c["num_experts"] == 64 and c["experts_first"] == 0
    assert arch.delta_mixer_params(c) == 33_718_464 and arch.attention_mixer_params(c) == 27_263_488
    assert arch.expert_layer_shared_params(c) == 4_200_448
    assert arch.total_params(c) == 2_929_374_400  # ISSUE 48's 2,929.4 M
    assert 5.85e9 < 2 * arch.total_params(c) < 5.87e9
    assert arch.matmul_params(c, active=True) < arch.matmul_params(c) < arch.total_params(c)
    assert arch.train_flops_per_token(c, 4096) > 6 * arch.matmul_params(c, active=True)
    # the uncut model: 48 layers, 512 experts, the whole vocabulary: 80 B, of which 3 B a token passes through
    whole = dict(c, **c["published"])
    assert 79e9 < arch.total_params(whole) < 82e9 and 2.5e9 < arch.matmul_params(whole, active=True) < 4e9
    assert (arch.delta_layers(whole), arch.attention_layers(whole)) == (36, 12)


@pytest.mark.parametrize("name", CELLS)
def test_runs_of_a_kind_are_the_programs_stacks(name):
    """Published layers 0-11, ``LLLF`` three times: 6 scans, the DeltaNet runs
    three layers deep. A stage that starts inside a period keeps the
    published order."""
    _, _, c, arch = _of(name)
    assert arch.runs(c) == [("L", 0, 3), ("F", 3, 1), ("L", 4, 3), ("F", 7, 1), ("L", 8, 3), ("F", 11, 1)]
    assert arch.pattern(dict(c, stage_first_layer=2, num_hidden_layers=5)) == "LFLLL"


@pytest.mark.parametrize("name", CELLS)
def test_the_adapter_gives_the_programs_own_tree_and_back(name, optimized_xla):
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
    assert model.config.num_params == arch.total_params(c) == sum(x.size for x in jax.tree_util.tree_leaves(tree))
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
    """Every width of the public file, one at a time (the hidden, the three
    head widths, the three feed-forward widths, the heads): halved, it fails."""
    entry, c, public = _public(name)
    published_widths.check(c, entry, public)  # sound as committed
    c[key] = c[key] // 2
    with pytest.raises(AssertionError):
        published_widths.check(c, entry, public)


@pytest.mark.parametrize("case", ["seven_experts", "vocabulary_under_an_eighth", "less_than_a_period",
                                  "experts_held_not_listed_as_reduced", "another_interval", "fewer_experts_a_token",
                                  "a_whole_head_rotated", "another_theta", "fewer_convolution_taps", "a_dense_layer"])
@pytest.mark.parametrize("name", CELLS)
def test_a_cut_under_the_floors_or_of_the_mathematics_fails_the_public_values(name, case):
    entry, c, public = _public(name)
    roles = public["roles"]
    if case == "seven_experts":
        c[roles["experts"]] = 7
    elif case == "vocabulary_under_an_eighth":
        c[roles["vocabulary"]] = public["values"][roles["vocabulary"]] // 8 - 1
    elif case == "less_than_a_period":
        c[roles["depth"]] = roles["layer_period"] - 1
    elif case == "experts_held_not_listed_as_reduced":
        for e in (c, entry):
            e["reduced"] = [k for k in e["reduced"] if k != roles["experts"]]
    elif case == "another_interval":
        c["full_attention_interval"] = 2
    elif case == "fewer_experts_a_token":
        c["num_experts_per_tok"] = 8
    elif case == "a_whole_head_rotated":
        c["partial_rotary_factor"] = 1.0
    elif case == "another_theta":
        c["rope_theta"] = 10000
    elif case == "fewer_convolution_taps":
        c["linear_conv_kernel_dim"] = 2
    elif case == "a_dense_layer":
        c["mlp_only_layers"] = [0]
    with pytest.raises(AssertionError):
        published_widths.check(c, entry, public)


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("name", CELLS)
def test_control_serving_through_the_state_in_fp8_fails_the_limit(name, seed, optimized_xla):
    """The cell's rehearsal with its control: the program holds every limit
    of the rehearsal (the file's ``rehearsal_limits`` gives the readings the
    limits lie between), and the reference computed in fp8 in the program's
    place fails the percentile's by 1.5 times. The window is 120 iterations of
    the loop, not seconds."""
    import run as R

    args = types.SimpleNamespace(seed=seed, seconds=1.0, iterations=120, trace=0, cpu_rehearsal=True, control="fp8")
    ctx = R.Context(cells.find(name), args)
    out = M.load_driver("closed_loop").run(ctx)
    assert out["correct"] is True and out["failed"] == 0
    assert out["iterations"]["warm_in"] + 120 == len(out["iterations"]["live"])
    held = [c for c in out["check"]["cases"] if c["held"]]
    assert len(held) == ctx.traffic["check_requests"]
    numbers, control = out["check"]["numbers"], out["check"]["control"]
    percentiles = [k for k in numbers if k != "served_logit_gap"]
    assert percentiles and set(numbers) == set(control) == set(ctx.limits)
    assert all(limit >= value for value, limit in numbers.values())
    assert any(value >= 1.5 * limit for value, limit in (control[k] for k in percentiles)), control


@pytest.mark.parametrize("name", CELLS)
def test_the_cells_readers_find_the_programs_counts(name, optimized_xla):
    """A rehearsed run leaves in the program's span ring what the cell's own
    readers take: the slots and rows whose state advanced on the dispatch
    spans, the pairs sent to held experts, the experts idle and touched and the
    chunks beside them; the gauges say which kernel and which form of a pack is
    engaged; the counting reader reads the ring; a reader that finds none of it
    returns None."""
    import run as R

    args = types.SimpleNamespace(seed=7, seconds=2.0, trace=0, cpu_rehearsal=True, control=None)
    cell = cells.find(name)
    ctx = R.Context(cell, args)
    out = M.load_driver("closed_loop").run(ctx)
    from accelerate_tpu.telemetry import spans as program

    ring = program.snapshot()
    decode = [s[5] for s in ring if s[2] == "serving/decode_dispatch" and s[5] and "ssm_slots" in s[5]]
    prefill = [s[5] for s in ring if s[2] == "serving/prefill_dispatch" and s[5] and "ssm_rows" in s[5]]
    assert decode and prefill
    a = [d for d in decode if "experts_touched" in d][-1]  # (the load is noted when the step is read, an iteration on)
    assert a["ssm_slots"] == a["ssm_rows"] == a["slots"] > 0 and a["arena_in_place"] == 1
    held = ctx.arch.experts_held(ctx.settings)
    assert a["experts_touched"] + a["experts_idle"] == held and a["experts_touched"] <= a["expert_pairs"]
    assert a["expert_chunks"] >= 1
    p = prefill[-1]
    assert p["ssm_rows"] >= p["ssm_slots"] >= 1 and p["arena_in_place"] == 1
    new = [m["name"] for m in cell["per_layer"] if m["workloads"] == [name]]
    assert len(new) == 6
    for metric in new:
        assert M.load_metric_reader(metric).read(None, None, {}, {"chips": 1, "peaks": {}}) is None
    counted = [m for m in new if M.load_metric_reader(m).SOURCE == "program_counter"]
    assert counted
    for metric in counted:
        value = M.load_metric_reader(metric).read(None, ctx.spans, out["counters"], cell)
        assert value is not None and 1.0 <= value <= ctx.traffic["clients"] * ctx.settings["num_experts_per_tok"]
