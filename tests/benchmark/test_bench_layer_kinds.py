"""Configurations whose architecture states layer kinds and a share of its
experts, found in the manifest by what their seam modules offer (no test
here names an architecture): the counts by layer kind against a hand count,
the adapter against the program's own tree, the public-values file against
altered configurations that have to fail, and the cell's fp8 control."""

import json
import types

import numpy as np
import pytest

import cells
import manifest as M
import published_widths


def _by_kind():
    """(cell, configuration entry, configuration, seam module) of every cell
    whose architecture answers by layer kind."""
    man, out = M.load_manifest(), []
    for w in man["workloads"]:
        cell = cells.find(w["name"])
        arch = M.load_arch(cell["config_values"]["model_type"])
        if hasattr(arch, "layer_kinds"):
            out.append((w["name"], cell["config_entry"], cell["config_values"], arch))
    return out


BY_KIND = _by_kind()
CELLS = [name for name, *_ in BY_KIND]


def test_the_manifest_has_a_cell_by_layer_kind():
    assert BY_KIND, "no cell's architecture states layer kinds"
    for name, entry, c, arch in BY_KIND:
        kinds = arch.layer_kinds(c)
        assert len(kinds) == c["num_hidden_layers"] and len(set(kinds)) >= 3
        assert entry["reduced"] == c["reduced"] and set(c["published"]) == set(c["reduced"])
        assert {"deployment", "assumed", "serving", "limits", "rehearsal"} <= set(c)
        # the two published lists are kept whole; the depth reads their head
        public = published_widths.load_public(c["model_type"])["values"]
        lists = [k for k, v in public.items() if isinstance(v, list)]
        assert lists and all(c[k] == public[k] and len(c[k]) == public["num_hidden_layers"] for k in lists)


@pytest.mark.parametrize("name", CELLS)
def test_cache_bytes_by_layer_kind_are_a_hand_count(name):
    """At the published widths: a full layer keeps 4 kv heads x (192 + 128) x
    2 B = 2,560 B a token and walks the page-rounded context; a window layer
    keeps 8 x 320 x 2 = 5,120 B and walks the pages that hold its last 128
    positions; the lanes a page pads its keys with are not counted."""
    (_, _, c, arch), = [b for b in BY_KIND if b[0] == name]
    kinds = arch.layer_kinds(c)
    n_window, n_full = sum(w for w, _ in kinds), sum(not w for w, _ in kinds)
    assert (n_full, n_window) == (2, 5)
    assert (arch.kv_token_bytes(c, False), arch.kv_token_bytes(c, True)) == (2560, 5120)
    assert arch.kv_bytes_per_token(c) == 2 * 2560  # only the full layers grow with the context
    # write position 100: 7 pages either way (the window reaches back past position 0)
    assert arch.window_pages(c, 100, 16) == 7
    assert arch.decode_kv_bytes(c, 100, 16) == 2 * 2560 * 112 + 5 * 5120 * 112
    # write position 1000: 63 pages of context; the window's first position 873 lies in page 54
    assert arch.window_pages(c, 1000, 16) == 9
    assert arch.decode_kv_bytes(c, 1000, 16) == 2 * 2560 * 1008 + 5 * 5120 * 144 == 8_847_360
    # the longest context of the mix: the window ends on a page's last position and spans 8 pages
    assert arch.decode_kv_bytes(c, 8191, 16) == 2 * 2560 * 8192 + 5 * 5120 * 128
    # a window that ends on a page's last position spans 8 pages
    assert arch.window_pages(c, 127 + 16 * 20, 16) == 8
    # three matrices of 4096 x 2048 in bf16 an expert that got a token
    assert arch.expert_weight_bytes(c, 1) == 3 * 4096 * 2048 * 2
    assert arch.expert_weight_bytes(c, 96) == 96 * 50_331_648


@pytest.mark.parametrize("name", CELLS)
def test_parameters_of_the_cut_are_the_issues_arithmetic(name):
    (_, _, c, arch), = [b for b in BY_KIND if b[0] == name]
    assert arch.vocab(c) == c["vocab_size"] == 19072 == c["published"]["vocab_size"] // 8
    assert 3.42e9 < arch.total_params(c) < 3.44e9  # 6.39 GiB in bf16
    assert 6.38 < 2 * arch.total_params(c) / 2**30 < 6.40
    # a token passes through 8 experts a layer where 16 are held
    assert arch.matmul_params(c) - arch.matmul_params(c, active=True) == 6 * 8 * 3 * 4096 * 2048


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
    (_, entry, c, _), = [b for b in BY_KIND if b[0] == name]
    return dict(entry), json.loads(json.dumps(c)), published_widths.load_public(c["model_type"])


WIDTHS = sorted({k for name in CELLS for k, v in _public(name)[2]["values"].items()
                 if published_widths.WIDTH.search(k) and isinstance(v, int)})


@pytest.mark.parametrize("key", WIDTHS)
@pytest.mark.parametrize("name", CELLS)
def test_a_cut_width_fails_the_public_values(name, key):
    """Every width of the public file, one at a time: halved, it fails."""
    entry, c, public = _public(name)
    published_widths.check(c, entry, public)  # sound as committed
    c[key] = c[key] // 2
    with pytest.raises(AssertionError):
        published_widths.check(c, entry, public)


@pytest.mark.parametrize("case", ["seven_experts", "vocabulary_under_an_eighth", "five_layers_after_the_dense_one",
                                  "experts_held_not_listed_as_reduced", "a_layer_list_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_a_cut_under_the_floors_fails_the_public_values(name, case):
    entry, c, public = _public(name)
    roles = public["roles"]
    if case == "seven_experts":
        c[roles["experts"]] = 7
    elif case == "vocabulary_under_an_eighth":
        c[roles["vocabulary"]] = public["values"][roles["vocabulary"]] // 8 - 1
    elif case == "five_layers_after_the_dense_one":
        c[roles["depth"]] = roles["leading_dense_layers"] + 5
    elif case == "experts_held_not_listed_as_reduced":
        for e in (c, entry):
            e["reduced"] = [k for k in e["reduced"] if k != roles["experts"]]
    elif case == "a_layer_list_altered":
        key = next(k for k, v in public["values"].items() if isinstance(v, list))
        c[key] = c[key][:-1] + [1 - c[key][-1]]
    with pytest.raises(AssertionError):
        published_widths.check(c, entry, public)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_control_serving_by_kind_in_fp8_fails_the_limit(name, seed, optimized_xla):
    """The cell's rehearsal with its control: the program holds every limit of
    the rehearsal with room (the maximum by 1.5 times, a percentile by 2), and
    the reference computed in fp8 in the program's place fails a percentile's
    by 1.5 times (the file's readings leave 2.4 times, CPU). The maximum is
    not asked to fail the control: one token whose last expert flipped reads
    as far as the control's worst. Nothing here moves with the machine's
    pace: the window is 120 iterations of the loop, not seconds, and the
    sample is drawn from the first requests submitted in it
    (``closed_loop.pick_sample``), so every machine compares the same tokens
    of the same requests; that all of them are the window's is asserted."""
    import run as R

    args = types.SimpleNamespace(seed=seed, seconds=1.0, iterations=120, trace=0, cpu_rehearsal=True, control="fp8")
    ctx = R.Context(cells.find(name), args)
    out = M.load_driver("closed_loop").run(ctx)
    assert out["correct"] is True and out["failed"] == 0
    assert out["iterations"]["warm_in"] + 120 == len(out["iterations"]["live"])
    held = [c for c in out["check"]["cases"] if c["held"]]
    assert len(held) == ctx.traffic["check_requests"] < len(out["check"]["cases"])
    assert all(c["submit_iter"] >= out["iterations"]["warm_in"] for c in out["check"]["cases"])
    numbers, control = out["check"]["numbers"], out["check"]["control"]
    percentiles = [k for k in numbers if k != "served_logit_gap"]
    assert percentiles and set(numbers) == set(control) == set(ctx.limits)
    value, limit = numbers["served_logit_gap"]
    assert limit >= 1.5 * value
    assert all(limit >= 2 * value for value, limit in (numbers[k] for k in percentiles))
    assert any(value >= 1.5 * limit for value, limit in (control[k] for k in percentiles)), control


@pytest.mark.parametrize("name", CELLS)
def test_the_cells_readers_find_the_programs_counts(name, optimized_xla):
    """A rehearsed run leaves in the program's span ring what the cell's own
    readers take: load on the dispatch spans, bytes and live tokens on the
    step spans; a reader that finds none of it returns None."""
    import run as R

    args = types.SimpleNamespace(seed=7, seconds=2.0, trace=0, cpu_rehearsal=True, control=None)
    cell = cells.find(name)
    ctx = R.Context(cell, args)
    out = M.load_driver("closed_loop").run(ctx)
    from accelerate_tpu.telemetry import spans as program

    ring = program.snapshot()
    decode = [s[5] for s in ring if s[2] == "serving/decode_dispatch" and s[5] and "expert_pairs" in s[5]]
    prefill = [s[5] for s in ring if s[2] == "serving/prefill_dispatch" and s[5] and "expert_pairs" in s[5]]
    steps = [s[5] for s in ring if s[2] == "serving/step" and s[5] and "kv_bytes_in_use" in s[5]]
    grow = [s[5] for s in ring if s[2] == "serving/decode_grow" and s[5] and "pages_released" in s[5]]
    assert decode and prefill and steps and grow
    a = decode[-1]
    assert 0 < a["expert_pairs"] <= a["expert_pairs_all"] and a["expert_load_max"] >= 1 and a["experts_idle"] >= 0
    assert sum(g["pages_released"] for g in grow) > 0 and any(k.startswith("walked_tokens.") for k in grow[-1])
    assert any(k.startswith("pages_in_use.") for k in steps[-1])
    new = [m["name"] for m in cell["per_layer"] if m["workloads"] == [name]]
    assert len(new) >= 4
    for metric in new:
        assert M.load_metric_reader(metric).read(None, None, {}, {"chips": 1, "peaks": {}}) is None
