"""Configurations whose architecture keeps a closing window with pooled
summaries, found in the manifest by what their seam modules offer
(``entries_held``; no test here names an architecture): the counts from
shapes against a hand count at the published widths, the adapter against the
program's own tree, the plain reference against the layer's equations written
out position by position, and the cell's fp8 control at the rehearsal's size."""

import json
import types

import numpy as np
import pytest

import cells
import manifest as M


def _closing():
    """(cell, configuration entry, configuration, seam module) of every cell
    whose architecture counts entries and not tokens."""
    out = []
    for w in M.load_manifest()["workloads"]:
        cell = cells.find(w["name"])
        arch = M.load_arch(cell["config_values"]["model_type"])
        if hasattr(arch, "entries_held"):
            out.append((w["name"], cell["config_entry"], cell["config_values"], arch))
    return out


CLOSING = _closing()
CELLS = [name for name, *_ in CLOSING]


def _rehearsal(c: dict) -> dict:
    c = json.loads(json.dumps(c))
    for group, over in c.pop("rehearsal").items():
        if isinstance(c.get(group), dict):
            c[group].update(over)
        else:
            c[group] = over
    return c


def test_the_manifest_has_a_cell_with_a_closing_window():
    assert CLOSING, "no cell's architecture keeps a closing window"
    for name, entry, c, arch in CLOSING:
        assert entry["reduced"] == c["reduced"] == ["num_hidden_layers"] and set(c["published"]) == set(c["reduced"])
        assert {"deployment", "assumed", "serving", "limits", "rehearsal"} <= set(c)
        s = c["serving"]
        assert s["page_size"] == c["chunk_size"] and s["max_cache_len"] % c["window_size"] == 0
        assert s["engine_kwargs"]["prefix_cache"] is False  # refused for the kind
        # the longest request of the mix fits a slot, and the pool holds the mix's entries with room
        traffic = cells.find(name)["traffic_values"]
        longest = traffic["prompt"]["max"] + traffic["output"]["max"]
        assert longest <= s["max_cache_len"]
        assert arch.entries_held(c, longest) < s["num_pages"] * s["page_size"] // 4
        for key in ("pooling_scale", "pooling_init", "head_dim"):
            assert key in c["assumed"], key  # every filled detail of the layer is stated


@pytest.mark.parametrize("name", CELLS)
def test_counts_from_shapes_are_a_hand_count_at_the_published_widths(name):
    """32 kv heads x (128 + 128) x 2 B = 16,384 B an entry a layer, 131,072 B
    over 8 layers; a slot at context L holds (L mod 2048) + 128 floor(L / 2048)
    entries; a layer has 4 x 4096^2 + 3 x 4096 x 11,008 matrix parameters, two
    norms and two pooling vectors of 32 x 128; the head has 320 x 8 rows."""
    (_, _, c, arch), = [b for b in CLOSING if b[0] == name]
    assert arch.entry_bytes(c) == 16_384 and arch.kv_bytes_per_token(c) == 131_072
    for length, entries in ((0, 0), (2047, 2047), (2048, 128), (2049, 129), (4096, 256), (18_432, 1152),
                            (20_479, 9 * 128 + 2047)):
        assert arch.entries_held(c, length) == entries, length
    # a write at position 4,100 reads 2 x 128 summaries and 5 open tokens: 17 pages of 16
    assert arch.decode_kv_bytes(c, 4100, 16) == 17 * 16 * 131_072
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11_008
    assert layer == 202_375_168
    assert arch.matmul_params(c) == 8 * layer + 4096 * 320 * 8
    assert arch.total_params(c) == 8 * (layer + 2 * 4096 + 2 * 32 * 128) + 320 * 4096 + 4096 * 2560 + 4096 == 1_630_932_992
    assert arch.pool_page_bytes(c) == 8 * (16 + 1) * 16_384
    assert arch.pool_page_flops(c) > 0 and arch.train_flops_per_token(c, 4096) > 6 * arch.matmul_params(c)
    cfg = arch.decoder_config(c, max_seq_len=c["serving"]["max_cache_len"], remat=False)
    assert cfg.num_params == arch.total_params(c)
    assert (cfg.eva_window, cfg.eva_chunk, cfg.num_pred_heads, cfg.cache_kind) == (2048, 16, 8, "closing2048")
    assert cfg.norm_unit_offset and cfg.fp32_logits and cfg.residual_dtype is not None and cfg.rope_theta == 1e5


@pytest.mark.parametrize("name", CELLS)
def test_the_adapter_gives_the_programs_own_tree_and_back(name, optimized_xla):
    import jax
    import jax.numpy as jnp

    import weights
    from accelerate_tpu.parallel.sharding import unbox_params

    (_, _, full, arch), = [b for b in CLOSING if b[0] == name]
    c = _rehearsal(full)
    cfg = arch.decoder_config(c, max_seq_len=64, remat=False)
    own, _ = unbox_params(jax.eval_shape(
        lambda: arch.module(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    w = weights.make_jit(arch.reference, c, 7, jnp.bfloat16)
    tree = arch.to_program_tree(c)(w)
    assert jax.tree_util.tree_map(lambda x: x.shape, tree) == jax.tree_util.tree_map(lambda x: x.shape, own)
    back = arch.from_program_tree(c, tree)
    assert set(back) == set(w) == set(arch.reference.shapes(c))
    for k in w:
        assert np.array_equal(np.asarray(back[k], np.float32), np.asarray(w[k], np.float32)), k
    # the leaves weights.py has no rule for: norms around 0 (the scale is 1 + w), pooling vectors of order 1
    assert abs(float(jnp.mean(w["norm_attn"].astype(jnp.float32)))) < 0.05
    assert 0.8 < float(jnp.std(w["mu"].astype(jnp.float32))) < 1.2


@pytest.mark.parametrize("name", CELLS)
def test_the_reference_is_the_layers_equations_position_by_position(name, optimized_xla):
    """One layer's attention, from rotated q, k, v drawn at random, against the
    equations written out in numpy for every position: the pooled key and value
    of each chunk, the local set (its own window up to itself), the remote set
    (the chunks of every window before its own), one softmax over both."""
    import jax.numpy as jnp

    (_, _, full, arch), = [b for b in CLOSING if b[0] == name]
    ref, c = arch.reference, _rehearsal(full)
    w_, cs, h, d = c["window_size"], c["chunk_size"], 2, 8
    t = 2 * w_ + w_ // 2
    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal((t, h, d)).astype(np.float32) for _ in range(3))
    mu, phi = (rng.standard_normal((h, d)).astype(np.float32) for _ in range(2))
    s = d ** -0.5
    softmax = lambda x: np.exp(x - x.max()) / np.exp(x - x.max()).sum()
    kbar, vbar = np.zeros((t // cs, h, d)), np.zeros((t // cs, h, d))
    for n in range(t // cs):
        for head in range(h):
            kc, vc = k[n * cs:(n + 1) * cs, head].astype(np.float64), v[n * cs:(n + 1) * cs, head].astype(np.float64)
            kbar[n, head] = softmax(s * (kc @ mu[head])) @ kc
            vbar[n, head] = softmax(s * (kc @ phi[head] - 0.5 * (kc * kc).sum(-1))) @ vc
    want = np.zeros((t, h, d))
    for pos in range(t):
        local = [m for m in range(t) if m // w_ == pos // w_ and m <= pos]
        remote = [n for n in range(t // cs) if (n * cs) // w_ < pos // w_]
        for head in range(h):
            keys = np.concatenate([kbar[remote, head], k[local, head]])
            vals = np.concatenate([vbar[remote, head], v[local, head]])
            want[pos, head] = softmax(s * (keys @ q[pos, head].astype(np.float64))) @ vals
    got_kbar, got_vbar = ref.pool(c, jnp.asarray(k), jnp.asarray(v), jnp.asarray(mu), jnp.asarray(phi))
    np.testing.assert_allclose(np.asarray(got_kbar), kbar, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_vbar), vbar, atol=2e-5)
    pad = (-t) % w_
    k_p, v_p = (jnp.pad(jnp.asarray(x), ((0, pad), (0, 0), (0, 0))) for x in (k, v))
    b = ref.query_block(c)
    got = np.concatenate([np.asarray(ref._attend_block(c, "float32", jnp.asarray(q[i:i + b]), i, k_p, v_p,
                                                       got_kbar, got_vbar)) for i in range(0, t, b)])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # entries a cache would hold, from the seam, against the sets above
    for pos in (0, w_ - 1, w_, 2 * w_ + 3):
        seen = len([m for m in range(t) if m // w_ == pos // w_ and m <= pos]) + \
            len([n for n in range(t // cs) if (n * cs) // w_ < pos // w_])
        assert arch.entries_held(c, pos) + 1 == seen


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", CELLS)
def test_control_serving_in_fp8_fails_the_rehearsals_limits(name, seed, optimized_xla):
    """The cell's driver at the rehearsal's size, a window of 120 iterations:
    the program passes every rehearsal limit with at least twice the room, and
    the reference computed in fp8 in its place fails at least one; the sample
    holds requests whose prompt closed windows and that closed one decoding."""
    import run as R

    args = types.SimpleNamespace(seed=seed, seconds=2.0, iterations=120, trace=0, cpu_rehearsal=True, control="fp8")
    ctx = R.Context(cells.find(name), args)
    out = M.load_driver("closed_loop").run(ctx)
    assert out["correct"] is True and out["failed"] == 0
    numbers, control = out["check"]["numbers"], out["check"]["control"]
    assert set(numbers) == set(ctx.limits) and len(ctx.limits) >= 2
    assert all(value <= limit / 2 for value, limit in numbers.values()), numbers
    assert any(value > limit for value, limit in control.values()), control
    w_ = ctx.settings["window_size"]
    held = [case for case in out["check"]["cases"] if case["held"]]
    assert any(case["prompt_tokens"] >= 3 * w_ for case in held)
    assert any((case["prompt_tokens"] + len(case["gaps"])) // w_ > case["prompt_tokens"] // w_ for case in held)
