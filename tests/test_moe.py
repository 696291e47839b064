"""Mixture-of-experts tests on the 8-device CPU sim: routing math, parity
with the dense MLP at degenerate settings, expert sharding, and training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import DecoderConfig, DecoderLM, MoeMLP
from accelerate_tpu.models.moe import (
    expert_rows,
    grouped_mlp,
    load_balance_loss,
    routed_experts,
    router_scores,
    sort_pairs,
    top_k_routing,
)
from accelerate_tpu.parallel.mesh import build_mesh


def _experts(num, d=16, m=32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (num, d, m)) * d ** -0.5, jax.random.normal(k[1], (num, d, m)) * d ** -0.5,
            jax.random.normal(k[2], (num, m, d)) * m ** -0.5)


def _dense_share(x, experts, weights, wg, wu, wd, first=0):
    """Every pair by hand: the held experts' part of the layer's result."""
    y = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j]) - first
            if 0 <= e < wg.shape[0]:
                h = jax.nn.silu(x[t] @ wg[e]) * (x[t] @ wu[e])
                y[t] += float(weights[t, j]) * np.asarray(h @ wd[e])
    return y


class TestRouting:
    """The routing of before (a capacity a group and dropped overflow) is
    gone; each of its five tests is rewritten here as a case of the routing
    by sorted pairs, in the same order, and the new options follow."""

    def test_weights_sum_to_one_and_every_pair_is_routed(self):
        """(was: dispatch combines to gates) the chosen scores normalise to
        1 a token, and the sorted pairs are every (token, choice) once."""
        scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (16, 4)), -1)
        experts, weights = top_k_routing(scores, top_k=2)
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), np.ones(16), rtol=1e-5)
        order, sizes, n_held = sort_pairs(experts, 0, 4)
        assert sorted(np.asarray(order).tolist()) == list(range(32)) and int(n_held) == 32
        assert int(sizes.sum()) == 32
        # sorted by expert: the keys along the order never fall
        keys = np.asarray(experts).reshape(-1)[np.asarray(order)]
        assert (np.diff(keys) >= 0).all()

    def test_skew_overflows_into_further_chunks_and_drops_nothing(self):
        """(was: capacity drops overflow) every token on the same held
        experts: 4 x the rows one pass multiplies, and every pair's product
        is in the result all the same."""
        tokens, k, held, outputs = 40, 4, 8, 32
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, 16))
        wg, wu, wd = _experts(held)
        experts = jnp.tile(jnp.arange(k, dtype=jnp.int32)[None], (tokens, 1))
        weights = jnp.full((tokens, k), 1.0 / k)
        assert expert_rows(tokens, k, held, outputs) * 2 == tokens * k  # two chunks
        y, sizes = jax.jit(lambda *a: routed_experts(*a, first=0, outputs=outputs))(x, experts, weights, wg, wu, wd)
        assert np.asarray(sizes).tolist() == [tokens] * k + [0] * (held - k)
        np.testing.assert_allclose(np.asarray(y), _dense_share(x, experts, weights, wg, wu, wd), atol=2e-5)

    def test_aux_loss_minimized_at_balance(self):
        balanced = jnp.full((32, 4), 0.25)
        first = jnp.arange(32)[:, None] % 4  # first choices spread evenly
        aux_b = load_balance_loss(balanced, first)
        skewed = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (32, 1))
        aux_s = load_balance_loss(skewed, top_k_routing(skewed, 1)[0])
        assert float(aux_b) == pytest.approx(1.0, rel=1e-5)
        assert float(aux_s) > float(aux_b)

    def test_rows_formula(self):
        """(was: capacity formula) all pairs where every expert is held;
        twice the expected pairs on a held share, in sublane tiles, at
        least 16, never more than there are pairs."""
        assert expert_rows(128, 2, 8, 8) == 256
        assert expert_rows(64, 8, 16, 256) == 64      # the serving cell's decode step
        assert expert_rows(256, 8, 16, 256) == 256    # ... and its prefill pack
        assert expert_rows(4, 2, 2, 64) == 8          # never more than the pairs
        assert expert_rows(100, 8, 1, 256) == 16      # floor

    def test_shapes_linear_in_tokens(self):
        """(was: dispatch memory linear in batch) the layer's output shape
        follows the tokens, and the rows multiplied at once grow with them
        in proportion, not with their square."""
        cfg4 = DecoderConfig.tiny(moe_num_experts=4, moe_top_k=2)
        moe = MoeMLP(cfg4, None)
        x_small = jnp.zeros((2, 16, cfg4.embed_dim), cfg4.dtype)
        x_big = jnp.zeros((8, 16, cfg4.embed_dim), cfg4.dtype)
        v = moe.init(jax.random.PRNGKey(0), x_small)
        from accelerate_tpu.parallel.sharding import unbox_params

        raw, _ = unbox_params(v["params"])
        shapes_small = jax.eval_shape(lambda p, x: moe.apply({"params": p}, x), raw, x_small)
        shapes_big = jax.eval_shape(lambda p, x: moe.apply({"params": p}, x), raw, x_big)
        assert shapes_small[0].shape[1:] == shapes_big[0].shape[1:]
        assert expert_rows(8 * 16, 2, 4, 4) == 4 * expert_rows(2 * 16, 2, 4, 4)

    def test_selection_bias_moves_the_choice_and_not_the_weights(self):
        scores = jnp.asarray([[0.9, 0.8, 0.1, 0.05]])
        experts, weights = top_k_routing(scores, 2)
        assert sorted(np.asarray(experts[0]).tolist()) == [0, 1]
        biased, w_b = top_k_routing(scores, 2, selection_bias=jnp.asarray([0.0, -1.0, 1.0, 0.0]))
        assert sorted(np.asarray(biased[0]).tolist()) == [0, 2]
        # the weights are the scores themselves (0.9, 0.1), normalised over the chosen
        np.testing.assert_allclose(sorted(np.asarray(w_b[0]).tolist()), [0.1, 0.9], rtol=1e-6)

    def test_sigmoid_scores_each_output_alone(self):
        logits = jnp.asarray([[2.0, -1.0, 0.5]])
        np.testing.assert_allclose(np.asarray(router_scores(logits, "sigmoid")),
                                   1 / (1 + np.exp(-np.asarray(logits))), rtol=1e-6)
        np.testing.assert_allclose(float(router_scores(logits, "softmax").sum()), 1.0, rtol=1e-6)

    @pytest.mark.parametrize("impl", ["xla", "interpret"])
    def test_the_shares_add_up_to_the_whole_layer(self, impl):
        """Four shares of 8 experts over 32 router outputs: each computes
        its own experts' part with the weights normalised over all k
        chosen, and the parts add up to what one holder of all 32 gives."""
        tokens, k, outputs, d = 24, 4, 32, 16
        x = jax.random.normal(jax.random.PRNGKey(2), (tokens, d))
        wg, wu, wd = _experts(outputs, d)
        scores = router_scores(jax.random.normal(jax.random.PRNGKey(3), (tokens, outputs)), "sigmoid")
        experts, weights = top_k_routing(scores, k)
        whole, _ = routed_experts(x, experts, weights, wg, wu, wd, impl=impl)
        parts = sum(routed_experts(x, experts, weights, wg[f:f + 8], wu[f:f + 8], wd[f:f + 8],
                                   first=f, outputs=outputs, impl=impl)[0] for f in range(0, outputs, 8))
        np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=3e-5)
        np.testing.assert_allclose(np.asarray(whole), _dense_share(x, experts, weights, wg, wu, wd), atol=3e-5)

    def test_masked_tokens_are_routed_nowhere(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
        wg, wu, wd = _experts(4)
        experts, weights = top_k_routing(router_scores(x[:, :4], "softmax"), 2)
        mask = jnp.arange(8) < 5
        y, sizes = routed_experts(x, experts, weights, wg, wu, wd, token_mask=mask)
        assert int(sizes.sum()) == 10 and not np.asarray(y[5:]).any()

    def test_kernel_skips_experts_without_a_row(self):
        """The grouped product by the kernel (interpreted) and by
        ragged_dot agree where half the experts got nothing, and rows past
        the groups come out as zeros."""
        xs = jax.random.normal(jax.random.PRNGKey(5), (16, 16))
        wg, wu, wd = _experts(6)
        sizes = jnp.asarray([3, 0, 5, 0, 0, 4], jnp.int32)
        a = grouped_mlp(xs, wg, wu, wd, sizes, "xla")
        b = grouped_mlp(xs, wg, wu, wd, sizes, "interpret")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
        assert not np.asarray(b[12:]).any()

    @pytest.mark.parametrize("layer", [0, 1, 2])
    def test_kernel_reads_one_layers_experts_out_of_the_stack(self, layer):
        """Three layers' leaves stacked as the layer scan holds them, and a
        traced layer index: the kernel (interpreted) on the stack is the
        kernel on that layer's slice bit for bit and ``ragged_dot`` on it
        within rounding, experts without a row among them, rows past the
        groups zeros."""
        xs = jax.random.normal(jax.random.PRNGKey(5), (16, 16))
        stacks = [jnp.stack(ws) for ws in zip(*(_experts(6, seed=seed) for seed in range(3)))]
        mine = [w[layer] for w in stacks]
        sizes = jnp.asarray([3, 0, 5, 0, 0, 4], jnp.int32)
        want = grouped_mlp(xs, *mine, sizes, "xla")
        got = jax.jit(lambda at: grouped_mlp(xs, *stacks, sizes, "interpret", at))(jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(grouped_mlp(xs, *mine, sizes, "interpret")))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        assert np.asarray(got[:12]).any() and not np.asarray(got[12:]).any()


class TestMoeParity:
    def test_identical_experts_match_dense_mlp(self):
        """With every expert holding the SAME weights and top_k=E, MoE output
        == dense MLP output (gates sum to 1)."""
        from accelerate_tpu.models.decoder import DecoderMLP

        cfg = DecoderConfig.tiny(moe_num_experts=4, moe_top_k=4)
        dense_cfg = DecoderConfig.tiny()
        moe = MoeMLP(cfg, None)
        dense = DecoderMLP(dense_cfg, None)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, cfg.embed_dim), cfg.dtype)
        mv = moe.init(jax.random.PRNGKey(1), x)
        dv = dense.init(jax.random.PRNGKey(2), x)
        from accelerate_tpu.parallel.sharding import unbox_params

        mraw, _ = unbox_params(mv["params"])
        draw, _ = unbox_params(dv["params"])
        for name in ("w_gate", "w_up", "w_down"):
            mraw[name] = jnp.tile(draw[name][None], (4,) + (1,) * draw[name].ndim)
        y_moe, aux = moe.apply({"params": mraw}, x)
        y_dense = dense.apply({"params": draw}, x)
        np.testing.assert_allclose(np.asarray(y_moe), np.asarray(y_dense), rtol=1e-4, atol=1e-5)
        assert np.isfinite(float(aux))


_MOE_KW = dict(num_layers=4, moe_num_experts=4)


def _moe_pipeline_fixtures():
    """dense + pipelined MoE models sharing remapped params (module-level so
    the gpipe and 1f1b parity tests stay independently runnable)."""
    from accelerate_tpu.parallel.pipeline import remap_params_to_pipeline
    from accelerate_tpu.parallel.sharding import unbox_params

    dense = DecoderLM(DecoderConfig.tiny(**_MOE_KW))
    pipe = DecoderLM(
        DecoderConfig.tiny(pipeline_stages=2, pipeline_microbatches=2, **_MOE_KW)
    )
    ids0 = jnp.zeros((4, 16), jnp.int32)
    dense_p, _ = unbox_params(dense.init(jax.random.PRNGKey(0), ids0)["params"])
    pipe_t, _ = unbox_params(pipe.init(jax.random.PRNGKey(0), ids0)["params"])
    pipe_p = remap_params_to_pipeline(dense_p, pipe_t, 2)
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)
    return dense, pipe, dense_p, pipe_p, ids


class TestMoeDecoder:
    def test_moe_lm_trains_and_reports_aux(self):
        cfg = DecoderConfig.tiny(num_layers=2, moe_num_experts=4, moe_top_k=2)
        model = DecoderLM(cfg, None)
        ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 256)
        variables = model.init(jax.random.PRNGKey(1), ids)
        from accelerate_tpu.parallel.sharding import unbox_params

        raw, _ = unbox_params(variables["params"])

        # one compile: forward outputs ride along as grad aux
        def loss_and_out(p):
            o = model.apply({"params": p}, ids, labels=ids)
            return o["loss"], o

        grads, out = jax.grad(loss_and_out, has_aux=True)(raw)
        assert {"loss", "lm_loss", "aux_loss"} <= set(out)
        assert np.isfinite(float(out["loss"]))
        flat_leaves = jax.tree_util.tree_leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in flat_leaves)
        # router grads must be nonzero (aux loss reaches the router)
        router_grads = [
            np.asarray(v)
            for path, v in jax.tree_util.tree_leaves_with_path(grads)
            if "router" in str(path)
        ]
        assert router_grads and any((g != 0).any() for g in router_grads)

    def test_expert_weights_sharded_on_expert_axis(self):
        mesh = build_mesh({"expert": 2, "data": 4})
        cfg = DecoderConfig.tiny(num_layers=2, moe_num_experts=4, moe_top_k=2)
        model = DecoderLM(cfg, mesh)
        ids = jnp.zeros((4, 16), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), ids)
        from accelerate_tpu.parallel.sharding import (
            infer_param_sharding,
            shard_params,
            unbox_params,
        )
        from accelerate_tpu.utils.dataclasses import ShardingConfig

        raw, axes = unbox_params(variables["params"])
        params = shard_params(raw, infer_param_sharding(raw, mesh, ShardingConfig(), axes))
        expert_leaves = []

        def _walk(tree, path=""):
            for key, value in tree.items():
                p = f"{path}/{key}"
                if isinstance(value, dict):
                    _walk(value, p)
                elif "moe_mlp" in p and key in ("w_gate", "w_up", "w_down"):
                    expert_leaves.append((p, value))

        _walk(params)
        assert expert_leaves
        for path, leaf in expert_leaves:
            spec = leaf.sharding.spec
            # scan adds a leading layer dim; the expert dim must carry "expert"
            assert "expert" in [ax for e in spec if e for ax in (e if isinstance(e, tuple) else (e,))], (path, spec)

        @jax.jit
        def loss_fn(p, batch):
            return model.apply({"params": p}, batch, labels=batch)["loss"]

        loss = float(loss_fn(params, jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 256)))
        assert np.isfinite(loss)

    def test_moe_gpipe_matches_dense(self):
        """MoE through the GPipe pipeline: the belt carries the router aux —
        loss AND aux_loss parity with the dense scan on remapped params.
        Routing is deterministic, so parity is exact up to f32 reduction
        order."""
        dense, pipe, dense_p, pipe_p, ids = _moe_pipeline_fixtures()
        out_d = dense.apply({"params": dense_p}, ids, labels=ids)
        out_p = pipe.apply({"params": pipe_p}, ids, labels=ids)
        assert float(out_d["aux_loss"]) > 0
        np.testing.assert_allclose(
            float(out_d["aux_loss"]), float(out_p["aux_loss"]), rtol=2e-5
        )
        np.testing.assert_allclose(
            float(out_d["loss"]), float(out_p["loss"]), rtol=2e-5
        )

    @pytest.mark.slow
    def test_moe_1f1b_matches_ad_grads(self):
        """The 1F1B manual backward matches AD grads including the
        router-balance term (stage_aux_weight cotangent seeding)."""
        dense, _, dense_p, pipe_p, ids = _moe_pipeline_fixtures()
        out_d = dense.apply({"params": dense_p}, ids, labels=ids)

        pipe1f = DecoderLM(
            DecoderConfig.tiny(
                pipeline_stages=2, pipeline_microbatches=2,
                pipeline_schedule="1f1b", **_MOE_KW,
            )
        )
        vag = pipe1f.pipeline_value_and_grad()
        assert vag is not None
        out_m, grads_m = jax.jit(vag)(pipe_p, ids, ids)
        # MoE hooks surface the AD-path outputs contract
        np.testing.assert_allclose(
            float(out_m["aux_loss"]), float(out_d["aux_loss"]), rtol=2e-5
        )

        def loss_fn(p):
            return dense.apply({"params": p}, ids, labels=ids)["loss"]

        ld, gd = jax.value_and_grad(loss_fn)(dense_p)
        np.testing.assert_allclose(float(out_m["loss"]), float(ld), rtol=2e-5)

        def _flat(tree, prefix=""):
            out = {}
            for k, v in tree.items():
                p = f"{prefix}/{k}" if prefix else k
                if isinstance(v, dict):
                    out.update(_flat(v, p))
                else:
                    out[p] = v
            return out

        gm, gdf = _flat(grads_m), _flat(gd)
        for path, leaf in gm.items():
            if "stages/layers/" in path:
                ref = np.asarray(gdf[path.replace("pipeline/schedule/stages/layers", "layers")])
                np.testing.assert_allclose(
                    np.asarray(leaf).reshape(ref.shape), ref,
                    rtol=5e-4, atol=2e-5, err_msg=path,
                )
            else:
                np.testing.assert_allclose(
                    np.asarray(leaf), np.asarray(gdf[path]),
                    rtol=5e-4, atol=2e-5, err_msg=path,
                )


# -- the experts read out of their scanned stack (models/decoder.expert_stacks) --

def _served_model(shape: str, kernel="interpret", experts=4, params_dtype=jnp.bfloat16):
    """``one_kind``: three layers with experts in one scanned stack.
    ``by_kind``: a full kind with a dense MLP and a window kind with experts,
    a run of two and one alone. Weights in bfloat16, the layers' dtype, as a
    server holds them."""
    common = dict(vocab_size=128, embed_dim=64, num_heads=4, head_dim=16, max_seq_len=96, dtype=jnp.bfloat16,
                  scan_layers=True, remat=False, decode_kernel=kernel, prefill_kernel=kernel)
    moe = dict(mlp_dim=128, moe_num_experts=experts, moe_top_k=2 if experts else 1)
    if shape == "one_kind":
        cfg = DecoderConfig(num_layers=3, num_kv_heads=2, **moe, **common)
    else:
        cfg = DecoderConfig(
            num_layers=5, mlp_dim=256,
            layer_kinds=(("full", dict(num_kv_heads=1)),
                         ("window", dict(num_kv_heads=2, attn_window=16, attn_sink=True, **moe))),
            layer_pattern=(0, 1, 1, 0, 1), **common)
    model = DecoderLM(cfg)
    from accelerate_tpu.parallel.sharding import unbox_params

    params, _ = unbox_params(model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)["params"])
    return model, jax.tree_util.tree_map(lambda x: x.astype(params_dtype), params)


def _serving_engine(shape, model, params):
    from accelerate_tpu.serving import ServingEngine

    args = dict(num_slots=3, max_cache_len=96, page_size=8, prefill_chunks=(8, 16), prefix_cache=False)
    if shape == "by_kind":
        args.update(num_pages=1 + 3 * 12, kind_pages={"window16": 1 + 3 * 5})
    return ServingEngine(model, params, **args)


@pytest.fixture
def optimized_xla():
    """The suite compiles with most XLA optimizations off; whole engines with
    interpreted kernels are then far slower (tests/benchmark/conftest)."""
    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


@pytest.mark.parametrize("shape", ["one_kind", "by_kind"])
def test_tokens_and_pages_are_the_same_from_the_stack_and_from_each_layers_slice(shape, monkeypatch, optimized_xla):
    """Engines side by side, kernels interpreted, five requests through three
    slots (packs of several sizes, slots used again, forty decode steps): the
    ``moe_experts`` kernel given the run's stack and a layer index serves the
    same tokens and leaves the same arena, bit for bit, as given each layer's
    own slice, the threading of before."""
    import accelerate_tpu.models.decoder as decoder

    model, params = _served_model(shape)
    prompts = [np.arange(3, 3 + n) % 120 + 3 for n in (5, 17, 8, 30, 11)]

    def serve():
        eng = _serving_engine(shape, model, params)
        outs = eng.generate_batched(prompts, max_new_tokens=40)
        return eng, [np.asarray(o) for o in outs], jax.tree_util.tree_map(np.asarray, eng._arena)

    eng, got, arena = serve()
    m = eng.metrics()
    assert m["serving/experts_from_stack"] == 1 and m["serving/arena_in_place"] == m["serving/prefill_arena_in_place"] == 1
    monkeypatch.setattr(decoder, "expert_stacks", lambda *a, **k: {})
    _, want, arena_sliced = serve()
    assert len({tuple(w) for w in want}) > 1  # (not one token over and over)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    jax.tree_util.tree_map(np.testing.assert_array_equal, arena, arena_sliced)


@pytest.mark.parametrize("case,gauge", [("experts", 1), ("by_kind", 1), ("float32_leaves", 0), ("dense_kernel", 0),
                                        ("no_experts", 0)])
def test_the_gauge_says_whether_the_kernel_reads_the_stack(case, gauge):
    """``serving/experts_from_stack``: 1 where both serving programs hand the
    kernel the scanned stacks; 0 where the leaves are not of the layers'
    dtype (a cast of the stack would be the copy again), where the experts
    take ``ragged_dot`` (``decode_kernel="dense"``), and for a dense model."""
    shape = "by_kind" if case == "by_kind" else "one_kind"
    model, params = _served_model(
        shape, kernel="dense" if case == "dense_kernel" else "interpret", experts=0 if case == "no_experts" else 4,
        params_dtype=jnp.float32 if case == "float32_leaves" else jnp.bfloat16)
    assert _serving_engine(shape, model, params).metrics()["serving/experts_from_stack"] == gauge


# -- group-limited routing, the scaling factor, a shared expert (ISSUE 42) ---------

def _brute_force_choice(scores, bias, k, n_group, topk_group):
    """The group stage by hand, a token at a time: a group's score is the sum
    of its two largest biased scores; experts outside the best groups are
    out; the k largest biased scores among the rest are chosen."""
    chosen = []
    for row, brow in zip(np.asarray(scores, np.float64), np.asarray(scores + bias, np.float64)):
        size = len(row) // n_group
        group_score = [sum(sorted(brow[g * size:(g + 1) * size])[-2:]) for g in range(n_group)]
        best = sorted(range(n_group), key=lambda g: -group_score[g])[:topk_group]
        allowed = [e for e in range(len(row)) if e // size in best]
        chosen.append(sorted(sorted(allowed, key=lambda e: -brow[e])[:k]))
    return chosen


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_group_stage_is_a_brute_force_choice(seed):
    """32 outputs in 8 groups of 4, 4 groups kept, 8 chosen, over 64 tokens
    of random sigmoid scores with a selection bias: the same experts as the
    choice by hand, and never one outside the kept groups."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    scores = jax.nn.sigmoid(jax.random.normal(k1, (64, 32)))
    bias = 0.05 * jax.random.normal(k2, (32,))
    experts, weights = top_k_routing(scores, 8, bias, n_group=8, topk_group=4)
    want = _brute_force_choice(scores, bias, 8, 8, 4)
    assert [sorted(map(int, row)) for row in np.asarray(experts)] == want
    # without the stage some token chooses otherwise: the stage binds
    plain, _ = top_k_routing(scores, 8, bias)
    assert any(sorted(map(int, a)) != b for a, b in zip(np.asarray(plain), want))
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


def test_a_token_whose_best_eight_lie_in_five_groups_loses_one():
    """Scores by hand: the eight largest lie two each in groups 0-2 and one
    each in groups 3 and 4; group 4's second best is smaller than group 3's,
    so group 4 is out, its expert (the fifth best of all) with it, and the
    ninth best, in group 3, is chosen in its place."""
    scores = np.full((1, 32), 0.01, np.float32)
    scores[0, [0, 1, 4, 5, 8, 9]] = [0.9, 0.8, 0.85, 0.75, 0.7, 0.65]
    scores[0, 12], scores[0, 13] = 0.6, 0.3     # group 3: its two best 0.9
    scores[0, 16], scores[0, 17] = 0.78, 0.02   # group 4: 0.80, under group 3's
    experts, weights = top_k_routing(jnp.asarray(scores), 8, n_group=8, topk_group=4)
    assert sorted(map(int, experts[0])) == [0, 1, 4, 5, 8, 9, 12, 13]
    plain, _ = top_k_routing(jnp.asarray(scores), 8)
    assert sorted(map(int, plain[0])) == [0, 1, 4, 5, 8, 9, 12, 16]
    assert _brute_force_choice(jnp.asarray(scores), jnp.zeros(32), 8, 8, 4) == [[0, 1, 4, 5, 8, 9, 12, 13]]


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_the_routed_scaling_factor_multiplies_the_normalised_weights(scale):
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5), (16, 32)))
    experts, weights = top_k_routing(scores, 8, n_group=8, topk_group=4, routed_scale=scale)
    same, unit = top_k_routing(scores, 8, n_group=8, topk_group=4)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(same))
    np.testing.assert_allclose(np.asarray(weights), scale * np.asarray(unit), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), scale, rtol=1e-6)


@pytest.mark.parametrize("fault", [dict(moe_n_group=5), dict(moe_n_group=8, moe_topk_group=9),
                                   dict(moe_n_group=8, moe_topk_group=1, moe_top_k=8), dict(moe_n_group=0)])
def test_a_group_stage_that_cannot_be_right_is_refused(fault):
    base = dict(moe_num_experts=32, moe_top_k=8, moe_n_group=8, moe_topk_group=4)
    DecoderConfig.tiny(**base)
    with pytest.raises(ValueError, match="group"):
        DecoderConfig.tiny(**dict(base, **fault))


def _deepseek_arch():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import manifest

    return manifest.load_arch("deepseek_v3")


def test_the_shares_of_all_holders_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """The share test of the model-configs guide, section 4, for a layer with
    a group stage, a scaling factor and a shared expert: 4 chips hold 8 of
    32 experts each (a whole group of the router's 8 groups of 4, twice: the
    burstiest case, a token whose kept groups leave a chip's experts out
    sends it nothing); the program's expert layer on each computes its own
    experts' part and the shared expert whole; the four parts less three
    times the shared expert's result equal what the reference gives for the
    whole layer with all 32 held."""
    import json
    import os

    arch = _deepseek_arch()
    import weights as W

    ref = arch.reference
    with open(os.path.join(os.path.dirname(arch.__file__), "..", "configs", "gigachat3.1-702b-serve-6l-ep32.json")) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    whole = dict(c, num_hidden_layers=1, stage_first_layer=3, n_routed_experts=32, published={"n_routed_experts": 32})
    w = W.make_jit(ref, whole, 5, jnp.float32)
    lw = ref.layer_weights(whole, w, 0)
    y = jax.random.normal(jax.random.PRNGKey(0), (24, c["hidden_size"]))
    want = np.asarray(ref.experts(whole, "float32", y, lw))
    shared = np.asarray(ref._mlp(y, lw["gate_shared"], lw["up_shared"], lw["down_shared"], "float32"))
    parts = 0
    for first in range(0, 32, 8):
        cfg = DecoderConfig.tiny(
            embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"], moe_num_experts=8,
            moe_router_outputs=32, moe_experts_held=(first, 8), moe_top_k=8, moe_scoring="sigmoid",
            moe_selection_bias=True, moe_n_group=8, moe_topk_group=4, moe_routed_scale=2.5, moe_shared_experts=1)
        held = {"router": lw["router"], "selection_bias": lw["router_bias"],
                "w_gate": lw["gate_exp"][first:first + 8], "w_up": lw["up_exp"][first:first + 8],
                "w_down": lw["down_exp"][first:first + 8], "shared_gate": lw["gate_shared"],
                "shared_up": lw["up_shared"], "shared_down": lw["down_shared"]}
        part, _ = MoeMLP(cfg).apply({"params": held}, y[None])
        # the reference given the same share says the same of it
        share = dict(whole, n_routed_experts=8, experts_first=first)
        cut = dict(lw, gate_exp=held["w_gate"], up_exp=held["w_up"], down_exp=held["w_down"])
        np.testing.assert_allclose(np.asarray(part[0]), np.asarray(ref.experts(share, "float32", y, cut)), atol=2e-5)
        parts = parts + np.asarray(part[0])
    np.testing.assert_allclose(parts - 3 * shared, want, atol=3e-5)
    assert np.abs(want - shared).max() > 0.05 and np.abs(shared).max() > 0.05  # both add something to be right about


def test_expert_chunks_count_the_loops_passes():
    """``expert_chunks`` is the ``while_loop``'s own count: a decode step of
    16 tokens with 8 of 256 held multiplies 16 rows at once; 16 held pairs
    are one pass, 17 two, none none; a layer that holds every expert makes
    its one pass over all pairs."""
    from accelerate_tpu.models.moe import expert_chunks

    rows = expert_rows(16, 8, 8, 256)
    assert rows == 16 and expert_rows(256, 8, 8, 256) == 128
    assert [expert_chunks(n, rows) for n in (0, 1, 16, 17, 33)] == [0, 1, 1, 2, 3]
    assert expert_chunks(64, expert_rows(8, 8, 16, 16)) == 1


# -- two-matrix experts in a latent, 22 experts a token (ISSUE 44) ------------------

def _relu2_loop(xs, wu, wd, sizes):
    """``relu(x Wu_e)^2 Wd_e`` expert by expert over rows sorted by expert."""
    out, lo = np.zeros((xs.shape[0], wd.shape[-1]), np.float32), 0
    for e, n in enumerate(np.asarray(sizes)):
        rows = np.asarray(xs[lo:lo + n], np.float32)
        out[lo:lo + n] = np.square(np.maximum(rows @ np.asarray(wu[e], np.float32), 0.0)) @ np.asarray(wd[e], np.float32)
        lo += n
    return out


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("sizes", [[3, 0, 9, 1, 0, 0, 20, 7], [0, 0, 0, 0, 0, 0, 0, 5], [40, 0, 0, 0, 0, 0, 0, 0],
                                   [0] * 8], ids=["uneven", "the_last_expert_alone", "one_expert_over_row_tiles", "none"])
def test_two_matrix_relu2_experts_are_a_loop_over_the_experts(impl, sizes):
    """``grouped_mlp`` without a gate matrix: ``ragged_dot`` and the
    ``moe_experts_relu2`` kernel interpreted (rows in tiles of 16, an expert's
    rows anywhere in them, rows past the experts' sum left zero) against a
    loop over the experts."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    xs = jax.random.normal(k[0], (48, 32))
    wu = jax.random.normal(k[1], (8, 32, 128)) * 32 ** -0.5
    wd = jax.random.normal(k[2], (8, 128, 32)) * 128 ** -0.5
    got = grouped_mlp(xs, None, wu, wd, jnp.asarray(sizes, jnp.int32), impl)
    np.testing.assert_allclose(np.asarray(got), _relu2_loop(xs, wu, wd, sizes), atol=2e-5)
    assert not np.asarray(got[sum(sizes):]).any()


def test_the_relu2_kernel_reads_its_layer_out_of_the_stack():
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    xs = jax.random.normal(k[0], (24, 32))
    wu = jax.random.normal(k[1], (3, 4, 32, 128)) * 32 ** -0.5
    wd = jax.random.normal(k[2], (3, 4, 128, 32)) * 128 ** -0.5
    sizes = jnp.asarray([5, 0, 11, 2], jnp.int32)
    for layer in range(3):
        got = grouped_mlp(xs, None, wu, wd, sizes, "interpret", layer=jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(got), _relu2_loop(xs, wu[layer], wd[layer], sizes), atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_22_of_512_like_routing_is_a_brute_force_choice(seed):
    """11 of 64 sigmoid scores with a selection bias and a scaling factor of
    5, no group stage: the same experts as the choice by hand over the biased
    scores, the weights the unbiased scores normalised over the chosen, times
    5; and the pairs sorted for a held quarter are that quarter's, by expert."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    scores = jax.nn.sigmoid(jax.random.normal(k1, (48, 64)))
    bias = 0.05 * jax.random.normal(k2, (64,))
    experts, weights = top_k_routing(scores, 11, bias, routed_scale=5.0)
    assert [sorted(map(int, row)) for row in np.asarray(experts)] == _brute_force_choice(scores, bias, 11, 1, 1)
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(experts), axis=-1)
    np.testing.assert_allclose(np.asarray(weights), 5.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    order, sizes, n_held = sort_pairs(experts, 16, 16)
    flat = np.asarray(experts).reshape(-1)
    held = [(e - 16, i) for i, e in enumerate(flat) if 16 <= e < 32]
    assert int(n_held) == len(held) and list(np.asarray(sizes)) == [sum(1 for e, _ in held if e == j) for j in range(16)]
    assert [int(i) for i in np.asarray(order)[:len(held)]] == [i for _, i in sorted(held)]
    # what the program multiplies at once: twice the expected pairs of the held quarter
    assert expert_rows(48, 11, 16, 64) == 264 and expert_rows(96, 22, 128, 512) == 1056
    assert expert_rows(256, 22, 128, 512) == 2816


def _nemotron_arch():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import manifest

    return manifest.load_arch("nemotron_h")


@pytest.mark.parametrize("impl", [None, "interpret"], ids=["ragged_dot", "kernel_interpreted"])
def test_the_four_shares_of_a_latent_layer_add_up_to_the_uncut_layer(impl):
    """The share test of the model-configs guide, section 4, for LatentMoE: 4
    chips hold 8 of 32 two-matrix relu2 experts each; the program's expert
    layer on each projects into the latent, computes its own experts' part
    there, projects the partial sum back (linear, so the shares add up) and
    adds the shared expert whole; the four parts less three times the shared
    expert's result equal what the reference gives for the whole layer with
    all 32 held: both latent projections and the shared expert counted once."""
    import json
    import os

    arch = _nemotron_arch()
    import weights as W

    ref = arch.reference
    with open(os.path.join(os.path.dirname(arch.__file__), "..", "configs",
                           "nemotron3-super-120b-serve-11l-ep4.json")) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    whole = dict(c, num_hidden_layers=1, hybrid_override_pattern="E", n_routed_experts=32,
                 published={"n_routed_experts": 32})
    w = W.make_jit(ref, whole, 5, jnp.float32)
    lw = ref.layer_weights(whole, w, 0)
    u = jax.random.normal(jax.random.PRNGKey(0), (24, c["hidden_size"]))
    want = np.asarray(ref.latent_moe(whole, "float32", u, lw))
    shared = np.asarray(ref._relu2_mlp(u, lw["up_shared"], lw["down_shared"], "float32"))
    parts = 0
    for first in range(0, 32, 8):
        cfg = DecoderConfig.tiny(
            embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"], mlp_kind="relu2", moe_num_experts=8,
            moe_router_outputs=32, moe_experts_held=(first, 8), moe_top_k=c["num_experts_per_tok"],
            moe_scoring="sigmoid", moe_selection_bias=True, moe_routed_scale=5.0,
            moe_latent_dim=c["moe_latent_size"], moe_shared_dim=c["moe_shared_expert_intermediate_size"],
            decode_kernel=impl)
        held = {"router": lw["router"], "selection_bias": lw["router_bias"], "w_latent_in": lw["latent_in"],
                "w_latent_out": lw["latent_out"], "w_up": lw["up_exp"][first:first + 8],
                "w_down": lw["down_exp"][first:first + 8], "shared_up": lw["up_shared"],
                "shared_down": lw["down_shared"]}
        part, _ = MoeMLP(cfg, decode=impl is not None).apply({"params": held}, u[None])
        # the reference given the same share says the same of it
        share = dict(whole, n_routed_experts=8, experts_first=first)
        cut = dict(lw, up_exp=held["w_up"], down_exp=held["w_down"])
        np.testing.assert_allclose(np.asarray(part[0]), np.asarray(ref.latent_moe(share, "float32", u, cut)), atol=3e-5)
        parts = parts + np.asarray(part[0])
    np.testing.assert_allclose(parts - 3 * shared, want, atol=6e-5)
    assert np.abs(want - shared).max() > 0.05 and np.abs(shared).max() > 0.05  # both add something to be right about


def test_a_dense_relu2_mlp_is_two_matrices():
    from accelerate_tpu.models.decoder import DecoderMLP

    cfg = DecoderConfig.tiny(mlp_kind="relu2")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, cfg.embed_dim))
    params = DecoderMLP(cfg).init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"w_up", "w_down"}
    up, down = (np.asarray(jax.tree_util.tree_leaves(params[k])[0], np.float32) for k in ("w_up", "w_down"))
    want = np.square(np.maximum(np.asarray(x[0]) @ up, 0.0)) @ down
    np.testing.assert_allclose(np.asarray(DecoderMLP(cfg).apply({"params": params}, x)[0]), want, atol=1e-5)
    assert cfg.num_params == DecoderConfig.tiny().num_params - cfg.num_layers * cfg.embed_dim * cfg.mlp_dim


# -- 10 of 512 narrow gated experts and a shared expert behind a gate of its own (ISSUE 48) ------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_10_of_512_like_softmax_routing_is_a_brute_force_choice(seed):
    """5 of 64 softmax scores, no bias, no group stage, no scale: the same
    experts as the choice by hand, the weights the chosen probabilities
    normalised to sum to one; and the pairs sorted for a held eighth are that
    eighth's, by expert."""
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(seed), (48, 64))
    scores = router_scores(logits, "softmax")
    np.testing.assert_allclose(np.asarray(scores).sum(-1), 1.0, rtol=1e-6)
    experts, weights = top_k_routing(scores, 5)
    assert [sorted(map(int, row)) for row in np.asarray(experts)] == _brute_force_choice(scores, jnp.zeros(64), 5, 1, 1)
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(experts), axis=-1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    order, sizes, n_held = sort_pairs(experts, 8, 8)
    flat = np.asarray(experts).reshape(-1)
    held = [(e - 8, i) for i, e in enumerate(flat) if 8 <= e < 16]
    assert int(n_held) == len(held) and list(np.asarray(sizes)) == [sum(1 for e, _ in held if e == j) for j in range(8)]
    assert [int(i) for i in np.asarray(order)[:len(held)]] == [i for _, i in sorted(held)]
    # what the program multiplies at once at the published shape: twice the expected pairs of the held eighth
    assert expert_rows(128, 10, 64, 512) == 320 and expert_rows(256, 10, 64, 512) == 640


def _qwen3_next_arch():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import manifest

    return manifest.load_arch("qwen3_next")


def _qwen3_next_layer(experts: int):
    """A one-layer configuration at the rehearsal's widths that holds
    ``experts`` of 32, its reference weights and a batch of inputs."""
    import json
    import os

    arch = _qwen3_next_arch()
    import weights as W

    with open(os.path.join(os.path.dirname(arch.__file__), "..", "configs", "qwen3-next-80b-serve-12l-ep8.json")) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    c = dict(c, num_hidden_layers=1, num_experts=experts, published={"num_experts": 32})
    w = W.make_jit(arch.reference, c, 5, jnp.float32)
    return arch.reference, c, arch.reference.layer_weights(c, w, 0), jax.random.normal(jax.random.PRNGKey(0), (24, c["hidden_size"]))


def _share_params(lw, first, count):
    return {"router": lw["router"], "w_gate": lw["gate_exp"][first:first + count], "w_up": lw["up_exp"][first:first + count],
            "w_down": lw["down_exp"][first:first + count], "shared_gate": lw["gate_shared"], "shared_up": lw["up_shared"],
            "shared_down": lw["down_shared"], "shared_out_gate": lw["shared_gate"]}


def _share_config(c, first, count, impl=None, **over):
    fields = dict(embed_dim=c["hidden_size"], mlp_dim=c["moe_intermediate_size"], moe_num_experts=count,
                  moe_router_outputs=32, moe_experts_held=(first, count), moe_top_k=c["num_experts_per_tok"],
                  moe_scoring="softmax", moe_shared_dim=c["shared_expert_intermediate_size"], moe_shared_gate=True,
                  decode_kernel=impl)
    fields.update(over)
    return DecoderConfig.tiny(**fields)


@pytest.mark.parametrize("impl", [None, "interpret"], ids=["ragged_dot", "kernel_interpreted"])
def test_the_eight_shares_of_4_of_32_experts_add_up_to_the_uncut_layer(impl):
    """The share test of the model-configs guide, section 4: 8 chips hold 4 of
    32 gated experts each; the program's expert layer on each computes its own
    experts' part under the router's full width and adds the shared expert
    behind its gate whole; the eight parts less seven times the gated shared
    expert's result equal what the reference gives for the whole layer with
    all 32 held: router, shared expert and gate counted once."""
    ref, whole, lw, u = _qwen3_next_layer(32)
    want = np.asarray(ref.experts(whole, "float32", u, lw))
    shared = np.asarray(jax.nn.sigmoid(u @ lw["shared_gate"]) * ref._gated_mlp(
        u, lw["gate_shared"], lw["up_shared"], lw["down_shared"], "float32"))
    parts = 0
    for first in range(0, 32, 4):
        cfg = _share_config(whole, first, 4, impl)
        part, _ = MoeMLP(cfg, decode=impl is not None).apply({"params": _share_params(lw, first, 4)}, u[None])
        # the reference given the same share says the same of it
        share = dict(whole, num_experts=4, experts_first=first)
        cut = dict(lw, gate_exp=lw["gate_exp"][first:first + 4], up_exp=lw["up_exp"][first:first + 4],
                   down_exp=lw["down_exp"][first:first + 4])
        np.testing.assert_allclose(np.asarray(part[0]), np.asarray(ref.experts(share, "float32", u, cut)), atol=3e-5)
        parts = parts + np.asarray(part[0])
    np.testing.assert_allclose(parts - 7 * shared, want, atol=6e-5)
    assert np.abs(want - shared).max() > 0.05 and np.abs(shared).max() > 0.05  # both add something to be right about


def test_the_shared_experts_gate_is_one_scalar_a_token():
    """With the gate's weights at zero the shared expert comes in at a half;
    without the field the layer has no such leaf and the shared expert comes in
    whole: the routed part is the same in all three."""
    ref, c, lw, u = _qwen3_next_layer(32)
    params = _share_params(lw, 0, 32)
    run = lambda cfg, p: np.asarray(MoeMLP(cfg).apply({"params": p}, u[None])[0][0])
    shared = np.asarray(ref._gated_mlp(u, lw["gate_shared"], lw["up_shared"], lw["down_shared"], "float32"))
    gate = np.asarray(jax.nn.sigmoid(u @ lw["shared_gate"]))
    assert gate.shape == (24, 1) and gate.std() > 0.05
    gated = run(_share_config(c, 0, 32), params)
    halved = run(_share_config(c, 0, 32), dict(params, shared_out_gate=jnp.zeros_like(lw["shared_gate"])))
    plain = {k: v for k, v in params.items() if k != "shared_out_gate"}
    whole = run(_share_config(c, 0, 32, moe_shared_gate=False), plain)
    np.testing.assert_allclose(gated - gate * shared, whole - shared, atol=3e-5)
    np.testing.assert_allclose(halved - 0.5 * shared, whole - shared, atol=3e-5)
    cfg = _share_config(c, 0, 32)
    assert cfg.num_params - _share_config(c, 0, 32, moe_shared_gate=False).num_params == cfg.num_layers * cfg.embed_dim


def _gated_loop(xs, wg, wu, wd, sizes):
    """``(silu(x Wg_e) * x Wu_e) Wd_e`` expert by expert over rows sorted by expert."""
    out, lo = np.zeros((xs.shape[0], wd.shape[-1]), np.float32), 0
    for e, n in enumerate(np.asarray(sizes)):
        rows = jnp.asarray(xs[lo:lo + n], jnp.float32)
        out[lo:lo + n] = np.asarray((jax.nn.silu(rows @ wg[e]) * (rows @ wu[e])) @ wd[e])
        lo += n
    return out


@pytest.mark.parametrize("sizes", [[30, 0, 90, 1, 0, 0, 33, 7], [0, 0, 0, 0, 0, 0, 0, 5], [300, 0, 0, 0, 0, 0, 0, 0],
                                   [0] * 8], ids=["uneven", "the_last_expert_alone", "one_expert_over_row_tiles", "none"])
def test_past_256_rows_the_gated_kernel_walks_each_experts_own_row_tiles(sizes):
    """``grouped_mlp`` with more rows than every expert should multiply (320
    here, a decode step of 128 slots at 10 of 512 with 64 held): the
    ``moe_experts`` kernel interpreted takes each expert over the 16-row tiles
    that hold its rows, out of the layers' stack, against a loop over the
    experts; rows past the experts' sum are left zero. At 256 rows and under
    the kernel is the one of before (every expert over every row)."""
    from accelerate_tpu.models import moe

    k = jax.random.split(jax.random.PRNGKey(3), 4)
    xs = jax.random.normal(k[0], (320, 32))
    wg, wu = (jax.random.normal(kk, (2, 8, 32, 128)) * 32 ** -0.5 for kk in k[1:3])
    wd = jax.random.normal(k[3], (2, 8, 128, 32)) * 128 ** -0.5
    assert 320 > moe._ALL_ROWS_MAX >= 256
    got = grouped_mlp(xs, wg, wu, wd, jnp.asarray(sizes, jnp.int32), "interpret", layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), _gated_loop(xs, wg[1], wu[1], wd[1], sizes), atol=3e-5)
    assert not np.asarray(got[sum(sizes):]).any()
    few = grouped_mlp(xs[:256], wg, wu, wd, jnp.minimum(jnp.asarray(sizes, jnp.int32), 32), "interpret", layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(few), _gated_loop(xs[:256], wg[1], wu[1], wd[1], np.minimum(sizes, 32)), atol=3e-5)
