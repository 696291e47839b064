"""Pipeline-parallelism tests on the 8-device CPU sim: schedule correctness
(parity with the non-PP model), gradient parity, and mesh integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.parallel.mesh import build_mesh
from accelerate_tpu.parallel.pipeline import (
    merge_microbatches,
    split_microbatches,
    stack_layers_to_stages,
    stages_to_stack_layers,
)


def _cfg(**kw):
    kw.setdefault("num_layers", 4)
    kw.setdefault("dropout_rate", 0.0)
    return DecoderConfig.tiny(**kw)


def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(_flat(v, path))
        else:
            out[path] = v
    return out


def _dense_to_pipelined(dense_params, pipe_params, num_stages):
    from accelerate_tpu.parallel.pipeline import remap_params_to_pipeline

    return remap_params_to_pipeline(dense_params, pipe_params, num_stages)


class TestMicrobatchHelpers:
    def test_split_merge_roundtrip(self):
        x = jnp.arange(24.0).reshape(12, 2)
        mb = split_microbatches(x, 4)
        assert mb.shape == (4, 3, 2)
        np.testing.assert_array_equal(merge_microbatches(mb), x)

    def test_split_indivisible_raises(self):
        with pytest.raises(ValueError, match="not divisible"):
            split_microbatches(jnp.zeros((10, 2)), 4)

    def test_stage_stack_roundtrip(self):
        tree = {"w": jnp.arange(24.0).reshape(6, 4)}
        staged = stack_layers_to_stages(tree, 2)
        assert staged["w"].shape == (2, 3, 4)
        back = stages_to_stack_layers(staged)
        np.testing.assert_array_equal(back["w"], tree["w"])


class TestPipelineParity:
    _dense_cache: dict = {}

    def _models_and_params(self, num_stages, num_micro, mesh=None):
        from accelerate_tpu.parallel.sharding import unbox_params

        cfg_dense = _cfg(scan_layers=True)
        cfg_pipe = _cfg(pipeline_stages=num_stages, pipeline_microbatches=num_micro)
        rng = jax.random.PRNGKey(0)
        ids = jnp.zeros((4, 16), jnp.int32)
        # the dense side is identical across the parametrized combos — init
        # it once per mesh (pure jax data, immune to the state resets)
        cache_key = id(mesh)
        if cache_key not in self._dense_cache:
            dense = DecoderLM(cfg_dense, mesh)
            dense_raw, _ = unbox_params(dense.init(rng, ids)["params"])
            type(self)._dense_cache[cache_key] = (dense, dense_raw)
        dense, dense_raw = self._dense_cache[cache_key]
        pipe = DecoderLM(cfg_pipe, mesh)
        pipe_vars = pipe.init(rng, ids)
        pipe_raw, _ = unbox_params(pipe_vars["params"])
        mapped = _dense_to_pipelined(dense_raw, pipe_raw, num_stages)
        return dense, pipe, dense_raw, mapped

    @pytest.mark.parametrize(
        "num_stages,num_micro",
        [(2, 2), pytest.param(4, 4, marks=pytest.mark.slow)],
    )
    def test_forward_parity(self, num_stages, num_micro):
        dense, pipe, dense_p, pipe_p = self._models_and_params(num_stages, num_micro)
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)
        out_d = dense.apply({"params": dense_p}, ids)["logits"]
        out_p = pipe.apply({"params": pipe_p}, ids)["logits"]
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_p), rtol=2e-5, atol=2e-5)

    def test_loss_and_grad_parity(self):
        # doubles as the (2, 4) forward-parity combo: loss parity implies
        # forward parity through the fused-CE head, one model build total
        dense, pipe, dense_p, pipe_p = self._models_and_params(2, 4)
        ids = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 256)

        def loss_d(p):
            return dense.apply({"params": p}, ids, labels=ids)["loss"]

        def loss_p(p):
            return pipe.apply({"params": p}, ids, labels=ids)["loss"]

        ld, gd = jax.value_and_grad(loss_d)(dense_p)
        lp, gp = jax.value_and_grad(loss_p)(pipe_p)
        np.testing.assert_allclose(float(ld), float(lp), rtol=1e-5)
        # compare a stage-stacked grad leaf against its dense counterpart
        gd_flat = _flat(gd)
        gp_flat = _flat(gp)
        for path, gleaf in gp_flat.items():
            if "stages/layers/" in path:
                tail = path.split("stages/layers/")[-1]
                dpath = [p for p in gd_flat if p.endswith(tail) and "layers/" in p]
                assert dpath, path
                np.testing.assert_allclose(
                    np.asarray(gleaf).reshape(np.asarray(gd_flat[dpath[0]]).shape),
                    np.asarray(gd_flat[dpath[0]]),
                    rtol=2e-4,
                    atol=2e-5,
                )

    def test_pipeline_on_stage_mesh(self):
        """End-to-end on a mesh with a real stage axis: loss finite + params
        stage-sharded."""
        mesh = build_mesh({"stage": 2, "data": 2, "tensor": 2})
        cfg = _cfg(pipeline_stages=2, pipeline_microbatches=2)
        model = DecoderLM(cfg, mesh)
        rng = jax.random.PRNGKey(0)
        ids = jnp.zeros((4, 16), jnp.int32)
        variables = model.init(rng, ids)
        from accelerate_tpu.parallel.sharding import (
            infer_param_sharding,
            shard_params,
            unbox_params,
        )
        from accelerate_tpu.utils.dataclasses import ShardingConfig

        raw, axes = unbox_params(variables["params"])
        shardings = infer_param_sharding(raw, mesh, ShardingConfig(), axes)
        params = shard_params(raw, shardings)
        flat = _flat(params)
        staged_leaves = [v for p, v in flat.items() if "stages/layers/" in p]
        assert staged_leaves
        for leaf in staged_leaves:
            # dim 0 (stage) must actually be sharded over the stage axis
            spec = leaf.sharding.spec
            assert spec and spec[0] == "stage", (leaf.shape, spec)

        @jax.jit
        def loss_fn(p, batch):
            return model.apply({"params": p}, batch, labels=batch)["loss"]

        loss = loss_fn(params, jax.random.randint(rng, (4, 16), 0, 256))
        assert np.isfinite(float(loss))


class TestPreparePippy:
    def test_pipelined_inference_matches_dense(self):
        from accelerate_tpu.inference import prepare_pippy
        from accelerate_tpu.parallel.sharding import unbox_params
        from accelerate_tpu.state import AcceleratorState
        from accelerate_tpu.utils.dataclasses import ShardingConfig

        AcceleratorState._reset_state(reset_partial_state=True)
        state = AcceleratorState(
            sharding_config=ShardingConfig(pipeline_parallel=2, data_parallel=2, tensor_parallel=2)
        )
        cfg = _cfg(scan_layers=True)
        dense = DecoderLM(cfg, None)
        variables = dense.init(jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))
        raw, _ = unbox_params(variables["params"])

        pipelined = prepare_pippy((dense, {"params": raw}), num_stages=2, num_microbatches=2)
        ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 256)
        out_pipe = np.asarray(pipelined(ids))
        out_dense = np.asarray(dense.apply({"params": raw}, ids)["logits"])
        np.testing.assert_allclose(out_pipe, out_dense, rtol=2e-5, atol=2e-5)

    def test_batch_padding_to_microbatches(self):
        from accelerate_tpu.inference import prepare_pippy
        from accelerate_tpu.parallel.sharding import unbox_params
        from accelerate_tpu.state import AcceleratorState
        from accelerate_tpu.utils.dataclasses import ShardingConfig

        AcceleratorState._reset_state(reset_partial_state=True)
        AcceleratorState(sharding_config=ShardingConfig(pipeline_parallel=2, data_parallel=4))
        cfg = _cfg(scan_layers=True)
        dense = DecoderLM(cfg, None)
        variables = dense.init(jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))
        raw, _ = unbox_params(variables["params"])
        pipelined = prepare_pippy((dense, {"params": raw}), num_stages=2, num_microbatches=4)
        ids = jax.random.randint(jax.random.PRNGKey(4), (6, 16), 0, 256)  # 6 % 4 != 0
        out = pipelined(ids)
        assert out.shape[0] == 6


class TestAutoWiring:
    def test_stage_mesh_auto_enables_pipeline(self):
        """ShardingConfig(pipeline_parallel=k) alone (no model knob) routes
        DecoderLM through the pipeline path."""
        mesh = build_mesh({"stage": 2, "data": 4})
        cfg = _cfg(scan_layers=True)  # pipeline_stages left at 1
        model = DecoderLM(cfg, mesh)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))
        from accelerate_tpu.parallel.sharding import unbox_params

        raw, _ = unbox_params(variables["params"])
        flat = _flat(raw)
        assert any("pipeline" in p for p in flat), list(flat)[:5]

        out = model.apply({"params": raw}, jnp.zeros((4, 16), jnp.int32))
        assert out["logits"].shape == (4, 16, cfg.vocab_size)


class TestMicrobatchAdaptation:
    @pytest.mark.slow
    def test_odd_batch_adapts_schedule(self):
        """init_variables (batch 1) and ragged eval batches trace fine: M
        adapts down to divide the batch."""
        mesh = build_mesh({"stage": 2, "data": 4})
        cfg = _cfg(scan_layers=True)
        model = DecoderLM(cfg, mesh)
        variables = model.init_variables(jax.random.PRNGKey(0))  # batch 1
        from accelerate_tpu.parallel.sharding import unbox_params

        raw, _ = unbox_params(variables["params"])
        out = model.apply({"params": raw}, jnp.zeros((3, 16), jnp.int32))  # 3 % 2 != 0
        assert out["logits"].shape == (3, 16, cfg.vocab_size)

    def test_prepare_pippy_requires_stage_axis_or_explicit(self):
        from accelerate_tpu.inference import prepare_pippy
        from accelerate_tpu.state import AcceleratorState

        AcceleratorState._reset_state(reset_partial_state=True)
        AcceleratorState()  # default mesh: no stage axis
        cfg = _cfg(scan_layers=True)
        dense = DecoderLM(cfg, None)
        variables = dense.init(jax.random.PRNGKey(0), jnp.zeros((2, 16), jnp.int32))
        from accelerate_tpu.parallel.sharding import unbox_params

        raw, _ = unbox_params(variables["params"])
        with pytest.raises(ValueError, match="no 'stage' axis"):
            prepare_pippy((dense, {"params": raw}))


class TestOneFOneB:
    """1F1B schedule (parallel/pipeline.one_f_one_b): manual interleaved
    backward matching AD exactly, with an O(S) — not O(M) — activation
    stash (reference Megatron 1F1B analog, megatron_lm.py:926-1033).

    The decoder tests share ONE warm model/params/vag build (class-scoped
    fixtures — pure jax data, so the per-test state reset cannot stale it):
    the grads-parity, loss-scale, and uneven-padding tests all use the same
    S=2 stage net, and the two dropout tests share a second build. This
    module is the suite's biggest compile bill (tests/TIMINGS.md)."""

    @pytest.fixture(scope="class")
    def shared_1f1b(self):
        """(cfg, params, vag, ids, l0, g0): the S=2/M=4 decoder, its 1f1b
        value-and-grad, and one unscaled baseline run on clean labels."""
        import dataclasses

        from accelerate_tpu.parallel.sharding import unbox_params

        cfg = dataclasses.replace(
            _cfg(num_layers=4), pipeline_stages=2, pipeline_microbatches=4,
            remat=False, dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        ids = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg.vocab_size)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((8, 16), jnp.int32))
        params, _ = unbox_params(variables["params"])
        vag = DecoderLM(
            dataclasses.replace(cfg, pipeline_schedule="1f1b")
        ).pipeline_value_and_grad()
        assert vag is not None
        jvag = jax.jit(vag)
        l0, g0 = jvag(params, ids, ids)
        return cfg, model, params, jvag, ids, l0, g0

    @pytest.fixture(scope="class")
    def shared_1f1b_dropout(self):
        """(cfg, params, vag) for the dropout-configured S=2/M=2 decoder."""
        import dataclasses

        from accelerate_tpu.parallel.sharding import unbox_params

        cfg = dataclasses.replace(
            _cfg(num_layers=4), pipeline_stages=2, pipeline_microbatches=2,
            pipeline_schedule="1f1b", dropout_rate=0.2, remat=False,
            dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((4, 16), jnp.int32))
        params, _ = unbox_params(variables["params"])
        vag = model.pipeline_value_and_grad()
        assert vag is not None
        return cfg, params, vag

    def test_toy_stage_net_matches_ad(self):
        from accelerate_tpu.parallel.pipeline import one_f_one_b

        S, M, mb, d = 3, 6, 2, 5
        rng = np.random.RandomState(0)
        params = {
            "w": jnp.asarray(rng.randn(S, d, d) * 0.3),
            "b": jnp.asarray(rng.randn(S, d) * 0.1),
        }
        x = jnp.asarray(rng.randn(M * mb, d))
        targets = jnp.asarray(rng.randn(M * mb, d))

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        def ref_loss(p, xx):
            x_mb = split_microbatches(xx, M)
            t_mb = split_microbatches(targets, M)
            h = x_mb
            for s in range(S):
                h = jax.vmap(
                    lambda v: stage_fn(jax.tree_util.tree_map(lambda l: l[s], p), v)
                )(h)
            return jnp.mean(jnp.mean((h - t_mb) ** 2, axis=(1, 2)))

        ref_l, (ref_g, ref_dx) = jax.value_and_grad(ref_loss, argnums=(0, 1))(params, x)

        x_mb = split_microbatches(x, M)
        t_mb = split_microbatches(targets, M)

        def make_dy(m, y):
            tm = jax.lax.dynamic_index_in_dim(t_mb, m, 0, keepdims=False)
            lm, dy = jax.value_and_grad(lambda yy: jnp.mean((yy - tm) ** 2))(y)
            return {"loss": lm / M}, dy / M

        aux, grads, dx_mb = jax.jit(
            lambda p, xm: one_f_one_b(
                stage_fn, p, xm, make_dy, num_stages=S, num_microbatches=M,
                buffer_logical_axes=("stage", "batch", "embed"),
            )
        )(params, x_mb)

        np.testing.assert_allclose(float(aux["loss"]), float(ref_l), rtol=1e-5)
        for k in ref_g:
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(ref_g[k]), rtol=1e-4, atol=1e-6
            )
        ref_dx_mb = split_microbatches(ref_dx, M)
        np.testing.assert_allclose(
            np.asarray(dx_mb), np.asarray(ref_dx_mb), rtol=1e-4, atol=1e-6
        )

    def test_decoder_1f1b_matches_gpipe_grads(self, shared_1f1b):
        cfg, model, params, _jvag, ids, l, g = shared_1f1b

        ref_l, ref_g = jax.jit(
            jax.value_and_grad(
                lambda p: model.apply({"params": p}, ids, labels=ids)["loss"]
            )
        )(params)

        np.testing.assert_allclose(float(l), float(ref_l), rtol=2e-5)
        fr, f1 = _flat(ref_g), _flat(g)
        assert set(fr) == set(f1)
        for k in fr:
            a = np.asarray(fr[k], np.float32)
            b = np.asarray(f1[k], np.float32)
            err = np.abs(a - b).max() / (np.abs(a).max() + 1e-8)
            assert err < 2e-4, (k, err)

    def test_1f1b_loss_scale_seeds_backward(self, shared_1f1b):
        """fp16 loss scaling must run the MANUAL backward in the scaled
        domain (advisor r4): vag(..., scale=s) returns s * vag(...) grads and
        an unchanged loss."""
        cfg, model, params, jvag, ids, l0, g0 = shared_1f1b
        s = jnp.asarray(512.0, jnp.float32)
        vag_fn = jvag.__wrapped__
        l1, g1 = jax.jit(lambda p, i, t: vag_fn(p, i, t, scale=s))(params, ids, ids)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        f0, f1 = _flat(g0), _flat(g1)
        for k in f0:
            np.testing.assert_allclose(
                np.asarray(f1[k]), 512.0 * np.asarray(f0[k]), rtol=1e-4, atol=1e-6
            )

    def test_decoder_1f1b_matches_gpipe_with_uneven_ignore_padding(self, shared_1f1b):
        """Loss is the GLOBAL mean over non-ignored tokens in both schedules:
        per-microbatch means must be valid-token-share weighted, or uneven
        -100 padding across microbatches skews 1f1b (round-4 review)."""
        cfg, model, params, jvag, ids, _, _ = shared_1f1b
        labels = np.asarray(ids).copy()
        # heavy padding on some rows only -> microbatch token counts differ
        labels[::3, 6:] = -100
        labels[1, 2:] = -100
        labels = jnp.asarray(labels)

        ref_l, ref_g = jax.jit(
            jax.value_and_grad(
                lambda p: model.apply({"params": p}, ids, labels=labels)["loss"]
            )
        )(params)
        l, g = jvag(params, ids, labels)

        np.testing.assert_allclose(float(l), float(ref_l), rtol=2e-5)
        fr, f1 = _flat(ref_g), _flat(g)
        for k in fr:
            a = np.asarray(fr[k], np.float32)
            b = np.asarray(f1[k], np.float32)
            err = np.abs(a - b).max() / (np.abs(a).max() + 1e-8)
            assert err < 2e-4, (k, err)

    def test_manual_vag_falls_back_on_extra_call_args(self):
        """A batch carrying positions/masks must NOT silently hit the manual
        path (it only covers the plain (input_ids, labels) signature)."""
        from accelerate_tpu.accelerator import _extract_lm_batch

        ids, labels = _extract_lm_batch((), {"input_ids": 1, "labels": 2})
        assert ids == 1 and labels == 2
        assert _extract_lm_batch(
            (), {"input_ids": 1, "labels": 2, "positions": 3}
        ) == (None, None)
        assert _extract_lm_batch((1, 2, 3), {}) == (None, None)

    def test_gpipe_schedule_returns_no_manual_vag(self):
        cfg = _cfg(num_layers=4, pipeline_stages=2)
        assert DecoderLM(cfg).pipeline_value_and_grad() is None
        # unpipelined 1f1b config is also a no-op
        import dataclasses

        cfg2 = dataclasses.replace(_cfg(), pipeline_schedule="1f1b")
        assert DecoderLM(cfg2).pipeline_value_and_grad() is None

    def test_1f1b_dropout_matches_sequential_reference(self, shared_1f1b_dropout):
        """Dropout in 1F1B (round-4 weak #5, Megatron per-microbatch RNG
        parity): the schedule derives one key per (stage, microbatch) and
        reuses it in the remat backward. Grads must equal an AD reference
        that runs the stages SEQUENTIALLY with the same key derivation —
        which can only hold if each pair's forward and backward sampled the
        same masks."""
        from accelerate_tpu.models.decoder import (
            StageStack,
            _embed_lookup,
            _head_ce_loss,
        )
        from accelerate_tpu.ops.layers import rotary_embedding_tables
        from accelerate_tpu.parallel.pipeline import split_microbatches

        cfg, params, vag = shared_1f1b_dropout
        S, M = cfg.pipeline_stages, cfg.pipeline_microbatches
        ids = jax.random.randint(jax.random.PRNGKey(11), (4, 16), 0, cfg.vocab_size)
        key = jax.random.PRNGKey(42)
        l, g = jax.jit(lambda p: vag(p, ids, ids, rng=key))(params)

        def ref_loss(p):
            outer = {k: v for k, v in p.items() if k != "pipeline"}
            stages = p["pipeline"]["schedule"]["stages"]
            x = _embed_lookup(outer["embedding"], ids, cfg, None)
            x_mb = split_microbatches(x, M)
            labels_mb = split_microbatches(ids, M)
            counts = jnp.sum(labels_mb[:, :, 1:] != -100, axis=(1, 2)).astype(jnp.float32)
            weights = counts / jnp.maximum(jnp.sum(counts), 1.0)
            sin, cos = rotary_embedding_tables(
                jnp.arange(16), cfg.head_dim, theta=cfg.rope_theta, dtype=cfg.dtype
            )
            total = jnp.float32(0.0)
            for m in range(M):
                xm = x_mb[m]
                for st in range(S):
                    k_sm = jax.random.fold_in(key, st * M + m)
                    p_s = jax.tree_util.tree_map(lambda v: v[st], stages)
                    xm = StageStack(cfg, None).apply(
                        {"params": p_s}, xm, sin, cos, False,
                        rngs={"dropout": k_sm},
                    )
                total = total + _head_ce_loss(
                    xm, outer["ln_final"], outer["embedding"], outer.get("lm_head"),
                    labels_mb[m], cfg, None, weight=weights[m],
                )
            return total

        ref_l, ref_g = jax.jit(jax.value_and_grad(ref_loss))(params)
        np.testing.assert_allclose(float(l), float(ref_l), rtol=2e-5)
        fr, f1 = _flat(ref_g), _flat(g)
        assert set(fr) == set(f1)
        for k in fr:
            a = np.asarray(fr[k], np.float32)
            b = np.asarray(f1[k], np.float32)
            err = np.abs(a - b).max() / (np.abs(a).max() + 1e-8)
            assert err < 2e-4, (k, err)

    def test_1f1b_dropout_without_rng_is_deterministic(self, shared_1f1b_dropout):
        """No rng passed -> the schedule runs deterministic stages even for
        a dropout-configured model (eval semantics, old behavior)."""
        cfg, params, vag = shared_1f1b_dropout
        ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg.vocab_size)
        jvag = jax.jit(vag)
        l1, _ = jvag(params, ids, ids)
        l2, _ = jvag(params, ids, ids)
        np.testing.assert_allclose(float(l1), float(l2), rtol=0)

    @pytest.mark.slow
    def test_1f1b_peak_activation_below_gpipe(self):
        """The schedule's reason to exist: compiled temp memory (stash +
        belts) must undercut AD-through-GPipe once M >> S."""
        import dataclasses

        from accelerate_tpu.parallel.sharding import unbox_params

        M = 16
        cfg = dataclasses.replace(
            _cfg(num_layers=4), pipeline_stages=4, pipeline_microbatches=M,
            remat=True, dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        ids = jnp.zeros((M * 2, 64), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), ids[:1])
        params, _ = unbox_params(variables["params"])

        def gpipe_vag(p, i, l):
            return jax.value_and_grad(
                lambda pp: model.apply({"params": pp}, i, labels=l)["loss"]
            )(p)

        vag = DecoderLM(
            dataclasses.replace(cfg, pipeline_schedule="1f1b")
        ).pipeline_value_and_grad()

        temp = {}
        for name, fn in [("gpipe", gpipe_vag), ("1f1b", vag)]:
            ma = jax.jit(fn).lower(params, ids, ids).compile().memory_analysis()
            temp[name] = ma.temp_size_in_bytes
        assert temp["1f1b"] < temp["gpipe"], temp

    @pytest.mark.slow
    def test_engine_1f1b_on_stage_mesh_matches_gpipe(self):
        """Full Accelerator.build_train_step on a stage=2 mesh: the manual
        schedule must reproduce the AD loss/grad-norm and train."""
        import dataclasses

        import optax

        from accelerate_tpu import Accelerator, Model
        from accelerate_tpu.state import (
            AcceleratorState,
            GradientState,
            PartialState,
        )
        from accelerate_tpu.utils.dataclasses import (
            ShardingConfig,
            ShardingStrategy,
        )

        def run(schedule):
            AcceleratorState._reset_state()
            PartialState._reset_state()
            GradientState._reset_state()
            sc = ShardingConfig(
                strategy=ShardingStrategy.FSDP,
                pipeline_parallel=2, data_parallel=2, fsdp=2,
            )
            acc = Accelerator(mixed_precision="bf16", sharding_config=sc)
            cfg = dataclasses.replace(
                _cfg(num_layers=4), dtype=jnp.float32, remat=False,
                pipeline_stages=2, pipeline_microbatches=4,
                pipeline_schedule=schedule,
            )
            model_def = DecoderLM(cfg, mesh=acc.mesh)
            variables = model_def.init_variables(
                jax.random.PRNGKey(0), batch_size=16, seq_len=16
            )
            model, opt = acc.prepare(Model(model_def, variables), optax.adamw(1e-3))
            step = acc.build_train_step()
            ids = np.random.RandomState(1).randint(0, cfg.vocab_size, (16, 16))
            batch = acc.prepare_for_eval({"input_ids": ids, "labels": ids})
            m0 = step(batch)
            m1 = step(batch)
            return (
                float(jax.device_get(m0["loss"])),
                float(jax.device_get(m1["loss"])),
                float(jax.device_get(m0["grad_norm"])),
            )

        l0g, l1g, gng = run("gpipe")
        l0f, l1f, gnf = run("1f1b")
        assert abs(l0g - l0f) < 1e-3, (l0g, l0f)
        assert abs(gng - gnf) / max(gng, 1e-6) < 1e-2, (gng, gnf)
        assert l1f < l0f  # it actually trains


@pytest.mark.slow
class TestScheduleComposition:
    def test_fp16_1f1b_dropout_steps_per_call_compose(self):
        """The four hardest engine features in ONE program: fp16 loss
        scaling (scaled manual cotangent), the 1F1B schedule, per-(stage,
        microbatch) dropout keys, and the fused K-step scan. Finite,
        decreasing, and loss_mean present."""
        import dataclasses

        import optax

        from accelerate_tpu import Accelerator, Model
        from accelerate_tpu.state import (
            AcceleratorState,
            GradientState,
            PartialState,
        )
        from accelerate_tpu.utils.dataclasses import ShardingConfig

        AcceleratorState._reset_state()
        PartialState._reset_state()
        GradientState._reset_state()
        acc = Accelerator(
            mixed_precision="fp16",
            sharding_config=ShardingConfig(pipeline_parallel=2, data_parallel=4),
        )
        cfg = dataclasses.replace(
            _cfg(num_layers=4, max_seq_len=32), dtype=jnp.float32,
            dropout_rate=0.2, remat=False, pipeline_stages=2,
            pipeline_microbatches=2, pipeline_schedule="1f1b",
        )
        mdef = DecoderLM(cfg, mesh=acc.mesh)
        v = mdef.init_variables(jax.random.PRNGKey(0), batch_size=8, seq_len=32)
        model, opt = acc.prepare(Model(mdef, v), optax.adam(2e-3))
        K = 3
        step = acc.build_train_step(steps_per_call=K)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (K, 8, 32))
        batch = acc.prepare_for_eval({"input_ids": ids, "labels": ids}, batch_dim=1)
        m0 = step(batch)
        l0 = float(jax.device_get(m0["loss"]))
        assert np.isfinite(float(jax.device_get(m0["loss_mean"])))
        l1 = float(jax.device_get(step(batch)["loss"]))
        assert np.isfinite(l1) and l1 < l0, (l0, l1)


class TestSeq2SeqPipeline:
    """Decoder-tower pipelining for the T5-family model: the packed
    [target; memory] belt (Seq2SeqStageStack), per-microbatch encoder mask
    consts, and the 1F1B manual backward."""

    @pytest.fixture(scope="class")
    def shared(self):
        """One init + remap for the whole class: the gpipe and 1f1b configs
        share an identical param structure (the schedule is not part of the
        tree), so both tests reuse these trees."""
        from accelerate_tpu.models import Seq2SeqConfig, Seq2SeqLM

        cfg_dense = Seq2SeqConfig.tiny()
        dense = Seq2SeqLM(cfg_dense)
        pipe = Seq2SeqLM(
            Seq2SeqConfig.tiny(pipeline_stages=2, pipeline_microbatches=2)
        )
        rng = jax.random.PRNGKey(0)
        dense_v = dense.init_variables(rng, batch_size=2, seq_len=12, target_len=8)
        pipe_v = pipe.init_variables(rng, batch_size=2, seq_len=12, target_len=8)
        from accelerate_tpu.parallel.sharding import unbox_params

        dense_p, _ = unbox_params(dense_v["params"])
        pipe_p, _ = unbox_params(pipe_v["params"])
        return dense, pipe, dense_p, _dense_to_pipelined(dense_p, pipe_p, 2)

    def test_gpipe_loss_parity_with_mask(self, shared):
        """Pipelined loss == dense loss, WITH an encoder padding mask (the
        per-microbatch const path) and uneven -100 label padding — parity
        against the masked dense model proves the pipeline honors the mask
        (a dropped mask would break it), and the DENSE model's mask
        semantics are themselves pinned by
        test_seq2seq.py::test_loss_contract invariant 3."""
        dense, pipe, dense_p, pipe_p = shared
        r = jax.random.PRNGKey(1)
        src = jax.random.randint(r, (4, 12), 0, 256)
        labels = jax.random.randint(jax.random.fold_in(r, 1), (4, 8), 0, 256)
        labels = labels.at[0, 5:].set(-100).at[2, 2:].set(-100)
        mask = jnp.ones((4, 12), jnp.int32).at[1, 6:].set(0).at[3, 3:].set(0)

        ld = dense.apply({"params": dense_p}, src, labels=labels, attention_mask=mask)["loss"]
        lp = pipe.apply({"params": pipe_p}, src, labels=labels, attention_mask=mask)["loss"]
        np.testing.assert_allclose(float(ld), float(lp), rtol=2e-5)

    @pytest.mark.slow
    def test_1f1b_matches_ad_grads(self, shared):
        """Manual 1F1B value-and-grad == AD through the dense model on the
        remapped params: loss and every grad leaf (encoder, embedding,
        stages, head) agree with uneven ignore padding. Slow-marked: the
        non-slow tier keeps gpipe parity + the engine-path routing tests;
        this AD-grad check runs in the full matrix and the dryrun covers
        the engine path."""
        from accelerate_tpu.models import Seq2SeqConfig, Seq2SeqLM

        dense, _, dense_p, pipe_p = shared
        pipe = Seq2SeqLM(
            Seq2SeqConfig.tiny(
                pipeline_stages=2, pipeline_microbatches=2,
                pipeline_schedule="1f1b",
            )
        )
        r = jax.random.PRNGKey(2)
        src = jax.random.randint(r, (4, 12), 0, 256)
        labels = jax.random.randint(jax.random.fold_in(r, 3), (4, 8), 0, 256)
        labels = labels.at[1, 4:].set(-100)

        vag = pipe.pipeline_value_and_grad()
        assert vag is not None
        loss_m, grads_m = jax.jit(vag)(pipe_p, src, labels)

        def loss_d(p):
            return dense.apply({"params": p}, src, labels=labels)["loss"]

        ld, gd = jax.value_and_grad(loss_d)(dense_p)
        np.testing.assert_allclose(float(loss_m), float(ld), rtol=2e-5)
        gm_flat = _flat(grads_m)
        gd_flat = _flat(gd)
        for path, gleaf in gm_flat.items():
            if "stages/layers/" in path:
                dpath = path.replace("pipeline/schedule/stages/layers", "layers")
                ref = np.asarray(gd_flat[dpath])
                np.testing.assert_allclose(
                    np.asarray(gleaf).reshape(ref.shape), ref,
                    rtol=5e-4, atol=1e-5, err_msg=path,
                )
            else:
                np.testing.assert_allclose(
                    np.asarray(gleaf), np.asarray(gd_flat[path]),
                    rtol=5e-4, atol=1e-5, err_msg=path,
                )

    def test_gpipe_returns_no_manual_vag(self):
        from accelerate_tpu.models import Seq2SeqConfig, Seq2SeqLM

        cfg = Seq2SeqConfig.tiny(pipeline_stages=2)
        assert Seq2SeqLM(cfg).pipeline_value_and_grad() is None

    @pytest.mark.slow
    def test_1f1b_dropout_trains_on_stage_mesh(self):
        """End-to-end engine path on a real stage mesh: Seq2SeqLM +
        1f1b + dropout trains to a finite decreasing loss."""
        import dataclasses

        import optax

        from accelerate_tpu import Accelerator, Model
        from accelerate_tpu.models import Seq2SeqConfig, Seq2SeqLM
        from accelerate_tpu.state import (
            AcceleratorState,
            GradientState,
            PartialState,
        )
        from accelerate_tpu.utils.dataclasses import ShardingConfig

        AcceleratorState._reset_state()
        PartialState._reset_state()
        GradientState._reset_state()
        acc = Accelerator(
            sharding_config=ShardingConfig(pipeline_parallel=2, data_parallel=4)
        )
        cfg = Seq2SeqConfig.tiny(
            dropout_rate=0.1, pipeline_stages=2, pipeline_microbatches=2,
            pipeline_schedule="1f1b", max_seq_len=16, max_target_len=16,
        )
        mdef = Seq2SeqLM(cfg, mesh=acc.mesh)
        v = mdef.init_variables(jax.random.PRNGKey(0), batch_size=4, seq_len=16, target_len=16)
        model, opt = acc.prepare(Model(mdef, v), optax.adam(2e-3))
        step = acc.build_train_step()
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (4, 16))
        batch = acc.prepare_for_eval(
            {"input_ids": ids, "labels": ids}, batch_dim=0
        )
        l0 = float(jax.device_get(step(batch)["loss"]))
        for _ in range(3):
            l1 = float(jax.device_get(step(batch)["loss"]))
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0, (l0, l1)


class TestManualPathRouting:
    """Engine routing guards around the model-owned 1F1B backward."""

    def test_tuple_batch_binds_by_model_signature(self):
        """A positional (input_ids, decoder_input_ids) seq2seq batch must
        NOT be misread as (input_ids, labels) by the manual path: args are
        bound against the MODEL's parameter order before the gate."""
        from accelerate_tpu.accelerator import _extract_lm_batch

        s2s_names = ("input_ids", "decoder_input_ids", "labels", "attention_mask")
        ids = jnp.zeros((2, 4), jnp.int32)
        assert _extract_lm_batch((ids, ids), {}, s2s_names) == (None, None)
        got = _extract_lm_batch((ids,), {"labels": ids}, s2s_names)
        assert got[0] is ids and got[1] is ids
        # decoder order keeps working positionally
        dec_names = ("input_ids", "labels", "positions", "deterministic")
        got = _extract_lm_batch((ids, ids), {}, dec_names)
        assert got[0] is ids and got[1] is ids

    def test_training_defaults_dropout_on(self):
        """dropout_rate > 0 means TRAINING applies dropout on the AD path
        too (torch .train() parity) — so gpipe vs 1f1b schedule choice
        never toggles regularization. One engine build, three contracts:
        default training calls draw fresh masks; an explicit
        deterministic=True kwarg wins; a POSITIONAL deterministic must not
        collide with the injected default."""
        import dataclasses

        import optax

        from accelerate_tpu import Accelerator, Model
        from accelerate_tpu.state import (
            AcceleratorState,
            GradientState,
            PartialState,
        )

        AcceleratorState._reset_state()
        PartialState._reset_state()
        GradientState._reset_state()
        acc = Accelerator()
        cfg = dataclasses.replace(
            _cfg(num_layers=1, max_seq_len=8), dropout_rate=0.3, remat=False
        )
        mdef = DecoderLM(cfg)
        v = mdef.init_variables(jax.random.PRNGKey(0), batch_size=2, seq_len=8)
        model, _ = acc.prepare(Model(mdef, v), optax.sgd(0.0))
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 8)))
        model.train()
        l1 = float(model(ids, labels=ids)["loss"])
        l2 = float(model(ids, labels=ids)["loss"])
        assert l1 != l2, "dropout masks should differ across training calls"
        l3 = float(model(ids, labels=ids, deterministic=True)["loss"])
        l4 = float(model(ids, labels=ids, deterministic=True)["loss"])
        assert l3 == l4, "explicit deterministic=True must win"
        # DecoderLM signature: (input_ids, labels, positions, deterministic)
        p1 = float(model(ids, ids, None, True)["loss"])
        p2 = float(model(ids, ids, None, True)["loss"])
        assert p1 == p2, "positional deterministic=True must win"
        assert p1 == l3, "positional and kwarg deterministic must agree"
