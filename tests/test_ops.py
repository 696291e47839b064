"""Ops layer tests: the pallas flash-attention kernels run under
interpret=True on CPU and are checked numerically (values + grads) against
the XLA reference — the same validation the reference repo gets from
gloo-on-localhost for its collectives (SURVEY §4: fake backend = real code
on cheap hardware)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops import (
    apply_rotary_embedding,
    dot_product_attention,
    flash_attention,
    fused_linear_cross_entropy,
    mha_reference,
    rms_norm,
    rotary_embedding_tables,
    softmax_cross_entropy,
)


def _rand_qkv(key, b=1, h=2, s=256, d=128, kvh=None, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    kvh = kvh or h
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, kvh, s, d), dtype)
    v = jax.random.normal(kv, (b, kvh, s, d), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_reference(self, causal):
        q, k, v = _rand_qkv(jax.random.PRNGKey(0))
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_multiple_kv_blocks(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(1), s=512)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(2), h=4, kvh=2)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = _rand_qkv(jax.random.PRNGKey(3), s=256)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_gqa_grads_sum_over_group(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(4), h=4, kvh=2)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)

        gf = jax.grad(lambda *a: loss(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True), *a), argnums=(1, 2))(q, k, v)
        gr = jax.grad(lambda *a: loss(lambda q, k, v: mha_reference(q, k, v, causal=True), *a), argnums=(1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_dispatcher_fallback_on_odd_shapes(self):
        # 100-length sequence has no 128-multiple block → XLA path, still correct
        q, k, v = _rand_qkv(jax.random.PRNGKey(5), s=100, d=64)
        out = dot_product_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_kv_mask_matches_bias_reference(self, causal):
        q, k, v = _rand_qkv(jax.random.PRNGKey(6), b=2, s=256)
        rng = np.random.RandomState(0)
        lengths = rng.randint(64, 256, size=2)
        kv_mask = (np.arange(256)[None, :] < lengths[:, None]).astype(np.int32)
        out = flash_attention(q, k, v, causal=causal, kv_mask=jnp.asarray(kv_mask), interpret=True)
        from accelerate_tpu.ops.attention import NEG_INF

        bias = jnp.where(jnp.asarray(kv_mask)[:, None, None, :] != 0, 0.0, NEG_INF)
        ref = mha_reference(q, k, v, causal=causal, bias=bias)
        # only unpadded query rows are meaningful (padded rows never feed loss)
        valid_q = kv_mask.astype(bool)
        np.testing.assert_allclose(
            np.asarray(out)[:, :, valid_q[0], :][:1],
            np.asarray(ref)[:, :, valid_q[0], :][:1],
            atol=2e-5, rtol=2e-5,
        )
        for bi in range(2):
            rows = np.nonzero(valid_q[bi])[0]
            np.testing.assert_allclose(
                np.asarray(out)[bi][:, rows], np.asarray(ref)[bi][:, rows], atol=2e-5, rtol=2e-5
            )

    def test_kv_mask_grads_match_reference(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(7), b=2, s=256)
        kv_mask = jnp.asarray(
            (np.arange(256)[None, :] < np.array([[200], [128]])).astype(np.int32)
        )
        from accelerate_tpu.ops.attention import NEG_INF

        bias = jnp.where(kv_mask[:, None, None, :] != 0, 0.0, NEG_INF)
        # weight the loss by the query mask so padded rows don't contribute
        w = kv_mask[:, None, :, None].astype(q.dtype)

        def loss_flash(q, k, v):
            return jnp.sum((flash_attention(q, k, v, kv_mask=kv_mask, interpret=True) * w) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum((mha_reference(q, k, v, bias=bias) * w) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_segment_ids_block_cross_attention(self):
        # two packed sequences per row: tokens must not attend across the seam
        q, k, v = _rand_qkv(jax.random.PRNGKey(8), b=1, s=256)
        seg = jnp.asarray((np.arange(256) >= 128).astype(np.int32))[None, :]
        out = flash_attention(
            q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg, interpret=True
        )
        # reference: causal + segment bias
        from accelerate_tpu.ops.attention import NEG_INF

        same = seg[:, None, :, None] == seg[:, None, None, :]
        bias = jnp.where(same, 0.0, NEG_INF)
        ref = mha_reference(q, k, v, causal=True, bias=bias)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # and grads
        gf = jax.grad(
            lambda q: jnp.sum(
                flash_attention(q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg, interpret=True) ** 2
            )
        )(q)
        gr = jax.grad(lambda q: jnp.sum(mha_reference(q, k, v, causal=True, bias=bias) ** 2))(q)
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)

    def test_gqa_with_kv_mask(self):
        q, k, v = _rand_qkv(jax.random.PRNGKey(9), h=4, kvh=2, s=256)
        kv_mask = jnp.asarray((np.arange(256) < 192).astype(np.int32))[None, :]
        from accelerate_tpu.ops.attention import NEG_INF

        bias = jnp.where(kv_mask[:, None, None, :] != 0, 0.0, NEG_INF)
        out = flash_attention(q, k, v, causal=True, kv_mask=kv_mask, interpret=True)
        ref = mha_reference(q, k, v, causal=True, bias=bias)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_dispatcher_routes_kv_mask_to_kernel_shapes(self):
        # kv_mask path: dispatcher must not fall back to XLA for maskable pads
        q, k, v = _rand_qkv(jax.random.PRNGKey(10), s=256)
        kv_mask = jnp.ones((1, 256), jnp.int32)
        out = dot_product_attention(q, k, v, kv_mask=kv_mask, interpret=True)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_kernel_under_mesh_runs_per_shard(self):
        """Under a multi-device mesh the kernel must sit inside a shard_map
        over the batch and head axes: the chip's SPMD partitioner refuses to
        split a Mosaic custom call ("cannot be automatically partitioned"),
        which the CPU sim's reference path never showed. Forward and grads
        match the unsharded reference (with a padding mask, so a [B, S]
        companion is sharded too; the mask-free form is what
        ``chip_smoke.py --cpu-rehearsal --chips 4`` trains with), and the
        program really contains the shard_map."""
        from accelerate_tpu.ops.attention import NEG_INF
        from accelerate_tpu.parallel.context import dot_product_attention_sharded
        from accelerate_tpu.parallel.mesh import build_mesh

        mesh = build_mesh({"fsdp": 2, "tensor": 2}, devices=jax.devices()[:4])
        q, k, v = _rand_qkv(jax.random.PRNGKey(11), b=2, h=4, kvh=2, s=256)
        kv_mask = jnp.asarray(np.tile((np.arange(256) < 192).astype(np.int32), (2, 1)))
        bias = jnp.where(kv_mask[:, None, None, :] != 0, 0.0, NEG_INF)

        def loss_kernel(q, k, v):
            out = dot_product_attention_sharded(
                q, k, v, mesh, causal=True, kv_mask=kv_mask, interpret=True
            )
            return jnp.sum(out ** 2), out

        def loss_ref(q, k, v):
            out = mha_reference(q, k, v, causal=True, bias=bias)
            return jnp.sum(out ** 2), out

        assert "shard_map" in str(jax.make_jaxpr(loss_kernel)(q, k, v))
        (_, out), gk = jax.jit(jax.value_and_grad(loss_kernel, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        (_, ref), gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    @pytest.mark.parametrize("with_bias", [False, True])
    def test_xla_impl_honors_kv_mask(self, with_bias):
        # regression (advisor r4): impl="xla" used to early-return before the
        # kv_mask->bias conversion, silently attending over padding keys —
        # wrong Seq2SeqLM cross-attention under attention_impl="xla"
        from accelerate_tpu.ops.attention import NEG_INF

        q, k, v = _rand_qkv(jax.random.PRNGKey(11), b=2, s=256)
        kv_mask = jnp.asarray(
            (np.arange(256)[None, :] < np.array([[192], [128]])).astype(np.int32)
        )
        mask_bias = jnp.where(kv_mask[:, None, None, :] != 0, 0.0, NEG_INF)
        extra = (
            0.1 * jax.random.normal(jax.random.PRNGKey(12), (2, 1, 256, 256))
            if with_bias
            else None
        )
        out = dot_product_attention(
            q, k, v, kv_mask=kv_mask, bias=extra, impl="xla"
        )
        ref_bias = mask_bias if extra is None else mask_bias + extra
        ref = mha_reference(q, k, v, bias=ref_bias)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # and the masked rows actually differ from the unmasked computation
        unmasked = mha_reference(q, k, v, bias=extra)
        assert np.abs(np.asarray(out) - np.asarray(unmasked)).max() > 1e-3

    def test_xla_impl_honors_segment_ids(self):
        from accelerate_tpu.ops.attention import NEG_INF

        q, k, v = _rand_qkv(jax.random.PRNGKey(13), b=1, s=256)
        seg = jnp.asarray((np.arange(256) >= 128).astype(np.int32))[None, :]
        out = dot_product_attention(
            q, k, v, q_segment_ids=seg, kv_segment_ids=seg, impl="xla"
        )
        same = seg[:, None, :, None] == seg[:, None, None, :]
        ref = mha_reference(q, k, v, bias=jnp.where(same, 0.0, NEG_INF))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestLayers:
    def test_rms_norm_matches_manual(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jnp.ones((32,)) * 1.5
        y = rms_norm(x, w)
        expected = x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6) * 1.5
        np.testing.assert_allclose(y, expected, atol=1e-5)

    def test_rms_norm_bf16_fp32_internal(self):
        x = (jax.random.normal(jax.random.PRNGKey(1), (4, 128)) * 100).astype(jnp.bfloat16)
        y = rms_norm(x, jnp.ones((128,)))
        assert y.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))

    def test_rope_preserves_norm_and_zero_position(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 8, 64))
        sin, cos = rotary_embedding_tables(jnp.arange(8), 64)
        y = apply_rotary_embedding(x, sin, cos)
        # rotation preserves per-pair norms
        np.testing.assert_allclose(
            jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
        )
        # position 0 → identity
        np.testing.assert_allclose(y[:, :, 0], x[:, :, 0], atol=1e-6)

    def test_rope_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n
        d = 64
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, d))
        k = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, d))
        def dot_at(pq, pk):
            sq, cq = rotary_embedding_tables(jnp.asarray([pq]), d)
            sk, ck = rotary_embedding_tables(jnp.asarray([pk]), d)
            qq = apply_rotary_embedding(q, sq, cq)
            kk = apply_rotary_embedding(k, sk, ck)
            return float(jnp.sum(qq * kk))
        assert abs(dot_at(5, 3) - dot_at(12, 10)) < 1e-3


class TestLosses:
    def test_softmax_ce_matches_optax(self):
        import optax

        logits = jax.random.normal(jax.random.PRNGKey(0), (16, 50))
        labels = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 50)
        ours = softmax_cross_entropy(logits, labels)
        theirs = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        np.testing.assert_allclose(ours, theirs, rtol=1e-6)

    def test_ignore_index(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (8, 10))
        labels = jnp.array([1, 2, -100, 3, -100, 4, 5, 6])
        masked = softmax_cross_entropy(logits, labels, ignore_index=-100)
        keep = jnp.array([0, 1, 3, 5, 6, 7])
        manual = softmax_cross_entropy(logits[keep], labels[keep])
        np.testing.assert_allclose(masked, manual, rtol=1e-6)

    def test_fused_linear_ce_matches_unfused(self):
        n, e, v = 64, 32, 100
        h = jax.random.normal(jax.random.PRNGKey(0), (n, e))
        w = jax.random.normal(jax.random.PRNGKey(1), (e, v)) * 0.1
        labels = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
        fused = fused_linear_cross_entropy(h, w, labels, num_chunks=4)
        unfused = softmax_cross_entropy(h @ w, labels)
        np.testing.assert_allclose(fused, unfused, rtol=1e-5)

    def test_fused_linear_ce_grads(self):
        n, e, v = 32, 16, 50
        h = jax.random.normal(jax.random.PRNGKey(0), (n, e))
        w = jax.random.normal(jax.random.PRNGKey(1), (e, v)) * 0.1
        labels = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
        gf = jax.grad(lambda h, w: fused_linear_cross_entropy(h, w, labels, num_chunks=4), argnums=(0, 1))(h, w)
        gu = jax.grad(lambda h, w: softmax_cross_entropy(h @ w, labels), argnums=(0, 1))(h, w)
        for a, b in zip(gf, gu):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)

    def test_fused_linear_ce_ignore_index(self):
        n, e, v = 16, 8, 20
        h = jax.random.normal(jax.random.PRNGKey(0), (n, e))
        w = jax.random.normal(jax.random.PRNGKey(1), (e, v)) * 0.1
        labels = jnp.where(jnp.arange(n) % 3 == 0, -100, jnp.arange(n) % v)
        fused = fused_linear_cross_entropy(h, w, labels, ignore_index=-100, num_chunks=2)
        unfused = softmax_cross_entropy(h @ w, labels, ignore_index=-100)
        np.testing.assert_allclose(fused, unfused, rtol=1e-5)
