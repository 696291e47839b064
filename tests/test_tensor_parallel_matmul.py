"""The tensor-parallel products that exchange their rows while they multiply
(parallel/context.gather_einsum, einsum_scatter) on the 8-device CPU sim:
against the plain einsum, bit for bit against the two-partial sum an
all-reduce gives, the conditions that keep a product on GSPMD's path, and a
scanned, recomputed decoder under ``tensor_parallel=2`` against ``1``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.models.decoder import DecoderMLP
from accelerate_tpu.ops.layers import swiglu
from accelerate_tpu.parallel.context import (
    einsum_scatter,
    gather_einsum,
    mlp_exchange,
    record_exchanged_products,
)
from accelerate_tpu.parallel.mesh import build_mesh
from accelerate_tpu.parallel.sharding import infer_param_sharding, unbox_params
from accelerate_tpu.utils.dataclasses import ShardingConfig

GATHER, SCATTER = "bse,ehd->bhsd", "bhsd,hde->bse"
BLOCK_PRODUCTS = ("bhsd,hde->bse", "bse,ehd->bhsd", "bse,em->bsm", "bsm,me->bse")


def _devices_mesh(fsdp, tensor):
    return Mesh(np.array(jax.devices()[:fsdp * tensor]).reshape(fsdp, tensor), ("fsdp", "tensor"))


def _named_mesh(**axes):
    base = {"replica": 1, "stage": 1, "data": 1, "fsdp": 1, "expert": 1, "sequence": 1, "tensor": 1}
    return build_mesh({**base, **axes})


def _operands(dtype, b=4, s=16, e=32, h=8, d=4):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (b, s, e), dtype)
    wq, wk = jax.random.normal(k[1], (e, h, d), dtype), jax.random.normal(k[2], (e, h // 2, d), dtype)
    heads, wo = jax.random.normal(k[3], (b, h, s, d), dtype), jax.random.normal(k[4], (h, d, e), dtype)
    return x, wq, wk, heads, wo


def _mlp_operands(dtype, e=32, m=64):
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    return tuple(jax.random.normal(k[j], shape, dtype) / 4 for j, shape in enumerate([(e, m), (e, m), (m, e)]))


def _plain_mlp(x, w_gate, w_up, w_down):
    return swiglu(x @ w_gate, x @ w_up) @ w_down


def _weigh(outs):
    """A scalar with a different weight on every output element."""
    return sum(jnp.vdot(jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape), o.astype(jnp.float32))
               for o in outs)


@pytest.mark.parametrize("tensor", [2, 4], ids=["one_hop", "three_hops"])
@pytest.mark.parametrize("helper", ["gather", "scatter", "mlp"])
def test_float32_matches_the_plain_einsum(helper, tensor):
    mesh = _devices_mesh(2, tensor)
    x, wq, wk, heads, wo = _operands(jnp.float32)
    if helper == "gather":
        args = (x, wq, wk)
        new = lambda x, wq, wk: gather_einsum(GATHER, x, (wq, wk), mesh, shard="h")
        old = lambda x, wq, wk: (jnp.einsum(GATHER, x, wq), jnp.einsum(GATHER, x, wk))
    elif helper == "scatter":
        args = (heads, wo)
        new = lambda a, w: (einsum_scatter(SCATTER, a, w, mesh, shard="h"),)
        old = lambda a, w: (jnp.einsum(SCATTER, a, w),)
    else:  # both at once, the hidden rows never put together
        args = (x, *_mlp_operands(jnp.float32))
        new = lambda x, w_gate, w_up, w_down: (mlp_exchange(x, (w_gate, w_up), w_down, swiglu, mesh),)
        old = lambda *a: (_plain_mlp(*a),)
    grads = lambda f: jax.jit(jax.value_and_grad(lambda *a: _weigh(f(*a)), argnums=tuple(range(len(args)))))
    for got, want in zip(jax.jit(new)(*args), old(*args)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    (loss, got), (ref_loss, want) = grads(new)(*args), grads(old)(*args)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    for g, w in zip(got, want):  # the input's gradient, then each weight's
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-5)


def test_bfloat16_is_the_two_partial_sum_bit_for_bit():
    """With two chips the exchange adds one bf16 partial product to one bf16
    partial product, which is what the all-reduce it replaces adds; a
    gathered product sums nothing and is the plain product's numbers."""
    mesh = _devices_mesh(2, 2)
    x, wq, wk, heads, wo = _operands(jnp.bfloat16)
    bits = lambda a: np.asarray(a.astype(jnp.float32))
    halves = lambda a, axis: jnp.split(a, 2, axis)

    out = jax.jit(lambda a, w: einsum_scatter(SCATTER, a, w, mesh, shard="h"))(heads, wo)
    (a0, a1), (w0, w1) = halves(heads, 1), halves(wo, 0)
    np.testing.assert_array_equal(bits(out), bits(jnp.einsum(SCATTER, a0, w0) + jnp.einsum(SCATTER, a1, w1)))

    q, k = jax.jit(lambda x, wq, wk: gather_einsum(GATHER, x, (wq, wk), mesh, shard="h"))(x, wq, wk)
    np.testing.assert_array_equal(bits(q), bits(jnp.einsum(GATHER, x, wq)))
    np.testing.assert_array_equal(bits(k), bits(jnp.einsum(GATHER, x, wk)))

    # backwards: the gathered products' input gradient is the sum of the two
    # chips' partial sums, the scattered product's a plain product
    dq = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape).astype(jnp.bfloat16)
    dx, dwq = jax.jit(jax.grad(
        lambda x, w: jnp.vdot(dq.astype(jnp.float32), gather_einsum(GATHER, x, (w,), mesh, shard="h")[0].astype(jnp.float32)),
        argnums=(0, 1)))(x, wq)
    (d0, d1), (w0, w1) = halves(dq, 1), halves(wq, 1)
    np.testing.assert_array_equal(
        bits(dx), bits(jnp.einsum("bhsd,ehd->bse", d0, w0) + jnp.einsum("bhsd,ehd->bse", d1, w1)))
    # a weight's gradient is one rounded partial product a hop's rows
    (x0, x1), (r0, r1) = halves(x, 1), halves(dq, 2)
    np.testing.assert_array_equal(
        bits(dwq), bits(jnp.einsum("bse,bhsd->ehd", x0, r0) + jnp.einsum("bse,bhsd->ehd", x1, r1)))
    do = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(out.shape).astype(jnp.bfloat16)
    dheads = jax.jit(jax.grad(
        lambda a: jnp.vdot(do.astype(jnp.float32), einsum_scatter(SCATTER, a, wo, mesh, shard="h").astype(jnp.float32))))(heads)
    np.testing.assert_array_equal(bits(dheads), bits(jnp.einsum("bse,hde->bhsd", do, wo)))

    # the MLP's two products at once: each chip's half of the width gives one partial sum
    w_gate, w_up, w_down = _mlp_operands(jnp.bfloat16)
    out = jax.jit(lambda x: mlp_exchange(x, (w_gate, w_up), w_down, swiglu, mesh))(x)
    (g0, g1), (u0, u1), (d0, d1) = halves(w_gate, 1), halves(w_up, 1), halves(w_down, 0)
    np.testing.assert_array_equal(bits(out), bits(_plain_mlp(x, g0, u0, d0) + _plain_mlp(x, g1, u1, d1)))


def _tiny(**over):
    base = dict(vocab_size=64, num_layers=2, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=8, mlp_dim=64,
                max_seq_len=16, dtype=jnp.float32, attention_impl="xla")
    return DecoderConfig(**{**base, **over})


def _forward_jaxpr(cfg, mesh, seq=16, mlp_only=False, outer_manual=False, **call):
    """(the traced forward pass as text, the products that took the exchange path)"""
    ids = jnp.zeros((4, seq), jnp.int32)
    if mlp_only:
        module, inputs = DecoderMLP(cfg, mesh), jnp.zeros((4, seq, cfg.embed_dim), cfg.dtype)
    else:
        module, inputs = DecoderLM(cfg, mesh=mesh), ids
    variables = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), inputs, **call))
    variables = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), variables)
    apply = lambda v, i: module.apply(v, i, mutable=["cache", "fp8_stats"], **call)
    if outer_manual:  # as the compressed-replica train step and LocalSGD run the model
        apply = shard_map(apply, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                          axis_names=frozenset({"tensor"}), check_vma=False)
    with record_exchanged_products() as exchanged:
        text = str(jax.make_jaxpr(apply)(variables, inputs))
    return text, exchanged


def test_a_block_under_a_tensor_axis_exchanges_its_four_products():
    text, exchanged = _forward_jaxpr(_tiny(), _named_mesh(fsdp=2, tensor=2, data=2))
    assert tuple(sorted(exchanged)) == BLOCK_PRODUCTS
    assert "shard_map" in text and "ppermute" in text


@pytest.mark.parametrize("case", ["no_mesh", "tensor_1", "tensor_manual", "rows_not_divisible", "use_cache", "use_fp8",
                                  "sequence_axis"])
def test_elsewhere_the_products_are_gspmds(case):
    """Each condition alone keeps every product on the parent's path: the
    traced program holds no shard_map and no ppermute."""
    tp = dict(fsdp=2, tensor=2, data=2)
    cfg, mesh, kw = _tiny(), _named_mesh(**tp), {}
    if case == "no_mesh":
        mesh = None
    elif case == "tensor_1":
        mesh = _named_mesh(fsdp=2, data=4)
    elif case == "tensor_manual":
        kw = dict(outer_manual=True)
    elif case == "rows_not_divisible":
        kw = dict(seq=15)
    elif case == "use_cache":
        cfg, kw = _tiny(remat=False), dict(use_cache=True)
    elif case == "use_fp8":
        cfg = _tiny(use_fp8=True)
    elif case == "sequence_axis":  # (its attention is the ring's own shard_map: the MLP alone)
        mesh, kw = _named_mesh(fsdp=2, tensor=2, sequence=2), dict(mlp_only=True)
    text, exchanged = _forward_jaxpr(cfg, mesh, **kw)
    assert not exchanged and "ppermute" not in text
    assert text.count("shard_map") == (1 if case == "tensor_manual" else 0)  # (there the test's own)


def _rehearsal_decoder():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", "mistral-7b-v0.3-train-8l-4chip.json")
    with open(path) as f:
        c = json.load(f)
    r, t = c["rehearsal"], c["training"]
    cfg = DecoderConfig(
        vocab_size=r["vocab_size"], num_layers=2, embed_dim=r["hidden_size"], num_heads=r["num_attention_heads"],
        num_kv_heads=r["num_key_value_heads"], head_dim=r["head_dim"], mlp_dim=r["intermediate_size"], max_seq_len=64,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]), tie_embeddings=False, dtype=jnp.bfloat16,
        scan_layers=True, remat=t["remat"], remat_policy=t["remat_policy"], attention_impl="xla")
    return cfg, r["limits"]


def test_a_scanned_recomputed_decoder_agrees_with_tensor_parallel_1():
    """Two layers of the training cell's rehearsal widths, bf16 activations,
    remat on, the layers scanned: loss and every leaf's gradient norm under
    fsdp 2 x tensor 2 (the exchange path) against fsdp 2 x tensor 1
    (GSPMD's), within the rehearsal's own limits."""
    cfg, limits = _rehearsal_decoder()
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab_size)
    results = {}
    for tensor in (2, 1):
        mesh = _named_mesh(fsdp=2, tensor=tensor, data=4 // tensor)
        model = DecoderLM(cfg, mesh=mesh)
        boxed = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))["params"]
        raw, axes = unbox_params(boxed)
        shardings = infer_param_sharding(raw, mesh, ShardingConfig(fsdp=2, tensor_parallel=tensor), axes)
        params = jax.jit(lambda k: unbox_params(model.init(k, jnp.zeros((1, 8), jnp.int32))["params"])[0],
                         out_shardings=shardings)(jax.random.PRNGKey(0))

        def loss(p, ids):
            p = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p)
            return model.apply({"params": p}, ids, labels=ids)["loss"].astype(jnp.float32)

        batch = jax.device_put(ids, NamedSharding(mesh, P(("data", "fsdp"))))
        with record_exchanged_products() as exchanged:
            value, grads = jax.jit(jax.value_and_grad(loss))(params, batch)
        assert tuple(sorted(exchanged)) == (BLOCK_PRODUCTS if tensor == 2 else ())
        norms = {jax.tree_util.keystr(k): float(jnp.linalg.norm(g.astype(jnp.float32)))
                 for k, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
        results[tensor] = (float(value), norms)
    (loss2, norms2), (loss1, norms1) = results[2], results[1]
    assert abs(loss2 - loss1) <= limits["loss_abs"]
    worst = max(abs(norms2[k] - norms1[k]) / norms1[k] for k in norms1)
    assert worst <= limits["grad_norm_rel"], worst


def test_the_train_step_keeps_the_products_its_trace_exchanged():
    import optax

    from accelerate_tpu import Accelerator, Model

    accelerator = Accelerator(mixed_precision="bf16",
                              sharding_config=ShardingConfig(fsdp=2, tensor_parallel=2, data_parallel=2))
    cfg = _tiny(dtype=jnp.bfloat16, scan_layers=True, remat=True, remat_policy="save_attention")
    module = DecoderLM(cfg, mesh=accelerator.mesh)
    accelerator.prepare(Model(module, module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))), optax.adamw(1e-3))
    step = accelerator.build_train_step()
    assert step._audit_tp_products == ()
    ids = np.zeros((8, 16), np.int32)
    for _ in range(2):  # the second call traces nothing and must not clear it
        assert np.isfinite(float(step({"input_ids": ids, "labels": ids})["loss"]))
        assert step._audit_tp_products == BLOCK_PRODUCTS
