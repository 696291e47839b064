"""L1 operations tests (parity: reference test_utils/scripts/test_ops.py +
tests/test_utils.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accelerate_tpu import AcceleratorState
from accelerate_tpu.utils import operations as ops


def test_recursively_apply_nested():
    data = {"a": jnp.ones((2,)), "b": [jnp.zeros((3,)), "keep"]}
    out = ops.recursively_apply(lambda t: t + 1, data)
    assert out["b"][1] == "keep"
    assert float(out["a"][0]) == 2.0


def test_send_to_device_and_convert():
    import torch

    data = {"x": torch.ones(4, 2), "y": np.zeros((3,)), "z": 5}
    out = ops.send_to_device(data, jax.devices()[0])
    assert isinstance(out["x"], jax.Array)
    assert out["x"].shape == (4, 2)
    assert out["z"] == 5


def test_make_global_batch_shards_batch_dim():
    state = AcceleratorState()
    batch = {"x": np.arange(16, dtype=np.float32).reshape(16, 1)}
    out = ops.make_global_batch(batch, state.mesh)
    x = out["x"]
    assert x.shape == (16, 1)
    # sharded over the 8-device data axis → each shard has 2 rows
    assert len(x.addressable_shards) == 8
    assert x.addressable_shards[0].data.shape == (2, 1)
    np.testing.assert_array_equal(np.asarray(x), batch["x"])


def test_gather_identity_single_process():
    x = {"t": jnp.arange(8)}
    out = ops.gather(x)
    np.testing.assert_array_equal(np.asarray(out["t"]), np.arange(8))


def test_gather_object_single_process():
    assert ops.gather_object([{"a": 1}]) == [{"a": 1}]


def test_psum_inside_shard_map():
    from jax import shard_map

    state = AcceleratorState()
    mesh = state.mesh
    x = jnp.arange(8.0)

    def f(x):
        return ops.psum(jnp.sum(x), ("data",))

    out = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)(x)
    assert float(out) == 28.0


def test_psum_outside_jit_is_noop():
    x = jnp.ones((2,))
    np.testing.assert_array_equal(np.asarray(ops.psum(x)), np.ones((2,)))


def test_pad_across_processes_noop_when_equal():
    x = jnp.ones((3, 2))
    out = ops.pad_across_processes(x, dim=0)
    assert out.shape == (3, 2)


def test_pad_input_tensors():
    x = {"t": jnp.arange(10).reshape(10, 1)}
    out = ops.pad_input_tensors(x, batch_size=10, num_processes=4)
    assert out["t"].shape == (12, 1)
    assert int(out["t"][-1, 0]) == 9  # padded with the final sample


def test_concatenate_nested():
    a = {"x": jnp.ones((2, 3))}
    b = {"x": jnp.zeros((1, 3))}
    out = ops.concatenate([a, b])
    assert out["x"].shape == (3, 3)


def test_convert_to_fp32():
    data = {"h": jnp.ones((2,), jnp.bfloat16), "i": jnp.ones((2,), jnp.int32)}
    out = ops.convert_to_fp32(data)
    assert out["h"].dtype == jnp.float32
    assert out["i"].dtype == jnp.int32


def test_initialize_tensors_roundtrip():
    data = {"x": jnp.ones((4, 2)), "n": 3}
    skeleton = ops.get_data_structure(data)
    assert isinstance(skeleton["x"], jax.ShapeDtypeStruct)
    rebuilt = ops.initialize_tensors(skeleton)
    assert rebuilt["x"].shape == (4, 2)


def test_find_batch_size_and_listify():
    data = {"x": jnp.ones((5, 2))}
    assert ops.find_batch_size(data) == 5
    assert ops.listify(data)["x"] == [[1.0, 1.0]] * 5
