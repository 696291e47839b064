"""The toy architecture's published layout and plain reference: the dense
reference's layer (the sibling ``mistral.py``, found by its path) over a
fused ``qkv`` leaf [E, (H + 2 KV) * D]. Nothing of the program is imported."""

import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference_dense_for_toy", os.path.join(os.path.dirname(os.path.abspath(__file__)), "mistral.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)

LAYER_LEAVES = ("qkv", "o", "gate", "up", "down", "norm_attn", "norm_mlp")
head_logits = _dense.head_logits


def shapes(c):
    e, f, v, d = c["hidden_size"], c["intermediate_size"], c["vocab_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return {"embed": (v, e), "qkv": (e, (h + 2 * kv) * d), "o": (h * d, e), "gate": (e, f), "up": (e, f),
            "down": (f, e), "norm_attn": (e,), "norm_mlp": (e,), "norm_final": (e,), "head": (e, v)}


def layer(c, precision, h, w):
    hd, kvd = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    split = {"q": w["qkv"][:, :hd], "k": w["qkv"][:, hd: hd + kvd], "v": w["qkv"][:, hd + kvd:]}
    return _dense.layer(c, precision, h, {**{k: x for k, x in w.items() if k != "qkv"}, **split})


def logits_at(c, weights, ids, rows, precision="float32", pad_to=256):
    n = len(ids)
    t = -(-n // pad_to) * pad_to
    h = jnp.take(weights["embed"], jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32)), axis=0)
    h = h.astype(jnp.float32)
    one = jax.jit(lambda h, lw: layer(c, precision, h, lw))
    for i in range(c["num_hidden_layers"]):
        h = one(h, {k: weights[k][i] for k in LAYER_LEAVES})
    top = {k: x for k, x in weights.items() if k not in LAYER_LEAVES and k != "embed"}
    return head_logits(c, precision, top, h[jnp.asarray(rows, jnp.int32)])
