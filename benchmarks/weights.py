"""Seeded weights, made on the device in one jitted call, in published layout.

Both the program (through a driver's adapter) and the plain reference get
their weights from here and from ``--seed`` alone, so the reference takes
nothing the program has made. Keys are threefry whatever the platform's
default generator is: its bits do not depend on how an array is sharded.

Layout (x @ W everywhere): ``embed`` [V, E]; per layer, stacked on a leading
layer axis, ``q`` [E, H*D], ``k``/``v`` [E, KV*D], ``o`` [H*D, E], ``gate``/
``up`` [E, F], ``down`` [F, E], ``norm_attn``/``norm_mlp`` [E]; ``norm_final``
[E]; ``head`` [E, V]. Matrices are N(0, 1/fan_in); norm scales are 1 + 0.1 N
so that a norm left out or misplaced shows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("q", "k", "v", "o", "gate", "up", "down", "norm_attn", "norm_mlp")


def shapes(c: dict) -> dict:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd, kvd = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    return {"embed": (v, e), "q": (e, hd), "k": (e, kvd), "v": (e, kvd), "o": (hd, e),
            "gate": (e, f), "up": (e, f), "down": (f, e), "norm_attn": (e,), "norm_mlp": (e,),
            "norm_final": (e,), "head": (e, v)}


def seed_key(seed: int):
    """A threefry key for any whole ``--seed``, also past 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32")
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, name: str, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("norm"):
        x = 1.0 + 0.1 * x
    elif name == "embed":
        pass  # N(0, 1): the first norm rescales it
    else:
        x = x * (shape[0] ** -0.5)
    return x.astype(dtype)


def make(c: dict, key, dtype) -> dict:
    """Traceable: call under ``jax.jit`` (``make_jit``). Layer leaves are made
    layer by layer so that no float32 copy of a whole stack ever exists."""
    shp = shapes(c)
    names = sorted(shp)
    out = {}
    for i, name in enumerate(names):
        k = jax.random.fold_in(key, i)
        if name in LAYER_LEAVES:
            layer_keys = jax.random.split(k, c["num_hidden_layers"])
            out[name] = jax.lax.map(lambda lk, n=name: _leaf(lk, n, shp[n], dtype), layer_keys)
        else:
            out[name] = _leaf(k, name, shp[name], dtype)
    return out


def make_jit(c: dict, seed: int, dtype, adapt=None, out_shardings=None):
    """Weights for ``seed`` in ``dtype``; ``adapt`` maps the published layout
    to another tree inside the same program (a driver's adapter)."""
    frozen = tuple(sorted((k, v) for k, v in c.items() if isinstance(v, (int, float, bool))))

    @functools.partial(jax.jit, out_shardings=out_shardings)
    def build(key):
        tree = make(dict(frozen), key, dtype)
        return adapt(tree) if adapt is not None else tree

    return build(seed_key(seed))
