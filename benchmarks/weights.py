"""Seeded weights, made on the device in one jitted call, in published layout.

Both the program (through the architecture's adapter) and the plain
reference get their weights from here and from ``--seed`` alone, so the
reference takes nothing the program has made. Keys are threefry whatever the
platform's default generator is: its bits do not depend on how an array is
sharded.

The layout is the architecture's (``reference/<model_type>.py``):
``shapes(c)`` gives every leaf's name and shape (x @ W everywhere),
``LAYER_LEAVES`` the leaves stacked on a leading layer axis, and an optional
``INIT`` ``{leaf: rule(key, shape) -> float32}`` the leaves no rule here
covers. The rules here, by name: ``norm*`` scales are 1 + 0.1 N, so that a
norm left out or misplaced shows; ``embed`` is N(0, 1); every other leaf is
N(0, 1/fan_in) with the fan-in its first dimension. A leaf's key is
``fold_in`` of its index among the sorted names, ``split`` by layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

def seed_key(seed: int):
    """A threefry key for any whole ``--seed``, also past 2**31."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32")
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, name: str, shape, dtype, rule=None):
    if rule is not None:
        return rule(key, shape).astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("norm"):
        x = 1.0 + 0.1 * x
    elif name == "embed":
        pass  # N(0, 1): the first norm rescales it
    else:
        x = x * (shape[0] ** -0.5)
    return x.astype(dtype)


def make(layout, c: dict, key, dtype) -> dict:
    """Traceable: call under ``jax.jit`` (``make_jit``). Layer leaves are made
    layer by layer so that no float32 copy of a whole stack ever exists."""
    shp = layout.shapes(c)
    rules = getattr(layout, "INIT", {})
    names = sorted(shp)
    out = {}
    for i, name in enumerate(names):
        k = jax.random.fold_in(key, i)
        if name in layout.LAYER_LEAVES:
            layer_keys = jax.random.split(k, c["num_hidden_layers"])
            out[name] = jax.lax.map(lambda lk, n=name: _leaf(lk, n, shp[n], dtype, rules.get(n)), layer_keys)
        else:
            out[name] = _leaf(k, name, shp[name], dtype, rules.get(name))
    return out


def make_jit(layout, c: dict, seed: int, dtype, adapt=None, out_shardings=None):
    """Weights of the configuration ``c`` (whole, lists and groups included)
    for ``seed`` in ``dtype``; ``adapt`` maps the published layout to another
    tree inside the same program (the architecture's adapter)."""

    @functools.partial(jax.jit, out_shardings=out_shardings)
    def build(key):
        tree = make(layout, c, key, dtype)
        return adapt(tree) if adapt is not None else tree

    return build(seed_key(seed))
