"""The plain reference for training: loss, gradients and AdamW in float32.

The forward pass is the architecture's plain reference (``model``: the
module ``reference/<model_type>.py``, handed in by the caller; it gives
``LAYER_LEAVES``, ``layer`` and ``head_logits``) with its layer over each row
of the batch (``jax.vmap``), the loss is the mean cross-entropy of every position's
next token, gradients are ``jax.grad`` of that (``loss_and_grad``; at the
timed size the same gradient is taken layer by layer, ``LayerByLayer``, so
that it fits beside the optimizer's state), and AdamW is written out
(Loshchilov & Hutter; the same update ``optax.adamw`` documents: moments with
bias correction, eps outside the root, decoupled decay, all times the
learning rate). ``jax.checkpoint`` around a layer and sharding hints on the
arguments only decide what is kept and where it lives; nothing of the
program is imported.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

LOSS_BLOCK = 1024  # positions whose logits are held at once


def _top(model, w: dict) -> dict:
    """The leaves outside the layers that the head reads."""
    return {k: x for k, x in w.items() if k not in model.LAYER_LEAVES and k != "embed"}


def loss_fn(model, c: dict, precision: str, w: dict, ids):
    """ids [B, T] -> mean next-token cross-entropy over B * (T - 1)."""
    one_layer = jax.checkpoint(lambda h, lw: jax.vmap(lambda row: model.layer(c, precision, row, lw))(h))
    h = jnp.take(w["embed"], ids, axis=0).astype(jnp.float32)
    for i in range(c["num_hidden_layers"]):
        h = one_layer(h, {k: w[k][i] for k in model.LAYER_LEAVES})
    return _head_loss(model, c, precision, _top(model, w), h, ids)


def _head_loss(model, c: dict, precision: str, top: dict, h, ids):
    """Final norm, output head and cross-entropy of the rows' last hidden
    states h [B, T, E], the logits a block of positions at a time."""
    b, t = ids.shape

    @jax.checkpoint
    def block_sum(hx, targets, valid):
        logp = jax.nn.log_softmax(model.head_logits(c, precision, top, hx), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0] * valid)

    targets = jnp.concatenate([ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], axis=1)
    valid = (jnp.arange(t) < t - 1).astype(jnp.float32)[None, :] * jnp.ones((b, 1), jnp.float32)
    n = t // LOSS_BLOCK if t > LOSS_BLOCK and t % LOSS_BLOCK == 0 else 1
    split = lambda x: jnp.moveaxis(x.reshape(b, n, t // n, *x.shape[2:]), 1, 0)
    total = jnp.sum(jax.lax.map(lambda a: block_sum(*a), (split(h), split(targets), split(valid))))
    return total / (b * (t - 1))


def loss_and_grad(model, c: dict, precision: str, w: dict, ids):
    """ids [M, B, T]: the mean loss and its gradient over M equal groups of
    rows taken one after another (what is held at once is one group's)."""
    def micro(carry, group):
        loss, g = jax.value_and_grad(lambda p: loss_fn(model, c, precision, p, group))(w)
        return (carry[0] + loss, jax.tree_util.tree_map(jnp.add, carry[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, w))
    (loss, g), _ = jax.lax.scan(micro, zero, ids)
    m = ids.shape[0]
    return loss / m, jax.tree_util.tree_map(lambda x: x / m, g)


class LayerByLayer:
    """``loss_and_grad`` again, one layer at a time, for sizes at which the
    whole model's backward pass does not fit beside the optimizer's state:
    the forward pass keeps each layer's input, the backward pass walks the
    layers in reverse through ``jax.vjp`` of that one layer and adds its
    weights' gradients into the stacks. The same arithmetic in the same
    order (``tests/benchmark`` holds the two together); only one layer's
    weights are whole at a time. ``place(x, kind)`` may say where a weight
    about to be used ("weight") or the rows' activations ("rows") live, and
    ``layout`` where each gradient does; neither changes a value."""

    def __init__(self, model, c: dict, precision: str, place=None, layout=None):
        self.model = model
        place = place or (lambda x, kind: x)
        cut = lambda stacks, i: {k: place(jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), "weight")
                                 for k, x in stacks.items()}
        rows = lambda h, lw: jax.vmap(lambda row: model.layer(c, precision, row, lw))(h)
        keep = (lambda g, k: jax.lax.with_sharding_constraint(g, layout[k])) if layout is not None else (lambda g, k: g)

        def head(h, top, ids):
            return _head_loss(model, c, precision, {k: place(x, "weight") for k, x in top.items()}, h, ids)

        def backward(h, stacks, i, dh, grads):
            _, vjp = jax.vjp(lambda hh, lw: rows(hh, lw), h, cut(stacks, i))
            dh_in, d_lw = vjp(dh)
            grads = {k: keep(jax.lax.dynamic_update_index_in_dim(
                g, jax.lax.dynamic_index_in_dim(g, i, 0, keepdims=False) + d_lw[k], i, 0), k)
                for k, g in grads.items()}
            return place(dh_in, "rows"), grads

        self.embed = jax.jit(lambda table, ids: place(jnp.take(place(table, "weight"), ids, axis=0)
                                                      .astype(jnp.float32), "rows"))
        self.forward = jax.jit(lambda h, stacks, i: place(rows(h, cut(stacks, i)), "rows"))
        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))
        self.backward = jax.jit(backward, donate_argnums=(3, 4))
        self.embed_back = jax.jit(lambda g, ids, dh: keep(g.at[ids].add(dh), "embed"), donate_argnums=(0,))
        self.layers = c["num_hidden_layers"]

    def __call__(self, w: dict, ids):
        """ids [M, B, T] -> (mean loss, gradients), group after group."""
        leaves, top = self.model.LAYER_LEAVES, _top(self.model, w)
        stacks = {k: w[k] for k in leaves}
        grads = jax.tree_util.tree_map(jnp.zeros_like, w)
        g_stacks = {k: grads[k] for k in leaves}
        loss = 0.0
        for group in ids:
            inputs = [self.embed(w["embed"], group)]
            for i in range(self.layers):
                inputs.append(self.forward(inputs[-1], stacks, i))
            part, (dh, d_top) = self.head(inputs.pop(), top, group)
            loss = loss + part
            grads.update({k: grads[k] + d for k, d in d_top.items()})
            for i in reversed(range(self.layers)):
                dh, g_stacks = self.backward(inputs.pop(), stacks, i, dh, g_stacks)
            grads["embed"] = self.embed_back(grads["embed"], group, dh)
        grads.update(g_stacks)
        m = len(ids)
        return loss / m, jax.tree_util.tree_map(lambda x: x / m, grads)


def adamw(hyper: dict, step: int, p, g, m, v):
    b1, b2, eps = hyper["b1"], hyper["b2"], hyper["eps"]
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    p = jax.tree_util.tree_map(
        lambda p_, m_, v_: p_ - hyper["learning_rate"] * (
            (m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + hyper["weight_decay"] * p_),
        p, m, v)
    return p, m, v


def leaf_norms(model, tree: dict) -> dict:
    """Norm of every leaf, a layer's slice of a stacked leaf counting as one:
    {"q/0": ..., "embed": ...} as float32 scalars."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name in model.LAYER_LEAVES:
            per = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"{name}/{i}"] = per[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


def follow(model, c: dict, hyper: dict, make_w0, batches: list, precision: str = "float32", place=None,
           say=lambda msg: None) -> dict:
    """The first ``len(batches)`` steps from the weights ``make_w0()`` gives
    (float32, in the published layout; made again at the end rather
    than kept): each step's loss, the per-leaf norm of the first gradient,
    and the per-leaf norm of the parameters' change after the last step.
    Each batch is [M, B, T]: M groups of rows taken one after another."""
    t0 = time.perf_counter()
    p = make_w0()
    layout = jax.tree_util.tree_map(lambda x: x.sharding, p) if place is not None else None
    grad = LayerByLayer(model, c, precision, place, layout)
    update = jax.jit(lambda n, p, g, m, v: adamw(hyper, n, p, g, m, v), static_argnums=0, donate_argnums=(1, 2, 3, 4))
    norms = jax.jit(lambda tree: leaf_norms(model, tree))
    m, v = (jax.tree_util.tree_map(jnp.zeros_like, p) for _ in range(2))
    losses, grad_norms = [], None
    for n, ids in enumerate(batches, start=1):
        loss, g = grad(p, ids)
        losses.append(float(loss))
        say(f"  {precision} reference step {n}: loss {losses[-1]:.5f} at {time.perf_counter() - t0:.1f}s")
        if n == 1:
            grad_norms = {k: float(x) for k, x in norms(g).items()}
        p, m, v = update(n, p, g, m, v)
        del g
    del m, v
    delta = jax.jit(lambda a, b: leaf_norms(model, jax.tree_util.tree_map(lambda x, y: x - y, a, b)))(p, make_w0())
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": {k: float(x) for k, x in delta.items()}}


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The largest |got - want| over the leaves, measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some gradients are all but zero). Returns (gap, leaf)."""
    import statistics

    floor = statistics.median(want.values())
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf
