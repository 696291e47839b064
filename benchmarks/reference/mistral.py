"""The plain reference: Mistral's forward pass in float32 ``jax.numpy``.

Follows the published model (Hugging Face ``modeling_mistral.py``): RMSNorm
before attention and before the MLP, rotary embedding on halves of the head
(``rotate_half``), grouped-query causal attention scaled by head_dim**-0.5,
SwiGLU, a final RMSNorm and an untied output head. No kernel, no cache, no
batching tricks; nothing of the program is imported. Matrix multiplications
run at ``precision``:

- ``"float32"``: float32 inputs under ``jax.default_matmul_precision
  ("highest")`` -- the reference proper;
- ``"bfloat16"``: inputs rounded to bfloat16, float32 accumulation -- what
  the configurations state;
- ``"fp8"``: inputs rounded to float8_e4m3fn after a per-tensor scale, the
  nearest precision below bfloat16 -- the control that has to fail.

Departure from the published code: weights are x @ W (the published
checkpoints store W transposed). The layout, which ``weights.py`` fills from
the seed: ``embed`` [V, E]; a layer, stacked on a leading layer axis, ``q``
[E, H*D], ``k``/``v`` [E, KV*D], ``o`` [H*D, E], ``gate``/``up`` [E, F],
``down`` [F, E], ``norm_attn``/``norm_mlp`` [E]; ``norm_final`` [E]; ``head``
[E, V].
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("q", "k", "v", "o", "gate", "up", "down", "norm_attn", "norm_mlp")


def shapes(c: dict) -> dict:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd, kvd = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    return {"embed": (v, e), "q": (e, hd), "k": (e, kvd), "v": (e, kvd), "o": (hd, e),
            "gate": (e, f), "up": (e, f), "down": (f, e), "norm_attn": (e,), "norm_mlp": (e,),
            "norm_final": (e,), "head": (e, v)}


def _round(x, precision: str):
    """Round a matrix multiplication's input to ``precision``. Gradients pass
    straight through the rounding (the backward pass then multiplies by the
    rounded operands, as a low-precision kernel's would)."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        low = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(low - x)


def _mm(x, w, precision: str):
    return jnp.matmul(_round(x, precision), _round(w.astype(jnp.float32), precision),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """x [T, heads, D]; rotate_half convention."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


QUERY_BLOCK = 1024  # attention runs over this many query rows at a time once a sequence is longer


def _attend(q, k, v, q_pos, k_pos, d, precision: str):
    """softmax(q k^T / sqrt(d), causal) v for queries at ``q_pos``: q [Tq, KV, G, D]."""
    s = jnp.einsum("tkgd,skd->kgts", _round(q, precision), _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) * (d ** -0.5)
    s = jnp.where(q_pos[None, None, :, None] >= k_pos[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgts,skd->tkgd", _round(p, precision), _round(v, precision),
                      precision=jax.lax.Precision.HIGHEST)


def layer(c: dict, precision: str, h, w, query_block: int = QUERY_BLOCK):
    """One decoder layer over one sequence h [T, E] in float32. A long
    sequence's queries go through attention in blocks of rows (each block
    against every key), which changes what is held at once, not the result."""
    t = h.shape[0]
    nh, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    pos = jnp.arange(t)
    x = rms_norm(h, w["norm_attn"], c["rms_norm_eps"])
    q = _rope(_mm(x, w["q"], precision).reshape(t, nh, d), pos, c["rope_theta"])
    k = _rope(_mm(x, w["k"], precision).reshape(t, nkv, d), pos, c["rope_theta"])
    v = _mm(x, w["v"], precision).reshape(t, nkv, d)
    q = q.reshape(t, nkv, nh // nkv, d)
    block = next((b for b in (query_block, query_block // 2, query_block // 4) if b and t % b == 0), t)
    if t <= query_block or block == t:
        a = _attend(q, k, v, pos, pos, d, precision)
    else:
        one = jax.checkpoint(lambda qp: _attend(qp[0], k, v, qp[1], pos, d, precision))
        a = jax.lax.map(one, (q.reshape(t // block, block, *q.shape[1:]), pos.reshape(t // block, block)))
    h = h + _mm(a.reshape(t, nh * d), w["o"], precision)
    x = rms_norm(h, w["norm_mlp"], c["rms_norm_eps"])
    y = jax.nn.silu(_mm(x, w["gate"], precision)) * _mm(x, w["up"], precision)
    return h + _mm(y, w["down"], precision)


def head_logits(c: dict, precision: str, w: dict, h):
    """Final norm and output head over hidden states h [..., E]; ``w`` holds
    the leaves outside the layers."""
    return _mm(rms_norm(h, w["norm_final"], c["rms_norm_eps"]), w["head"], precision)


@functools.lru_cache(maxsize=None)
def _compiled(frozen_c: str, precision: str):
    c = json.loads(frozen_c)
    embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0).astype(jnp.float32))
    # the layer's weights are cut from the stacks inside the program: cut
    # outside, every layer index would be a small program of its own, made
    # anew in every process
    one = jax.jit(lambda h, stacks, i: layer(c, precision, h, jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), stacks)))
    head = jax.jit(lambda h, top, rows: head_logits(c, precision, top, jnp.take(h, rows, axis=0)))
    return embed, one, head


def logits_at(c: dict, weights: dict, ids, rows, precision: str = "float32", pad_to: int = 256):
    """Logits [len(rows), V] of one sequence ``ids`` at positions ``rows``,
    layer by layer so that only one layer's float32 copy is live. The
    sequence is padded at its end to a multiple of ``pad_to`` (causal
    attention never lets a position see what follows it), and ``rows`` to a
    multiple of 64, so that few shapes compile."""
    embed, one, head = _compiled(json.dumps(c, sort_keys=True), precision)
    n = len(ids)
    t = -(-n // pad_to) * pad_to
    padded = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    h = embed(weights["embed"], padded)
    stacks = {name: weights[name] for name in LAYER_LEAVES}
    for i in range(c["num_hidden_layers"]):
        h = one(h, stacks, i)
    r = -(-len(rows) // 64) * 64
    rows_p = jnp.zeros((r,), jnp.int32).at[: len(rows)].set(jnp.asarray(rows, jnp.int32))
    top = {name: x for name, x in weights.items() if name not in LAYER_LEAVES and name != "embed"}
    return head(h, top, rows_p)[: len(rows)]
