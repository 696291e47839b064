"""The plain reference: EvaByte's forward pass in float32 ``jax.numpy``.

From the public ``config.json`` (``model_type`` ``evabyte``, ``attention_class``
``eva``) and Zheng et al., "Efficient Attention via Control Variates"
(arXiv:2302.04542), in the causal chunked form: one kind of block,
``num_hidden_layers`` times,

    h += o_proj(EVA(norm(h)));  h += down(silu(gate(x)) * up(x)), x = norm(h)

with ``norm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * (1 + w)``
(``norm_add_unit_offset``), no biases, a final norm and an untied head
``hidden_size -> vocab_size x num_pred_heads`` whose row block ``j`` predicts
byte ``t + 1 + j``. The logits returned are block 0's (the source's
``generate`` without ``multibyte_decoding``).

**EVA attention**, per head of width ``d``, ``s = d^-1/2``, ``W =
window_size``, ``C = chunk_size``; ``q_t``, ``k_m`` rotated over the whole
head width (``rotate_half``, ``rope_theta``, ``rope_scaling`` null);
``mu``, ``phi`` in R^d learned a head a layer:

- chunk ``c`` holds positions ``cC .. cC + C - 1``; a position's window is
  ``w(t) = floor(t / W)``;
- ``kbar_c = sum_m softmax_m(s mu . k_m) k_m``;
- ``vbar_c = sum_m softmax_m(s (phi . k_m - |k_m|^2 / 2)) v_m``, both
  softmaxes over the chunk's C positions, in float32 at every ``precision``
  (``mixedp_attn``);
- ``o_t = softmax over {k_m : w(m) = w(t), m <= t} and {kbar_c : floor(cC / W)
  < w(t)}, one softmax over both lists``, of the values ``v_m`` and ``vbar_c``.
  A chunk is seen only once its whole window has closed, so every chunk that
  is seen is complete.

No kernel, no cache, no batching; nothing of the program or of the adapter is
imported. A long sequence's queries go through attention in blocks of rows,
each against its own window's keys and every summary, which changes what is
held at once and not the result: 18,432 positions fit.

Departures and assumed details (each also under ``assumed`` in the
configuration): ``head_dim`` is ``hidden_size / num_attention_heads`` (the
config gives none); **the scale ``s`` multiplies both pooling logits**, as it
multiplies the attention's own (the catalog row does not say where the scale
enters the pooling); weights are ``x @ W`` (the checkpoints store W
transposed); the residual sum and the logits are float32 (``fp32_skip_add``,
``fp32_logits``), which float32 ``jax.numpy`` is anyway. Initial values are
seeded (``weights.py``), not the source's ``init_fn`` ``v2`` / ``init_std``:
every matrix N(0, 1/fan_in) and ``embed`` N(0, 1) as ``weights.py`` draws
them; **not drawn as ``weights.py`` draws them** (``INIT``): the norm leaves
``norm_attn``, ``norm_mlp``, ``norm_final`` are 0.1 N (the scale is ``1 +
w``, so the scales are 1 + 0.1 N as in every other architecture), and
``mu``, ``phi`` are N(0, 1) (the source starts them elsewhere; N(0, 1) makes
``s mu . k`` of order 1 over a chunk, so that a pooling left out, or taken
with the wrong vector, shows).

Matrix multiplications run at ``precision`` ("float32" at HIGHEST: the
reference proper; "bfloat16": inputs rounded, float32 accumulation, what the
configuration states; "fp8": float8_e4m3fn after a per-tensor scale, the
control that has to fail).

The layout, which ``weights.py`` fills from the seed: ``embed`` [V, E]; a
layer, stacked on a leading layer axis, ``q``, ``k``, ``v`` [E, H*D], ``o``
[H*D, E], ``mu``, ``phi`` [H, D], ``gate``/``up`` [E, F], ``down`` [F, E],
``norm_attn``/``norm_mlp`` [E]; ``norm_final`` [E]; ``head`` [E, V x
num_pred_heads].
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("q", "k", "v", "o", "mu", "phi", "gate", "up", "down", "norm_attn", "norm_mlp")


def _small_normal(key, shape):
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def _unit_normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


# the leaves ``weights.py`` has no rule for (see the header)
INIT = {"norm_attn": _small_normal, "norm_mlp": _small_normal, "norm_final": _small_normal,
        "mu": _unit_normal, "phi": _unit_normal}


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def shapes(c: dict) -> dict:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, d = c["num_attention_heads"], head_dim(c)
    return {"embed": (v, e), "q": (e, h * d), "k": (e, h * d), "v": (e, h * d), "o": (h * d, e),
            "mu": (h, d), "phi": (h, d), "gate": (e, f), "up": (e, f), "down": (f, e),
            "norm_attn": (e,), "norm_mlp": (e,), "norm_final": (e,), "head": (e, v * c["num_pred_heads"])}


def _round(x, precision: str):
    """Round a matrix multiplication's input to ``precision``."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(x, w, precision: str):
    return jnp.matmul(_round(x, precision), _round(w.astype(jnp.float32), precision),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    """``norm_add_unit_offset``: the scale is 1 + w."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, positions, theta):
    """x [T, heads, D]; rotate_half convention, over the whole head width."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def pool(c: dict, k, v, mu, phi):
    """``(kbar, vbar)`` [T / C, H, D] of the complete chunks of one sequence's
    rotated keys ``k`` and values ``v`` [T, H, D], float32 whatever the
    precision of the matrix multiplications."""
    t, h, d = k.shape
    cs, s = c["chunk_size"], d ** -0.5
    nc = t // cs
    kc, vc = k[:nc * cs].reshape(nc, cs, h, d), v[:nc * cs].reshape(nc, cs, h, d)
    mu32, phi32 = mu.astype(jnp.float32), phi.astype(jnp.float32)
    lk = s * jnp.einsum("hd,nmhd->nmh", mu32, kc, precision=jax.lax.Precision.HIGHEST)
    lv = s * (jnp.einsum("hd,nmhd->nmh", phi32, kc, precision=jax.lax.Precision.HIGHEST)
              - 0.5 * jnp.sum(kc * kc, axis=-1))
    wk, wv = jax.nn.softmax(lk, axis=1), jax.nn.softmax(lv, axis=1)
    return jnp.sum(wk[..., None] * kc, axis=1), jnp.sum(wv[..., None] * vc, axis=1)


def query_block(c: dict) -> int:
    """Rows attention takes at a time: the largest power of two up to 256
    that divides the window, so that a block lies in one window."""
    return next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if c["window_size"] % b == 0)


def _attend_block(c: dict, precision: str, q, start, k, v, kbar, vbar):
    """EVA attention of the query rows ``start .. start + len(q) - 1`` (one
    window's): q [B, H, D]; k, v the sequence's [T padded to windows, H, D];
    kbar, vbar every complete chunk's [N, H, D]."""
    w, cs, d = c["window_size"], c["chunk_size"], q.shape[-1]
    win = start // w
    k_win = jax.lax.dynamic_slice_in_dim(k, win * w, w, axis=0)
    v_win = jax.lax.dynamic_slice_in_dim(v, win * w, w, axis=0)
    q_pos = start + jnp.arange(q.shape[0])
    local = (win * w + jnp.arange(w))[None, :] <= q_pos[:, None]            # [B, W]
    remote = jnp.broadcast_to(((jnp.arange(kbar.shape[0]) * cs) // w < win)[None, :],
                              (q.shape[0], kbar.shape[0]))                      # [B, N]
    keys, vals = jnp.concatenate([kbar, k_win], axis=0), jnp.concatenate([vbar, v_win], axis=0)
    seen = jnp.concatenate([remote, local], axis=1)
    s = jnp.einsum("thd,shd->hts", _round(q, precision), _round(keys, precision),
                   precision=jax.lax.Precision.HIGHEST) * (d ** -0.5)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)  # one softmax over both lists
    return jnp.einsum("hts,shd->thd", _round(p, precision), _round(vals, precision),
                      precision=jax.lax.Precision.HIGHEST)


def layer(c: dict, precision: str, h, w):
    """One block over one sequence h [T, E] in float32; T a multiple of
    :func:`query_block`."""
    t = h.shape[0]
    nh, d, win = c["num_attention_heads"], head_dim(c), c["window_size"]
    pos = jnp.arange(t)
    x = rms_norm(h, w["norm_attn"], c["rms_norm_eps"])
    q = _rope(_mm(x, w["q"], precision).reshape(t, nh, d), pos, c["rope_theta"])
    k = _rope(_mm(x, w["k"], precision).reshape(t, nh, d), pos, c["rope_theta"])
    v = _mm(x, w["v"], precision).reshape(t, nh, d)
    kbar, vbar = pool(c, k, v, w["mu"], w["phi"])
    pad = (-t) % win  # whole windows of keys, so that a block's window is one slice
    k_p, v_p = jnp.pad(k, ((0, pad), (0, 0), (0, 0))), jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    b = query_block(c)
    one = lambda qs: _attend_block(c, precision, qs[0], qs[1], k_p, v_p, kbar, vbar)
    a = jax.lax.map(one, (q.reshape(t // b, b, nh, d), jnp.arange(0, t, b)))
    h = h + _mm(a.reshape(t, nh * d), w["o"], precision)
    x = rms_norm(h, w["norm_mlp"], c["rms_norm_eps"])
    y = jax.nn.silu(_mm(x, w["gate"], precision)) * _mm(x, w["up"], precision)
    return h + _mm(y, w["down"], precision)


def head_logits(c: dict, precision: str, w: dict, h):
    """Final norm and the head's block 0 (the next byte) over hidden states h
    [..., E]; ``w`` holds the leaves outside the layers."""
    return _mm(rms_norm(h, w["norm_final"], c["rms_norm_eps"]), w["head"][:, :c["vocab_size"]], precision)


def closed_entries(c: dict, w: dict, ids, layer_index: int, precision: str = "float32"):
    """What a cache holds for one layer of a sequence: ``(kbar, vbar)`` of its
    complete chunks [len(ids) // C, H, D] and the rotated ``(k, v)`` [len(ids),
    H, D] they were pooled from. For the test that ties the serving cache to
    the model. (The sequence is padded to whole query blocks on the way.)"""
    n, b = len(ids), query_block(c)
    t = -(-n // b) * b
    padded = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    h = jnp.take(w["embed"], padded, axis=0).astype(jnp.float32)
    cut = lambda i: {name: w[name][i] for name in LAYER_LEAVES}
    for i in range(layer_index):
        h = layer(c, precision, h, cut(i))
    lw, nh, d = cut(layer_index), c["num_attention_heads"], head_dim(c)
    x = rms_norm(h, lw["norm_attn"], c["rms_norm_eps"])
    k = _rope(_mm(x, lw["k"], precision).reshape(t, nh, d), jnp.arange(t), c["rope_theta"])
    v = _mm(x, lw["v"], precision).reshape(t, nh, d)
    kbar, vbar = pool(c, k, v, lw["mu"], lw["phi"])
    chunks = n // c["chunk_size"]
    return kbar[:chunks], vbar[:chunks], k[:n], v[:n]


@functools.lru_cache(maxsize=None)
def _compiled(frozen_c: str, precision: str):
    c = json.loads(frozen_c)
    embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0).astype(jnp.float32))
    one = jax.jit(lambda h, stacks, i: layer(c, precision, h, jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), stacks)))
    head = jax.jit(lambda h, top, rows: head_logits(c, precision, top, jnp.take(h, rows, axis=0)))
    return embed, one, head


def logits_at(c: dict, weights: dict, ids, rows, precision: str = "float32", pad_to: int = 256):
    """Logits [len(rows), V] of one sequence ``ids`` at positions ``rows``,
    layer by layer so that only one layer's float32 copy is live. The
    sequence is padded at its end to a multiple of ``pad_to`` and of the
    query block (causal attention never lets a position see what follows it,
    and a chunk is seen only from the windows after its own), and ``rows`` to
    a multiple of 64, so that few shapes compile."""
    embed, one, head = _compiled(json.dumps(c, sort_keys=True), precision)
    n, b = len(ids), query_block(c)
    step = max(pad_to // b, 1) * b
    t = -(-n // step) * step
    padded = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    h = embed(weights["embed"], padded)
    stacks = {name: weights[name] for name in LAYER_LEAVES}
    for i in range(c["num_hidden_layers"]):
        h = one(h, stacks, i)
    r = -(-len(rows) // 64) * 64
    rows_p = jnp.zeros((r,), jnp.int32).at[: len(rows)].set(jnp.asarray(rows, jnp.int32))
    top = {name: x for name, x in weights.items() if name not in LAYER_LEAVES and name != "embed"}
    return head(h, top, rows_p)[: len(rows)]
