"""The plain reference: the Jamba family's forward pass in float32 ``jax.numpy``.

From the public ``config.json`` (``model_type`` ``jamba``): a decoder of
``num_hidden_layers`` layers, each ``x = x + mixer(RMSNorm(x)); x = x +
W_down(silu(W_gate h) * W_up h), h = RMSNorm(x)``, a final RMSNorm and logits
``x E^T`` over the tied embedding. Layer ``l`` mixes with attention where
``l % attn_layer_period == attn_layer_offset`` and with a selective
state-space block (Mamba-1) everywhere else. ``num_experts`` 1: every
feed-forward is the dense gated MLP of ``intermediate_size``
(``expert_layer_period`` / ``expert_layer_offset`` select nothing).

- *Attention*: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``, no bias, causal
  softmax at ``1/sqrt(head_dim)``, **no rotary or other position embedding**
  (the config has no rope key: the state-space layers carry the order).
- *State-space mixer*, ``D = mamba_expand x hidden_size``, ``N =
  mamba_d_state``, ``K = mamba_d_conv``, ``R = mamba_dt_rank``:
  ``[u, z] = h W_in`` (no bias, ``mamba_proj_bias`` false); ``u' =
  silu(conv(u) + b_conv)``, a causal depthwise convolution of K taps
  (``mamba_conv_bias``); ``[d, B, C] = u' W_x``; **d, B and C each pass an
  RMSNorm of their own** (the family's addition to Mamba-1); ``Delta =
  softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(Delta_t (x) A) *
  S_{t-1} + (Delta_t * u'_t) (x) B_t``, ``S`` in R^(D x N); ``y_t = S_t C_t +
  D * u'_t``; ``out = (y * silu(z)) W_out``.

The recurrence is a plain ``lax.scan`` over tokens, no kernel, cache or
batching, and nothing of the program or of the adapter is imported.
Attention runs over blocks of query rows once a sequence is long, which
changes what is held at once, not the result: a 5,000-token request fits.

Departures from the published description, each listed under ``assumed`` in
the configuration: ``head_dim`` is ``hidden_size / num_attention_heads`` (the
config gives none); the recurrence and its state are float32 at every
``precision`` (the checkpoints run it so; the published dtype, bfloat16, is
the matrices'); weights are ``x @ W`` (the checkpoints store W transposed)
and the convolution's taps are ``[K, D]``, tap K-1 on the current token;
initial values are seeded (``weights.py``): ``A_log = log(1..N)`` a channel
and ``b_dt`` the inverse softplus of a step drawn log-uniform in [1e-3,
1e-1], as the family initialises them, so that the random model forgets at a
realistic rate; the embedding N(0, 1/hidden_size) where ``weights.py`` draws
N(0, 1), because the head is tied to it: the logits are then of order 1, as
in the cells whose head is a matrix of its own, and not of order
sqrt(hidden_size) = 50, where bfloat16's spacing alone is half a logit (the
first norm rescales the embedding either way); the convolution's bias N(0, 0.1^2) and the skip ``D`` 1 + 0.1
N, wide enough that leaving either out shows; norm scales 1 + 0.1 N; every
matrix N(0, 1/fan_in).

Matrix multiplications run at ``precision`` ("float32" at HIGHEST: the
reference proper; "bfloat16": inputs rounded, float32 accumulation, what the
configuration states; "fp8": float8_e4m3fn after a per-tensor scale, the
control that has to fail).

The layout, which ``weights.py`` fills from the seed: what every layer
shares is stacked over all layers (``LAYER_LEAVES``: ``norm_mixer``,
``norm_mlp`` [E], ``gate``, ``up`` [E, F], ``down`` [F, E]); the mixers'
leaves are stacked by kind, in published order within the kind (``INIT``):
``q`` [attention layers, E, H*dh], ``k``, ``v`` [.., E, KV*dh], ``o`` [..,
H*dh, E]; ``in_proj`` [state-space layers, E, 2D], ``conv_w`` [.., K, D],
``conv_b`` [.., D], ``x_proj`` [.., D, R+2N], ``norm_dt`` [.., R],
``norm_b``, ``norm_c`` [.., N], ``dt_proj`` [.., R, D], ``dt_bias`` [.., D],
``a_log`` [.., D, N], ``d`` [.., D], ``out_proj`` [.., D, E]; ``embed`` [V,
E] and ``norm_final`` [E].
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("norm_mixer", "norm_mlp", "gate", "up", "down")
HEAD_LEAVES = ("norm_final", "embed")
ATTENTION_LEAVES = ("q", "k", "v", "o")
SSM_LEAVES = ("in_proj", "conv_w", "conv_b", "x_proj", "norm_dt", "norm_b", "norm_c", "dt_proj", "dt_bias",
              "a_log", "d", "out_proj")


def layer_kinds(c: dict) -> list:
    """True for an attention layer, False for a state-space layer, in
    published order."""
    return [l % c["attn_layer_period"] == c["attn_layer_offset"] for l in range(c["num_hidden_layers"])]


def kind_index(c: dict, l: int) -> int:
    """Layer ``l``'s index within its kind's stacks."""
    kinds = layer_kinds(c)
    return sum(1 for a in kinds[:l] if a == kinds[l])


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def inner_dim(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def shapes(c: dict) -> dict:
    e, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    d, n, k, r = inner_dim(c), c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
    n_attn = sum(layer_kinds(c))
    n_ssm = c["num_hidden_layers"] - n_attn
    out = {"embed": (v, e), "norm_final": (e,),
           "norm_mixer": (e,), "norm_mlp": (e,), "gate": (e, f), "up": (e, f), "down": (f, e),
           "q": (n_attn, e, h * dh), "k": (n_attn, e, kv * dh), "v": (n_attn, e, kv * dh), "o": (n_attn, h * dh, e),
           "in_proj": (n_ssm, e, 2 * d), "conv_w": (n_ssm, k, d), "conv_b": (n_ssm, d),
           "x_proj": (n_ssm, d, r + 2 * n), "norm_dt": (n_ssm, r), "norm_b": (n_ssm, n), "norm_c": (n_ssm, n),
           "dt_proj": (n_ssm, r, d), "dt_bias": (n_ssm, d), "a_log": (n_ssm, d, n), "d": (n_ssm, d),
           "out_proj": (n_ssm, d, e)}
    return {name: s for name, s in out.items() if s[0] > 0}


def _stacked(std=None, mean: float = 0.0):
    """``mean`` + N(0, std^2) over a kind's stack, made layer by layer; std
    None is 1/sqrt(fan_in), the fan-in the first dimension after the stack's."""
    def rule(key, shape):
        scale = std if std is not None else shape[1] ** -0.5
        one = lambda k: mean + jax.random.normal(k, shape[1:], jnp.float32) * scale
        return jax.lax.map(one, jax.random.split(key, shape[0]))
    return rule


def _a_log(key, shape):
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)


def _dt_bias(key, shape, lo: float = 1e-3, hi: float = 1e-1):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(bias) = dt


def _embed(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * shape[1] ** -0.5


INIT = {"embed": _embed,
        **{name: _stacked() for name in ("q", "k", "v", "o", "in_proj", "conv_w", "x_proj", "dt_proj", "out_proj")},
        **{name: _stacked(0.1, 1.0) for name in ("norm_dt", "norm_b", "norm_c", "d")},
        "conv_b": _stacked(0.1), "a_log": _a_log, "dt_bias": _dt_bias}


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
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


QUERY_BLOCK = 1024  # attention runs over this many query rows at a time once a sequence is longer


def _attend(q, k, v, q_pos, k_pos, precision: str):
    """q [Tq, KV, G, dh], k, v [S, KV, dh]: causal softmax, no rotation."""
    s = jnp.einsum("tkgd,skd->kgts", _round(q, precision), _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) * (q.shape[-1] ** -0.5)
    s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("kgts,skd->tkgd", _round(p, precision), _round(v, precision),
                      precision=jax.lax.Precision.HIGHEST)


def attention(c: dict, precision: str, x, w, query_block: int = QUERY_BLOCK):
    t = x.shape[0]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    pos = jnp.arange(t)
    q = _mm(x, w["q"], precision).reshape(t, kv, h // kv, dh)
    k = _mm(x, w["k"], precision).reshape(t, kv, dh)
    v = _mm(x, w["v"], precision).reshape(t, kv, dh)
    block = next((b for b in (query_block, query_block // 2, query_block // 4) if b and t % b == 0), t)
    if t <= query_block or block == t:
        a = _attend(q, k, v, pos, pos, precision)
    else:
        one = jax.checkpoint(lambda qp: _attend(qp[0], k, v, qp[1], pos, precision))
        a = jax.lax.map(one, (q.reshape(t // block, block, *q.shape[1:]), pos.reshape(t // block, block)))
    return _mm(a.reshape(t, h * dh), w["o"], precision)


def state_space(c: dict, precision: str, x, w):
    """The selective state-space mixer over one sequence x [T, E], from a
    zero state: the recurrence one token at a time, in float32."""
    t = x.shape[0]
    d, n, k, r = inner_dim(c), c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
    eps = c["rms_norm_eps"]
    uz = _mm(x, w["in_proj"], precision)
    u, z = uz[:, :d], uz[:, d:]
    padded = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), u], axis=0)
    conv = sum(padded[j:j + t] * w["conv_w"][j].astype(jnp.float32) for j in range(k))  # tap k-1: the token itself
    if c["mamba_conv_bias"]:
        conv = conv + w["conv_b"].astype(jnp.float32)
    uc = jax.nn.silu(conv)
    xp = _mm(uc, w["x_proj"], precision)
    dlt = rms_norm(xp[:, :r], w["norm_dt"], eps)
    b = rms_norm(xp[:, r:r + n], w["norm_b"], eps)
    cc = rms_norm(xp[:, r + n:], w["norm_c"], eps)
    delta = jax.nn.softplus(_mm(dlt, w["dt_proj"], precision) + w["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(w["a_log"].astype(jnp.float32))  # [D, N]

    def token(s, row):
        delta_t, u_t, b_t, c_t = row
        s = jnp.exp(delta_t[:, None] * a) * s + (delta_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(token, jnp.zeros((d, n), jnp.float32), (delta, uc, b, cc))
    y = y + w["d"].astype(jnp.float32) * uc
    return _mm(y * jax.nn.silu(z), w["out_proj"], precision)


def layer(c: dict, precision: str, h, w, l: int, query_block: int = QUERY_BLOCK):
    """Decoder layer ``l`` over one sequence h [T, E] in float32; ``w`` holds
    that layer's leaves (``layer_weights``)."""
    x = rms_norm(h, w["norm_mixer"], c["rms_norm_eps"])
    if layer_kinds(c)[l]:
        h = h + attention(c, precision, x, w, query_block)
    else:
        h = h + state_space(c, precision, x, w)
    y = rms_norm(h, w["norm_mlp"], c["rms_norm_eps"])
    return h + _mm(jax.nn.silu(_mm(y, w["gate"], precision)) * _mm(y, w["up"], precision), w["down"], precision)


def layer_weights(c: dict, weights: dict, l, attention_layer=None, index=None) -> dict:
    """Layer ``l``'s leaves cut from the stacks. ``l`` may be traced where
    the kind and the ``index`` within it (:func:`kind_index`) are given."""
    if attention_layer is None:
        attention_layer, index = layer_kinds(c)[l], kind_index(c, l)
    cut = lambda x, i: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
    w = {name: cut(weights[name], l) for name in LAYER_LEAVES}
    for name in (ATTENTION_LEAVES if attention_layer else SSM_LEAVES):
        w[name] = cut(weights[name], index)
    return w


def head_logits(c: dict, precision: str, w: dict, h):
    """Final norm and the tied output head over hidden states h [..., E]."""
    return _mm(rms_norm(h, w["norm_final"], c["rms_norm_eps"]), w["embed"].T, precision)


@functools.lru_cache(maxsize=None)
def _compiled(frozen_c: str, precision: str):
    c = json.loads(frozen_c)
    embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0).astype(jnp.float32))
    # one program a layer kind; the layer's weights are cut from the stacks
    # inside it, by traced indices, so that a layer index is no program
    first = {}
    for l, kind in enumerate(layer_kinds(c)):
        first.setdefault(kind, l)
    one = {kind: jax.jit(functools.partial(
        lambda h, stacks, l, i, kind, l0: layer(c, precision, h, layer_weights(c, stacks, l, kind, i), l0),
        kind=kind, l0=l0)) for kind, l0 in first.items()}
    head = jax.jit(lambda h, top, rows: head_logits(c, precision, top, jnp.take(h, rows, axis=0)))
    return embed, one, head


def logits_at(c: dict, weights: dict, ids, rows, precision: str = "float32", pad_to: int = 1024):
    """Logits [len(rows), V] of one sequence ``ids`` at positions ``rows``,
    layer by layer so that only one layer's float32 copy is live. The
    sequence is padded at its end to a multiple of ``pad_to`` (neither mixer
    lets a position see what follows it), and ``rows`` to a multiple of 64,
    so that few shapes compile: a program with a 1,000-step loop in it
    compiles for longer than it runs (24 s against 2 s a request on the chip,
    PERF.md section 6, PR 36), so the lengths are few and coarse."""
    embed, one, head = _compiled(json.dumps(c, sort_keys=True), precision)
    n = len(ids)
    t = -(-n // pad_to) * pad_to
    padded = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
    h = embed(weights["embed"], padded)
    stacks = {name: x for name, x in weights.items() if name not in HEAD_LEAVES}
    for l, kind in enumerate(layer_kinds(c)):
        h = one[kind](h, stacks, l, kind_index(c, l))
    r = -(-len(rows) // 64) * 64
    rows_p = jnp.zeros((r,), jnp.int32).at[: len(rows)].set(jnp.asarray(rows, jnp.int32))
    return head(h, {name: weights[name] for name in HEAD_LEAVES}, rows_p)[: len(rows)]
