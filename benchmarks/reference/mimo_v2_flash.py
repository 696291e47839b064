"""The plain reference: MiMo-V2-Flash's forward pass in float32 ``jax.numpy``.

Implements the equations of ISSUE 28 (``PERF.md`` section 4 repeats them)
from the public ``config.json``: RMSNorm before attention and before the
MLP; per layer kind (``hybrid_layer_pattern``: 1 = window, 0 = full) its own
kv heads (``swa_num_key_value_heads`` / ``num_key_value_heads``) and rotary
base (``swa_rope_theta`` / ``rope_theta``); keys and queries 192 wide, values
128; ``rotate_half`` over the first ``int(192 x partial_rotary_factor)`` = 64
dimensions of a head, the rest passed through; causal scores over sqrt(192);
in a window layer key j is visible to query i iff ``0 <= i - j < 128`` and a
learned scalar per query head joins the softmax's denominator with no value;
the output is ``attention_value_scale`` times the weighted values; per layer
(``moe_layer_freq``) a dense SwiGLU MLP or sparse experts: sigmoid scores in
float32 over the published 256 router outputs, top 8 of score + selection
bias, weights the chosen scores normalised over those 8, no shared expert;
a final RMSNorm and an untied output head. No kernel, no cache, no batching;
nothing of the program is imported.

Readings of the config that are this file's (the configuration lists them
under ``assumed``): the window counts the query's own position; the value
scale multiplies the values; ``n_group`` = ``topk_group`` = 1 means no group
stage; ``routed_scaling_factor`` null is 1. Departures from the published
model: weights are x @ W (the checkpoints store W transposed); the three
multi-token-prediction layers the model card names have no key in the config
and are left out; **the share** (model-configs guide, section 4):
``n_routed_experts`` in the configuration is the number of experts *held*
(experts 0..n-1 of ``published.n_routed_experts`` router outputs), the
router keeps the published width and 8 a token, only the held experts'
products are added and the weights stay normalised over all 8 chosen, so
what the absent experts would add is left out here exactly as in the
program; ``vocab_size`` is the slice of rows held.

Matrix multiplications run at ``precision`` ("float32" at HIGHEST: the
reference proper; "bfloat16": inputs rounded, float32 accumulation, what the
configuration states; "fp8": float8_e4m3fn after a per-tensor scale, the
control that has to fail). The router's scores are float32 at every
precision, as published.

The layout, which ``weights.py`` fills from the seed. The kinds have
different leaf shapes, so only what every layer shares is stacked over all
layers (``LAYER_LEAVES``: ``q`` [E, H*192], ``o`` [H*128, E], ``norm_attn``,
``norm_mlp`` [E]); the rest is stacked per kind, in published order within
the kind, with ``INIT`` rules that keep N(0, 1/fan_in): ``k_full`` /
``v_full`` [full layers, E, 4*192 | 4*128], ``k_swa`` / ``v_swa`` [window
layers, E, 8*192 | 8*128], ``sink_swa`` [window layers, H] N(0, 1);
``gate_dense`` / ``up_dense`` [dense layers, E, F], ``down_dense`` [.., F,
E]; ``router`` [expert layers, E, 256], ``router_bias`` [.., 256] N(0,
0.01), ``gate_exp`` / ``up_exp`` [.., held, E, M], ``down_exp`` [.., held,
M, E]; ``embed`` [V, E], ``norm_final`` [E], ``head`` [E, V]. The sink and
the selection bias are drawn wide enough that leaving either out shows.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("q", "o", "norm_attn", "norm_mlp")
HEAD_LEAVES = ("norm_final", "head")


def layer_kinds(c: dict) -> list:
    """(window?, experts?) for each of the first ``num_hidden_layers``
    entries of the two published 48-entry lists."""
    n = c["num_hidden_layers"]
    return [(bool(a), bool(m)) for a, m in zip(c["hybrid_layer_pattern"][:n], c["moe_layer_freq"][:n])]


def kind_index(c: dict, l: int) -> tuple:
    """Layer ``l``'s index within its attention kind's stack and within
    its MLP kind's stack."""
    kinds = layer_kinds(c)
    return (sum(1 for w, _ in kinds[:l] if w == kinds[l][0]),
            sum(1 for _, m in kinds[:l] if m == kinds[l][1]))


def rotary_dim(c: dict) -> int:
    return int(c["head_dim"] * c["partial_rotary_factor"])


def router_outputs(c: dict) -> int:
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def shapes(c: dict) -> dict:
    e, f, m, v = c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"], c["vocab_size"]
    h, dk, dv = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    kinds = layer_kinds(c)
    n_swa, n_moe = sum(w for w, _ in kinds), sum(x for _, x in kinds)
    n_full, n_dense = len(kinds) - n_swa, len(kinds) - n_moe
    kv_f, kv_s = c["num_key_value_heads"], c["swa_num_key_value_heads"]
    held, r = c["n_routed_experts"], router_outputs(c)
    out = {"embed": (v, e), "q": (e, h * dk), "o": (h * dv, e), "norm_attn": (e,), "norm_mlp": (e,),
           "norm_final": (e,), "head": (e, v),
           "k_full": (n_full, e, kv_f * dk), "v_full": (n_full, e, kv_f * dv),
           "k_swa": (n_swa, e, kv_s * dk), "v_swa": (n_swa, e, kv_s * dv), "sink_swa": (n_swa, h),
           "gate_dense": (n_dense, e, f), "up_dense": (n_dense, e, f), "down_dense": (n_dense, f, e),
           "router": (n_moe, e, r), "router_bias": (n_moe, r),
           "gate_exp": (n_moe, held, e, m), "up_exp": (n_moe, held, e, m), "down_exp": (n_moe, held, m, e)}
    return {k: s for k, s in out.items() if s[0] > 0}


def _stacked(lead: int, std=None):
    """N(0, 1/fan_in), the fan-in being the first dimension after the
    ``lead`` stacking axes (or N(0, std^2)), made slice by slice."""
    def rule(key, shape):
        n = 1
        for d in shape[:lead]:
            n *= d
        scale = std if std is not None else shape[lead] ** -0.5
        one = lambda k: jax.random.normal(k, shape[lead:], jnp.float32) * scale
        return jax.lax.map(one, jax.random.split(key, n)).reshape(shape)
    return rule


INIT = {"k_full": _stacked(1), "v_full": _stacked(1), "k_swa": _stacked(1), "v_swa": _stacked(1),
        "sink_swa": _stacked(1, std=1.0),
        "gate_dense": _stacked(1), "up_dense": _stacked(1), "down_dense": _stacked(1),
        "router": _stacked(1), "router_bias": _stacked(1, std=0.01),
        "gate_exp": _stacked(2), "up_exp": _stacked(2), "down_exp": _stacked(2)}


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


def _rope(x, positions, theta, rot: int):
    """x [T, heads, D]: ``rotate_half`` over the first ``rot`` dimensions."""
    half = rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


QUERY_BLOCK = 1024  # attention runs over this many query rows at a time once a sequence is longer


def _attend(q, k, v, q_pos, k_pos, d, precision: str, window, sink, value_scale):
    """q [Tq, KV, G, D], k [S, KV, D], v [S, KV, Dv], sink [KV, G] or None."""
    s = jnp.einsum("tkgd,skd->kgts", _round(q, precision), _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) * (d ** -0.5)
    dist = q_pos[:, None] - k_pos[None, :]
    seen = dist >= 0
    if window is not None:
        seen = seen & (dist < window)
    s = jnp.where(seen[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        b = sink.astype(jnp.float32)[:, :, None, None]
        m = jnp.maximum(m, b)
        p = jnp.exp(s - m)
        p = p / (jnp.exp(b - m) + jnp.sum(p, axis=-1, keepdims=True))
    else:
        p = jnp.exp(s - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
    return value_scale * jnp.einsum("kgts,skd->tkgd", _round(p, precision), _round(v, precision),
                                    precision=jax.lax.Precision.HIGHEST)


def experts(c: dict, precision: str, y, w):
    """The held experts' part of an expert layer's result for y [T, E]."""
    z = jax.nn.sigmoid(_mm(y, w["router"], "float32"))                       # [T, R] float32
    _, chosen = jax.lax.top_k(z + w["router_bias"].astype(jnp.float32), c["num_experts_per_tok"])
    zc = jnp.take_along_axis(z, chosen, axis=-1)
    weight = zc / jnp.sum(zc, axis=-1, keepdims=True) if c["norm_topk_prob"] else zc
    weight = weight * (c.get("routed_scaling_factor") or 1.0)

    def one(acc, ew):
        e, wg, wu, wd = ew
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)        # [T]; 0 where e was not chosen
        out = _mm(jax.nn.silu(_mm(y, wg, precision)) * _mm(y, wu, precision), wd, precision)
        return acc + mine[:, None] * out, None

    held = w["gate_exp"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(y), (jnp.arange(held), w["gate_exp"], w["up_exp"], w["down_exp"]))
    return acc


def layer(c: dict, precision: str, h, w, l: int, query_block: int = QUERY_BLOCK):
    """Decoder layer ``l`` over one sequence h [T, E] in float32; ``w`` holds
    that layer's leaves (``layer_weights``). A long sequence's queries go
    through attention in blocks of rows, which changes what is held at
    once, not the result."""
    window_layer, expert_layer = layer_kinds(c)[l]
    t = h.shape[0]
    nh, d, dv = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    nkv = c["swa_num_key_value_heads"] if window_layer else c["num_key_value_heads"]
    theta = c["swa_rope_theta"] if window_layer else c["rope_theta"]
    has_sink = c["add_swa_attention_sink_bias"] if window_layer else c["add_full_attention_sink_bias"]
    window = c["sliding_window"] if window_layer else None
    eps, rot = c["layernorm_epsilon"], rotary_dim(c)
    pos = jnp.arange(t)
    x = rms_norm(h, w["norm_attn"], eps)
    q = _rope(_mm(x, w["q"], precision).reshape(t, nh, d), pos, theta, rot)
    k = _rope(_mm(x, w["k"], precision).reshape(t, nkv, d), pos, theta, rot)
    v = _mm(x, w["v"], precision).reshape(t, nkv, dv)
    q = q.reshape(t, nkv, nh // nkv, d)
    sink = w["sink"].reshape(nkv, nh // nkv) if has_sink else None
    args = (d, precision, window, sink, c["attention_value_scale"])
    block = next((b for b in (query_block, query_block // 2, query_block // 4) if b and t % b == 0), t)
    if t <= query_block or block == t:
        a = _attend(q, k, v, pos, pos, *args)
    else:
        one = jax.checkpoint(lambda qp: _attend(qp[0], k, v, qp[1], pos, *args))
        a = jax.lax.map(one, (q.reshape(t // block, block, *q.shape[1:]), pos.reshape(t // block, block)))
    h = h + _mm(a.reshape(t, nh * dv), w["o"], precision)
    y = rms_norm(h, w["norm_mlp"], eps)
    if expert_layer:
        return h + experts(c, precision, y, w)
    return h + _mm(jax.nn.silu(_mm(y, w["gate"], precision)) * _mm(y, w["up"], precision), w["down"], precision)


def layer_weights(c: dict, weights: dict, l, kind=None, index=None) -> dict:
    """Layer ``l``'s leaves cut from the stacks. ``l`` may be traced where
    ``kind`` (window?, experts?) and ``index`` (:func:`kind_index`) are
    given."""
    window_layer, expert_layer = kind if kind is not None else layer_kinds(c)[l]
    ia, im = index if index is not None else kind_index(c, l)
    cut = lambda x, i: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
    w = {name: cut(weights[name], l) for name in LAYER_LEAVES}
    a = "swa" if window_layer else "full"
    w["k"], w["v"] = cut(weights[f"k_{a}"], ia), cut(weights[f"v_{a}"], ia)
    if window_layer:
        w["sink"] = cut(weights["sink_swa"], ia)
    if expert_layer:
        for name in ("router", "router_bias", "gate_exp", "up_exp", "down_exp"):
            w[name] = cut(weights[name], im)
    else:
        for name in ("gate", "up", "down"):
            w[name] = cut(weights[f"{name}_dense"], im)
    return w


def head_logits(c: dict, precision: str, w: dict, h):
    """Final norm and output head over hidden states h [..., E]; ``w`` holds
    the leaves outside the layers."""
    return _mm(rms_norm(h, w["norm_final"], c["layernorm_epsilon"]), w["head"], precision)


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
        lambda h, stacks, l, ia, im, kind, l0: layer(
            c, precision, h, layer_weights(c, stacks, l, kind, (ia, im)), l0), kind=kind, l0=l0))
        for kind, l0 in first.items()}
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
    stacks = {name: x for name, x in weights.items() if name not in HEAD_LEAVES and name != "embed"}
    for l, kind in enumerate(layer_kinds(c)):
        ia, im = kind_index(c, l)
        h = one[kind](h, stacks, l, ia, im)
    r = -(-len(rows) // 64) * 64
    rows_p = jnp.zeros((r,), jnp.int32).at[: len(rows)].set(jnp.asarray(rows, jnp.int32))
    return head(h, {name: weights[name] for name in HEAD_LEAVES}, rows_p)[: len(rows)]
