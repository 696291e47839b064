"""The plain reference: a ``deepseek_v3`` decoder's forward pass in float32
``jax.numpy`` (GigaChat3.1-702B-A36B's ``config.json`` is of this
``model_type``).

Implements the equations of ISSUE 42 (``PERF.md`` section 4 repeats them)
from the public ``config.json`` and the public ``deepseek_v3`` modelling
code. ``norm`` is RMS with ``rms_norm_eps`` and a learned scale; no biases.

- *Latent attention*, ``x = norm(h)``: ``c_q = norm(x W_qa)`` (``q_lora_rank``
  wide), ``[q_nope | q_pe] = c_q W_qb`` a head (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``), ``q_pe`` rotated; ``[c | k_pe] = x W_kva``
  (``kv_lora_rank`` | ``qk_rope_head_dim``), ``c`` normed, ``k_pe`` rotated,
  **one for all heads**; ``[k_nope | v] = c W_kvb`` a head
  (``qk_nope_head_dim`` | ``v_head_dim``); causal scores ``s (q_nope . k_nope
  + q_pe . k_pe)`` with ``s = (qk_nope + qk_rope)^-1/2 x mscale^2``, ``mscale =
  0.1 x mscale_all_dim x ln(factor) + 1``; the heads' outputs through
  ``W_o``. This file computes the **expanded** form: every head's keys and
  values are made from the latents; nothing is absorbed and nothing cached.
- *YaRN* on the rotated dimensions: ``f_j = theta^(-2j/d)``; the dimension
  that turns b times over the original context ``cd(b) = d ln(original / (2
  pi b)) / (2 ln theta)``; ``low = floor(cd(beta_fast))``, ``high =
  ceil(cd(beta_slow))``, clipped to [0, d - 1]; ``ramp_j = clip((j - low) /
  (high - low), 0, 1)``; ``inv_freq_j = (f_j / factor) ramp_j + f_j (1 -
  ramp_j)``; cos and sin times ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``.
- *Dense MLP* (layers below ``first_k_dense_replace``): ``h += W_down(silu(
  W_gate x) * W_up x)``.
- *Expert layer*: ``sc = sigmoid(x W_r)`` in float32; ``ch = sc +
  e_score_correction_bias``; group g of ``n_group`` scores the sum of its
  two largest ``ch``; experts outside the ``topk_group`` best groups are
  out; the ``num_experts_per_tok`` largest ``ch`` among the rest are
  chosen; ``w_e = routed_scaling_factor x sc_e / (sum of the chosen sc +
  1e-20)``; ``h += sum_e w_e E_e(x) + S(x)``, ``E_e`` and the shared expert
  ``S`` gated SiLU MLPs ``moe_intermediate_size`` wide (``S`` of
  ``n_shared_experts`` times that).
- Final norm, untied head. No kernel, no cache, no batching; nothing of the
  program is imported.

Departures from the published model (the configuration lists them under
``assumed``): weights are x @ W (the checkpoints store W transposed); the
source de-interleaves the rotated dimensions of ``q_pe`` and ``k_pe``
before it rotates them ``rotate_half``-wise, which is one fixed permutation
of both and leaves every score as it is under seeded weights, so it is left
out; the multi-token-prediction module (``num_nextn_predict_layers``) is
not on the served path of the source's own inference code and is left out;
**the share** (model-configs guide, section 4): ``n_routed_experts`` in the
configuration is the number of experts *held* (experts ``experts_first ..
experts_first + n - 1`` of ``published.n_routed_experts`` router outputs,
``experts_first`` 0 where the file has none), the router keeps the published
width, the group stage and ``num_experts_per_tok``, only the held experts'
products are added, the weights stay normalised over all chosen, and the
shared expert is whole, so what the absent experts would add is left out
here exactly as in the program; ``vocab_size`` is the slice of rows held;
**the stage**: ``stage_first_layer`` (a key of the deployment, 0 where the
file has none) says which published layer the first layer held is, so layer
``l`` here is dense iff ``stage_first_layer + l < first_k_dense_replace``.

Matrix multiplications run at ``precision`` ("float32" at HIGHEST: the
reference proper; "bfloat16": inputs rounded, float32 accumulation, what the
configuration states; "fp8": float8_e4m3fn after a per-tensor scale, the
control that has to fail). The router's scores and every norm are float32
at every precision, as published. A long sequence goes through attention
eight heads and ``QUERY_BLOCK`` query rows at a time and through the MLPs
``ROW_BLOCK`` rows at a time, which changes what is held at once, not the
result.

The layout, which ``weights.py`` fills from the seed. Stacked over all
layers (``LAYER_LEAVES``): ``q_a`` [E, r_q], ``norm_q`` [r_q], ``q_b`` [r_q,
H (n + p)], ``kv_a`` [E, r + p], ``norm_kv`` [r], ``kv_b`` [r, H (n + v)],
``o`` [H v, E], ``norm_attn``, ``norm_mlp`` [E]. Stacked by kind, in
published order within the kind, with ``INIT`` rules that keep N(0,
1/fan_in): ``gate_dense`` / ``up_dense`` [dense layers, E, F], ``down_dense``
[.., F, E]; ``router`` [expert layers, E, R], ``router_bias`` [.., R] N(0,
0.01) (drawn wide enough that leaving it out shows), ``gate_exp`` /
``up_exp`` [.., held, E, M], ``down_exp`` [.., held, M, E], ``gate_shared``
/ ``up_shared`` [.., E, S M], ``down_shared`` [.., S M, E]; ``embed`` [V, E],
``norm_final`` [E], ``head`` [E, V].
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("q_a", "norm_q", "q_b", "kv_a", "norm_kv", "kv_b", "o", "norm_attn", "norm_mlp")
HEAD_LEAVES = ("norm_final", "head")
DENSE_LEAVES = ("gate_dense", "up_dense", "down_dense")
EXPERT_LEAVES = ("router", "router_bias", "gate_exp", "up_exp", "down_exp",
                 "gate_shared", "up_shared", "down_shared")


def layer_is_dense(c: dict) -> list:
    """For each layer held, in order: whether it is one of the leading dense
    layers of the published model."""
    first = c.get("stage_first_layer", 0)
    return [first + l < c["first_k_dense_replace"] or (first + l) % c["moe_layer_freq"] != 0
            for l in range(c["num_hidden_layers"])]


def kind_index(c: dict, l: int) -> int:
    """Layer ``l``'s index within its MLP kind's stack."""
    dense = layer_is_dense(c)
    return sum(1 for d in dense[:l] if d == dense[l])


def router_outputs(c: dict) -> int:
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def shapes(c: dict) -> dict:
    e, f, m, v = c["hidden_size"], c["intermediate_size"], c["moe_intermediate_size"], c["vocab_size"]
    h, rq, r = c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    n, p, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    n_dense = sum(layer_is_dense(c))
    n_moe = c["num_hidden_layers"] - n_dense
    held, ro, sm = c["n_routed_experts"], router_outputs(c), m * c["n_shared_experts"]
    out = {"embed": (v, e), "norm_final": (e,), "head": (e, v),
           "q_a": (e, rq), "norm_q": (rq,), "q_b": (rq, h * (n + p)), "kv_a": (e, r + p), "norm_kv": (r,),
           "kv_b": (r, h * (n + dv)), "o": (h * dv, e), "norm_attn": (e,), "norm_mlp": (e,),
           "gate_dense": (n_dense, e, f), "up_dense": (n_dense, e, f), "down_dense": (n_dense, f, e),
           "router": (n_moe, e, ro), "router_bias": (n_moe, ro),
           "gate_exp": (n_moe, held, e, m), "up_exp": (n_moe, held, e, m), "down_exp": (n_moe, held, m, e),
           "gate_shared": (n_moe, e, sm), "up_shared": (n_moe, e, sm), "down_shared": (n_moe, sm, e)}
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


INIT = {"gate_dense": _stacked(1), "up_dense": _stacked(1), "down_dense": _stacked(1),
        "router": _stacked(1), "router_bias": _stacked(1, std=0.01),
        "gate_exp": _stacked(2), "up_exp": _stacked(2), "down_exp": _stacked(2),
        "gate_shared": _stacked(1), "up_shared": _stacked(1), "down_shared": _stacked(1)}


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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(c: dict) -> np.ndarray:
    """The rotated dimensions' frequencies [qk_rope_head_dim / 2], float32."""
    d, theta, rs = c["qk_rope_head_dim"], float(c["rope_theta"]), c.get("rope_scaling")
    j = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / d)
    if not rs:
        return f.astype(np.float32)
    cd = lambda b: d * math.log(rs["original_max_position_embeddings"] / (2 * math.pi * b)) / (2 * math.log(theta))
    low, high = max(math.floor(cd(rs["beta_fast"])), 0), min(math.ceil(cd(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    return ((f / rs["factor"]) * ramp + f * (1.0 - ramp)).astype(np.float32)


def softmax_scale(c: dict) -> float:
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(c: dict, x, positions):
    """x [T, heads, d]: ``rotate_half`` over all d rotated dimensions."""
    rs = c.get("rope_scaling")
    table = 1.0 if not rs else yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(c))[None, :]
    cos, sin = (jnp.cos(ang) * table)[:, None, :], (jnp.sin(ang) * table)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


QUERY_BLOCK = 1024  # attention runs over this many query rows at a time once a sequence is longer
HEAD_BLOCK = 8      # ... and over this many heads at a time
ROW_BLOCK = 2048    # the MLPs run over this many rows at a time once a sequence is longer


def _attend(q, k, v, q_pos, k_pos, scale, precision: str):
    """q [Tq, H, D], k [S, H, D], v [S, H, Dv]: causal, every head its own
    keys and values."""
    s = jnp.einsum("thd,shd->hts", _round(q, precision), _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("hts,shd->thd", _round(p, precision), _round(v, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _by_rows(fn, x, block: int):
    """``fn`` over ``x`` [T, ...] in blocks of rows where T is longer than
    one and a multiple of it."""
    t = x.shape[0]
    if t <= block or t % block:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(t // block, block, *x.shape[1:]))
    return out.reshape(t, *out.shape[2:])


def attention(c: dict, precision: str, x, w, query_block: int = QUERY_BLOCK):
    """Latent attention over one sequence's normed input x [T, E], expanded:
    the heads' outputs [T, H v] before ``W_o``."""
    t = x.shape[0]
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    n, p, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, pos, scale = c["rms_norm_eps"], jnp.arange(t), softmax_scale(c)
    cq = rms_norm(_mm(x, w["q_a"], precision), w["norm_q"], eps)
    ckv = _mm(x, w["kv_a"], precision)
    lat = rms_norm(ckv[:, :r], w["norm_kv"], eps)
    k_pe = _rope(c, ckv[:, None, r:], pos)                                   # [T, 1, p], one for all heads
    hb = next(b for b in (HEAD_BLOCK, 4, 2, 1) if h % b == 0)
    block = next((b for b in (query_block, query_block // 2, query_block // 4) if b and t % b == 0), t)

    def heads(wqb, wkvb):
        """``hb`` heads: wqb [r_q, hb (n + p)], wkvb [r, hb (n + v)]."""
        q = _mm(cq, wqb, precision).reshape(t, hb, n + p)
        q = jnp.concatenate([q[..., :n], _rope(c, q[..., n:], pos)], axis=-1)
        kv = _mm(lat, wkvb, precision).reshape(t, hb, n + dv)
        k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe, (t, hb, p))], axis=-1)
        v = kv[..., n:]
        if t <= query_block or block == t:
            return _attend(q, k, v, pos, pos, scale, precision)
        one = jax.checkpoint(lambda qp: _attend(qp[0], k, v, qp[1], pos, scale, precision))
        out = jax.lax.map(one, (q.reshape(t // block, block, hb, n + p), pos.reshape(t // block, block)))
        return out.reshape(t, hb, dv)

    wq = w["q_b"].reshape(-1, h // hb, hb * (n + p)).transpose(1, 0, 2)      # [chunks, r_q, hb (n + p)]
    wkv = w["kv_b"].reshape(r, h // hb, hb * (n + dv)).transpose(1, 0, 2)
    out = jax.lax.map(lambda ws: heads(*ws), (wq, wkv))                      # [chunks, T, hb, v]
    return out.transpose(1, 0, 2, 3).reshape(t, h * dv)


def route(c: dict, y, w):
    """(chosen [T, k] int32, weight [T, k] float32) for y [T, E]: sigmoid
    scores in float32, the group stage, the top k, the scaled weights."""
    z = jax.nn.sigmoid(_mm(y, w["router"], "float32"))                       # [T, R]
    ch = z + w["router_bias"].astype(jnp.float32)
    t, r = ch.shape
    g = c["n_group"]
    if g > 1:
        grouped = ch.reshape(t, g, r // g)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)        # a group: its two best
        _, best = jax.lax.top_k(group_score, c["topk_group"])
        keep = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], best].set(True)
        ch = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, r)
    _, chosen = jax.lax.top_k(ch, c["num_experts_per_tok"])
    zc = jnp.take_along_axis(z, chosen, axis=-1)
    weight = zc / (jnp.sum(zc, axis=-1, keepdims=True) + 1e-20) if c["norm_topk_prob"] else zc
    return chosen, weight * (c.get("routed_scaling_factor") or 1.0)


def _mlp(y, wg, wu, wd, precision: str):
    return _mm(jax.nn.silu(_mm(y, wg, precision)) * _mm(y, wu, precision), wd, precision)


def experts(c: dict, precision: str, y, w, shared: bool = True):
    """The held experts' part of an expert layer's result for y [T, E], and
    (``shared``) the shared expert's, which is whole."""
    chosen, weight = route(c, y, w)
    first = c.get("experts_first", 0)

    def one(acc, ew):
        e, wg, wu, wd = ew
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)        # [T]; 0 where e was not chosen
        return acc + mine[:, None] * _mlp(y, wg, wu, wd, precision), None

    held = w["gate_exp"].shape[0]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(y), (first + jnp.arange(held), w["gate_exp"], w["up_exp"], w["down_exp"]))
    if shared and c["n_shared_experts"]:
        acc = acc + _mlp(y, w["gate_shared"], w["up_shared"], w["down_shared"], precision)
    return acc


def layer(c: dict, precision: str, h, w, l: int, query_block: int = QUERY_BLOCK):
    """Decoder layer ``l`` over one sequence h [T, E] in float32; ``w`` holds
    that layer's leaves (``layer_weights``)."""
    eps = c["rms_norm_eps"]
    a = attention(c, precision, rms_norm(h, w["norm_attn"], eps), w, query_block)
    h = h + _mm(a, w["o"], precision)
    y = rms_norm(h, w["norm_mlp"], eps)
    if layer_is_dense(c)[l]:
        return h + _by_rows(lambda rows: _mlp(rows, w["gate"], w["up"], w["down"], precision), y, ROW_BLOCK)
    return h + _by_rows(lambda rows: experts(c, precision, rows, w), y, ROW_BLOCK)


def layer_weights(c: dict, weights: dict, l, dense=None, index=None) -> dict:
    """Layer ``l``'s leaves cut from the stacks. ``l`` may be traced where
    ``dense`` and ``index`` (:func:`kind_index`) are given."""
    dense = layer_is_dense(c)[l] if dense is None else dense
    i = kind_index(c, l) if index is None else index
    cut = lambda x, j: jax.lax.dynamic_index_in_dim(x, j, 0, keepdims=False)
    w = {name: cut(weights[name], l) for name in LAYER_LEAVES}
    if dense:
        for name in ("gate", "up", "down"):
            w[name] = cut(weights[f"{name}_dense"], i)
    else:
        for name in EXPERT_LEAVES:
            if name in weights:
                w[name] = cut(weights[name], i)
    return w


def head_logits(c: dict, precision: str, w: dict, h):
    """Final norm and output head over hidden states h [..., E]; ``w`` holds
    the leaves outside the layers."""
    return _mm(rms_norm(h, w["norm_final"], c["rms_norm_eps"]), w["head"], precision)


@functools.lru_cache(maxsize=None)
def _compiled(frozen_c: str, precision: str):
    c = json.loads(frozen_c)
    embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0).astype(jnp.float32))
    # one program a layer kind; the layer's weights are cut from the stacks
    # inside it, by traced indices, so that a layer index is no program
    first = {}
    for l, dense in enumerate(layer_is_dense(c)):
        first.setdefault(dense, l)
    one = {dense: jax.jit(functools.partial(
        lambda h, stacks, l, i, dense, l0: layer(c, precision, h, layer_weights(c, stacks, l, dense, i), l0),
        dense=dense, l0=l0)) for dense, l0 in first.items()}
    head = jax.jit(lambda h, top, rows: head_logits(c, precision, top, jnp.take(h, rows, axis=0)))
    return embed, one, head


def logits_at(c: dict, weights: dict, ids, rows, precision: str = "float32", pad_to: int = 256):
    """Logits [len(rows), V] of one sequence ``ids`` at positions ``rows``,
    layer by layer so that only one layer's float32 copy is live. The
    sequence is padded at its end to a multiple of ``pad_to`` (causal
    attention never lets a position see what follows it), and ``rows`` to a
    multiple of 64, so that few shapes compile."""
    with jax.default_matmul_precision("highest"):
        embed, one, head = _compiled(json.dumps(c, sort_keys=True), precision)
        n = len(ids)
        t = -(-n // pad_to) * pad_to
        padded = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
        h = embed(weights["embed"], padded)
        stacks = {name: x for name, x in weights.items() if name not in HEAD_LEAVES and name != "embed"}
        for l, dense in enumerate(layer_is_dense(c)):
            h = one[dense](h, stacks, l, kind_index(c, l))
        r = -(-len(rows) // 64) * 64
        rows_p = jnp.zeros((r,), jnp.int32).at[: len(rows)].set(jnp.asarray(rows, jnp.int32))
        return head(h, {name: weights[name] for name in HEAD_LEAVES}, rows_p)[: len(rows)]
