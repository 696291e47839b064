"""The plain reference: a ``qwen3_next`` decoder's forward pass in float32
``jax.numpy`` (Qwen3-Next-80B-A3B-Instruct's ``config.json`` is of this
``model_type``).

Implements the equations of ISSUE 48 (``PERF.md`` section 4 repeats them)
from the public ``config.json`` and the public ``qwen3_next`` modelling code.
Every layer is ``h <- h + mixer(RMSNorm(h)); h <- h + experts(RMSNorm(h))``
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []: every layer has experts);
layer ``l`` of the published stack is full attention (``F``) where ``(l + 1) %
full_attention_interval == 0``, else Gated DeltaNet (``L``). Every RMS norm
but the one inside the DeltaNet scales by ``1 + weight`` (``rms_norm_eps``).
A final norm, an untied head. No bias anywhere.

- ``L``, *Gated DeltaNet*. ``Hk = linear_num_key_heads``, ``Hv =
  linear_num_value_heads``, ``dk = linear_key_head_dim``, ``dv =
  linear_value_head_dim``, ``K = linear_conv_kernel_dim``: ``u W_qkvz`` (E -> 2
  Hk dk + 2 Hv dv) is laid out **by key head**, as the checkpoint has it: key
  head ``j``'s columns are ``[q_j (dk) | k_j (dk) | v (Hv/Hk x dv) | z (Hv/Hk x
  dv)]``, the values and gates of the value heads ``j Hv/Hk ..`` it serves; ``u
  W_ba`` (E -> 2 Hv) likewise ``[b (Hv/Hk) | a (Hv/Hk)]`` a key head. ``[q | k
  | v] <- silu(conv([q | k | v]))`` over the flat concatenation (all q, all k,
  all v), causal, depthwise, K taps; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``, one scalar a value head; ``q <- l2norm(q) dk^-1/2``,
  ``k <- l2norm(k)`` within each head (``x rsqrt(sum x^2 + 1e-6)``); value head
  ``h`` reads key head ``h // (Hv / Hk)``; a value head's state ``S`` in R^(dk
  x dv), from zero: ``S <- exp(g_t) S; r = S^T k_t; S <- S + k_t (x) (beta_t
  (v_t - r)); o_t = S^T q_t``; ``o <- RMSNorm(o) w silu(z)`` within each head
  of dv (the norm first, then the gate; a plain weight, not ``1 + w``); ``out
  = o W_out``. **The recurrence runs one token at a time** (a ``lax.scan`` over
  the rows, no chunked form), float32 at every ``precision``.
- ``F``, *gated attention*: ``u W_q`` (E -> H x 2 dh) holds a head's ``dh``
  query columns and its ``dh`` gate columns together; ``k``, ``v`` E -> KV dh;
  ``q``, ``k`` each through an RMSNorm over the head's ``dh`` (``1 + w``); the
  first ``partial_rotary_factor x dh`` dimensions rotated at ``rope_theta``
  (the halves of those dimensions paired, as the source's ``rotate_half``);
  causal softmax at ``dh^-1/2``; ``out = (attn * sigmoid(gate)) W_o``.
- *the expert layer*: ``p = softmax(u W_r)`` in float32 over the published
  ``num_experts`` outputs; the ``num_experts_per_tok`` largest are chosen and
  ``w_e = p_e / sum of the chosen p`` (``norm_topk_prob``); ``routed = sum_e
  w_e W_d,e (silu(W_g,e u) * W_u,e u)``; ``out = routed + sigmoid(u w_sg)
  shared(u)``, one shared gated expert of ``shared_expert_intermediate_size``
  behind a scalar gate of its own. The experts are a loop over the held ones,
  each over every row.

Departures from the published model (the configuration lists them under
``assumed``): weights are x @ W (the checkpoints store W transposed) and the
convolution's taps are [K, channels], tap K - 1 on the current token; initial
values are seeded (``weights.py`` and ``INIT`` below: every matrix N(0,
1/fan_in), the ``1 + w`` norms' weights N(0, 0.1^2), the DeltaNet's own norm
weight 1 + 0.1 N, ``A`` uniform in [1, 16] a head and ``dt_bias`` the inverse
softplus of a step drawn log-uniform in [1e-3, 1e-1], as the mixer's authors
initialise them, so that a head forgets over 0.6 to 1,000 tokens); **the
share** (model-configs guide, section 4): ``num_experts`` in the configuration
is the number of experts *held* (experts ``experts_first .. experts_first + n
- 1`` of ``published.num_experts`` router outputs), the router keeps the
published width and ``num_experts_per_tok``, only the held experts' products
are added, the weights stay normalised over all chosen, and the shared expert
and its gate are whole, so what the absent experts would add is left out here
exactly as in the program; ``vocab_size`` is the slice of rows held; **the
stage**: ``stage_first_layer`` says which published layer the first layer held
is; the multi-token prediction module is not on the served path and is left
out; the residual stream is float32.

Matrix multiplications run at ``precision`` ("float32" at HIGHEST: the
reference proper; "bfloat16": inputs rounded, float32 accumulation, what the
configuration states; "fp8": float8_e4m3fn after a per-tensor scale, the
control that has to fail). The router's scores, every norm, the convolution,
``g``, ``beta`` and the recurrence are float32 at each of these. A fourth,
"bfloat16_state", is float32 but for the delta rule's state, rounded to
bfloat16 after every token: what a program that kept its state in bfloat16
would serve, read once on the chip to see whether a limit on logits can hold
the state to float32 (``PERF.md`` section 6, PR 48; ``run.py --control`` does
not list it). A long
sequence goes through attention ``QUERY_BLOCK`` query rows at a time and
through the experts ``ROW_BLOCK`` rows at a time, which changes what is held
at once, not the result. Nothing of the program or of ``arch/`` is imported.

The layout, which ``weights.py`` fills from the seed. Stacked over all layers
held (``LAYER_LEAVES``): ``norm_attn``, ``norm_mlp`` [E], ``router`` [E, R],
``gate_exp``, ``up_exp`` [held, E, M], ``down_exp`` [held, M, E],
``gate_shared``, ``up_shared`` [E, S], ``down_shared`` [S, E], ``shared_gate``
[E, 1]. Stacked by kind, in published order within the kind (``INIT``):
``in_proj_qkvz`` [L layers, E, 2 Hk dk + 2 Hv dv], ``in_proj_ba`` [.., E, 2
Hv], ``conv_w`` [.., K, 2 Hk dk + Hv dv], ``dt_bias``, ``a_log`` [.., Hv],
``norm_gate`` [.., dv], ``out_proj`` [.., Hv dv, E]; ``q`` [F layers, E, H x 2
dh], ``k``, ``v`` [.., E, KV dh], ``o`` [.., H dh, E], ``q_norm``, ``k_norm``
[.., dh]; ``embed`` [V, E], ``norm_final`` [E], ``head`` [E, V].
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("norm_attn", "norm_mlp", "router", "gate_exp", "up_exp", "down_exp",
                "gate_shared", "up_shared", "down_shared", "shared_gate")
HEAD_LEAVES = ("norm_final", "head")
KIND_LEAVES = {
    "L": ("in_proj_qkvz", "in_proj_ba", "conv_w", "dt_bias", "a_log", "norm_gate", "out_proj"),
    "F": ("q", "k", "v", "o", "q_norm", "k_norm"),
}


def layer_pattern(c: dict) -> str:
    """The kinds of the layers held, in order: ``L`` or ``F`` each."""
    first, every = c.get("stage_first_layer", 0), c["full_attention_interval"]
    return "".join("F" if (first + l + 1) % every == 0 else "L" for l in range(c["num_hidden_layers"]))


def kind_index(c: dict, l: int) -> int:
    """Layer ``l``'s index within its kind's stacks."""
    pattern = layer_pattern(c)
    return pattern[:l].count(pattern[l])


def router_outputs(c: dict) -> int:
    return c.get("published", {}).get("num_experts", c["num_experts"])


def key_dim(c: dict) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_dim(c: dict) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def conv_dim(c: dict) -> int:
    return 2 * key_dim(c) + value_dim(c)


def shapes(c: dict) -> dict:
    e, v, pattern = c["hidden_size"], c["vocab_size"], layer_pattern(c)
    kd, vd, cd, hv = key_dim(c), value_dim(c), conv_dim(c), c["linear_num_value_heads"]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    held, ro, m, s = c["num_experts"], router_outputs(c), c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    nl, nf = pattern.count("L"), pattern.count("F")
    out = {"embed": (v, e), "norm_final": (e,), "head": (e, v), "norm_attn": (e,), "norm_mlp": (e,),
           "router": (e, ro), "gate_exp": (held, e, m), "up_exp": (held, e, m), "down_exp": (held, m, e),
           "gate_shared": (e, s), "up_shared": (e, s), "down_shared": (s, e), "shared_gate": (e, 1),
           "in_proj_qkvz": (nl, e, 2 * kd + 2 * vd), "in_proj_ba": (nl, e, 2 * hv),
           "conv_w": (nl, c["linear_conv_kernel_dim"], cd), "dt_bias": (nl, hv), "a_log": (nl, hv),
           "norm_gate": (nl, c["linear_value_head_dim"]), "out_proj": (nl, vd, e),
           "q": (nf, e, h * 2 * dh), "k": (nf, e, kv * dh), "v": (nf, e, kv * dh), "o": (nf, h * dh, e),
           "q_norm": (nf, dh), "k_norm": (nf, dh)}
    return {name: shape for name, shape in out.items() if shape[0] > 0}


def _stacked(lead: int, std=None, mean: float = 0.0):
    """``mean`` + N(0, std^2) (std None: 1/fan_in, the fan-in the first
    dimension after the ``lead`` stacking axes), made slice by slice."""
    def rule(key, shape):
        n = 1
        for dim in shape[:lead]:
            n *= dim
        scale = std if std is not None else shape[lead] ** -0.5
        one = lambda k: mean + jax.random.normal(k, shape[lead:], jnp.float32) * scale
        return jax.lax.map(one, jax.random.split(key, n)).reshape(shape)
    return rule


def _a_log(key, shape, lo: float = 1.0, hi: float = 16.0):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))


def _dt_bias(key, shape, lo: float = 1e-3, hi: float = 1e-1):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(bias) = dt


INIT = {**{name: _stacked(1) for name in ("in_proj_qkvz", "in_proj_ba", "conv_w", "out_proj", "q", "k", "v", "o")},
        **{name: _stacked(1) for name in ("gate_exp", "up_exp", "down_exp")},  # (a layer's: the experts lead)
        # the norms that scale by 1 + w keep w around 0; a layer's leaves are made a layer at a time (lead 0)
        **{name: _stacked(0, 0.1) for name in ("norm_attn", "norm_mlp", "norm_final")},
        **{name: _stacked(1, 0.1) for name in ("q_norm", "k_norm")},
        "norm_gate": _stacked(1, 0.1, 1.0), "a_log": _a_log, "dt_bias": _dt_bias}


def _round(x, precision: str):
    """Round a matrix multiplication's input to ``precision``."""
    if precision in ("float32", "bfloat16_state"):
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


def rms_norm(x, w, eps, unit_offset: bool = True):
    """``x rsqrt(mean x^2 + eps) (1 + w)``; the DeltaNet's own norm scales by ``w``."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    w = w.astype(jnp.float32)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w if unit_offset else w)


QUERY_BLOCK = 1024  # attention runs over this many query rows at a time once a sequence is longer
ROW_BLOCK = 2048    # the experts run over this many rows at a time once a sequence is longer


def _by_rows(fn, x, block: int):
    """``fn`` over ``x`` [T, ...] in blocks of rows where T is longer than
    one and a multiple of it."""
    t = x.shape[0]
    if t <= block or t % block:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(t // block, block, *x.shape[1:]))
    return out.reshape(t, *out.shape[2:])


def rotate(c: dict, x, pos):
    """The first ``partial_rotary_factor x dh`` dimensions of x [T, heads, dh]
    rotated by position, their halves paired; the rest pass through."""
    rot = int(c["head_dim"] * c["partial_rotary_factor"])
    inv = c["rope_theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]                        # [T, rot / 2]
    sin, cos = jnp.sin(angle)[:, None, :], jnp.cos(angle)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _attend(q, k, v, q_pos, k_pos, precision: str):
    """q [Tq, KV, G, dh], k, v [S, KV, dh]: causal softmax at dh^-1/2."""
    s = jnp.einsum("tkgd,skd->kgts", _round(q, precision), _round(k, precision),
                   precision=jax.lax.Precision.HIGHEST) * (q.shape[-1] ** -0.5)
    s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("kgts,skd->tkgd", _round(p, precision), _round(v, precision),
                      precision=jax.lax.Precision.HIGHEST)


def attention(c: dict, precision: str, x, w, query_block: int = QUERY_BLOCK):
    """Gated attention over one sequence's normed input x [T, E]."""
    t = x.shape[0]
    h, kv, dh, eps = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["rms_norm_eps"]
    pos = jnp.arange(t)
    qg = _mm(x, w["q"], precision).reshape(t, h, 2 * dh)       # a head's query and gate columns together
    q, gate = qg[..., :dh], qg[..., dh:].reshape(t, h * dh)
    k = _mm(x, w["k"], precision).reshape(t, kv, dh)
    v = _mm(x, w["v"], precision).reshape(t, kv, dh)
    q = rotate(c, rms_norm(q, w["q_norm"], eps), pos).reshape(t, kv, h // kv, dh)
    k = rotate(c, rms_norm(k, w["k_norm"], eps), pos)
    block = next((b for b in (query_block, query_block // 2, query_block // 4) if b and t % b == 0), t)
    if t <= query_block or block == t:
        a = _attend(q, k, v, pos, pos, precision)
    else:
        one = jax.checkpoint(lambda qp: _attend(qp[0], k, v, qp[1], pos, precision))
        a = jax.lax.map(one, (q.reshape(t // block, block, *q.shape[1:]), pos.reshape(t // block, block)))
    return _mm(a.reshape(t, h * dh) * jax.nn.sigmoid(gate), w["o"], precision)


def delta_rule(q, k, v, g, beta, state_bfloat16: bool = False):
    """``q``, ``k`` [T, Hv, dk] (a key head's rows already given to each value
    head it serves), ``v`` [T, Hv, dv], ``g``, ``beta`` [T, Hv]: the state
    advanced one token at a time from zero, float32; ``o`` [T, Hv, dv].
    ``state_bfloat16``: the state is rounded to bfloat16 after every token
    (the "bfloat16_state" control), by ``lax.reduce_precision``: the chip's
    compiler takes a pair of casts there and back out of the loop, and the
    control then reads float32 to the bit (my chip run, PR 48, call 6)."""
    hi = jax.lax.Precision.HIGHEST

    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[:, None, None] * s
        r = jnp.einsum("hkv,hk->hv", s, k_t, precision=hi)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - r))[:, None, :]
        if state_bfloat16:
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=hi)

    _, o = jax.lax.scan(token, jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32), (q, k, v, g, beta))
    return o


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def gated_delta_net(c: dict, precision: str, u, w):
    """The Gated DeltaNet mixer over one sequence's normed input u [T, E],
    from a zero state."""
    t = u.shape[0]
    hk, hv, dk, dv = (c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"],
                      c["linear_value_head_dim"])
    per, kc = hv // hk, c["linear_conv_kernel_dim"]
    # the published columns, a key head at a time: [q | k | v of its value heads | z of its value heads]
    qkvz = _mm(u, w["in_proj_qkvz"], precision).reshape(t, hk, 2 * dk + 2 * per * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + per * dv].reshape(t, hv, dv)
    z = qkvz[..., 2 * dk + per * dv:].reshape(t, hv, dv)
    ba = _mm(u, w["in_proj_ba"], precision).reshape(t, hk, 2 * per)
    b, a = ba[..., :per].reshape(t, hv), ba[..., per:].reshape(t, hv)
    # the convolution runs over the flat concatenation: all q, all k, all v
    flat = jnp.concatenate([q.reshape(t, hk * dk), k.reshape(t, hk * dk), v.reshape(t, hv * dv)], axis=-1)
    padded = jnp.concatenate([jnp.zeros((kc - 1, flat.shape[1]), jnp.float32), flat], axis=0)
    flat = jax.nn.silu(sum(padded[j:j + t] * w["conv_w"][j].astype(jnp.float32) for j in range(kc)))
    q = _l2norm(flat[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = _l2norm(flat[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    v = flat[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["a_log"].astype(jnp.float32)) * jax.nn.softplus(a + w["dt_bias"].astype(jnp.float32))
    o = delta_rule(jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1), v, g, beta,
                   state_bfloat16=precision == "bfloat16_state")
    o = rms_norm(o, w["norm_gate"], c["rms_norm_eps"], unit_offset=False) * jax.nn.silu(z)
    return _mm(o.reshape(t, hv * dv), w["out_proj"], precision)


def route(c: dict, u, w):
    """(chosen [T, k] int32, weight [T, k] float32) for u [T, E]: a softmax in
    float32 over the published outputs, the top k, their weights normalised."""
    p = jax.nn.softmax(_mm(u, w["router"], "float32"), axis=-1)
    weight, chosen = jax.lax.top_k(p, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, weight


def _gated_mlp(x, w_gate, w_up, w_down, precision: str):
    return _mm(jax.nn.silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision), w_down, precision)


def experts(c: dict, precision: str, u, w):
    """The held experts' part of an expert layer's result for u [T, E], and
    the shared expert's behind its gate, which is whole."""
    chosen, weight = route(c, u, w)
    first = c.get("experts_first", 0)

    def one(acc, ew):
        e, w_gate, w_up, w_down = ew
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)        # [T]; 0 where e was not chosen
        return acc + mine[:, None] * _gated_mlp(u, w_gate, w_up, w_down, precision), None

    held = w["up_exp"].shape[0]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (first + jnp.arange(held), w["gate_exp"], w["up_exp"], w["down_exp"]))
    shared = _gated_mlp(u, w["gate_shared"], w["up_shared"], w["down_shared"], precision)
    return routed + jax.nn.sigmoid(_mm(u, w["shared_gate"], precision)) * shared


def layer(c: dict, precision: str, h, w, kind: str, query_block: int = QUERY_BLOCK):
    """One published layer of ``kind`` over one sequence h [T, E] in float32;
    ``w`` holds that layer's leaves (``layer_weights``)."""
    u = rms_norm(h, w["norm_attn"], c["rms_norm_eps"])
    h = h + (gated_delta_net(c, precision, u, w) if kind == "L" else attention(c, precision, u, w, query_block))
    u = rms_norm(h, w["norm_mlp"], c["rms_norm_eps"])
    return h + _by_rows(lambda rows: experts(c, precision, rows, w), u, ROW_BLOCK)


def layer_weights(c: dict, weights: dict, l, kind=None, index=None) -> dict:
    """Layer ``l``'s leaves cut from the stacks. ``l`` may be traced where
    the ``kind`` and the ``index`` within it (:func:`kind_index`) are given."""
    if kind is None:
        kind, index = layer_pattern(c)[l], kind_index(c, l)
    cut = lambda x, i: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
    w = {name: cut(weights[name], l) for name in LAYER_LEAVES}
    w.update({name: cut(weights[name], index) for name in KIND_LEAVES[kind]})
    return w


def head_logits(c: dict, precision: str, w: dict, h):
    """Final norm and output head over hidden states h [..., E]."""
    return _mm(rms_norm(h, w["norm_final"], c["rms_norm_eps"]), w["head"], precision)


@functools.lru_cache(maxsize=None)
def _compiled(frozen_c: str, precision: str):
    c = json.loads(frozen_c)
    embed = jax.jit(lambda table, ids: jnp.take(table, ids, axis=0).astype(jnp.float32))
    # one program a layer kind; the layer's weights are cut from the stacks
    # inside it, by traced indices, so that a layer index is no program
    one = {kind: jax.jit(functools.partial(
        lambda h, stacks, l, i, kind: layer(c, precision, h, layer_weights(c, stacks, l, kind, i), kind), kind=kind))
        for kind in set(layer_pattern(c))}
    head = jax.jit(lambda h, top, rows: head_logits(c, precision, top, jnp.take(h, rows, axis=0)))
    return embed, one, head


def logits_at(c: dict, weights: dict, ids, rows, precision: str = "float32", pad_to: int = 1024):
    """Logits [len(rows), V] of one sequence ``ids`` at positions ``rows``,
    layer by layer so that only one layer's float32 copy is live. The sequence
    is padded at its end to a multiple of ``pad_to`` (no mixer lets a position
    see what follows it), and ``rows`` to a multiple of 64, so that few shapes
    compile: a program with a loop of a thousand steps in it compiles for
    longer than it runs."""
    with jax.default_matmul_precision("highest"):
        embed, one, head = _compiled(json.dumps(c, sort_keys=True), precision)
        n = len(ids)
        t = -(-n // pad_to) * pad_to
        padded = jnp.zeros((t,), jnp.int32).at[:n].set(jnp.asarray(ids, jnp.int32))
        h = embed(weights["embed"], padded)
        stacks = {name: x for name, x in weights.items() if name not in HEAD_LEAVES and name != "embed"}
        for l, kind in enumerate(layer_pattern(c)):
            h = one[kind](h, stacks, l, kind_index(c, l))
        r = -(-len(rows) // 64) * 64
        rows_p = jnp.zeros((r,), jnp.int32).at[: len(rows)].set(jnp.asarray(rows, jnp.int32))
        return head(h, {name: weights[name] for name in HEAD_LEAVES}, rows_p)[: len(rows)]
