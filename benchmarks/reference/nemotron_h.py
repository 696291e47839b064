"""The plain reference: a ``nemotron_h`` decoder's forward pass in float32
``jax.numpy`` (NVIDIA-Nemotron-3-Super-120B-A12B-BF16's ``config.json`` is of
this ``model_type``).

Implements the equations of ISSUE 44 (``PERF.md`` section 4 repeats them)
from the public ``config.json`` and the public ``nemotron_h`` modelling code.
Every published layer is ``h <- h + mixer(u)``, ``u = RMSNorm(h,
layer_norm_epsilon)`` with one learned scale of its own; the kind of layer
``l`` is character ``l`` of ``hybrid_override_pattern``: ``M`` Mamba-2, ``*``
attention, ``E`` LatentMoE. A final norm, an untied head. No biases but the
convolution's (``use_conv_bias``).

- ``M``, *Mamba-2*. ``H = mamba_num_heads``, ``P = mamba_head_dim``, ``D = H
  P``, ``G = n_groups``, ``N = ssm_state_size``, ``K = conv_kernel``:
  ``[z | xBC | dt] = u W_in`` (E -> D + (D + 2 G N) + H); ``xBC <-
  silu(conv(xBC) + b_conv)``, causal, depthwise, K taps over all D + 2 G N
  channels; ``[x | B | C] = xBC``, ``x`` as [H, P], ``B``, ``C`` as [G, N],
  head ``h`` reads group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``
  and ``A = -exp(A_log)``, one scalar a head each; ``S_t[h] = exp(dt_t[h]
  A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]``, ``S[h]`` in R^(P x N);
  ``y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]``; ``y <- y * silu(z)``, then
  RMSNorm within each of the G groups of D / G channels, times a weight [D]
  (the source's ``MambaRMSNormGated`` with ``group_size = D / n_groups``, the
  gate before the norm); ``out = y W_out``. **The recurrence runs one token
  at a time** (a ``lax.scan`` over the rows; ``chunk_size`` is the source
  kernel's blocking and changes no number), float32 at every ``precision``.
- ``*``, *attention*: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``, causal softmax at
  ``head_dim^-1/2``, **no position embedding** (the source's ``nemotron_h``
  attention rotates nothing and reads neither ``rope_theta`` nor
  ``partial_rotary_factor``).
- ``E``, *LatentMoE*: ``s = sigmoid(u W_r)`` in float32 over the published
  ``n_routed_experts`` outputs; the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` are chosen (``n_group`` 1, ``topk_group`` 1: no
  group stage); ``w_e = routed_scaling_factor x s_e / (sum of the chosen s +
  1e-20)`` (``norm_topk_prob``); ``l = u W_lat_in`` (E -> ``moe_latent_size``);
  ``routed = sum_e w_e relu(l W1_e)^2 W2_e`` (``mlp_hidden_act`` ``relu2``:
  two matrices an expert, no gate matrix); ``out = routed W_lat_out + relu(u
  W1_s)^2 W2_s``, the one shared expert of
  ``moe_shared_expert_intermediate_size`` on the full width. The experts are
  a loop over the held ones, each over every row.

Departures from the published model (the configuration lists them under
``assumed``): weights are x @ W (the checkpoints store W transposed) and the
convolution's taps are [K, channels], tap K - 1 on the current token; initial
values are seeded (``weights.py`` and ``INIT`` below: every matrix N(0,
1/fan_in), norm scales and the skip ``D`` 1 + 0.1 N, the convolution's bias
N(0, 0.1^2), the router's bias N(0, 0.01^2), ``A`` uniform in [1, 16] a head
and ``dt_bias`` the inverse softplus of a step drawn log-uniform in
[``time_step_min``, ``time_step_max``], as the family initialises them);
**the share** (model-configs guide, section 4): ``n_routed_experts`` in the
configuration is the number of experts *held* (experts ``experts_first ..
experts_first + n - 1`` of ``published.n_routed_experts`` router outputs), the
router keeps the published width and ``num_experts_per_tok``, only the held
experts' products are added, the weights stay normalised over all chosen, and
the two latent projections and the shared expert are whole, so what the
absent experts would add is left out here exactly as in the program;
``vocab_size`` is the slice of rows held; **the stage**: ``stage_first_layer``
says which published layer the first layer held is, so layer ``l`` here is of
kind ``hybrid_override_pattern[stage_first_layer + l]``; the multi-token
prediction module (``num_nextn_predict_layers``, ``mtp_hybrid_override_pattern``)
is not on the served path and is left out; the residual stream is float32
(``residual_in_fp32`` is false in the source).

Matrix multiplications run at ``precision`` ("float32" at HIGHEST: the
reference proper; "bfloat16": inputs rounded, float32 accumulation, what the
configuration states; "fp8": float8_e4m3fn after a per-tensor scale, the
control that has to fail). The router's scores, every norm, the convolution
and the recurrence are float32 at every precision. A long sequence goes
through attention ``QUERY_BLOCK`` query rows at a time and through the experts
``ROW_BLOCK`` rows at a time, which changes what is held at once, not the
result. Nothing of the program or of ``arch/`` is imported.

The layout, which ``weights.py`` fills from the seed. Stacked over all layers
held (``LAYER_LEAVES``): ``norm`` [E], a layer's one norm. Stacked by kind, in
published order within the kind (``INIT``): ``in_proj`` [M layers, E, 2 D + 2
G N + H], ``conv_w`` [.., K, D + 2 G N], ``conv_b`` [.., D + 2 G N],
``dt_bias``, ``a_log``, ``d`` [.., H], ``norm_gate`` [.., D], ``out_proj``
[.., D, E]; ``q`` [* layers, E, Hq dh], ``k``, ``v`` [.., E, KV dh], ``o`` [..,
Hq dh, E]; ``router`` [E layers, E, R], ``router_bias`` [.., R], ``latent_in``
[.., E, L], ``latent_out`` [.., L, E], ``up_exp`` [.., held, L, M],
``down_exp`` [.., held, M, L], ``up_shared`` [.., E, S], ``down_shared`` [..,
S, E]; ``embed`` [V, E], ``norm_final`` [E], ``head`` [E, V].
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("norm",)
HEAD_LEAVES = ("norm_final", "head")
KIND_LEAVES = {
    "M": ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d", "norm_gate", "out_proj"),
    "*": ("q", "k", "v", "o"),
    "E": ("router", "router_bias", "latent_in", "latent_out", "up_exp", "down_exp", "up_shared", "down_shared"),
}


def layer_pattern(c: dict) -> str:
    """The kinds of the layers held, in order: ``M``, ``*`` or ``E`` each."""
    first = c.get("stage_first_layer", 0)
    return c["hybrid_override_pattern"][first:first + c["num_hidden_layers"]]


def kind_index(c: dict, l: int) -> int:
    """Layer ``l``'s index within its kind's stacks."""
    pattern = layer_pattern(c)
    return pattern[:l].count(pattern[l])


def router_outputs(c: dict) -> int:
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def inner_dim(c: dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_dim(c: dict) -> int:
    return inner_dim(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def shapes(c: dict) -> dict:
    e, v, pattern = c["hidden_size"], c["vocab_size"], layer_pattern(c)
    d, cd, hm, k = inner_dim(c), conv_dim(c), c["mamba_num_heads"], c["conv_kernel"]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    held, ro, lt = c["n_routed_experts"], router_outputs(c), c["moe_latent_size"]
    m, s = c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"]
    nm, na, ne = pattern.count("M"), pattern.count("*"), pattern.count("E")
    out = {"embed": (v, e), "norm_final": (e,), "head": (e, v), "norm": (e,),
           "in_proj": (nm, e, d + cd + hm), "conv_w": (nm, k, cd), "conv_b": (nm, cd), "dt_bias": (nm, hm),
           "a_log": (nm, hm), "d": (nm, hm), "norm_gate": (nm, d), "out_proj": (nm, d, e),
           "q": (na, e, h * dh), "k": (na, e, kv * dh), "v": (na, e, kv * dh), "o": (na, h * dh, e),
           "router": (ne, e, ro), "router_bias": (ne, ro), "latent_in": (ne, e, lt), "latent_out": (ne, lt, e),
           "up_exp": (ne, held, lt, m), "down_exp": (ne, held, m, lt),
           "up_shared": (ne, e, s), "down_shared": (ne, s, e)}
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


INIT = {**{name: _stacked(1) for name in ("in_proj", "conv_w", "out_proj", "q", "k", "v", "o", "router",
                                            "latent_in", "latent_out", "up_shared", "down_shared")},
        **{name: _stacked(2) for name in ("up_exp", "down_exp")},
        **{name: _stacked(1, 0.1, 1.0) for name in ("norm_gate", "d")},
        "conv_b": _stacked(1, 0.1), "router_bias": _stacked(1, 0.01), "a_log": _a_log, "dt_bias": _dt_bias}


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
ROW_BLOCK = 2048    # the experts run over this many rows at a time once a sequence is longer


def _by_rows(fn, x, block: int):
    """``fn`` over ``x`` [T, ...] in blocks of rows where T is longer than
    one and a multiple of it."""
    t = x.shape[0]
    if t <= block or t % block:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(t // block, block, *x.shape[1:]))
    return out.reshape(t, *out.shape[2:])


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
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
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


def recurrence(x, dt, b, cc, a, d_skip):
    """``x`` [T, H, P], ``dt`` [T, H], ``b``, ``cc`` [T, G, N], ``a``,
    ``d_skip`` [H]: the state advanced one token at a time from zero, float32;
    ``y`` [T, H, P]."""
    t, h, p = x.shape
    per = h // b.shape[1]  # heads a group

    def token(s, row):
        x_t, dt_t, b_t, c_t = row
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)          # [H, N]
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_h, precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, b.shape[-1]), jnp.float32), (x, dt, b, cc))
    return y + d_skip[None, :, None] * x


def mamba2(c: dict, precision: str, u, w):
    """The Mamba-2 mixer over one sequence's normed input u [T, E], from a
    zero state."""
    t = u.shape[0]
    d, cd, h, p = inner_dim(c), conv_dim(c), c["mamba_num_heads"], c["mamba_head_dim"]
    g, n, k = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    zxd = _mm(u, w["in_proj"], precision)
    z, xbc, dt = zxd[:, :d], zxd[:, d:d + cd], zxd[:, d + cd:]
    padded = jnp.concatenate([jnp.zeros((k - 1, cd), jnp.float32), xbc], axis=0)
    conv = sum(padded[j:j + t] * w["conv_w"][j].astype(jnp.float32) for j in range(k))  # tap k-1: the token itself
    if c["use_conv_bias"]:
        conv = conv + w["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d].reshape(t, h, p)
    b = xbc[:, d:d + g * n].reshape(t, g, n)
    cc = xbc[:, d + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(w["a_log"].astype(jnp.float32))
    y = recurrence(x, dt, b, cc, a, w["d"].astype(jnp.float32)).reshape(t, d)
    y = (y * jax.nn.silu(z)).reshape(t, g, d // g)  # the gate, then the norm within each group
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + c["layer_norm_epsilon"])
    return _mm(y.reshape(t, d) * w["norm_gate"].astype(jnp.float32), w["out_proj"], precision)


def route(c: dict, u, w):
    """(chosen [T, k] int32, weight [T, k] float32) for u [T, E]: sigmoid
    scores in float32, the top k of the biased scores, the scaled weights."""
    s = jax.nn.sigmoid(_mm(u, w["router"], "float32"))
    _, chosen = jax.lax.top_k(s + w["router_bias"].astype(jnp.float32), c["num_experts_per_tok"])
    sc = jnp.take_along_axis(s, chosen, axis=-1)
    weight = sc / (jnp.sum(sc, axis=-1, keepdims=True) + 1e-20) if c["norm_topk_prob"] else sc
    return chosen, weight * (c.get("routed_scaling_factor") or 1.0)


def _relu2_mlp(x, w_up, w_down, precision: str):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, precision))), w_down, precision)


def latent_moe(c: dict, precision: str, u, w):
    """The held experts' part of a LatentMoE layer's result for u [T, E],
    through the latent, and the shared expert's, which is whole."""
    chosen, weight = route(c, u, w)
    first = c.get("experts_first", 0)
    lat = _mm(u, w["latent_in"], precision)

    def one(acc, ew):
        e, w_up, w_down = ew
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)        # [T]; 0 where e was not chosen
        return acc + mine[:, None] * _relu2_mlp(lat, w_up, w_down, precision), None

    held = w["up_exp"].shape[0]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(lat), (first + jnp.arange(held), w["up_exp"], w["down_exp"]))
    return _mm(routed, w["latent_out"], precision) + _relu2_mlp(u, w["up_shared"], w["down_shared"], precision)


def layer(c: dict, precision: str, h, w, kind: str, query_block: int = QUERY_BLOCK):
    """One published layer of ``kind`` over one sequence h [T, E] in float32;
    ``w`` holds that layer's leaves (``layer_weights``)."""
    u = rms_norm(h, w["norm"], c["layer_norm_epsilon"])
    if kind == "M":
        return h + mamba2(c, precision, u, w)
    if kind == "*":
        return h + attention(c, precision, u, w, query_block)
    return h + _by_rows(lambda rows: latent_moe(c, precision, rows, w), u, ROW_BLOCK)


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
    return _mm(rms_norm(h, w["norm_final"], c["layer_norm_epsilon"]), w["head"], precision)


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
