"""The ``qwen3_next`` architecture as the harness meets it (``manifest.load_arch``
finds this file by the configuration's ``model_type``): the counts of the work
from shapes alone, by layer kind, and the adapter by which a published
configuration becomes the program's ``DecoderConfig`` and published-layout
weights its parameter tree. The published layout and the plain reference are
``reference/qwen3_next.py``, which imports nothing of this file; the manifest
puts it beside as ``.reference``. Only the drivers and the metric readers call
this file, and the program is imported inside its functions only.

Every function takes the configuration whole, as its file has it. Published
layer ``l`` is full attention (``F``) where ``(stage_first_layer + l + 1) %
full_attention_interval == 0``, else Gated DeltaNet (``L``); every layer has
experts (:func:`pattern`). ``num_experts`` is the experts held
(``published.num_experts`` the router's outputs). The counts are of what the
shapes force, the same whatever implements a kernel: the delta rule's are of
the row-by-row rule, not of a chunked form's own products.
"""

from __future__ import annotations

import costs

STATE_ITEMSIZE = 4   # the recurrent state is float32
CONV_ITEMSIZE = 4    # the convolution's kept inputs are float32 too
WEIGHT_ITEMSIZE = 2  # bfloat16 weights


def vocab(c: dict) -> int:
    return c["vocab_size"]


def pattern(c: dict) -> str:
    """The kinds of the published layers held, in order: ``L`` or ``F`` each."""
    first, every = c.get("stage_first_layer", 0), c["full_attention_interval"]
    return "".join("F" if (first + l + 1) % every == 0 else "L" for l in range(c["num_hidden_layers"]))


def runs(c: dict) -> list:
    """[(kind, first published layer, layers)] for each run of consecutive
    layers of one kind: the program's stacks ``layers_<i>``."""
    out = []
    for l, kind in enumerate(pattern(c)):
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, l, 1])
    return [tuple(r) for r in out]


def delta_layers(c: dict) -> int:
    return pattern(c).count("L")


def attention_layers(c: dict) -> int:
    return pattern(c).count("F")


def expert_layers(c: dict) -> int:
    """Every layer has experts (``decoder_sparse_step`` 1, ``mlp_only_layers`` [])."""
    return c["num_hidden_layers"]


def router_outputs(c: dict) -> int:
    return c.get("published", {}).get("num_experts", c["num_experts"])


def key_dim(c: dict) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_dim(c: dict) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def conv_dim(c: dict) -> int:
    """The channels of the convolution, q, k and v: 8,192 as published."""
    return 2 * key_dim(c) + value_dim(c)


def qkvz_width(c: dict) -> int:
    """``[q | k | v | z]``: 2,048 + 2,048 + 4,096 + 4,096 = 12,288 as published."""
    return conv_dim(c) + value_dim(c)


# -- the cache ---------------------------------------------------------------


def kv_token_bytes(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes one token takes in one attention layer, keys and values."""
    return c["num_key_value_heads"] * 2 * c["head_dim"] * kv_itemsize


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    return attention_layers(c) * kv_token_bytes(c, kv_itemsize)


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel has to read for one sequence whose
    next write lands at ``write_pos``: the attention layers walk the whole
    page-rounded context; the DeltaNet layers read no pages."""
    return attention_layers(c) * costs.page_rounded(write_pos, page_size) * kv_token_bytes(c, kv_itemsize)


def state_bytes_per_layer(c: dict) -> int:
    """The float32 state a slot keeps in one DeltaNet layer: value heads x dk x
    dv (2,097,152 B as published)."""
    return c["linear_num_value_heads"] * c["linear_key_head_dim"] * c["linear_value_head_dim"] * STATE_ITEMSIZE


def slot_state_bytes(c: dict) -> int:
    """What a slot keeps over all DeltaNet layers, whatever its context's
    length: the state and the convolution's last K - 1 inputs, float32 both."""
    conv = (c["linear_conv_kernel_dim"] - 1) * conv_dim(c) * CONV_ITEMSIZE
    return delta_layers(c) * (state_bytes_per_layer(c) + conv)


# -- the new kernels' work, from shapes alone ----------------------------------


def gdn_scan_bytes(c: dict, rows: int, slots: int) -> int:
    """Bytes the delta rule has to move in one call of a serving program, over
    all DeltaNet layers, whatever implements it: each advanced slot's float32
    state once in and once out, and each row's q and k (a key head's dk each),
    v and o (a value head's dv each), g and beta (a value head), float32."""
    row = (2 * key_dim(c) + 2 * value_dim(c) + 2 * c["linear_num_value_heads"]) * 4
    return delta_layers(c) * (rows * row + slots * 2 * state_bytes_per_layer(c))


def gdn_scan_flops(c: dict, rows: int) -> int:
    """Operations of the row-by-row rule over ``rows`` rows, over all DeltaNet
    layers: a state element a row takes the decay's product (1), the read (a
    product and a sum, 2), the write (2) and the output (2); not a chunked
    form's own products."""
    state = c["linear_num_value_heads"] * c["linear_key_head_dim"] * c["linear_value_head_dim"]
    return delta_layers(c) * rows * 7 * state


def expert_params(c: dict) -> int:
    """One routed expert: three matrices of hidden x expert width (3,145,728 as published)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def expert_weight_bytes(c: dict, touched: int) -> int:
    """Weight bytes of ``touched`` (layer, expert) pairs: what a step that
    sends each of them a token has to read of the routed experts."""
    return touched * expert_params(c) * WEIGHT_ITEMSIZE


def experts_held(c: dict) -> int:
    """(layer, expert) pairs this program holds."""
    return expert_layers(c) * c["num_experts"]


# -- parameters ----------------------------------------------------------------


def delta_mixer_params(c: dict) -> int:
    """One Gated DeltaNet mixer: 33,718,464 as published."""
    e, hv = c["hidden_size"], c["linear_num_value_heads"]
    return (e * qkvz_width(c) + e * 2 * hv + c["linear_conv_kernel_dim"] * conv_dim(c) + 2 * hv
            + c["linear_value_head_dim"] + value_dim(c) * e)


def attention_mixer_params(c: dict) -> int:
    """One gated attention mixer, the doubled query projection and the two
    head norms counted: 27,263,488 as published."""
    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return e * h * 2 * dh + 2 * e * kv * dh + h * dh * e + 2 * dh


def expert_layer_shared_params(c: dict) -> int:
    """A layer outside its mixer and its routed experts: the router, the
    shared expert, its gate, the layer's two norms (4,200,448 as published)."""
    e = c["hidden_size"]
    return e * router_outputs(c) + 3 * e * c["shared_expert_intermediate_size"] + e + 2 * e


def matmul_params(c: dict, active: bool = False) -> int:
    """Parameters in matrix multiplications: every projection, the router,
    the experts held (``active``: the ``num_experts_per_tok`` a token passes
    through), the shared expert with its gate and the head (the embedding is a
    lookup)."""
    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    n_exp = c["num_experts_per_tok"] if active else c["num_experts"]
    layer = n_exp * expert_params(c) + e * router_outputs(c) + 3 * e * c["shared_expert_intermediate_size"] + e
    delta = e * qkvz_width(c) + e * 2 * c["linear_num_value_heads"] + value_dim(c) * e
    attn = e * h * 2 * dh + 2 * e * kv * dh + h * dh * e
    return (delta_layers(c) * delta + attention_layers(c) * attn + expert_layers(c) * layer + e * c["vocab_size"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter a token
    passes through in a matrix multiplication, causal attention in the
    attention layers, and the delta rule's 7 operations a state element three
    times over. (No cell trains this architecture.)"""
    attn = attention_layers(c) * 3 * 2 * (seq_len / 2) * c["num_attention_heads"] * 2 * c["head_dim"]
    return 6.0 * matmul_params(c, active=True) + attn + 3 * gdn_scan_flops(c, 1)


def total_params(c: dict) -> int:
    """Every parameter held: the layers by kind with the experts held, the
    embedding's and the head's rows held, the final norm (2,929.4 M for the
    12-layer share of 64 experts and 18,992 rows)."""
    e = c["hidden_size"]
    every = c["num_experts"] * expert_params(c) + expert_layer_shared_params(c)
    layers = (delta_layers(c) * (delta_mixer_params(c) + every) + attention_layers(c) * (attention_mixer_params(c) + every))
    return layers + 2 * c["vocab_size"] * e + e


# -- the adapter ---------------------------------------------------------------


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    held, first = c["num_experts"], c.get("experts_first", 0)
    kinds = {
        "L": dict(mixer="gdn", ssm_num_heads=c["linear_num_value_heads"], ssm_head_dim=c["linear_value_head_dim"],
                  ssm_n_groups=c["linear_num_key_heads"], ssm_state_dim=c["linear_key_head_dim"],
                  ssm_conv_width=c["linear_conv_kernel_dim"], ssm_conv_bias=False),
        "F": dict(mixer="attention", attn_qk_norm=True, attn_output_gate=True),
    }
    names = list(dict.fromkeys(pattern(c)))
    # the interpreted kernels of a rehearsal: the serving kernels' switch covers the recurrence's too
    overrides.setdefault("ssm_kernel", "interpret" if overrides.get("prefill_kernel") == "interpret" else None)
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"], embed_dim=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_dim=int(c["head_dim"] * c["partial_rotary_factor"]), rope_theta=float(c["rope_theta"]),
        max_seq_len=max_seq_len, norm_eps=float(c["rms_norm_eps"]), norm_unit_offset=True,
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16, scan_layers=True,
        # the residual stream in float32 (the matrix multiplications in
        # bfloat16): the experts' discrete choice asks for it (DecoderConfig)
        residual_dtype=jnp.float32, fp32_logits=True,
        mlp_dim=c["moe_intermediate_size"], moe_num_experts=held, moe_router_outputs=router_outputs(c),
        moe_experts_held=(first, held), moe_top_k=c["num_experts_per_tok"], moe_scoring="softmax",
        moe_shared_dim=c["shared_expert_intermediate_size"], moe_shared_gate=True,
        layer_kinds=tuple((name, kinds[name]) for name in names),
        layer_pattern=tuple(names.index(kind) for kind in pattern(c)), **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


# the program's leaf <- the published one (the experts' and the norms' leaves are a layer's)
_EXPERTS = (("router", "router"), ("w_gate", "gate_exp"), ("w_up", "up_exp"), ("w_down", "down_exp"),
            ("shared_gate", "gate_shared"), ("shared_up", "up_shared"), ("shared_down", "down_shared"),
            ("shared_out_gate", "shared_gate"))
_DELTA = (("conv_w", "conv_w"), ("b_dt", "dt_bias"), ("a_log", "a_log"), ("norm_w", "norm_gate"), ("w_out", "out_proj"))
_ATTN_NORMS = (("q_norm", "q_norm"), ("k_norm", "k_norm"))
_FLOAT32 = ("b_dt", "a_log")  # the recurrence's own leaves


def _heads(c: dict) -> tuple:
    return (c["linear_num_key_heads"], c["linear_num_value_heads"] // c["linear_num_key_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"])


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (one scanned stack a run of layers of one kind, ``layers_<i>/block``).
    The published ``in_proj_qkvz`` holds, a key head at a time, ``[q | k | v of
    its value heads | z of its value heads]`` and ``in_proj_ba`` ``[b | a]`` of
    its value heads; the program's ``w_in`` is flat, ``[all q | all k | all v |
    all z]`` (the convolution's order, then the gate), and ``w_ba`` ``[all b |
    all a]``. The published ``q`` holds a head's query and gate columns
    together; the program keeps ``wq`` and ``wg`` apart."""
    import jax.numpy as jnp

    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    hk, per, dk, dv = _heads(c)
    p = pattern(c)

    def adapt(w: dict) -> dict:
        tree = {"embedding": w["embed"], "lm_head": w["head"], "ln_final": w["norm_final"]}
        for i, (kind, l0, n) in enumerate(runs(c)):
            k0 = p[:l0].count(kind)
            moe = {leaf: w[src][l0:l0 + n] for leaf, src in _EXPERTS}
            block = {"ln_attn": w["norm_attn"][l0:l0 + n], "ln_mlp": w["norm_mlp"][l0:l0 + n], "moe_mlp": moe}
            if kind == "L":
                qkvz = w["in_proj_qkvz"][k0:k0 + n].reshape(n, e, hk, 2 * dk + 2 * per * dv)
                cuts = (0, dk, 2 * dk, 2 * dk + per * dv, 2 * dk + 2 * per * dv)
                parts = [qkvz[..., a:b].reshape(n, e, -1) for a, b in zip(cuts, cuts[1:])]
                ba = w["in_proj_ba"][k0:k0 + n].reshape(n, e, hk, 2 * per)
                ssm = {leaf: w[src][k0:k0 + n] for leaf, src in _DELTA}
                ssm = {leaf: x.astype("float32") if leaf in _FLOAT32 else x for leaf, x in ssm.items()}
                ssm["w_in"] = jnp.concatenate(parts, axis=-1)
                ssm["w_ba"] = jnp.concatenate([ba[..., :per].reshape(n, e, -1), ba[..., per:].reshape(n, e, -1)], axis=-1)
                block["ssm"] = ssm
            else:
                qg = w["q"][k0:k0 + n].reshape(n, e, h, 2 * dh)
                attn = {"wq": qg[..., :dh], "wg": qg[..., dh:], "wk": w["k"][k0:k0 + n].reshape(n, e, kv, dh),
                        "wv": w["v"][k0:k0 + n].reshape(n, e, kv, dh), "wo": w["o"][k0:k0 + n].reshape(n, h, dh, e)}
                attn.update({leaf: w[src][k0:k0 + n] for leaf, src in _ATTN_NORMS})
                block["attn"] = attn
            tree[f"layers_{i}"] = {"block": block}
        return tree

    return adapt


def from_program_tree(c: dict, p: dict) -> dict:
    """The inverse of ``to_program_tree``: the program's stacks back in
    published layout, leaf by leaf."""
    import jax.numpy as jnp

    e = c["hidden_size"]
    hk, per, dk, dv = _heads(c)
    kd, vd = key_dim(c), value_dim(c)
    parts = {}

    def put(name, x):
        parts.setdefault(name, []).append(x)

    for i, (kind, l0, n) in enumerate(runs(c)):
        b = p[f"layers_{i}"]["block"]
        put("norm_attn", b["ln_attn"]); put("norm_mlp", b["ln_mlp"])
        for leaf, src in _EXPERTS:
            put(src, b["moe_mlp"][leaf])
        if kind == "L":
            s = b["ssm"]
            for leaf, src in _DELTA:
                put(src, s[leaf])
            w_in = s["w_in"]
            cuts = (0, kd, 2 * kd, 2 * kd + vd, 2 * kd + 2 * vd)
            q, k, v, z = (w_in[..., a:b_].reshape(n, e, hk, -1) for a, b_ in zip(cuts, cuts[1:]))
            put("in_proj_qkvz", jnp.concatenate([q, k, v, z], axis=-1).reshape(n, e, -1))
            ba = [s["w_ba"][..., a:b_].reshape(n, e, hk, per) for a, b_ in ((0, hk * per), (hk * per, 2 * hk * per))]
            put("in_proj_ba", jnp.concatenate(ba, axis=-1).reshape(n, e, -1))
        else:
            a = b["attn"]
            put("q", jnp.concatenate([a["wq"], a["wg"]], axis=-1).reshape(n, e, -1))
            put("k", a["wk"].reshape(n, e, -1)); put("v", a["wv"].reshape(n, e, -1)); put("o", a["wo"].reshape(n, -1, e))
            for leaf, src in _ATTN_NORMS:
                put(src, a[leaf])
    out = {name: jnp.concatenate(xs, axis=0) for name, xs in parts.items()}
    out.update(embed=p["embedding"], head=p["lm_head"], norm_final=p["ln_final"])
    return out
