"""The ``nemotron_h`` architecture as the harness meets it (``manifest.load_arch``
finds this file by the configuration's ``model_type``): the counts of the work
from shapes alone, by layer kind, and the adapter by which a published
configuration becomes the program's ``DecoderConfig`` and published-layout
weights its parameter tree. The published layout and the plain reference are
``reference/nemotron_h.py``, which imports nothing of this file; the manifest
puts it beside as ``.reference``. Only the drivers and the metric readers call
this file, and the program is imported inside its functions only.

Every function takes the configuration whole, as its file has it. Published
layer ``l`` is of the kind ``hybrid_override_pattern[stage_first_layer + l]``:
``M`` a Mamba-2 mixer, ``*`` attention without rotation, ``E`` a LatentMoE
feed-forward part, each ``h + part(norm(h))`` with one norm (:func:`pattern`).
**The program runs them as blocks** (:func:`blocks`): a mixer layer followed
by an ``E`` layer is one block of the program's shape, mixer then feed-forward
part, with the two layers' norms; a mixer layer that no ``E`` follows is a
block without a feed-forward part, an ``E`` that follows no mixer a block
without a mixer. The arithmetic is the published stack's, layer for layer;
what pairing buys is that runs of a kind still scan (layers 0-10,
``MEMEMEM*EME``, are ``ME ME ME | M | *E | ME``: four scans, not eleven) and
the experts' stack rides its scan. The counts below are of published layers.
"""

from __future__ import annotations

import costs

STATE_ITEMSIZE = 4   # the recurrent state is float32
CONV_ITEMSIZE = 4    # the convolution's kept inputs are float32 too
WEIGHT_ITEMSIZE = 2  # bfloat16 weights


def vocab(c: dict) -> int:
    return c["vocab_size"]


def pattern(c: dict) -> str:
    """The kinds of the published layers held, in order."""
    first = c.get("stage_first_layer", 0)
    return c["hybrid_override_pattern"][first:first + c["num_hidden_layers"]]


def blocks(c: dict) -> list:
    """[(kind name, first published layer held)]: the program's blocks, in
    order. The name is the layers' kinds: ``ME``, ``*E``, ``M``, ``*`` or
    ``E``."""
    out, l, p = [], 0, pattern(c)
    while l < len(p):
        pair = p[l] != "E" and p[l + 1:l + 2] == "E"
        out.append((p[l:l + 2] if pair else p[l], l))
        l += 2 if pair else 1
    return out


def runs(c: dict) -> list:
    """[(kind name, first published layer, blocks)] for each run of
    consecutive blocks of one kind: the program's stacks ``layers_<i>``."""
    out = []
    for name, l in blocks(c):
        if out and out[-1][0] == name:
            out[-1][2] += 1
        else:
            out.append([name, l, 1])
    return [tuple(r) for r in out]


def mamba_layers(c: dict) -> int:
    return pattern(c).count("M")


def attention_layers(c: dict) -> int:
    return pattern(c).count("*")


def expert_layers(c: dict) -> int:
    return pattern(c).count("E")


def router_outputs(c: dict) -> int:
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def inner_dim(c: dict) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_dim(c: dict) -> int:
    """The channels of the convolution: x, and B and C of every group."""
    return inner_dim(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def in_proj_width(c: dict) -> int:
    """``[z | xBC | dt]``: 8,192 + 10,240 + 128 = 18,560 as published."""
    return inner_dim(c) + conv_dim(c) + c["mamba_num_heads"]


# -- the cache ---------------------------------------------------------------


def kv_token_bytes(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes one token takes in one attention layer, keys and values."""
    return c["num_key_value_heads"] * 2 * c["head_dim"] * kv_itemsize


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    return attention_layers(c) * kv_token_bytes(c, kv_itemsize)


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel has to read for one sequence whose
    next write lands at ``write_pos``: the attention layers walk the whole
    page-rounded context; the other kinds read no pages."""
    return attention_layers(c) * costs.page_rounded(write_pos, page_size) * kv_token_bytes(c, kv_itemsize)


def state_bytes_per_layer(c: dict) -> int:
    """The float32 state a slot keeps in one Mamba-2 layer: heads x head_dim x
    state (4,194,304 B as published)."""
    return inner_dim(c) * c["ssm_state_size"] * STATE_ITEMSIZE


def slot_state_bytes(c: dict) -> int:
    """What a slot keeps over all Mamba-2 layers, whatever its context's
    length: the state and the convolution's last K - 1 inputs, float32 both."""
    return mamba_layers(c) * (state_bytes_per_layer(c) + (c["conv_kernel"] - 1) * conv_dim(c) * CONV_ITEMSIZE)


# -- the new kernels' work, from shapes alone ----------------------------------


def ssd_scan_bytes(c: dict, rows: int, slots: int) -> int:
    """Bytes the recurrence has to move in one call of a serving program, over
    all Mamba-2 layers, whatever implements it: each advanced slot's float32
    state once in and once out, and each row's x and y (D each), its step (a
    head) and its B and C (G x N each), float32."""
    row = (2 * inner_dim(c) + c["mamba_num_heads"] + 2 * c["n_groups"] * c["ssm_state_size"]) * 4
    return mamba_layers(c) * (rows * row + slots * 2 * state_bytes_per_layer(c))


def ssd_scan_flops(c: dict, rows: int) -> int:
    """Operations of the recurrence over ``rows`` rows, over all Mamba-2
    layers: 3 a state element a row to update it (the decay's product, the
    input's product, their sum) and 2 to read it (a product and a sum); not a
    chunked form's own products."""
    return mamba_layers(c) * rows * 5 * inner_dim(c) * c["ssm_state_size"]


def expert_params(c: dict) -> int:
    """One routed expert: two matrices in the latent (5,505,024 as published)."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def expert_weight_bytes(c: dict, touched: int) -> int:
    """Weight bytes of ``touched`` (layer, expert) pairs: what a step that
    sends each of them a token has to read of the routed experts."""
    return touched * expert_params(c) * WEIGHT_ITEMSIZE


def experts_held(c: dict) -> int:
    """(layer, expert) pairs this program holds."""
    return expert_layers(c) * c["n_routed_experts"]


# -- parameters ----------------------------------------------------------------


def mamba_layer_params(c: dict) -> int:
    """One ``M`` layer with its norm: 109,640,064 as published."""
    e, d, cd, h = c["hidden_size"], inner_dim(c), conv_dim(c), c["mamba_num_heads"]
    conv = c["conv_kernel"] * cd + (cd if c["use_conv_bias"] else 0)
    return e * in_proj_width(c) + conv + 3 * h + d + d * e + e


def attention_layer_params(c: dict) -> int:
    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return 2 * e * h * dh + 2 * e * kv * dh + e


def expert_layer_shared_params(c: dict) -> int:
    """An ``E`` layer outside its routed experts: the router and its bias, the
    two latent projections, the shared expert (54,526,464 as published) and
    the norm."""
    e, ro = c["hidden_size"], router_outputs(c)
    return e * ro + ro + 2 * e * c["moe_latent_size"] + 2 * e * c["moe_shared_expert_intermediate_size"] + e


def matmul_params(c: dict, active: bool = False) -> int:
    """Parameters in matrix multiplications: every projection, the router,
    the experts held (``active``: the ``num_experts_per_tok`` a token passes
    through), the shared expert and the head (the embedding is a lookup)."""
    e, d = c["hidden_size"], inner_dim(c)
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    experts = c["num_experts_per_tok"] if active else c["n_routed_experts"]
    per_expert_layer = (experts * expert_params(c) + e * router_outputs(c) + 2 * e * c["moe_latent_size"]
                        + 2 * e * c["moe_shared_expert_intermediate_size"])
    return (mamba_layers(c) * (e * in_proj_width(c) + d * e) + attention_layers(c) * (2 * e * h * dh + 2 * e * kv * dh)
            + expert_layers(c) * per_expert_layer + e * c["vocab_size"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter a token
    passes through in a matrix multiplication, causal attention in the
    attention layers, and the recurrence's 5 operations a state element three
    times over. (No cell trains this architecture.)"""
    attn = attention_layers(c) * 3 * 2 * (seq_len / 2) * c["num_attention_heads"] * 2 * c["head_dim"]
    return 6.0 * matmul_params(c, active=True) + attn + 3 * ssd_scan_flops(c, 1)


def total_params(c: dict) -> int:
    """Every parameter held: the layers by kind with the experts held, the
    embedding's and the head's rows held, the final norm."""
    e = c["hidden_size"]
    layers = (mamba_layers(c) * mamba_layer_params(c) + attention_layers(c) * attention_layer_params(c)
              + expert_layers(c) * (c["n_routed_experts"] * expert_params(c) + expert_layer_shared_params(c)))
    return layers + 2 * c["vocab_size"] * e + e


# -- the adapter ---------------------------------------------------------------


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    held, first = c["n_routed_experts"], c.get("experts_first", 0)
    mixers = {
        "M": dict(mixer="ssd", ssm_num_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
                  ssm_n_groups=c["n_groups"], ssm_state_dim=c["ssm_state_size"], ssm_conv_width=c["conv_kernel"],
                  ssm_conv_bias=bool(c["use_conv_bias"])),
        "*": dict(mixer="attention"),
        "E": dict(mixer="none"),
    }
    experts = dict(
        mlp_kind=c["mlp_hidden_act"], mlp_dim=c["moe_intermediate_size"], moe_num_experts=held,
        moe_router_outputs=router_outputs(c), moe_experts_held=(first, held), moe_top_k=c["num_experts_per_tok"],
        moe_scoring="sigmoid", moe_selection_bias=True, moe_n_group=c["n_group"], moe_topk_group=c["topk_group"],
        moe_routed_scale=float(c.get("routed_scaling_factor") or 1.0), moe_latent_dim=c["moe_latent_size"],
        moe_shared_dim=c["moe_shared_expert_intermediate_size"] * c["n_shared_experts"])
    kind = lambda name: dict(mixers[name[0]], **(experts if name.endswith("E") else dict(mlp_kind="none")))
    names = list(dict.fromkeys(name for name, _ in blocks(c)))
    # the interpreted kernels of a rehearsal: the serving kernels' switch covers the recurrence's too
    overrides.setdefault("ssm_kernel", "interpret" if overrides.get("prefill_kernel") == "interpret" else None)
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=len(blocks(c)), embed_dim=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_dim=0,  # no position embedding: the state-space layers carry the order
        max_seq_len=max_seq_len, norm_eps=float(c["layer_norm_epsilon"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16, scan_layers=True,
        # the residual stream in float32 (the matrix multiplications in
        # bfloat16): the experts' discrete choice asks for it (DecoderConfig)
        residual_dtype=jnp.float32,
        # the head's product leaves the unit in float32: rounded to bfloat16, two logits in ten of the seeded
        # model's 32,768 tie with their neighbour (spacing 0.03 at 4, the largest two 0.14 apart at the median)
        fp32_logits=True,
        layer_kinds=tuple((name, kind(name)) for name in names),
        layer_pattern=tuple(names.index(name) for name, _ in blocks(c)), **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


# the program's leaf <- the published one, by part of a block
_MAMBA = (("w_in", "in_proj"), ("conv_w", "conv_w"), ("conv_b", "conv_b"), ("b_dt", "dt_bias"), ("a_log", "a_log"),
          ("d_skip", "d"), ("norm_w", "norm_gate"), ("w_out", "out_proj"))
_ATTN = (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o"))
_EXPERTS = (("router", "router"), ("selection_bias", "router_bias"), ("w_latent_in", "latent_in"),
            ("w_latent_out", "latent_out"), ("w_up", "up_exp"), ("w_down", "down_exp"),
            ("shared_up", "up_shared"), ("shared_down", "down_shared"))
_FLOAT32 = ("b_dt", "a_log", "d_skip", "selection_bias")  # the recurrence's own leaves and the router's bias


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (one scanned stack a run of blocks of one kind, ``layers_<i>/block``)."""
    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    p = pattern(c)
    by_head = {"wq": (e, h, dh), "wk": (e, kv, dh), "wv": (e, kv, dh), "wo": (h, dh, e)}

    def adapt(w: dict) -> dict:
        tree = {"embedding": w["embed"], "lm_head": w["head"], "ln_final": w["norm_final"]}
        for i, (name, l0, n) in enumerate(runs(c)):
            block, step = {}, len(name)
            for j, kind in enumerate(name):  # the block's layers: published l0 + j, l0 + j + step, ...
                k0 = p[:l0 + j].count(kind)
                part = {leaf: w[src][k0:k0 + n] for leaf, src in {"M": _MAMBA, "*": _ATTN, "E": _EXPERTS}[kind]
                        if src in w}
                part = {leaf: x.astype("float32") if leaf in _FLOAT32 else x for leaf, x in part.items()}
                if kind == "*":
                    part = {leaf: x.reshape(n, *by_head[leaf]) for leaf, x in part.items()}
                block["ln_mlp" if kind == "E" else "ln_attn"] = w["norm"][l0 + j:l0 + j + n * step:step]
                block[{"M": "ssm", "*": "attn", "E": "moe_mlp"}[kind]] = part
            tree[f"layers_{i}"] = {"block": block}
        return tree

    return adapt


def from_program_tree(c: dict, p: dict) -> dict:
    """The inverse of ``to_program_tree``: the program's stacks back in
    published layout, leaf by leaf."""
    import jax.numpy as jnp

    parts, norms = {}, [None] * c["num_hidden_layers"]
    for i, (name, l0, n) in enumerate(runs(c)):
        block, step = p[f"layers_{i}"]["block"], len(name)
        for j, kind in enumerate(name):
            part = block[{"M": "ssm", "*": "attn", "E": "moe_mlp"}[kind]]
            for leaf, src in {"M": _MAMBA, "*": _ATTN, "E": _EXPERTS}[kind]:
                if leaf in part:
                    x = part[leaf]
                    if kind == "*":
                        x = x.reshape(n, x.shape[1], -1) if leaf != "wo" else x.reshape(n, -1, x.shape[-1])
                    parts.setdefault(src, []).append(x)
            for b, row in enumerate(block["ln_mlp" if kind == "E" else "ln_attn"]):
                norms[l0 + j + b * step] = row
    out = {name: jnp.concatenate(xs, axis=0) for name, xs in parts.items()}
    out.update(embed=p["embedding"], head=p["lm_head"], norm_final=p["ln_final"], norm=jnp.stack(norms))
    return out
