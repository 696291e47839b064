"""The ``deepseek_v3`` architecture as the harness meets it
(``manifest.load_arch`` finds this file by the configuration's
``model_type``; GigaChat3.1-702B-A36B's ``config.json`` is of it): the
counts of the work from shapes alone and the adapter by which a published
configuration becomes the program's ``DecoderConfig`` (latent attention,
the dense and the expert kind, ``experts_held``) and published-layout
weights its parameter tree. The published layout and the plain reference
are ``reference/deepseek_v3.py``, which imports nothing of this file; the
manifest puts it beside as ``.reference``. Only the drivers call the
adapter, and the program is imported inside its functions only.

Every function takes the configuration whole, as its file has it.
``n_routed_experts`` there is the experts held (``published.n_routed_experts``
the router's outputs), ``stage_first_layer`` (a key of the deployment) the
published layer that the first layer held is: layer ``l`` is dense iff
``stage_first_layer + l < first_k_dense_replace``.

The cache keeps one latent entry a token a layer, ``kv_lora_rank +
qk_rope_head_dim`` values (576: 1,152 B in bf16), from which every head's
keys and values are made; the program stores it padded to whole lanes (640:
1,280 B), which the counts here leave out, so a padded layout reads as a
lower share of the roofline.
"""

from __future__ import annotations

import costs


def vocab(c: dict) -> int:
    """Traffic draws its token ids from ``range(vocab(c))``: the slice held."""
    return c["vocab_size"]


def mlp_kinds(c: dict) -> list:
    """For each layer held, in order: True where it has experts, False where
    it is one of the published model's leading dense layers."""
    first = c.get("stage_first_layer", 0)
    return [first + l >= c["first_k_dense_replace"] and (first + l) % c["moe_layer_freq"] == 0
            for l in range(c["num_hidden_layers"])]


def router_outputs(c: dict) -> int:
    """The router's published width (``n_routed_experts`` is the experts held)."""
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def latent_width(c: dict) -> int:
    """Values of a cache entry: the latent and the one rotated key."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def kv_token_bytes(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes one token takes in one layer: one latent entry for all
    heads (576 x 2 B), not a head's keys and values (64 x 384 x 2 B)."""
    return latent_width(c) * kv_itemsize


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes a token of context adds for good, over the layers held."""
    return c["num_hidden_layers"] * kv_token_bytes(c, kv_itemsize)


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the decode kernel has to read, over all layers, for one
    sequence whose next write lands at ``write_pos``: every layer walks the
    whole page-rounded context once, an entry being key and value at once."""
    return costs.page_rounded(write_pos, page_size) * kv_bytes_per_token(c, kv_itemsize)


def mla_decode_work(c: dict, live_tokens_page_rounded: int, kv_itemsize: int = 2) -> tuple:
    """``(bytes, FLOP)`` of latent attention in decode steps that read
    ``live_tokens_page_rounded`` entries in each layer, **of the
    mathematics, whatever implements it**: each entry's 576 values read
    once, and the absorbed form's products: every head's 576-wide score and
    512-wide weighted sum, 2 x 64 x (576 + 512) = 139,264 FLOP an entry a
    layer, 121 a byte."""
    n, layers = int(live_tokens_page_rounded), c["num_hidden_layers"]
    flops = 2 * c["num_attention_heads"] * (latent_width(c) + c["kv_lora_rank"])
    return n * layers * kv_token_bytes(c, kv_itemsize), n * layers * flops


def mla_prefill_work(c: dict, pairs: int, rows: int = 0, entries: int = 0, itemsize: int = 2) -> tuple:
    """``(bytes, FLOP)`` of latent attention in packs whose rows see
    ``pairs`` (row, entry) pairs in each layer, of the mathematics: the
    **expanded** form's products, a head's 192-wide score and 192-wide
    weighted sum, 2 x 64 x 384 FLOP a visible pair a layer (the absorbed
    form multiplies 2.8 times as much and makes no key or value; it reads
    lower for it); and, once each, the ``rows``' queries in and outputs out
    (a head's 192 + 192) and the ``entries`` the rows see (cached and their
    own, 576 values each)."""
    layers, h = c["num_hidden_layers"], c["num_attention_heads"]
    head = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    moved = int(rows) * h * (head + c["v_head_dim"]) * itemsize + int(entries) * kv_token_bytes(c, itemsize)
    return layers * moved, int(pairs) * layers * 2 * h * (head + c["v_head_dim"])


def expert_weight_bytes(c: dict, experts_touched: int, itemsize: int = 2) -> int:
    """Weight bytes the expert multiplication has to read for
    ``experts_touched`` (layer, expert) pairs that got a token: three
    matrices of hidden x expert width each."""
    return experts_touched * 3 * c["hidden_size"] * c["moe_intermediate_size"] * itemsize


def _attention_matrices(c: dict) -> int:
    e, h, rq, r = c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    n, p, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return e * rq + rq * h * (n + p) + e * (r + p) + r * h * (n + dv) + h * dv * e


def matmul_params(c: dict, active: bool = False) -> int:
    """Parameters in matrix multiplications held here (``active``: that a
    token passes through: 8 routed experts a layer in place of those held;
    the shared expert either way)."""
    e, m = c["hidden_size"], c["moe_intermediate_size"]
    n_exp = c["num_experts_per_tok"] if active else c["n_routed_experts"]
    total = e * c["vocab_size"]  # the head
    for experts in mlp_kinds(c):
        total += _attention_matrices(c)
        total += ((n_exp + c["n_shared_experts"]) * 3 * e * m + e * router_outputs(c)) if experts \
            else 3 * e * c["intermediate_size"]
    return total


def total_params(c: dict) -> int:
    """Every parameter held: the matrices, the embedding, the norms (two a
    block, two inside its attention, the final one) and the routers'
    selection biases."""
    e = c["hidden_size"]
    norms = c["num_hidden_layers"] * (2 * e + c["q_lora_rank"] + c["kv_lora_rank"]) + e
    return matmul_params(c) + c["vocab_size"] * e + norms + sum(mlp_kinds(c)) * router_outputs(c)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter a
    token passes through in a matrix multiplication, and causal attention
    in its expanded form, QK^T over 192 and PV over 192 a head. (No cell
    trains this architecture.)"""
    h = c["num_attention_heads"]
    width = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return 6.0 * matmul_params(c, active=True) + c["num_hidden_layers"] * 3 * 2 * (seq_len / 2) * h * width


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    kinds = mlp_kinds(c)
    held, first = c["n_routed_experts"], c.get("experts_first", 0)
    fields = {
        False: ("dense", dict(mlp_dim=c["intermediate_size"], moe_num_experts=0)),
        True: ("experts", dict(
            mlp_dim=c["moe_intermediate_size"], moe_num_experts=held, moe_router_outputs=router_outputs(c),
            moe_experts_held=(first, held), moe_top_k=c["num_experts_per_tok"], moe_scoring=c["scoring_func"],
            moe_selection_bias=c["topk_method"] == "noaux_tc", moe_n_group=c["n_group"],
            moe_topk_group=c["topk_group"], moe_routed_scale=float(c.get("routed_scaling_factor") or 1.0),
            moe_shared_experts=int(c.get("n_shared_experts") or 0))),
    }
    names = list(dict.fromkeys(kinds))
    rs = c.get("rope_scaling")
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
        embed_dim=c["hidden_size"], num_heads=c["num_attention_heads"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        kv_lora_rank=c["kv_lora_rank"], q_lora_rank=c["q_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        rope_theta=float(c["rope_theta"]),
        rope_yarn=None if not rs else (rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
                                       rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"]),
        max_seq_len=max_seq_len, norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16, scan_layers=True,
        # the residual stream in float32 (the matrix multiplications in
        # bfloat16): the experts' discrete choice asks for it (DecoderConfig)
        residual_dtype=jnp.float32,
        layer_kinds=tuple(fields[k] for k in names),
        layer_pattern=tuple(names.index(k) for k in kinds), **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


def runs(c: dict) -> list:
    """[(first layer, layers, experts?)] for each run of consecutive layers
    of one kind: the program's stacks ``layers_<i>``."""
    out = []
    for l, kind in enumerate(mlp_kinds(c)):
        if out and out[-1][2] == kind:
            out[-1][1] += 1
        else:
            out.append([l, 1, kind])
    return [tuple(r) for r in out]


_ATTN = (("wq_a", "q_a"), ("q_norm", "norm_q"), ("wq_b", "q_b"), ("wkv_a", "kv_a"), ("kv_norm", "norm_kv"),
         ("wkv_b", "kv_b"), ("wo", "o"))
_DENSE = (("w_gate", "gate_dense"), ("w_up", "up_dense"), ("w_down", "down_dense"))
_EXPERTS = (("router", "router"), ("selection_bias", "router_bias"), ("w_gate", "gate_exp"), ("w_up", "up_exp"),
            ("w_down", "down_exp"), ("shared_gate", "gate_shared"), ("shared_up", "up_shared"),
            ("shared_down", "down_shared"))


def _head_shapes(c: dict) -> dict:
    """The program's shape of the leaves it keeps by head, after the layers."""
    e, h, rq, r = c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    n, p, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return {"wq_b": (rq, h, n + p), "wkv_b": (r, h, n + dv), "wo": (h, dv, e)}


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (one scanned stack a run of layers of one kind, ``layers_<i>/block``)."""
    kinds, by_head = mlp_kinds(c), _head_shapes(c)

    def adapt(w: dict) -> dict:
        tree = {"embedding": w["embed"], "lm_head": w["head"], "ln_final": w["norm_final"]}
        for i, (l0, n, experts) in enumerate(runs(c)):
            im = sum(1 for x in kinds[:l0] if x == experts)
            attn = {leaf: w[name][l0:l0 + n] for leaf, name in _ATTN}
            for leaf, shape in by_head.items():
                attn[leaf] = attn[leaf].reshape(n, *shape)
            block = {"attn": attn, "ln_attn": w["norm_attn"][l0:l0 + n], "ln_mlp": w["norm_mlp"][l0:l0 + n]}
            mlp = {leaf: w[name][im:im + n] for leaf, name in (_EXPERTS if experts else _DENSE) if name in w}
            if experts:
                mlp["selection_bias"] = mlp["selection_bias"].astype("float32")
            block["moe_mlp" if experts else "mlp"] = mlp
            tree[f"layers_{i}"] = {"block": block}
        return tree

    return adapt


def from_program_tree(c: dict, p: dict) -> dict:
    """The inverse of ``to_program_tree``: the program's stacks back in
    published layout, leaf by leaf."""
    import jax.numpy as jnp

    parts = {}
    for i, (l0, n, experts) in enumerate(runs(c)):
        b = p[f"layers_{i}"]["block"]
        for leaf, name in _ATTN:
            x = b["attn"][leaf]
            if leaf in ("wq_b", "wkv_b"):
                x = x.reshape(n, x.shape[1], -1)
            elif leaf == "wo":
                x = x.reshape(n, -1, x.shape[-1])
            parts.setdefault(name, []).append(x)
        parts.setdefault("norm_attn", []).append(b["ln_attn"])
        parts.setdefault("norm_mlp", []).append(b["ln_mlp"])
        mlp = b["moe_mlp" if experts else "mlp"]
        for leaf, name in (_EXPERTS if experts else _DENSE):
            if leaf in mlp:
                parts.setdefault(name, []).append(mlp[leaf])
    out = {name: jnp.concatenate(xs, axis=0) for name, xs in parts.items()}
    out.update(embed=p["embedding"], head=p["lm_head"], norm_final=p["ln_final"])
    return out
