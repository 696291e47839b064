"""The ``mimo_v2_flash`` architecture as the harness meets it
(``manifest.load_arch`` finds this file by the configuration's
``model_type``): the counts of the work from shapes alone, by layer kind,
and the adapter by which a published configuration becomes the program's
``DecoderConfig`` (layer kinds, ``experts_held``) and published-layout
weights its parameter tree. The published layout and the plain reference are
``reference/mimo_v2_flash.py``, which imports nothing of this file; the
manifest puts it beside as ``.reference``. Only the drivers call the
adapter, and the program is imported inside its functions only.

Every function takes the configuration whole, as its file has it, and reads
the first ``num_hidden_layers`` entries of the two published 48-entry lists
(``hybrid_layer_pattern``: 1 = window layer; ``moe_layer_freq``: 1 = expert
layer), which the configuration keeps whole. ``n_routed_experts`` there is
the experts held (``published.n_routed_experts`` the router's outputs).
"""

from __future__ import annotations

import costs


def vocab(c: dict) -> int:
    """Traffic draws its token ids from ``range(vocab(c))``: the slice held."""
    return c["vocab_size"]


def layer_kinds(c: dict) -> list:
    """(window?, experts?) a layer, in published order."""
    n = c["num_hidden_layers"]
    return [(bool(a), bool(m)) for a, m in zip(c["hybrid_layer_pattern"][:n], c["moe_layer_freq"][:n])]


def router_outputs(c: dict) -> int:
    """The router's published width (``n_routed_experts`` is the experts held)."""
    return c.get("published", {}).get("n_routed_experts", c["n_routed_experts"])


def kv_heads(c: dict, window_layer: bool) -> int:
    return c["swa_num_key_value_heads"] if window_layer else c["num_key_value_heads"]


def kv_token_bytes(c: dict, window_layer: bool, kv_itemsize: int = 2) -> int:
    """Cache bytes one token takes in one layer of a kind, keys and values
    at their true widths (192 + 128 a kv head: 2,560 B full, 5,120 B
    window); the lanes a page pads its keys with are not counted, so a
    padded layout shows as a lower share of the roofline."""
    return kv_heads(c, window_layer) * (c["head_dim"] + c["v_head_dim"]) * kv_itemsize


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes a token of context adds for good: the full layers' only.
    A window layer's pages are given back behind the window, so its share
    does not grow with the context (128 positions a slot, whatever its
    length)."""
    return sum(kv_token_bytes(c, False, kv_itemsize) for w, _ in layer_kinds(c) if not w)


def window_pages(c: dict, write_pos: int, page_size: int) -> int:
    """Pages that hold the positions a window layer's query at
    ``write_pos`` sees: from the page of ``write_pos - window + 1`` through
    that of ``write_pos``."""
    first = max(0, write_pos - c["sliding_window"] + 1) // page_size
    return write_pos // page_size + 1 - first


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel has to read, over all layers and
    by layer kind, for one sequence whose next write lands at ``write_pos``:
    a full layer walks the whole page-rounded context, a window layer the
    pages that hold its last ``sliding_window`` positions."""
    full = costs.page_rounded(write_pos, page_size)
    win = window_pages(c, write_pos, page_size) * page_size
    return sum((win if w else full) * kv_token_bytes(c, w, kv_itemsize) for w, _ in layer_kinds(c))


def expert_weight_bytes(c: dict, experts_touched: int, itemsize: int = 2) -> int:
    """Weight bytes the expert multiplication has to read for
    ``experts_touched`` (layer, expert) pairs that got a token: three
    matrices of hidden x expert width each."""
    return experts_touched * 3 * c["hidden_size"] * c["moe_intermediate_size"] * itemsize


def matmul_params(c: dict, active: bool = False) -> int:
    """Parameters in matrix multiplications held here (``active``: that a
    token passes through: 8 experts a layer in place of those held)."""
    e, h, dk, dv = c["hidden_size"], c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    r = router_outputs(c)
    n_exp = c["num_experts_per_tok"] if active else c["n_routed_experts"]
    total = e * c["vocab_size"]
    for w, m in layer_kinds(c):
        total += e * h * dk + e * kv_heads(c, w) * (dk + dv) + h * dv * e
        total += (n_exp * 3 * e * c["moe_intermediate_size"] + e * r) if m else 3 * e * c["intermediate_size"]
    return total


def total_params(c: dict) -> int:
    """Every parameter held: the matrices, the embedding, the norms, the
    window layers' sinks and the routers' selection biases."""
    e, r = c["hidden_size"], router_outputs(c)
    extra = sum((c["num_attention_heads"] if w and c["add_swa_attention_sink_bias"] else 0) + (r if m else 0)
                for w, m in layer_kinds(c))
    return matmul_params(c) + c["vocab_size"] * e + c["num_hidden_layers"] * 2 * e + e + extra


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter a
    token passes through in a matrix multiplication, and causal attention
    by layer kind: QK^T over 192, PV over 128, a window layer over at most
    ``sliding_window`` keys. (No cell trains this architecture yet.)"""
    h, dk, dv = c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    keys = lambda w: min(c["sliding_window"], seq_len / 2) if w else seq_len / 2
    attn = sum(3 * 2 * keys(w) * h * (dk + dv) for w, _ in layer_kinds(c))
    return 6.0 * matmul_params(c, active=True) + attn


def _kind_name(window_layer: bool, expert_layer: bool) -> str:
    return ("window" if window_layer else "full") + ("_experts" if expert_layer else "_dense")


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    kinds = layer_kinds(c)
    held, r = c["n_routed_experts"], router_outputs(c)

    def fields(window_layer, expert_layer):
        f = dict(num_kv_heads=kv_heads(c, window_layer),
                 rope_theta=float(c["swa_rope_theta"] if window_layer else c["rope_theta"]),
                 attn_window=c["sliding_window"] if window_layer else None,
                 attn_sink=bool(c["add_swa_attention_sink_bias"] if window_layer
                                else c["add_full_attention_sink_bias"]))
        if expert_layer:
            f.update(mlp_dim=c["moe_intermediate_size"], moe_num_experts=held, moe_router_outputs=r,
                     moe_experts_held=(0, held), moe_top_k=c["num_experts_per_tok"],
                     moe_scoring=c["scoring_func"], moe_selection_bias=c["topk_method"] == "noaux_tc")
        else:
            f.update(mlp_dim=c["intermediate_size"], moe_num_experts=0)
        return f

    names = list(dict.fromkeys(kinds))
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
        embed_dim=c["hidden_size"], num_heads=c["num_attention_heads"],
        head_dim=c["head_dim"], v_head_dim=c["v_head_dim"],
        rope_dim=int(c["head_dim"] * c["partial_rotary_factor"]),
        attn_value_scale=float(c["attention_value_scale"]),
        max_seq_len=max_seq_len, norm_eps=float(c["layernorm_epsilon"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16, scan_layers=True,
        # the residual stream in float32 (the matrix multiplications in
        # bfloat16): the experts' discrete choice asks for it (DecoderConfig)
        residual_dtype=jnp.float32,
        layer_kinds=tuple((_kind_name(*k), fields(*k)) for k in names),
        layer_pattern=tuple(names.index(k) for k in kinds), **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


def runs(c: dict) -> list:
    """[(first layer, layers, window?, experts?)] for each run of
    consecutive layers of one kind: the program's stacks ``layers_<i>``."""
    out = []
    for l, kind in enumerate(layer_kinds(c)):
        if out and tuple(out[-1][2:]) == kind:
            out[-1][1] += 1
        else:
            out.append([l, 1, *kind])
    return [tuple(r) for r in out]


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (one scanned stack a run of layers of one kind, ``layers_<i>/block``)."""
    e, h, dk, dv = c["hidden_size"], c["num_attention_heads"], c["head_dim"], c["v_head_dim"]
    kinds = layer_kinds(c)

    def adapt(w: dict) -> dict:
        tree = {"embedding": w["embed"], "lm_head": w["head"], "ln_final": w["norm_final"]}
        for i, (l0, n, window_layer, expert_layer) in enumerate(runs(c)):
            ia = sum(1 for x, _ in kinds[:l0] if x == window_layer)
            im = sum(1 for _, x in kinds[:l0] if x == expert_layer)
            kv, a = kv_heads(c, window_layer), "swa" if window_layer else "full"
            attn = {"wq": w["q"][l0:l0 + n].reshape(n, e, h, dk),
                    "wk": w[f"k_{a}"][ia:ia + n].reshape(n, e, kv, dk),
                    "wv": w[f"v_{a}"][ia:ia + n].reshape(n, e, kv, dv),
                    "wo": w["o"][l0:l0 + n].reshape(n, h, dv, e)}
            if window_layer and c["add_swa_attention_sink_bias"]:
                attn["sink"] = w["sink_swa"][ia:ia + n].astype("float32")
            block = {"attn": attn, "ln_attn": w["norm_attn"][l0:l0 + n], "ln_mlp": w["norm_mlp"][l0:l0 + n]}
            if expert_layer:
                block["moe_mlp"] = {
                    "router": w["router"][im:im + n],
                    "selection_bias": w["router_bias"][im:im + n].astype("float32"),
                    "w_gate": w["gate_exp"][im:im + n], "w_up": w["up_exp"][im:im + n],
                    "w_down": w["down_exp"][im:im + n]}
            else:
                block["mlp"] = {"w_gate": w["gate_dense"][im:im + n], "w_up": w["up_dense"][im:im + n],
                                "w_down": w["down_dense"][im:im + n]}
            tree[f"layers_{i}"] = {"block": block}
        return tree

    return adapt


def from_program_tree(c: dict, p: dict) -> dict:
    """The inverse of ``to_program_tree``: the program's stacks back in
    published layout, leaf by leaf."""
    import jax.numpy as jnp

    e = c["hidden_size"]
    parts = {}

    def put(name, x):
        parts.setdefault(name, []).append(x)

    for i, (l0, n, window_layer, expert_layer) in enumerate(runs(c)):
        b = p[f"layers_{i}"]["block"]
        a = "swa" if window_layer else "full"
        put("q", b["attn"]["wq"].reshape(n, e, -1)); put("o", b["attn"]["wo"].reshape(n, -1, e))
        put(f"k_{a}", b["attn"]["wk"].reshape(n, e, -1)); put(f"v_{a}", b["attn"]["wv"].reshape(n, e, -1))
        put("norm_attn", b["ln_attn"]); put("norm_mlp", b["ln_mlp"])
        if "sink" in b["attn"]:
            put("sink_swa", b["attn"]["sink"])
        if expert_layer:
            m = b["moe_mlp"]
            for name, leaf in (("router", "router"), ("router_bias", "selection_bias"), ("gate_exp", "w_gate"),
                               ("up_exp", "w_up"), ("down_exp", "w_down")):
                put(name, m[leaf])
        else:
            for name, leaf in (("gate_dense", "w_gate"), ("up_dense", "w_up"), ("down_dense", "w_down")):
                put(name, b["mlp"][leaf])
    out = {name: jnp.concatenate(xs, axis=0) for name, xs in parts.items()}
    out.update(embed=p["embedding"], head=p["lm_head"], norm_final=p["ln_final"])
    return out
