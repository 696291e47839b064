"""The ``jamba`` architecture as the harness meets it (``manifest.load_arch``
finds this file by the configuration's ``model_type``): the counts of the
work from shapes alone, by layer kind, and the adapter by which a published
configuration becomes the program's ``DecoderConfig`` (a state-space kind and
an attention kind without rotation) and published-layout weights its
parameter tree. The published layout and the plain reference are
``reference/jamba.py``, which imports nothing of this file; the manifest puts
it beside as ``.reference``. Only the drivers and the metric readers call
this file, and the program is imported inside its functions only.

Every function takes the configuration whole, as its file has it. Layer
``l`` is an attention layer where ``l % attn_layer_period ==
attn_layer_offset`` and a state-space (Mamba-1) layer otherwise
(:func:`mixers`; not called ``layer_kinds``, the name by which
``tests/benchmark/test_bench_layer_kinds.py`` finds the architectures it
holds to hand counts of windows and experts); ``num_experts`` 1 means every
feed-forward is the dense gated MLP.
"""

from __future__ import annotations

import costs

STATE_ITEMSIZE = 4   # the recurrent state is float32
CONV_ITEMSIZE = 2    # the convolution's kept inputs are bfloat16


def vocab(c: dict) -> int:
    return c["vocab_size"]


def mixers(c: dict) -> list:
    """True for an attention layer, False for a state-space layer, in
    published order."""
    return [l % c["attn_layer_period"] == c["attn_layer_offset"] for l in range(c["num_hidden_layers"])]


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def inner_dim(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def attention_layers(c: dict) -> int:
    return sum(mixers(c))


def ssm_layers(c: dict) -> int:
    return c["num_hidden_layers"] - attention_layers(c)


def kv_token_bytes(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes one token takes in one attention layer, keys and values."""
    return c["num_key_value_heads"] * 2 * head_dim(c) * kv_itemsize


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes a token of context adds: the attention layers' only (a
    state-space layer's state does not grow with the context)."""
    return attention_layers(c) * kv_token_bytes(c, kv_itemsize)


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel has to read for one sequence whose
    next write lands at ``write_pos``: the attention layers walk the whole
    page-rounded context; the state-space layers read no pages."""
    return attention_layers(c) * costs.page_rounded(write_pos, page_size) * kv_token_bytes(c, kv_itemsize)


def ssm_state_bytes(c: dict) -> int:
    """What a slot keeps over all state-space layers, whatever its context's
    length: the float32 state [D, N] and the convolution's last K - 1 inputs
    in bfloat16."""
    d = inner_dim(c)
    layer = d * c["mamba_d_state"] * STATE_ITEMSIZE + (c["mamba_d_conv"] - 1) * d * CONV_ITEMSIZE
    return ssm_layers(c) * layer


def ssm_scan_bytes(c: dict, rows: int, slots: int) -> int:
    """Bytes the ``ssm_scan`` kernel has to move in one call of a serving
    program, over all state-space layers: each row's inputs (u', the step, B
    and C) and its output, all float32, and each advanced slot's float32
    state once in and once out. The convolution's kept inputs are not the
    kernel's."""
    d, n = inner_dim(c), c["mamba_d_state"]
    row = d * (4 + 4 + 4) + 2 * n * 4
    return ssm_layers(c) * (rows * row + slots * 2 * d * n * STATE_ITEMSIZE)


def _ssm_layer_params(c: dict) -> tuple:
    """(in matrix multiplications, others) of one state-space mixer."""
    e, d, n, k, r = c["hidden_size"], inner_dim(c), c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
    matmul = e * 2 * d + d * (r + 2 * n) + r * d + d * e
    other = k * d + (d if c["mamba_conv_bias"] else 0) + (r + 2 * n) + d + d * n + d
    return matmul, other


def matmul_params(c: dict, active: bool = False) -> int:
    """Parameters in matrix multiplications (``active`` is the same: no
    experts); the tied embedding counts once, as the head."""
    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    total = e * c["vocab_size"]
    for attention_layer in mixers(c):
        total += (2 * e * h * dh + 2 * e * kv * dh) if attention_layer else _ssm_layer_params(c)[0]
        total += 3 * e * c["intermediate_size"]
    return total


def total_params(c: dict) -> int:
    """Every parameter held: the matrices (the tied embedding once), the
    norms, and the state-space mixers' convolution, biases, A and skip."""
    e = c["hidden_size"]
    return matmul_params(c) + ssm_layers(c) * _ssm_layer_params(c)[1] + c["num_hidden_layers"] * 2 * e + e


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter in a
    matrix multiplication, causal attention in the attention layers, and the
    recurrence's 9 operations a state element (exp, two products and a sum to
    advance it, a product and a sum to read it) three times over. (No cell
    trains this architecture.)"""
    attn = attention_layers(c) * 3 * 2 * (seq_len / 2) * c["num_attention_heads"] * 2 * head_dim(c)
    scan = ssm_layers(c) * 3 * 9 * inner_dim(c) * c["mamba_d_state"]
    return 6.0 * matmul_params(c) + attn + scan


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    kinds = mixers(c)
    fields = {
        False: dict(mixer="ssm", ssm_state_dim=c["mamba_d_state"], ssm_conv_width=c["mamba_d_conv"],
                    ssm_expand=c["mamba_expand"], ssm_dt_rank=c["mamba_dt_rank"], ssm_inner_norms=True,
                    ssm_conv_bias=bool(c["mamba_conv_bias"])),
        True: dict(mixer="attention", num_kv_heads=c["num_key_value_heads"]),
    }
    names = list(dict.fromkeys(kinds))
    # the interpreted kernels of a rehearsal: the serving kernels' switch covers the scan's too
    overrides.setdefault("ssm_kernel", "interpret" if overrides.get("prefill_kernel") == "interpret" else None)
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"], embed_dim=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        rope_dim=0,  # no position embedding: the state-space layers carry the order
        mlp_dim=c["intermediate_size"], max_seq_len=max_seq_len, norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16, scan_layers=True,
        # the residual stream in float32 (the matrix multiplications in bfloat16): see ``assumed``
        residual_dtype=jnp.float32,
        layer_kinds=tuple(("attention" if k else "state_space", fields[k]) for k in names),
        layer_pattern=tuple(names.index(k) for k in kinds), **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


def runs(c: dict) -> list:
    """[(first layer, layers, attention?)] for each run of consecutive layers
    of one kind: the program's stacks ``layers_<i>``."""
    out = []
    for l, kind in enumerate(mixers(c)):
        if out and out[-1][2] == kind:
            out[-1][1] += 1
        else:
            out.append([l, 1, kind])
    return [tuple(r) for r in out]


# published leaf -> the program's, in a state-space mixer (``a_log`` is laid out [N, D] there)
_SSM = (("in_proj", "w_in"), ("conv_w", "conv_w"), ("conv_b", "conv_b"), ("x_proj", "w_x"), ("norm_dt", "norm_dt"),
        ("norm_b", "norm_b"), ("norm_c", "norm_c"), ("dt_proj", "w_dt"), ("dt_bias", "b_dt"), ("a_log", "a_log"),
        ("d", "d_skip"), ("out_proj", "w_out"))
_FLOAT32 = ("b_dt", "a_log", "d_skip")  # the recurrence's own leaves stay float32


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (one scanned stack a run of layers of one kind, ``layers_<i>/block``)."""
    e, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    kinds = mixers(c)

    def adapt(w: dict) -> dict:
        tree = {"embedding": w["embed"], "ln_final": w["norm_final"]}
        for i, (l0, n, attention_layer) in enumerate(runs(c)):
            k0 = sum(1 for x in kinds[:l0] if x == attention_layer)
            cut = lambda name: w[name][k0:k0 + n]
            block = {"ln_attn": w["norm_mixer"][l0:l0 + n], "ln_mlp": w["norm_mlp"][l0:l0 + n],
                     "mlp": {"w_gate": w["gate"][l0:l0 + n], "w_up": w["up"][l0:l0 + n],
                             "w_down": w["down"][l0:l0 + n]}}
            if attention_layer:
                block["attn"] = {"wq": cut("q").reshape(n, e, h, dh), "wk": cut("k").reshape(n, e, kv, dh),
                                 "wv": cut("v").reshape(n, e, kv, dh), "wo": cut("o").reshape(n, h, dh, e)}
            else:
                ssm = {leaf: cut(name) for name, leaf in _SSM}
                ssm["a_log"] = ssm["a_log"].swapaxes(1, 2)
                ssm.update({leaf: ssm[leaf].astype("float32") for leaf in _FLOAT32})
                if not c["mamba_conv_bias"]:
                    del ssm["conv_b"]
                block["ssm"] = ssm
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

    for i, (l0, n, attention_layer) in enumerate(runs(c)):
        b = p[f"layers_{i}"]["block"]
        put("norm_mixer", b["ln_attn"]); put("norm_mlp", b["ln_mlp"])
        for name, leaf in (("gate", "w_gate"), ("up", "w_up"), ("down", "w_down")):
            put(name, b["mlp"][leaf])
        if attention_layer:
            a = b["attn"]
            put("q", a["wq"].reshape(n, e, -1)); put("k", a["wk"].reshape(n, e, -1))
            put("v", a["wv"].reshape(n, e, -1)); put("o", a["wo"].reshape(n, -1, e))
        else:
            for name, leaf in _SSM:
                if leaf in b["ssm"]:
                    put(name, b["ssm"][leaf].swapaxes(1, 2) if leaf == "a_log" else b["ssm"][leaf])
    out = {name: jnp.concatenate(xs, axis=0) for name, xs in parts.items()}
    out.update(embed=p["embedding"], norm_final=p["ln_final"])
    return out
