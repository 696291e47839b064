"""The ``evabyte`` architecture as the harness meets it (``manifest.load_arch``
finds this file by the configuration's ``model_type``): the counts of the
work from shapes alone, and the adapter by which a published configuration
becomes the program's ``DecoderConfig`` (EVA attention: a closing window
with pooled summaries) and published-layout weights its parameter tree. The
published layout and the plain reference are ``reference/evabyte.py``, which
imports nothing of this file; the manifest puts it beside as ``.reference``.
Only the drivers and the metric readers call this file, and the program is
imported inside its functions only.

Every function takes the configuration whole, as its file has it. A cache
*entry* is one key and one value a head a layer: a token of the open window,
or the pooled summary of a 16-token chunk of a window that has closed.
"""

from __future__ import annotations

import costs


def vocab(c: dict) -> int:
    """Traffic draws its bytes from ``range(vocab(c))``."""
    return c["vocab_size"]


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def entry_bytes(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes of one entry in one layer, key and value."""
    return c["num_key_value_heads"] * 2 * head_dim(c) * kv_itemsize


def entries_held(c: dict, length: int) -> int:
    """Entries a slot holds at a context of ``length`` positions: the open
    window's tokens and one entry a chunk of every closed window."""
    w, cs = c["window_size"], c["chunk_size"]
    return (length // w) * (w // cs) + length % w


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes a token of the open window adds, over all layers (a closed
    window keeps a sixteenth of it: :func:`entries_held`)."""
    return c["num_hidden_layers"] * entry_bytes(c, kv_itemsize)


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel reads, over all layers, for one
    sequence whose next write lands at ``write_pos``: the page-rounded
    *entries* up to that position's own, summaries and open window alike."""
    return costs.page_rounded(entries_held(c, write_pos), page_size) * kv_bytes_per_token(c, kv_itemsize)


def pool_page_bytes(c: dict, kv_itemsize: int = 2) -> int:
    """Bytes the ``eva_pool`` kernel has to move to pool one filled page, over
    all layers: the chunk's keys and values in, one entry out."""
    return c["num_hidden_layers"] * (c["chunk_size"] + 1) * entry_bytes(c, kv_itemsize)


def pool_page_flops(c: dict) -> int:
    """Operations of one pooled page, over all layers: two logits of 2 d
    operations a position and |k|^2, two weighted sums of 2 d a position."""
    per_position = c["num_key_value_heads"] * head_dim(c) * (2 + 2 + 2 + 2 + 2)
    return c["num_hidden_layers"] * c["chunk_size"] * per_position


def matmul_params(c: dict) -> int:
    """Parameters that take part in matrix multiplications: every projection
    and the whole head (all ``num_pred_heads`` row blocks are multiplied)."""
    e, h, kv, d = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    layer = e * h * d + 2 * e * kv * d + h * d * e + 3 * e * c["intermediate_size"]
    return c["num_hidden_layers"] * layer + e * c["vocab_size"] * c["num_pred_heads"]


def total_params(c: dict) -> int:
    """Every parameter held: the matrices, the embedding, the norms and the
    two pooling vectors a head a layer."""
    e = c["hidden_size"]
    pooling = 2 * c["num_key_value_heads"] * head_dim(c)
    return matmul_params(c) + c["vocab_size"] * e + c["num_hidden_layers"] * (2 * e + pooling) + e


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter in a
    matrix multiplication, and attention over the entries a position sees on
    average (half a window of tokens, and the summaries of the windows before
    it). (No cell trains this architecture.)"""
    w = c["window_size"]
    seen = min(seq_len, w) / 2 + max(seq_len - w, 0) / 2 / c["chunk_size"]
    attn = 3 * 4 * seen * c["num_attention_heads"] * head_dim(c) * c["num_hidden_layers"]
    return 6.0 * matmul_params(c) + attn


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    if c.get("attention_class") != "eva" or c.get("rope_scaling") is not None:
        raise ValueError("the adapter takes attention_class 'eva' without rope scaling")
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
        embed_dim=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        mlp_dim=c["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16, scan_layers=True,
        eva_window=c["window_size"], eva_chunk=c["chunk_size"],
        norm_unit_offset=bool(c["norm_add_unit_offset"]), num_pred_heads=c["num_pred_heads"],
        fp32_logits=bool(c["fp32_logits"]),
        residual_dtype=jnp.float32 if c["fp32_skip_add"] else None, **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (layers stacked under ``layers/block`` as ``scan_layers`` has them; the
    pooling vectors stay float32, as the pooling's logits are)."""
    n, e = c["num_hidden_layers"], c["hidden_size"]
    h, kv, d = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)

    def adapt(w: dict) -> dict:
        return {
            "embedding": w["embed"],
            "layers": {"block": {
                "attn": {"wq": w["q"].reshape(n, e, h, d), "wk": w["k"].reshape(n, e, kv, d),
                         "wv": w["v"].reshape(n, e, kv, d), "wo": w["o"].reshape(n, h, d, e),
                         "eva_mu": w["mu"].astype("float32"), "eva_phi": w["phi"].astype("float32")},
                "ln_attn": w["norm_attn"], "ln_mlp": w["norm_mlp"],
                "mlp": {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]},
            }},
            "lm_head": w["head"], "ln_final": w["norm_final"],
        }

    return adapt


def from_program_tree(c: dict, p: dict) -> dict:
    """The inverse, leaf by leaf."""
    n, e = c["num_hidden_layers"], c["hidden_size"]
    b = p["layers"]["block"]
    return {
        "embed": p["embedding"], "head": p["lm_head"], "norm_final": p["ln_final"],
        "q": b["attn"]["wq"].reshape(n, e, -1), "k": b["attn"]["wk"].reshape(n, e, -1),
        "v": b["attn"]["wv"].reshape(n, e, -1), "o": b["attn"]["wo"].reshape(n, -1, e),
        "mu": b["attn"]["eva_mu"], "phi": b["attn"]["eva_phi"],
        "norm_attn": b["ln_attn"], "norm_mlp": b["ln_mlp"],
        "gate": b["mlp"]["w_gate"], "up": b["mlp"]["w_up"], "down": b["mlp"]["w_down"],
    }
