"""The ``mistral`` architecture as the harness meets it (``manifest.load_arch``
finds this file by the configuration's ``model_type``): the counts of the
work from shapes alone, and the adapter by which a published configuration
becomes the program's ``DecoderConfig`` and published-layout weights its
parameter tree. The published layout and the plain reference are
``reference/mistral.py``, which imports nothing of this file; the manifest
puts it beside as ``.reference``. Only the drivers call the adapter, and the
program is imported inside its functions only.

Every function takes the configuration whole, as its file has it.
"""

from __future__ import annotations

import costs


def vocab(c: dict) -> int:
    """Traffic draws its token ids from ``range(vocab(c))``."""
    return c["vocab_size"]


def matmul_params(c: dict) -> int:
    """Parameters that take part in matrix multiplications: every projection
    and the output head. The embedding is a lookup and the norms are vectors."""
    e, h, kv, d = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    layer = e * h * d + 2 * e * kv * d + h * d * e + 3 * e * c["intermediate_size"]
    return c["num_hidden_layers"] * layer + e * c["vocab_size"]


def total_params(c: dict) -> int:
    e = c["hidden_size"]
    tied = c.get("tie_word_embeddings", False)
    return (matmul_params(c) + (0 if tied else c["vocab_size"] * e)
            + c["num_hidden_layers"] * 2 * e + e)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 a parameter in a
    matrix multiplication, and causal attention: QK^T and PV are 2*2*S*h*d a
    token forward when full, half of it under the causal mask, times 3."""
    attn = 3 * 0.5 * 4 * seq_len * c["num_attention_heads"] * c["head_dim"] * c["num_hidden_layers"]
    return 6.0 * matmul_params(c) + attn


def kv_bytes_per_token(c: dict, kv_itemsize: int = 2) -> int:
    """Cache bytes a token takes, keys and values over all layers."""
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * kv_itemsize


def decode_kv_bytes(c: dict, write_pos: int, page_size: int, kv_itemsize: int = 2) -> int:
    """Cache bytes the paged decode kernel reads, over all layers, for one
    sequence whose next write lands at ``write_pos``. Every layer attends to
    the whole context, so each walks the same page-rounded tokens; an
    architecture with window layers answers by layer kind here."""
    return costs.page_rounded(write_pos, page_size) * kv_bytes_per_token(c, kv_itemsize)


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    if c.get("sliding_window") is not None:
        raise ValueError("the program's decoder has no sliding window")
    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"],
        embed_dim=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mlp_dim=c["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), dtype=jnp.bfloat16,
        scan_layers=True, **overrides,
    )


def module(cfg, **kwargs):
    """The ``nn.Module`` the drivers build for a ``decoder_config``."""
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


def to_program_tree(c: dict):
    """Adapter for ``weights.make_jit``: published layout -> DecoderLM params
    (layers stacked under ``layers/block`` as ``scan_layers`` has them)."""
    n, e = c["num_hidden_layers"], c["hidden_size"]
    h, kv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]

    def adapt(w: dict) -> dict:
        return {
            "embedding": w["embed"],
            "layers": {"block": {
                "attn": {"wq": w["q"].reshape(n, e, h, d), "wk": w["k"].reshape(n, e, kv, d),
                         "wv": w["v"].reshape(n, e, kv, d), "wo": w["o"].reshape(n, h, d, e)},
                "ln_attn": w["norm_attn"], "ln_mlp": w["norm_mlp"],
                "mlp": {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]},
            }},
            "lm_head": w["head"], "ln_final": w["norm_final"],
        }

    return adapt


def from_program_tree(c: dict, p: dict) -> dict:
    """The inverse, for reading gradients and updates leaf by leaf."""
    n, e = c["num_hidden_layers"], c["hidden_size"]
    b = p["layers"]["block"]
    return {
        "embed": p["embedding"], "head": p["lm_head"], "norm_final": p["ln_final"],
        "q": b["attn"]["wq"].reshape(n, e, -1), "k": b["attn"]["wk"].reshape(n, e, -1),
        "v": b["attn"]["wv"].reshape(n, e, -1), "o": b["attn"]["wo"].reshape(n, -1, e),
        "norm_attn": b["ln_attn"], "norm_mlp": b["ln_mlp"],
        "gate": b["mlp"]["w_gate"], "up": b["mlp"]["w_up"], "down": b["mlp"]["w_down"],
    }
