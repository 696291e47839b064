"""Operations and bytes of the work, from shapes alone (no program code).

Copied arithmetic: the paged decode kernel's bytes follow
``ServingEngine._kernel_step_cost`` (page-rounded live tokens times bytes a
token); the original stays in the program for a later PR to delete.
"""

from __future__ import annotations


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
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * kv_itemsize


def page_rounded(write_pos: int, page_size: int) -> int:
    """Tokens the paged decode kernel walks for a sequence whose next write
    lands at ``write_pos``: whole pages up to and including that one."""
    return (write_pos // page_size + 1) * page_size
