"""Where the benchmark meets the program: a published configuration becomes
the program's ``DecoderConfig`` and published-layout weights become its
parameter tree. Only the drivers import this; the reference never does."""

from __future__ import annotations

import jax.numpy as jnp


def decoder_config(c: dict, *, max_seq_len: int, **overrides):
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
