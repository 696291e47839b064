"""A toy second architecture, as a ``model_config`` PR would bring one: what
``closed_loop`` asks of ``arch/<model_type>.py``. The program's dense decoder
runs it; the published layout differs (one fused ``qkv`` leaf), so the
adapter splits it."""

import costs


def vocab(c):
    return c["vocab_size"]


def kv_bytes_per_token(c, kv_itemsize=2):
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * kv_itemsize


def decode_kv_bytes(c, write_pos, page_size, kv_itemsize=2):
    return costs.page_rounded(write_pos, page_size) * kv_bytes_per_token(c, kv_itemsize)


def decoder_config(c, *, max_seq_len, **overrides):
    import jax.numpy as jnp

    from accelerate_tpu.models import DecoderConfig

    return DecoderConfig(
        vocab_size=c["vocab_size"], num_layers=c["num_hidden_layers"], embed_dim=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mlp_dim=c["intermediate_size"], max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), tie_embeddings=False, dtype=jnp.bfloat16, scan_layers=True, **overrides)


def module(cfg, **kwargs):
    from accelerate_tpu.models import DecoderLM

    return DecoderLM(cfg, **kwargs)


def to_program_tree(c):
    n, e = c["num_hidden_layers"], c["hidden_size"]
    h, kv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]

    def adapt(w):
        q, k, v = w["qkv"][..., : h * d], w["qkv"][..., h * d: (h + kv) * d], w["qkv"][..., (h + kv) * d:]
        return {
            "embedding": w["embed"],
            "layers": {"block": {
                "attn": {"wq": q.reshape(n, e, h, d), "wk": k.reshape(n, e, kv, d), "wv": v.reshape(n, e, kv, d),
                         "wo": w["o"].reshape(n, h, d, e)},
                "ln_attn": w["norm_attn"], "ln_mlp": w["norm_mlp"],
                "mlp": {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]},
            }},
            "lm_head": w["head"], "ln_final": w["norm_final"],
        }

    return adapt
