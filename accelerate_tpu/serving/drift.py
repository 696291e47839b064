"""KV-quantization drift harness: quantify what int8/int4 KV storage costs
in output quality, against the bf16 arena, on fixed seeds.

Two complementary measurements (docs/serving.md "Quantized KV cache"):

- **token-match rate** — run the SAME prompts/seeds through a bf16 engine
  and a quantized engine (greedy or sampled; both paths use the exact
  engine programs production serves with) and count position-wise token
  agreement over the generated continuations. This is the end-to-end
  number: it includes divergence cascades (one flipped argmax reroutes the
  rest of the stream), so it is the pessimistic bound a deployment should
  gate on.
- **teacher-forced logit error** — replay the bf16 continuation token by
  token through both cache precisions (prefill + scalar-index decode
  steps, the single-stream path) and compare the per-step logits: MSE and
  relative error vs the bf16 logits' own scale. Teacher forcing removes
  the cascade, so this isolates the per-step numeric cost of quantized
  storage — the number that should stay stable as generations get longer.

The harness is what the tier-1 drift tests (tests/test_kv_quant.py) run;
point it at a real model
via ``kv_quant_drift(definition, params, prompts, ...)`` when generation
quality looks degraded after enabling a quantized arena
(docs/troubleshooting.md has the recipe).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def _teacher_forced_logits(definition, params, tokens: np.ndarray,
                           n_prompt: int):
    """[steps, V] fp32 logits from prefill(prompt) + teacher-forced
    scalar-index decode steps over ``tokens[n_prompt:]`` — step i's row is
    the distribution the model holds BEFORE emitting tokens[n_prompt+i].
    Eager applies on purpose: the harness is a diagnostic, not a hot path,
    and skipping jit keeps it out of the compile counters a surrounding
    zero-recompile assertion may be watching."""
    import jax.numpy as jnp

    tokens = np.asarray(tokens, np.int32)
    steps = tokens.size - n_prompt
    out, mutated = definition.apply(
        {"params": params}, jnp.asarray(tokens[None, :n_prompt]),
        positions=jnp.arange(n_prompt), use_cache=True, mutable=["cache"],
    )
    logits = [out["logits"][0, -1]]
    cache = mutated["cache"]
    for i in range(steps - 1):
        pos = n_prompt + i
        out, mutated = definition.apply(
            {"params": params, "cache": cache},
            jnp.asarray(tokens[None, pos:pos + 1]),
            positions=jnp.asarray([pos]),
            use_cache=True, decode=True, mutable=["cache"],
        )
        cache = mutated["cache"]
        logits.append(out["logits"][0, -1])
    return np.stack([np.asarray(l, np.float32) for l in logits])


def kv_quant_drift(
    definition,
    params,
    prompts,
    *,
    kv_cache_dtype: str = "int8",
    max_new_tokens: int = 8,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    seeds=None,
    num_slots: Optional[int] = None,
    max_cache_len: Optional[int] = None,
    prefill_chunks=None,
    logit_prompts: int = 2,
    baseline: Optional[dict] = None,
    **engine_kwargs,
) -> dict:
    """Compare a ``kv_cache_dtype`` KV arena against bf16 on ``prompts``
    (list of 1-D token-id arrays) with fixed ``seeds``. Returns::

        {
          "kv_cache_dtype": ..., "kv_cache_bits": ...,
          "token_match_rate":  position-wise continuation agreement in [0, 1],
          "exact_streams":     continuations that matched end to end,
          "sequences":         len(prompts),
          "tokens_compared":   total continuation positions,
          "logit_mse":         teacher-forced mean squared logit error,
          "logit_rel_err":     logit_mse / mean(bf16 logit^2),
          "arena_bytes_bf16" / "arena_bytes_quant" / "arena_bytes_ratio":
                               per-engine KV arena HBM (ratio = the slots-
                               per-chip multiplier at equal budget),
        }

    The result also carries a ``"baseline"`` dict (the bf16 streams +
    arena bytes). Pass it back via ``baseline=`` on a second call with
    the SAME prompts/seeds/engine shape to compare another
    ``kv_cache_dtype`` without rebuilding and re-running the bf16 engine.
    """
    from .engine import ServingEngine
    from .pages import kv_cache_bits

    prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
    if seeds is None:
        seeds = list(range(len(prompts)))
    n_slots = num_slots or min(max(len(prompts), 1), 4)
    need = max(p.size for p in prompts) + max_new_tokens
    cap = max_cache_len or -(-need // 16) * 16
    chunks = prefill_chunks or (min(16, cap // 2), min(64, cap))
    kw = dict(
        num_slots=n_slots, max_cache_len=cap,
        prefill_chunks=tuple(sorted(set(chunks))),
        temperature=temperature, top_k=top_k, **engine_kwargs,
    )

    def run(kvq):
        engine = ServingEngine(definition, params, kv_cache_dtype=kvq, **kw)
        engine.telemetry = None
        streams = engine.generate_batched(
            prompts, max_new_tokens=max_new_tokens, seeds=seeds
        )
        bytes_ = engine.arena_bytes
        slots = engine.num_slots
        del engine
        return streams, bytes_, slots

    if baseline is None:
        base, base_bytes, slots = run("bf16")
        baseline = {
            "streams": base, "arena_bytes": base_bytes, "num_slots": slots,
        }
    else:
        base = baseline["streams"]
        base_bytes = baseline["arena_bytes"]
        slots = baseline["num_slots"]
    quant, quant_bytes, _ = run(kv_cache_dtype)

    matched = compared = exact = 0
    for p, a, b in zip(prompts, base, quant):
        ca, cb = np.asarray(a)[p.size:], np.asarray(b)[p.size:]
        matched += int(np.sum(ca == cb))
        compared += ca.size
        exact += int(np.array_equal(ca, cb))

    # teacher-forced logit error on the bf16 continuations (cascade-free)
    cfg = definition.config
    sized = dataclasses.replace(
        cfg, max_cache_len=cap, kv_cache_dtype="bf16",
        kv_page_size=None, kv_num_pages=None,
    )
    base_def = definition.clone(config=sized)
    quant_def = definition.clone(
        config=dataclasses.replace(sized, kv_cache_dtype=kv_cache_dtype)
    )
    sq_err = ref_sq = 0.0
    n_logits = 0
    for p, stream in list(zip(prompts, base))[:logit_prompts]:
        lb = _teacher_forced_logits(base_def, params, stream, p.size)
        lq = _teacher_forced_logits(quant_def, params, stream, p.size)
        sq_err += float(np.sum((lq - lb) ** 2))
        ref_sq += float(np.sum(lb ** 2))
        n_logits += lb.size
    logit_mse = sq_err / max(1, n_logits)
    return {
        "kv_cache_dtype": kv_cache_dtype,
        "kv_cache_bits": kv_cache_bits(kv_cache_dtype),
        "token_match_rate": matched / max(1, compared),
        "exact_streams": exact,
        "sequences": len(prompts),
        "tokens_compared": compared,
        "logit_mse": logit_mse,
        "logit_rel_err": logit_mse / max(1e-30, ref_sq / max(1, n_logits)),
        "arena_bytes_bf16": int(base_bytes),
        "arena_bytes_quant": int(quant_bytes),
        "arena_bytes_ratio": base_bytes / max(1, quant_bytes),
        "arena_bytes_per_slot_bf16": int(base_bytes) // slots,
        "arena_bytes_per_slot_quant": int(quant_bytes) // slots,
        "baseline": baseline,
    }
