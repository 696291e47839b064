"""Autoregressive generation with a static-shape KV cache.

The reference's headline benchmark is big-model *generation*
(/root/reference/benchmarks/big_model_inference/big_model_inference.py:
model load + s/token on dispatched models). This module is the TPU-native
counterpart:

- ``generate()`` prefill-then-decode: the prompt runs once through the
  model writing the KV cache (flash-kernel causal attention), then a single
  jitted ``lax.scan`` emits tokens one at a time against the cache — every
  shape static, so the whole decode loop is ONE compiled program with no
  per-token dispatch overhead (torch pays a python round-trip per token).
- works with plain params, offloaded DispatchedModel params (pinned-host
  weights stream per layer inside the loop), and QuantizedWeight trees
  (dequantized in-graph inside the loop so HBM keeps the packed form).
- greedy, temperature, and top-k sampling.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def clear_generation_caches():
    """Drop every module-level generation cache: compiled prefill/decode
    loops, right-sized definition clones, and de-pipelined param trees
    (which pin two full weight copies each). Call when retiring models from
    a long-lived server process."""
    _LOOP_CACHE.clear()
    _SIZED_DEF_CACHE.clear()
    _DEPIPE_DEF_CACHE.clear()


@jax.jit
def _sync_probe(x):
    """Tiny fully-replicated scalar depending on all of ``x`` — device_get of
    this forces completion of everything ``x`` depends on without fetching or
    re-committing ``x`` itself (multi-host safe: scalar jit outputs are
    replicated, so every host holds an addressable copy)."""
    return jnp.sum(x).astype(jnp.int32)


def _sample(logits, key, temperature: float, top_k: Optional[int]):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


# jitted decode loops cached per (definition identity, loop shape): flax
# modules/configs are unhashable, so the definition is closed over instead of
# passed as a jit static, and reuse across generate() calls avoids recompiles.
# Bounded FIFO: a long-lived server varying models/loop shapes must not pin
# compiled programs (and their captured definitions/placers) forever.
_LOOP_CACHE: dict = {}
_LOOP_CACHE_LIMIT = 32

# right-sized definition clones keyed by (id(original), cache_len): reusing
# the same clone keeps id(definition) stable so the jitted loops re-hit
_SIZED_DEF_CACHE: dict = {}

# de-pipelined definition clones, same id-stability trick
_DEPIPE_DEF_CACHE: dict = {}


def depipeline(definition, params):
    """(definition, params) with pipeline stages folded back into the layer
    scan — the form autoregressive decoding wants.

    A decode step is inherently SERIAL across pipeline stages (token t+1
    cannot enter stage 0 before token t left the last stage), so the GPipe
    schedule buys nothing at generation time; what works is running the
    stage-stacked layers as one layer scan with a KV cache. Params move from
    ``pipeline/stages/layers/...`` leaves [S, L/S, ...] to ``layers/...``
    leaves [L, ...] (the exact inverse of prepare_pippy's remap).

    ``generate()`` applies this automatically and caches the converted tree
    (keyed on the identity of every leaf), which PINS both the original and
    converted params until eviction or :func:`clear_generation_caches` —
    serving loops should call depipeline ONCE up front, keep the converted
    pair, and drop the stacked original.
    """
    cfg = getattr(definition, "config", None)
    stages = getattr(definition, "_effective_stages", lambda: 1)()
    if cfg is None or stages <= 1:
        return definition, params

    leaf_ids = tuple(id(l) for l in jax.tree_util.tree_leaves(params))
    key = id(definition)
    hit = _DEPIPE_DEF_CACHE.get(key)
    if hit is not None and hit[0] is definition:
        clone = hit[1]
        cached = hit[2]
        # cached[0] holds the ORIGINAL tree (strong ref — ids stay valid);
        # every leaf must be the same object, not just the first
        if cached is not None and cached[1] == leaf_ids:
            return clone, cached[2]  # repeat call, skip the re-layout
    else:
        clone = None

    import dataclasses as _dc

    from .parallel.pipeline import _flatten_paths, _unflatten_paths

    flat = _flatten_paths(params)
    out = {}
    for path, leaf in flat.items():
        # stage-vmapped layer-scan leaves live under .../stages/layers/
        # (e.g. pipeline/schedule/stages/layers/block/attn/wq, [S, L/S, ...])
        # — the same convention remap_params_to_pipeline writes
        if "stages/layers/" in path:
            tail = path.split("stages/layers/")[-1]
            out[f"layers/{tail}"] = leaf.reshape(
                leaf.shape[0] * leaf.shape[1], *leaf.shape[2:]
            )
        else:
            out[path] = leaf
    new_params = _unflatten_paths(out)

    if clone is None:
        new_cfg = _dc.replace(cfg, pipeline_stages=1, scan_layers=True)
        mesh = getattr(definition, "mesh", None)
        if mesh is not None and mesh.shape.get("stage", 1) > 1:
            # keep every non-stage axis (tensor/fsdp/data sharding must
            # survive decode); the stage devices fold into "data", where the
            # now layer-scanned params are simply replicated
            clone = definition.clone(config=new_cfg, mesh=_fold_stage_into_data(mesh))
        else:
            clone = definition.clone(config=new_cfg)
    if len(_DEPIPE_DEF_CACHE) >= _LOOP_CACHE_LIMIT:
        _DEPIPE_DEF_CACHE.pop(next(iter(_DEPIPE_DEF_CACHE)))
    # NB: pins BOTH trees (original + converted) until evicted or
    # clear_generation_caches() — the price of skipping the re-layout on
    # every serving call; see the docstring
    _DEPIPE_DEF_CACHE[key] = (definition, clone, (params, leaf_ids, new_params))
    return clone, new_params


def _fold_stage_into_data(mesh):
    """Same devices, stage axis merged into the data axis (stage dropped):
    decode has no pipeline schedule, so former stage devices act
    data-parallel (params replicated across them)."""
    from jax.sharding import Mesh

    names = list(mesh.axis_names)
    if "stage" not in names:
        return mesh
    if "data" not in names:
        # no data axis to merge into: rename "stage" -> "data" (same device
        # layout; batch specs shard over data, so former stage devices go
        # data-parallel). Non-stage axes are preserved either way.
        from jax.sharding import Mesh

        return Mesh(
            mesh.devices,
            tuple("data" if n == "stage" else n for n in names),
        )
    devices = mesh.devices
    s_ax, d_ax = names.index("stage"), names.index("data")
    # transpose so stage sits immediately before data, then merge the pair
    order = [i for i in range(devices.ndim) if i != s_ax]
    order.insert(order.index(d_ax), s_ax)
    arr = devices.transpose(order)
    pos = order.index(s_ax)
    shape = list(arr.shape)
    shape[pos:pos + 2] = [shape[pos] * shape[pos + 1]]
    new_names = [names[i] for i in order if i != s_ax]
    return Mesh(arr.reshape(shape), tuple(new_names))

_CACHE_BUCKET = 256


def _sized_definition(definition, cache_len: int):
    """Definition clone with ``max_cache_len = cache_len``, cached by
    (id(definition), cache_len) so repeat calls return the SAME clone and
    the jitted loops keyed on id(definition) re-hit. Shared by the
    single-stream right-sizing below and the serving engine's arena sizing
    (serving/engine.py), which needs an exact length, not a bucket."""
    cfg = getattr(definition, "config", None)
    if cfg is None or not hasattr(cfg, "max_cache_len"):
        return definition
    import dataclasses as _dc

    key = (id(definition), cache_len)
    hit = _SIZED_DEF_CACHE.get(key)
    # the stored original pins it alive AND guards against id() reuse after
    # an unrelated definition lands at the same address
    if hit is not None and hit[0] is definition:
        return hit[1]
    try:
        clone = definition.clone(config=_dc.replace(cfg, max_cache_len=cache_len))
    except Exception:
        return definition
    if len(_SIZED_DEF_CACHE) >= _LOOP_CACHE_LIMIT:
        _SIZED_DEF_CACHE.pop(next(iter(_SIZED_DEF_CACHE)))
    _SIZED_DEF_CACHE[key] = (definition, clone)
    return clone


def _right_size_cache(definition, prompt_len: int, max_new_tokens: int):
    """Clone the definition with max_cache_len = prompt+budget rounded up to
    a 256 bucket. Decode attention cost scales with the cache length, so a
    128-token prompt generating 64 tokens should not pay for a
    max_seq_len=2048 cache (~1 ms/token extra on a 0.39B model). Bucketing
    bounds recompiles; an explicit config.max_cache_len is respected."""
    cfg = getattr(definition, "config", None)
    if cfg is None or not hasattr(cfg, "max_cache_len") or cfg.max_cache_len is not None:
        return definition

    need = prompt_len + max_new_tokens
    sized = -(-need // _CACHE_BUCKET) * _CACHE_BUCKET
    limit = getattr(cfg, "max_seq_len", None)
    if limit is not None:
        sized = min(sized, limit)
    if sized < need:
        return definition  # over max_seq_len; let the capacity check raise
    return _sized_definition(definition, sized)


def _cache_put(key, value):
    if len(_LOOP_CACHE) >= _LOOP_CACHE_LIMIT:
        _LOOP_CACHE.pop(next(iter(_LOOP_CACHE)))
    _LOOP_CACHE[key] = value
    return value


def _decode_loop_for(definition, max_new_tokens, temperature, top_k, placer):
    key = (id(definition), max_new_tokens, temperature, top_k, id(placer))
    if key in _LOOP_CACHE:
        return _LOOP_CACHE[key]

    @jax.jit
    def loop(params, cache, last_token, start_pos, rng):
        def step(carry, _):
            cache, tok, pos, rng = carry
            rng, sub = jax.random.split(rng)
            p = placer(params)
            out, mutated = definition.apply(
                {"params": p, "cache": cache},
                tok[:, None],
                positions=pos[None],
                use_cache=True,
                decode=True,
                mutable=["cache"],
            )
            logits = out["logits"][:, -1]
            nxt = _sample(logits, sub, temperature, top_k)
            return (mutated["cache"], nxt, pos + 1, rng), nxt

        (cache, _, _, _), tokens = jax.lax.scan(
            step, (cache, last_token, start_pos, rng), None, length=max_new_tokens
        )
        return tokens.T  # [B, new_tokens]

    return _cache_put(key, loop)


def generate(
    definition,
    params,
    input_ids,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    return_prefill_seconds: bool = False,
    param_placer=None,
):
    """Generate ``max_new_tokens`` continuations of ``input_ids`` [B, S].
    ``temperature=0`` is greedy. Returns [B, S + new] token ids (and the
    prefill wall time when asked — the TTFT component). ``param_placer`` is
    an in-graph transform applied to params inside the jits (dispatch
    placement / dequantization); defaults to dequantize-only."""
    import time

    from .utils.compile_cache import ensure_persistent_compile_cache

    ensure_persistent_compile_cache()
    input_ids = jnp.asarray(input_ids)
    b, s = input_ids.shape
    definition, params = depipeline(definition, params)
    definition = _right_size_cache(definition, s, max_new_tokens)
    cfg = getattr(definition, "config", None)
    if cfg is not None:
        cap = getattr(cfg, "max_cache_len", None) or getattr(cfg, "max_seq_len", None)
        if cap is not None and s + max_new_tokens > cap:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds the KV cache "
                f"capacity ({cap}); raise config.max_cache_len"
            )
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if param_placer is None:
        from .utils.quantization import dequantize_params as param_placer  # noqa: F811

    prefill_rng, decode_rng = jax.random.split(rng)

    prefill = _prefill_for(definition, temperature, top_k, param_placer)
    t0 = time.perf_counter()
    last, cache = prefill(params, input_ids, prefill_rng)
    if return_prefill_seconds:
        # Force completion by device_get of a tiny scalar reduction rather
        # than device_get(last) (which would fail on multi-host meshes
        # where `last` spans non-addressable devices, and on one host would
        # re-commit `last` to the default device, dropping its sharding and
        # retracing the decode loop). The scalar jit output
        # is fully replicated, so every host can fetch it; `last` itself is
        # left untouched for the decode loop.
        jax.device_get(_sync_probe(last))
    prefill_seconds = time.perf_counter() - t0

    loop = _decode_loop_for(definition, max_new_tokens - 1, temperature, top_k, param_placer)
    tokens = loop(params, cache, last, jnp.asarray(s, jnp.int32), decode_rng)
    result = jnp.concatenate([input_ids, last[:, None], tokens], axis=1)
    if return_prefill_seconds:
        return result, prefill_seconds
    return result


def _prefill_for(definition, temperature, top_k, placer):
    key = ("prefill", id(definition), temperature, top_k, id(placer))
    if key in _LOOP_CACHE:
        return _LOOP_CACHE[key]

    @jax.jit
    def prefill(params, input_ids, rng):
        s = input_ids.shape[1]
        out, mutated = definition.apply(
            {"params": placer(params)},
            input_ids,
            positions=jnp.arange(s),
            use_cache=True,
            mutable=["cache"],
        )
        last = _sample(out["logits"][:, -1], rng, temperature, top_k)
        return last, mutated["cache"]

    return _cache_put(key, prefill)


def generate_dispatched(dispatched, input_ids, **kwargs):
    """generate() over a DispatchedModel: uses its placed (possibly
    offloaded / quantized) params, its streaming-enabled definition, and its
    in-graph placement transform."""
    params = dispatched._concrete(dispatched.params)
    # param_placer() is cached per placement state on the model, so repeat
    # calls hit the jitted loops while materialize()/offload() (which change
    # the device_map) naturally key a fresh placer + compile
    return generate(
        dispatched.definition, params, input_ids,
        param_placer=dispatched.param_placer(), **kwargs
    )


def _seq2seq_prefill_for(definition, temperature, top_k, placer):
    key = ("s2s_prefill", id(definition), temperature, top_k, id(placer))
    if key in _LOOP_CACHE:
        return _LOOP_CACHE[key]

    @jax.jit
    def prefill(params, input_ids, attention_mask, start_ids, rng):
        params = placer(params)
        enc = definition.apply({"params": params}, input_ids, attention_mask,
                               method="encode")
        logits, mutated = definition.apply(
            {"params": params},
            start_ids,
            encoder_states=enc,
            attention_mask=attention_mask,
            use_cache=True,
            mutable=["cache"],
            method="decode",
        )
        last = _sample(logits[:, -1], rng, temperature, top_k)
        return last, mutated["cache"]

    return _cache_put(key, prefill)


def _seq2seq_loop_for(definition, max_new_tokens, temperature, top_k, placer):
    key = ("s2s_loop", id(definition), max_new_tokens, temperature, top_k, id(placer))
    if key in _LOOP_CACHE:
        return _LOOP_CACHE[key]

    @jax.jit
    def loop(params, cache, last_token, start_pos, rng):
        def step(carry, _):
            cache, tok, pos, rng = carry
            rng, sub = jax.random.split(rng)
            p = placer(params)
            # encoder K/V were frozen in the cache at prefill: no
            # encoder_states needed, each step pays only the one-token
            # self-attn append + cross-attn read
            logits, mutated = definition.apply(
                {"params": p, "cache": cache},
                tok[:, None],
                positions=pos[None],
                use_cache=True,
                decode_step=True,
                mutable=["cache"],
                method="decode",
            )
            nxt = _sample(logits[:, -1], sub, temperature, top_k)
            return (mutated["cache"], nxt, pos + 1, rng), nxt

        (cache, _, _, _), tokens = jax.lax.scan(
            step, (cache, last_token, start_pos, rng), None, length=max_new_tokens
        )
        return tokens.T

    return _cache_put(key, loop)


def generate_seq2seq(
    definition,
    params,
    input_ids,
    *,
    max_new_tokens: int = 32,
    attention_mask=None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    param_placer=None,
):
    """Encoder-decoder generation (models/seq2seq.Seq2SeqLM): encode the
    source once, then a single jitted ``lax.scan`` emits target tokens
    against the self-attn KV cache + the frozen cross-attn encoder K/V
    (reference T5 generation capability, megatron_lm.py:840-877).
    Returns [B, max_new_tokens] generated ids (without the start token).
    ``param_placer`` is an in-graph transform applied to params inside the
    jits (dispatch placement / dequantization); defaults to
    dequantize-only, so QuantizedWeight trees work out of the box."""
    from .utils.compile_cache import ensure_persistent_compile_cache

    ensure_persistent_compile_cache()
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if param_placer is None:
        from .utils.quantization import dequantize_params as param_placer  # noqa: F811
    input_ids = jnp.asarray(input_ids)
    b = input_ids.shape[0]
    cfg = definition.config
    if input_ids.shape[1] > cfg.max_seq_len:
        raise ValueError(
            f"source length {input_ids.shape[1]} exceeds config.max_seq_len={cfg.max_seq_len}"
        )
    cap = cfg.max_cache_len or cfg.max_target_len
    # slots written: the start token at prefill + max_new_tokens-1 decode
    # appends (the final sampled token is returned, never fed back)
    if max_new_tokens > cap:
        raise ValueError(
            f"max_new_tokens ({max_new_tokens}) exceeds the decoder KV "
            f"cache capacity ({cap}); raise config.max_cache_len"
        )
    if attention_mask is not None:
        attention_mask = jnp.asarray(attention_mask)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    prefill_rng, decode_rng = jax.random.split(rng)

    start_ids = jnp.full((b, 1), cfg.decoder_start_token_id, jnp.int32)
    prefill = _seq2seq_prefill_for(definition, temperature, top_k, param_placer)
    last, cache = prefill(params, input_ids, attention_mask, start_ids, prefill_rng)
    loop = _seq2seq_loop_for(definition, max_new_tokens - 1, temperature, top_k, param_placer)
    tokens = loop(params, cache, last, jnp.asarray(1, jnp.int32), decode_rng)
    return jnp.concatenate([last[:, None], tokens], axis=1)


def generate_seq2seq_dispatched(dispatched, input_ids, **kwargs):
    """generate_seq2seq() over a DispatchedModel wrapping a Seq2SeqLM: uses
    its placed (possibly offloaded / quantized) params and its in-graph
    placement transform — the seq2seq counterpart of generate_dispatched."""
    params = dispatched._concrete(dispatched.params)
    return generate_seq2seq(
        dispatched.definition, params, input_ids,
        param_placer=dispatched.param_placer(), **kwargs
    )
