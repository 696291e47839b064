"""LLaMA-family causal decoder, written mesh-first.

Every parameter carries logical axis names (`nn.with_logical_partitioning`)
that `parallel/sharding.py` maps onto the device mesh — TP shards heads/mlp
over "tensor", ZeRO shards embed over "fsdp", and activations are pinned
with sharding constraints so GSPMD propagates the layout instead of
guessing. Blocks optionally roll into one `lax.scan` (O(1) compile time in
depth) with `jax.checkpoint` remat per block (the activation-checkpointing
analog of reference accelerator.py:1485-1499).

The reference has no in-repo model code (it wraps user torch models); this
file is the "what users actually run" counterpart to its GPT/BERT example
targets (reference examples/nlp_example.py, benchmarks/big_model_inference).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..ops.attention import dot_product_attention, mha_reference
from ..ops.layers import (
    apply_rotary_embedding,
    rms_norm,
    rotary_embedding_tables,
    swiglu,
    yarn_inv_freq,
    yarn_mscale,
)
from ..ops.losses import fused_linear_cross_entropy
from ..parallel.sharding import DEFAULT_AXIS_RULES, ROWS_OVER_TENSOR_RULES, logical_to_spec
from .configs import MOE_LOAD_COLLECTION as MOE_LOAD
from .configs import DecoderConfig


def _constrain(x, names, mesh: Optional[Mesh], rules=DEFAULT_AXIS_RULES):
    """Pin an activation's sharding (lives in parallel/sharding.py; this
    alias is the intra-package spelling used by the model files)."""
    from ..parallel.sharding import constrain_activation

    return constrain_activation(x, names, mesh, rules)


def _constrain_stream(x, mesh: Optional[Mesh], exchange: int):
    """The residual stream's constraint: [batch, seq, embed] with the batch
    over the data axes, and the rows over "tensor" where the block's products
    exchange them (``exchange``: parallel/context.tp_exchange_size)."""
    return _constrain(x, ("batch", "seq", "embed"), mesh, ROWS_OVER_TENSOR_RULES if exchange else DEFAULT_AXIS_RULES)


def _rotary_tables(positions, cfg, dtype):
    """(sin, cos) of a config's rotated width, or (None, None) where its
    layers do not rotate (a mixer with a recurrent state; attention with ``rope_dim`` 0)."""
    if getattr(cfg, "has_state", False) or not cfg.rotary_dim:
        return None, None
    if getattr(cfg, "kv_lora_rank", None) is not None:
        # latent attention rotates qk_rope_head_dim dimensions, under YaRN
        # where the config stretches the context (ops/layers.yarn_inv_freq)
        inv_freq, table_scale = None, 1.0
        if cfg.rope_yarn is not None:
            factor, original, fast, slow, mscale, mscale_all = cfg.rope_yarn
            inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, factor, int(original), fast, slow)
            table_scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
        return rotary_embedding_tables(positions, cfg.qk_rope_head_dim, theta=cfg.rope_theta, dtype=dtype,
                                       inv_freq=inv_freq, table_scale=table_scale)
    return rotary_embedding_tables(positions, cfg.rotary_dim, theta=cfg.rope_theta, dtype=dtype)


def _norm(x, weight, cfg):
    """The config's RMS norm (``norm_unit_offset``: scale ``1 + weight``)."""
    return rms_norm(x, weight, cfg.norm_eps, unit_offset=cfg.norm_unit_offset)


def _norm_init(cfg):
    return nn.initializers.zeros if cfg.norm_unit_offset else nn.initializers.ones


def _dense_init(scale: float = 1.0):
    return nn.initializers.variance_scaling(scale, "fan_in", "normal")


def _embed_lookup(embedding, input_ids, cfg, mesh):
    """Token embedding, shared by ``__call__`` and the 1f1b builder so the
    two schedules can never drift. With a sharded mesh, ``take`` lowers to a
    gather the SPMD partitioner can only reshard by full rematerialization
    (replicate-then-repartition — the round-1 dryrun warning). The one-hot
    matmul form partitions cleanly: vocab-sharded embedding x one-hot
    contracts over vocab with a psum, every other axis propagates, and the
    MXU eats the matmul."""
    if mesh is not None and any(
        mesh.shape.get(a, 1) > 1 for a in ("tensor", "fsdp", "sequence", "stage")
    ):
        one_hot = jax.nn.one_hot(input_ids, cfg.vocab_size, dtype=cfg.dtype)
        x = one_hot @ embedding.astype(cfg.dtype)
    else:
        x = jnp.take(embedding, input_ids, axis=0).astype(cfg.dtype)
    return _constrain(x, ("batch", "seq", "embed"), mesh)


def _tied_vocab_kernel(embedding, lm_head, cfg):
    """[E, V] LM-head kernel (the transpose of the embedding when tied)."""
    if cfg.tie_embeddings:
        return embedding.T.astype(cfg.dtype)
    return lm_head.astype(cfg.dtype)


def _head_ce_loss(x, ln_f, embedding, lm_head, labels, cfg, mesh, weight=None):
    """Final-norm + LM-head + fused CE, shared by ``__call__``'s labels path
    and the 1f1b builder. HF convention: labels == input_ids, shifted
    internally so position i predicts token i+1; mean over non-ignored
    tokens. ``weight`` rescales the mean (the 1f1b schedule passes each
    microbatch's valid-token share so the sum over microbatches equals the
    GLOBAL token mean even with uneven -100 padding)."""
    x = _norm(x, ln_f, cfg)
    x = _constrain(x, ("batch", "seq", "embed"), mesh)
    vocab_kernel = _tied_vocab_kernel(embedding, lm_head, cfg)
    if cfg.num_pred_heads > 1:  # trained on the next token: block 0
        vocab_kernel = vocab_kernel[:, :cfg.vocab_size]
    b, s = x.shape[0], x.shape[1]
    hidden = x[:, :-1].reshape(b * (s - 1), cfg.embed_dim)
    targets = labels[:, 1:].reshape(b * (s - 1))
    loss = fused_linear_cross_entropy(
        hidden, vocab_kernel, targets,
        ignore_index=-100, num_chunks=cfg.fused_ce_chunks,
    )
    return loss if weight is None else loss * weight


def _adapt_microbatches(b: int, configured: int, num_stages: int) -> int:
    """Largest M <= configured dividing batch b. M only affects the schedule
    (params are per-stage, not per-M), so odd batches (init's batch_size=1,
    ragged eval) still trace; warn when degrading a real batch."""
    m = configured
    while b % m != 0:
        m -= 1
    if m != configured and b > 1:
        import logging

        logging.getLogger(__name__).warning(
            "pipeline: batch %d is not divisible by the configured "
            "%d microbatches; running with M=%d — at M < num_stages "
            "(%d) the pipeline bubble dominates. Pick a batch size "
            "divisible by pipeline_microbatches.",
            b, configured, m, num_stages,
        )
    return m


def _stream_params_to_device(tree):
    """In-graph host->HBM transfer of a param subtree. Inside a scan body
    this runs on the per-layer *slice*, so only the live layer's weights
    occupy HBM (the per-layer-streaming capability of reference
    hooks.py:323-390); on already-device-resident params it is a no-op."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, jax.memory.Space.Device), tree
    )


def _maybe_streaming(body, cfg):
    if cfg.stream_layer_weights:
        return nn.map_variables(body, "params", trans_in_fn=_stream_params_to_device)
    return body


def _remat_policy(cfg):
    """jax.checkpoint policy for the block remat. "save_attention" keeps the
    flash kernel's named residuals (ops/attention.py checkpoint_name) so the
    backward pass reuses out/lse instead of re-running the kernel — the
    dominant recompute term at long context. "save_dots" additionally keeps
    every matmul output (dots_with_no_batch_dims_saveable): the backward
    recomputes only elementwise ops — more HBM than save_attention, fewer
    recomputed FLOPs; the right trade when activations fit."""
    policy = getattr(cfg, "remat_policy", "full")
    if policy == "save_attention":
        return jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse")
    if policy == "save_dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


class DecoderAttention(nn.Module):
    """``use_cache`` turns on the KV cache (a mutable "cache" collection):
    the prefill pass (decode=False) writes the prompt's K/V at [0:s] and
    attends causally on the flash path; each decode step (decode=True, s==1)
    appends at the running index and attends against the cache prefix. The
    cache is [B, KVH, max_cache_len, D] — static shapes, so the whole decode
    loop compiles once.

    ``cache_positions`` ([B] or [B, S] int32, decode-only, a paged cache
    only: ``config.kv_page_size`` with a ``page_table``) switches the
    cache to slot-arena semantics (``serving/``): each batch row is an
    independent request whose new K/V lands at its OWN offset(s) and whose
    attention sees only its own prefix — admission/eviction become pure
    data changes with no shape change and no recompile. In the [B, S] form
    S tokens per slot land at per-token positions and each query attends
    ``<= its own position`` (token i sees tokens 0..i written in the same
    call — exactly the incremental-decode semantics, batched; what a
    verify step over a model's own drafts would run, ROADMAP R12).

    ``page_table`` ([B, P] int32, with ``config.kv_page_size`` /
    ``kv_num_pages`` set) switches the cache storage to physical pages
    (``serving/pages.py``): leaves are [num_pages, KVH, page_size, D], the
    scatter routes each position through its slot's table entry, and the
    read (``ops/attention.paged_decode_attention``) walks only the slot's
    LIVE pages via the pallas decode kernel on TPU — HBM traffic per step
    is live tokens, not the arena reservation — falling back to the
    gather + masked-dense reference elsewhere (``config.decode_kernel``).
    Sharing one physical page across slots'
    tables is copy-on-write prefix sharing; the serving engine forks
    pages before divergent writes. ``kv_lengths`` ([B] int32, optional)
    is each slot's count of live tokens, the bound of that kernel's walk
    (absent: the last row position + 1); the serving engine passes 0 for
    an inactive slot, whose parked write position would otherwise read as
    a request at the end of the cache. ``cache_layer`` (the scanned stack's
    layer counter, :func:`arena_in_place`): the cache leaves are then the
    layers' stacks and the call's kernel writes this layer's new rows
    itself, a decode step's row a slot or a pack's rows.

    ``config.kv_cache_dtype`` ("int8"/"int4") makes the cache STORAGE
    quantized on both layouts: writes quantize the fresh K/V rows (one
    fp32 scale per token per kv head, kept in a parallel
    ``cached_key_scale``/``cached_value_scale`` arena) fused into the same
    scatter, reads dequantize in-register inside the pallas decode kernels
    or via the reference dequant on the masked-dense path. Because a
    write only ever quantizes the values it writes, page shares, CoW
    forks, preemption page-outs and prefix-cache hits move the quantized
    payload + scales verbatim — nothing is ever re-quantized.

    ``ragged_slots`` + ``slot_hist`` (with a paged cache) switch the call
    to the packed ragged PREFILL form: batch row 0's sequence axis packs
    every pending admission's tail — row r is token ``cache_positions[0,
    r]`` of slot ``ragged_slots[r]`` (-1 = token-block padding) — and the
    flash prefill kernel (``ops/attention.ragged_prefill_attention``,
    ``config.prefill_kernel``) attends each row
    against its slot's live arena prefix plus the packed fresh rows. On
    the carried stack (``cache_layer``) the kernel writes the rows into
    the slots' pages itself; else quantize-on-write is fused so the
    page-table scatter lands the kernel's payload+scales directly. One
    dispatch carries every pending tail; padding is token-block granularity.

    ``causal=False`` (+ optional ``kv_mask``) is the bidirectional form the
    seq2seq encoder reuses (models/seq2seq.py) — same projections, RoPE and
    logical axes, no cache. Ring attention over a "sequence" mesh axis is
    causal-only; masked/bidirectional inputs fall back to GSPMD-partitioned
    flash attention."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False
    causal: bool = True

    @nn.compact
    def __call__(self, x, sin, cos, deterministic: bool = True, kv_mask=None,
                 cache_positions=None, page_table=None, ragged_slots=None,
                 slot_hist=None, kv_lengths=None, cache_layer=None):
        cfg = self.config
        e, h, kv, d = cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        # a layer kind's own value width, window, sink and value scale
        # (getattr: Seq2SeqConfig reuses this module and has none of them)
        dv = getattr(cfg, "value_dim", d)
        window = getattr(cfg, "attn_window", None)
        value_scale = float(getattr(cfg, "attn_value_scale", 1.0))
        b, s = x.shape[0], x.shape[1]
        wq = self.param("wq", nn.with_logical_partitioning(_dense_init(), ("embed", "heads", "head_dim")), (e, h, d))
        wk = self.param("wk", nn.with_logical_partitioning(_dense_init(), ("embed", "kv_heads", "head_dim")), (e, kv, d))
        wv = self.param("wv", nn.with_logical_partitioning(_dense_init(), ("embed", "kv_heads", "head_dim")), (e, kv, dv))
        wo = self.param("wo", nn.with_logical_partitioning(_dense_init(), ("heads", "head_dim", "embed")), (h, dv, e))
        sink = None
        if getattr(cfg, "attn_sink", False):
            sink = self.param(
                "sink", nn.with_logical_partitioning(nn.initializers.zeros, ("heads",)),
                (h,), jnp.float32)
        # what the kernels and references take beyond q, k, v; empty for a
        # plain layer, whose calls are then the ones of before
        extras = {}
        if window is not None or sink is not None or value_scale != 1.0:
            extras = {"window": window, "sink": sink, "value_scale": value_scale}
        plain = not extras and dv == d
        # EVA attention (ops/eva.py): the cache's positions and lengths are
        # counted in entries, [summaries of closed windows][open window]
        eva = getattr(cfg, "eva_window", None) is not None
        if eva:
            from ..ops.eva import entry_index

            ew, ec = cfg.eva_window, cfg.eva_chunk
            mu = self.param(
                "eva_mu", nn.with_logical_partitioning(nn.initializers.normal(1.0), ("kv_heads", "head_dim")),
                (kv, d), jnp.float32)
            phi = self.param(
                "eva_phi", nn.with_logical_partitioning(nn.initializers.normal(1.0), ("kv_heads", "head_dim")),
                (kv, d), jnp.float32)
            entries = lambda pos: entry_index(pos, ew, ec)
            plain = False

        dt = cfg.dtype
        from ..parallel.context import einsum_scatter, gather_einsum, tp_exchange_size

        # the "tensor" axis's size where x's rows are sharded over it and the
        # products exchange them while they multiply, else 0
        exchange = tp_exchange_size(self.mesh, s, (h, kv), use_cache=self.use_cache,
                                    use_fp8=getattr(cfg, "use_fp8", False))
        if getattr(cfg, "use_fp8", False):
            # TE parity: QKV through the fp8 recipe (ops/fp8.fp8_attn_proj)
            from ..ops.fp8 import fp8_attn_proj

            q = fp8_attn_proj(self, "wq_fp8", x, wq.astype(dt), h, d, cfg)
            k = fp8_attn_proj(self, "wk_fp8", x, wk.astype(dt), kv, d, cfg)
            v = fp8_attn_proj(self, "wv_fp8", x, wv.astype(dt), kv, d, cfg)
        elif exchange:
            q, k, v = gather_einsum("bse,ehd->bhsd", x, (wq.astype(dt), wk.astype(dt), wv.astype(dt)),
                                    self.mesh, shard="h")
        else:
            q = jnp.einsum("bse,ehd->bhsd", x, wq.astype(dt))
            k = jnp.einsum("bse,ehd->bhsd", x, wk.astype(dt))
            v = jnp.einsum("bse,ehd->bhsd", x, wv.astype(dt))
        q = _constrain(q, ("batch", "heads", "seq", "head_dim"), self.mesh)
        k = _constrain(k, ("batch", "kv_heads", "seq", "head_dim"), self.mesh)
        if getattr(cfg, "attn_qk_norm", False):
            # an RMS norm over each head's width, one scale for the queries and one for the keys, before the rotation
            q, k = (_norm(t, self.param(name, nn.with_logical_partitioning(_norm_init(cfg), ("norm",)), (d,)), cfg)
                    for t, name in ((q, "q_norm"), (k, "k_norm")))
        gate = None
        if getattr(cfg, "attn_output_gate", False):
            # the doubled query projection's second half: a sigmoid gate on the attention's result
            wg = self.param("wg", nn.with_logical_partitioning(_dense_init(), ("embed", "heads", "head_dim")), (e, h, d))
            gate = jnp.einsum("bse,ehd->bhsd", x, wg.astype(dt))
        if sin is not None:  # None: a kind without rotation (rope_dim 0)
            q = apply_rotary_embedding(q, sin, cos)
            k = apply_rotary_embedding(k, sin, cos)

        if self.use_cache:
            # getattr: Seq2SeqConfig reuses this module and has no paging
            # (or KV-precision) knobs
            paged = getattr(cfg, "kv_page_size", None) is not None
            max_len = cfg.max_cache_len or cfg.max_seq_len
            # quantized KV storage (config.kv_cache_dtype): payloads are
            # int8 (int4 packs two head_dim values per byte) with a small
            # parallel fp32 scale arena — one symmetric scale per (token,
            # kv head), computed at the WRITE from the fresh K/V values, so
            # no write ever re-quantizes existing cache content. Scale
            # leaves keep the payloads' rank (trailing dim 1), so every
            # generic cache-tree op (slot views, page gathers/scatters,
            # CoW forks) moves payload and scale together untouched.
            kvq_bits = {"int8": 8, "int4": 4}.get(
                getattr(cfg, "kv_cache_dtype", "bf16"), 0
            )
            pd = d // 2 if kvq_bits == 4 else d
            pdv = dv // 2 if kvq_bits == 4 else dv
            store_dt = jnp.int8 if kvq_bits else k.dtype
            cached_ks = cached_vs = None
            if paged:
                from ..ops.attention import paged_key_lanes

                if not kvq_bits and paged_key_lanes(d) != d:
                    # key pages as Mosaic takes them: zero lanes up to the
                    # next 128-multiple, on the queries too, so the scores
                    # are the same numbers (ops/attention.paged_key_lanes)
                    pd = paged_key_lanes(d)
                    pad = ((0, 0), (0, 0), (0, 0), (0, pd - d))
                    q, k = jnp.pad(q, pad), jnp.pad(k, pad)
                    extras = dict(extras, sm_scale=d ** -0.5)
                page_shape = (cfg.kv_num_pages, kv, cfg.kv_page_size)
                cached_k = self.variable(
                    "cache", "cached_key", jnp.zeros, page_shape + (pd,), store_dt)
                cached_v = self.variable(
                    "cache", "cached_value", jnp.zeros, page_shape + (pdv,), store_dt)
                if kvq_bits:
                    cached_ks = self.variable(
                        "cache", "cached_key_scale", jnp.zeros,
                        page_shape + (1,), jnp.float32)
                    cached_vs = self.variable(
                        "cache", "cached_value_scale", jnp.zeros,
                        page_shape + (1,), jnp.float32)
            else:
                cached_k = self.variable("cache", "cached_key", jnp.zeros, (b, kv, max_len, pd), store_dt)
                cached_v = self.variable("cache", "cached_value", jnp.zeros, (b, kv, max_len, pdv), store_dt)
                if kvq_bits:
                    cached_ks = self.variable(
                        "cache", "cached_key_scale", jnp.zeros,
                        (b, kv, max_len, 1), jnp.float32)
                    cached_vs = self.variable(
                        "cache", "cached_value_scale", jnp.zeros,
                        (b, kv, max_len, 1), jnp.float32)
            cache_index = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            cur = cache_index.value
            if paged and (not self.decode or cache_positions is None or page_table is None):
                raise NotImplementedError(
                    "a paged KV cache (config.kv_page_size) supports only "
                    "slot-arena decode (decode=True with cache_positions "
                    "and page_table); prefill runs as the packed "
                    "ragged dispatch (ragged_slots/slot_hist)"
                )
            if cache_positions is not None and not paged:
                raise NotImplementedError(
                    "cache_positions (slot-arena decode) requires the paged "
                    "KV arena (config.kv_page_size with a page_table); a "
                    "dense cache decodes one stream at its scalar cache_index"
                )
            if eva and (not paged or (self.decode and cache_positions is not None
                                      and ragged_slots is None and s != 1)):
                raise NotImplementedError(
                    "EVA attention keeps its cache in pages (config.kv_page_size): one new "
                    "token a slot in a decode step, or the packed ragged prefill")
            if not self.decode:
                # prefill: cache starts at 0, so plain causal attention over
                # the freshly computed K/V stays on the flash-kernel path.
                # Quantized: store payload+scale and attend over the
                # DEQUANTIZED values — the stored cache is the source of
                # truth, so whole-prompt prefill stays token-identical to
                # the engine's packed prefill (which reads the cache back).
                if kvq_bits:
                    from ..utils.quantization import dequantize_kv, quantize_kv

                    k_q, k_s = quantize_kv(k, kvq_bits)
                    v_q, v_s = quantize_kv(v, kvq_bits)
                    cached_k.value = jax.lax.dynamic_update_slice(cached_k.value, k_q, (0, 0, 0, 0))
                    cached_v.value = jax.lax.dynamic_update_slice(cached_v.value, v_q, (0, 0, 0, 0))
                    cached_ks.value = jax.lax.dynamic_update_slice(cached_ks.value, k_s, (0, 0, 0, 0))
                    cached_vs.value = jax.lax.dynamic_update_slice(cached_vs.value, v_s, (0, 0, 0, 0))
                    k = dequantize_kv(k_q, k_s, kvq_bits, q.dtype)
                    v = dequantize_kv(v_q, v_s, kvq_bits, q.dtype)
                else:
                    cached_k.value = jax.lax.dynamic_update_slice(cached_k.value, k, (0, 0, 0, 0))
                    cached_v.value = jax.lax.dynamic_update_slice(cached_v.value, v, (0, 0, 0, 0))
                cache_index.value = jnp.asarray(s, jnp.int32)
                if plain:
                    out = dot_product_attention(q, k, v, causal=True, impl=cfg.attention_impl)
                else:
                    out = mha_reference(q, k, v, causal=True, **extras)
            elif ragged_slots is not None:
                # packed ragged prefill over the paged arena (serving/):
                # the batch axis is ONE packed dispatch of every pending
                # admission tail — row r of the sequence axis is token
                # position cache_positions[0, r] of slot ragged_slots[r]
                # (-1 rows are token-block padding). The flash prefill
                # kernel (ops/attention.ragged_prefill_attention) attends
                # each row against its slot's live arena prefix
                # (slot_hist, prefix-aware block skipping) plus the packed
                # fresh rows at <= its own position. The rows reach the
                # pages inside the kernel where the stack is carried
                # (cache_layer); else quantize-on-write is fused: the kernel
                # emits payload+scales which the scatter below lands
                # through the page table in the same program — no separate
                # quantize pass, no bucket padding.
                if not paged:
                    raise NotImplementedError(
                        "ragged_slots (packed ragged prefill) requires the "
                        "paged KV arena (config.kv_page_size)"
                    )
                if b != 1:
                    raise ValueError(
                        f"packed ragged prefill packs all tails into one "
                        f"batch row; got batch {b}"
                    )
                from ..ops.attention import ragged_prefill_attention

                row_pos = (
                    cache_positions[0]
                    if cache_positions.ndim == 2 else cache_positions
                )
                true_pos = row_pos
                if eva:
                    # a slot's rows of one pack lie in one window (the
                    # engine's plan), so their entries are consecutive too
                    row_pos, slot_hist = entries(row_pos), entries(slot_hist)
                ps = cfg.kv_page_size
                valid = (ragged_slots >= 0) & (row_pos >= 0)
                pk_impl = getattr(cfg, "prefill_kernel", None)
                attend = functools.partial(
                    ragged_prefill_attention, q, k, v, cached_k.value, cached_v.value,
                    page_table=page_table, row_slot=ragged_slots,
                    row_pos=row_pos, slot_hist=slot_hist, impl=pk_impl,
                    token_block=getattr(cfg, "prefill_kernel_block", None), **extras)
                if cache_layer is not None:
                    # the arena in place (arena_in_place): the cache leaves
                    # are the layers' stacks, carried through the scan, and
                    # the kernel writes the pack's rows into this layer's
                    # pages itself, through the table
                    out, cached_k.value, cached_v.value = attend(layer=cache_layer)
                else:
                    scale_kw = {}
                    if kvq_bits:
                        scale_kw = {"k_scale": cached_ks.value,
                                    "v_scale": cached_vs.value,
                                    "kv_quant_bits": kvq_bits}
                    out, k_pay, k_scl, v_pay, v_scl = attend(**scale_kw)
                    # fused scatter through the page table. Pad rows (-1) route
                    # to physical page 0 — the arena's reserved parking page —
                    # so the scatter stays a fixed-shape data move with no
                    # masking branch; parking content is never attended.
                    srow = jnp.maximum(ragged_slots, 0)
                    spos = jnp.maximum(row_pos, 0)
                    page = jnp.where(valid, page_table[srow, spos // ps], 0)
                    off = spos % ps
                    cached_k.value = cached_k.value.at[page, :, off].set(k_pay)
                    cached_v.value = cached_v.value.at[page, :, off].set(v_pay)
                    if kvq_bits:
                        cached_ks.value = cached_ks.value.at[page, :, off].set(k_scl)
                        cached_vs.value = cached_vs.value.at[page, :, off].set(v_scl)
                if eva:
                    # the pages this pack has filled, each pooled into its
                    # entry of the open window's summaries, after the rows'
                    # write: by the kernel on the carried stack, as a decode
                    # step pools, else a gather, the pooling and a second
                    # scatter of XLA's on the layer's pages
                    from ..ops.eva import eva_pool_pages, eva_pool_reference, pool_plan

                    plan = pool_plan(true_pos, valid, ragged_slots, page_table,
                                     window=ew, chunk=ec, size=max(1, s // ec))
                    if cache_layer is not None:
                        cached_k.value, cached_v.value = eva_pool_pages(
                            cached_k.value, cached_v.value, mu, phi, *plan, sm_scale=d ** -0.5,
                            layer=cache_layer, interpret=pk_impl == "interpret")
                    else:
                        cached_k.value, cached_v.value = eva_pool_reference(
                            cached_k.value, cached_v.value, mu, phi, *plan, d ** -0.5)
            elif cache_positions is not None:
                # slot-arena decode (serving/): every batch row writes its
                # new K/V at its own per-slot offset(s) and attends only
                # its own prefix. Stale entries past a slot's frontier
                # (the previous occupant's) are always overwritten at the
                # write position BEFORE being attended, so slot reuse
                # needs no cache clearing.
                pos2d = (
                    cache_positions[:, None]
                    if cache_positions.ndim == 1 else cache_positions
                )
                if pos2d.shape[1] != s:
                    raise ValueError(
                        f"cache_positions covers {pos2d.shape[1]} positions "
                        f"per slot but {s} tokens were fed"
                    )
                if eva:
                    true_pos, pos2d = pos2d[:, 0], entries(pos2d)
                    if kv_lengths is not None:
                        kv_lengths = jnp.where(kv_lengths > 0, entries(kv_lengths - 1) + 1, 0)
                rows = jnp.arange(b)
                kv_new = jnp.swapaxes(k, 1, 2)  # [B, S, KVH, D]
                vv_new = jnp.swapaxes(v, 1, 2)
                # quantize-on-write, fused into the cache scatter: only the
                # freshly computed token rows quantize (per-row scale over
                # D), existing cache content is never touched
                ks_new = vs_new = None
                if kvq_bits:
                    from ..utils.quantization import quantize_kv

                    kv_new, ks_new = quantize_kv(kv_new, kvq_bits)
                    vv_new, vs_new = quantize_kv(vv_new, kvq_bits)
                # decode-kernel knobs (ops/attention dispatch): the pallas
                # length-aware kernel on TPU / under "interpret", the
                # masked-dense reference otherwise. getattr: Seq2SeqConfig
                # reuses this module without the decode_kernel fields.
                from ..ops.attention import paged_decode_attention

                dk_impl = getattr(cfg, "decode_kernel", None)
                if cache_layer is not None:
                    # the arena in place (arena_in_place): the cache leaves
                    # are the layers' stacks, carried through the scan, and
                    # the kernel writes this layer's new rows itself
                    out, cached_k.value, cached_v.value = paged_decode_attention(
                        q, cached_k.value, cached_v.value,
                        page_table=page_table, q_positions=pos2d,
                        kv_lengths=kv_lengths, impl=dk_impl,
                        layer=cache_layer, k_new=k, v_new=v, **extras,
                    )
                else:
                    ps = cfg.kv_page_size
                    page = page_table[rows[:, None], pos2d // ps]  # [B, S]
                    off = pos2d % ps
                    k_pages = cached_k.value.at[page, :, off].set(kv_new)
                    v_pages = cached_v.value.at[page, :, off].set(vv_new)
                    cached_k.value = k_pages
                    cached_v.value = v_pages
                    scale_kw = {}
                    if kvq_bits:
                        k_sc = cached_ks.value.at[page, :, off].set(ks_new)
                        v_sc = cached_vs.value.at[page, :, off].set(vs_new)
                        cached_ks.value = k_sc
                        cached_vs.value = v_sc
                        scale_kw = {"k_scale": k_sc, "v_scale": v_sc,
                                    "kv_quant_bits": kvq_bits}
                    out = paged_decode_attention(
                        q, k_pages, v_pages,
                        page_table=page_table, q_positions=pos2d,
                        kv_lengths=kv_lengths, impl=dk_impl, **scale_kw, **extras,
                    )
                if eva:
                    # a slot whose new row filled its page: the page pooled
                    # into its entry of the open window's summaries (an idle
                    # slot, parked at the cache's last position, pools nothing)
                    from ..ops.eva import eva_pool_pages, eva_pool_reference, pool_plan

                    live = jnp.ones((b,), bool) if kv_lengths is None else kv_lengths > 0
                    plan = pool_plan(true_pos, live, rows, page_table, window=ew, chunk=ec, size=b)
                    if cache_layer is not None:  # the arena in place: the kernel, as the row's write
                        cached_k.value, cached_v.value = eva_pool_pages(
                            cached_k.value, cached_v.value, mu, phi, *plan, sm_scale=d ** -0.5,
                            layer=cache_layer, interpret=dk_impl == "interpret")
                    else:
                        cached_k.value, cached_v.value = eva_pool_reference(
                            cached_k.value, cached_v.value, mu, phi, *plan, d ** -0.5)
            else:
                scale_kw = {}
                if kvq_bits:
                    from ..utils.quantization import quantize_kv

                    k, k_s = quantize_kv(k, kvq_bits)
                    v, v_s = quantize_kv(v, kvq_bits)
                    k_sc = jax.lax.dynamic_update_slice(cached_ks.value, k_s, (0, 0, cur, 0))
                    v_sc = jax.lax.dynamic_update_slice(cached_vs.value, v_s, (0, 0, cur, 0))
                    cached_ks.value = k_sc
                    cached_vs.value = v_sc
                    scale_kw = {"k_scale": k_sc, "v_scale": v_sc,
                                "kv_quant_bits": kvq_bits}
                k_full = jax.lax.dynamic_update_slice(cached_k.value, k, (0, 0, cur, 0))
                v_full = jax.lax.dynamic_update_slice(cached_v.value, v, (0, 0, cur, 0))
                cached_k.value = k_full
                cached_v.value = v_full
                cache_index.value = cur + s
                from ..ops.attention import decode_attention

                # query i sits at global position cur+i; valid kv = [0, cur+i].
                # The single-stream decode loop (s == 1): the same kernel
                # dispatch as the slot-arena path, so generation.generate
                # reads live tokens, not the whole right-sized cache, per
                # step.
                out = decode_attention(
                    q, k_full, v_full, q_positions=cur + jnp.arange(s),
                    impl=getattr(cfg, "decode_kernel", None),
                    **scale_kw, **extras,
                )
        elif eva:
            # a forward pass over a whole sequence, no cache (ops/eva.py)
            if not self.causal or kv_mask is not None:
                raise NotImplementedError("EVA attention is causal and takes no key mask")
            from ..ops.eva import eva_attention

            out = eva_attention(q, k, v, mu, phi, window=ew, chunk=ec)
        elif not plain:
            # a layer kind the flash kernel has no form of: the plain
            # reference (forward passes of such a model; it is not trained)
            if not self.causal or kv_mask is not None:
                raise NotImplementedError(
                    "a window, a sink, a value scale or a value width of its own "
                    "needs causal attention without a key mask")
            out = mha_reference(q, k, v, causal=True, **extras)
        elif (
            self.causal
            and kv_mask is None
            and self.mesh is not None
            and self.mesh.shape.get("sequence", 1) > 1
        ):
            from ..parallel.context import ring_attention_sharded

            out = ring_attention_sharded(q, k, v, self.mesh, causal=True)
        else:
            from ..parallel.context import dot_product_attention_sharded

            out = dot_product_attention_sharded(
                q, k, v, self.mesh, causal=self.causal, kv_mask=kv_mask,
                impl=cfg.attention_impl,
            )
        out = _constrain(out, ("batch", "heads", "seq", "head_dim"), self.mesh)
        if gate is not None:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)
        if getattr(cfg, "use_fp8", False):
            from ..ops.fp8 import fp8_attn_out

            out = fp8_attn_out(self, "wo_fp8", out, wo.astype(dt), cfg)
        elif exchange:
            out = einsum_scatter("bhsd,hde->bse", out, wo.astype(dt), self.mesh, shard="h")
        else:
            out = jnp.einsum("bhsd,hde->bse", out, wo.astype(dt))
        return _constrain_stream(out, self.mesh, exchange)


class LatentAttention(nn.Module):
    """Latent attention (MLA; ``config.kv_lora_rank``), causal, in its two
    forms, which give the same numbers.

    ``x`` is the block's normed input. The queries pass a bottleneck with a
    norm inside (``q_lora_rank``): ``c_q = norm(x W_qa)``, ``[q_nope | q_pe]
    = c_q W_qb`` a head, ``q_pe`` rotated. Keys and values come from one
    latent a token: ``[c | k_pe] = x W_kva``, ``c`` normed, ``k_pe`` rotated
    and **one for all heads**; a head's ``[k_nope | v] = c W_kvb``. The score
    of query t and key m is ``s (q_nope . k_nope + q_pe . k_pe)`` with
    ``s = config.attn_sm_scale`` (YaRN's ``mscale^2`` in it).

    **Expanded** (no cache: a forward pass over a whole sequence): keys and
    values of every head are made from the latents and attended as they are,
    192 + 192 a head.

    **Absorbed** (``use_cache``, the serving programs): the cache keeps ``[c
    | k_pe]`` a token a layer, after the norm and the rotation, padded to
    whole lanes (``ops/attention.cache_entry_widths``: 576 -> 640; leaf
    ``cached_latent`` [num_pages, 1, page_size, lanes], the unit axis where
    other kinds have their kv heads: one entry for all heads), and no key
    or value of a head is ever made. ``W_kvb``'s key half is folded into the
    query (``q' = q_nope W_kvb^K^T``, 512 wide), a score is one product of
    ``[q' | q_pe]`` with the entry, the softmax weighs the latents
    themselves (the entry's first 512 lanes), and ``W_kvb``'s value half is
    applied to the result. A decode step is
    ``ops/attention.paged_latent_attention``, a pack
    ``ragged_latent_attention``; both write the new entries into the pages
    themselves where the stack is carried (``cache_layer``,
    :func:`arena_in_place`) and the caller scatters elsewhere. The cache is
    paged only; one new token a slot in a decode step."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False

    @nn.compact
    def __call__(self, x, sin, cos, cache_positions=None, page_table=None,
                 ragged_slots=None, slot_hist=None, kv_lengths=None, cache_layer=None):
        cfg = self.config
        e, h, dt = cfg.embed_dim, cfg.num_heads, cfg.dtype
        r, rq, dv = cfg.kv_lora_rank, cfg.q_lora_rank, cfg.value_dim
        n, p = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        b, s = x.shape[0], x.shape[1]

        def weight(name, axes, shape, init=_dense_init()):
            return self.param(name, nn.with_logical_partitioning(init, axes), shape)

        if rq is None:
            q = jnp.einsum("bse,ehd->bhsd", x, weight("wq", ("embed", "heads", "head_dim"), (e, h, n + p)).astype(dt))
        else:
            wq_a = weight("wq_a", ("embed", None), (e, rq))
            q_norm = weight("q_norm", ("norm",), (rq,), _norm_init(cfg))
            wq_b = weight("wq_b", (None, "heads", "head_dim"), (rq, h, n + p))
            q = jnp.einsum("bsr,rhd->bhsd", _norm(x @ wq_a.astype(dt), q_norm, cfg), wq_b.astype(dt))
        wkv_a = weight("wkv_a", ("embed", None), (e, r + p))
        kv_norm = weight("kv_norm", ("norm",), (r,), _norm_init(cfg))
        wkv_b = weight("wkv_b", (None, "heads", "head_dim"), (r, h, n + dv)).astype(dt)
        wo = weight("wo", ("heads", "head_dim", "embed"), (h, dv, e))
        q = _constrain(q, ("batch", "heads", "seq", "head_dim"), self.mesh)
        q_nope, q_pe = q[..., :n], apply_rotary_embedding(q[..., n:], sin, cos)
        ckv = x @ wkv_a.astype(dt)
        c = _norm(ckv[..., :r], kv_norm, cfg)                                  # [b, s, r]
        k_pe = apply_rotary_embedding(ckv[..., r:][:, None], sin, cos)         # [b, 1, s, p]
        scale = cfg.attn_sm_scale

        if not self.use_cache:
            # expanded: every head's keys and values from the latents
            kv = jnp.einsum("bsr,rhd->bhsd", c, wkv_b)
            k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe, (b, h, s, p))], axis=-1)
            out = mha_reference(jnp.concatenate([q_nope, q_pe], axis=-1), k, kv[..., n:],
                                causal=True, sm_scale=scale)
        else:
            from ..ops.attention import (
                cache_entry_widths,
                paged_latent_attention,
                ragged_latent_attention,
            )

            if cfg.kv_page_size is None or not self.decode or cache_positions is None or page_table is None:
                raise NotImplementedError(
                    "latent attention keeps its cache in pages (config.kv_page_size): one new token "
                    "a slot in a decode step (cache_positions, page_table) or the packed ragged "
                    "prefill (ragged_slots, slot_hist); there is no dense cache of latents")
            _, lanes, _ = cache_entry_widths(cfg)
            ps = cfg.kv_page_size
            cached = self.variable("cache", "cached_latent", jnp.zeros,
                                   (cfg.kv_num_pages, 1, ps, lanes), dt)
            # absorbed: the keys' up-projection into the query, both in the
            # entry's layout [latent | rotated key | zero lanes]
            zeros = lambda *lead: jnp.zeros(lead + (lanes - r - p,), dt)
            q_lat = jnp.concatenate(
                [jnp.einsum("bhsn,rhn->bhsr", q_nope, wkv_b[..., :n]), q_pe, zeros(b, h, s)], axis=-1)
            entry = jnp.concatenate([c[:, None], k_pe, zeros(b, 1, s)], axis=-1)   # [b, 1, s, lanes]
            if ragged_slots is not None:
                if b != 1:
                    raise ValueError(f"packed ragged prefill packs all tails into one batch row; got batch {b}")
                row_pos = cache_positions[0] if cache_positions.ndim == 2 else cache_positions
                attend = functools.partial(
                    ragged_latent_attention, q_lat, entry, cached.value, page_table=page_table,
                    row_slot=ragged_slots, row_pos=row_pos, slot_hist=slot_hist, latent=r,
                    sm_scale=scale, impl=cfg.prefill_kernel, token_block=cfg.prefill_kernel_block)
                if cache_layer is not None:
                    o_lat, cached.value = attend(layer=cache_layer)
                else:
                    o_lat, payload = attend()
                    valid = (ragged_slots >= 0) & (row_pos >= 0)
                    srow, spos = jnp.maximum(ragged_slots, 0), jnp.maximum(row_pos, 0)
                    page = jnp.where(valid, page_table[srow, spos // ps], 0)  # pads to the parking page
                    cached.value = cached.value.at[page, :, spos % ps].set(payload)
            else:
                pos2d = cache_positions[:, None] if cache_positions.ndim == 1 else cache_positions
                if pos2d.shape[1] != s or s != 1:
                    raise NotImplementedError(
                        f"a latent decode step takes one new token a slot; got {s} tokens "
                        f"for {pos2d.shape[1]} positions")
                attend = functools.partial(
                    paged_latent_attention, q_lat, page_table=page_table, q_positions=pos2d,
                    latent=r, sm_scale=scale, kv_lengths=kv_lengths, impl=cfg.decode_kernel)
                if cache_layer is not None:
                    o_lat, cached.value = attend(cached.value, layer=cache_layer, new=entry)
                else:
                    page = page_table[jnp.arange(b)[:, None], pos2d // ps]
                    cached.value = cached.value.at[page, :, pos2d % ps].set(jnp.swapaxes(entry, 1, 2))
                    o_lat = attend(cached.value)
            # the values' up-projection, after the softmax
            out = jnp.einsum("bhsr,rhd->bhsd", o_lat, wkv_b[..., n:])
        out = _constrain(out, ("batch", "heads", "seq", "head_dim"), self.mesh)
        out = jnp.einsum("bhsd,hde->bse", out, wo.astype(dt))
        return _constrain(out, ("batch", "seq", "embed"), self.mesh)


def arena_in_place(config, sq: int = 1, packed: bool = False) -> bool:
    """Does a paged serving program update the arena in place on a model
    with this config: a decode step of ``sq`` new tokens a slot, or
    (``packed``) the packed ragged prefill? Then the scanned stack carries
    the "cache" collection whole, ``[L, num_pages, KVH, page, D]`` a leaf,
    and the program's kernel takes the stack and a layer index and writes
    the new rows itself (``ops/attention.paged_decode_attention``: each
    slot's row; ``ragged_prefill_attention``: the pack's rows, page by page
    through the table): no layer's pages are sliced out of the stack,
    re-laid out for a scatter or put back. It takes what the kernel's write
    takes: the scanned stack, unquantized pages, and the kernel engaged: a
    decode step's (``decode_kernel_active``: on the chip or interpreted,
    128-multiple page widths) with one new token a slot, a pack's
    (``prefill_writes_pages``: likewise). Everything else (several new
    tokens a slot, the dense fallback, a quantized cache, pages of no whole lanes)
    splits the collection along the layers as before. The serving engine's
    ``arena_in_place`` gauges and span counters read this."""
    from ..ops.attention import decode_kernel_active, prefill_writes_pages

    if (not getattr(config, "scan_layers", False)
            or getattr(config, "kv_cache_dtype", "bf16") in ("int8", "int4")):
        return False
    return prefill_writes_pages(config) if packed else sq == 1 and decode_kernel_active(config, sq)


def _run_name(config, i: int) -> str:
    """The scanned stack of run ``i`` of ``config.run_configs()`` in the
    "params" and "cache" collections."""
    return f"layers_{i}" if config.layer_kinds else "layers"


def expert_stacks(config, decode: bool, params) -> dict:
    """For every scanned run of layers with experts, by its name in
    ``params``: the run's stacks ``(w_gate, w_up, w_down)``, each
    ``[L, E, ...]``, where the ``moe_experts`` kernel reads the experts out
    of them (the stack and a layer index ride the layer scan, and no layer's
    experts are sliced out before the call: 805 MB a layer in the MiMo
    cell), else None: the layers read their own slices. It takes the kernel
    engaged (``models/moe.experts_impl``: a serving program on the chip, or
    interpreted), a stack that exists (not at ``init``) and is held on the
    device, and leaves of the layers' dtype (a cast of the stack would be
    the copy again). The serving engine's ``experts_from_stack`` gauge reads
    this."""
    from .moe import experts_impl

    out = {}
    for i, run_cfg in enumerate(config.run_configs()):
        if run_cfg.moe_num_experts <= 1 or run_cfg.mlp_kind == "none":
            continue
        name = _run_name(config, i)
        out[name] = None
        if (not config.scan_layers or config.stream_layer_weights or name not in params
                or experts_impl(run_cfg, decode) == "xla"):
            continue
        moe = params[name]["block"]["moe_mlp"]
        # (two-matrix experts have no gate: the stack's first entry is None)
        stack = tuple(nn.unbox(moe[leaf]) if leaf in moe else None for leaf in ("w_gate", "w_up", "w_down"))
        if all(w is None or w.dtype == run_cfg.dtype for w in stack):
            out[name] = stack
    return out


def _relu2(up):
    return jnp.square(jax.nn.relu(up))


class DecoderMLP(nn.Module):
    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        e, m = cfg.embed_dim, cfg.mlp_dim
        # "relu2": two matrices, relu(x W_up)^2 W_down (the sequence-to-sequence config, which shares this MLP, states none)
        gated = getattr(cfg, "mlp_kind", "swiglu") != "relu2"
        if gated:
            wg = self.param("w_gate", nn.with_logical_partitioning(_dense_init(), ("embed", "mlp")), (e, m))
        wu = self.param("w_up", nn.with_logical_partitioning(_dense_init(), ("embed", "mlp")), (e, m))
        wd = self.param("w_down", nn.with_logical_partitioning(_dense_init(), ("mlp", "embed")), (m, e))
        dt = cfg.dtype
        from ..ops.fp8 import module_fp8_dot
        from ..parallel.context import mlp_exchange, tp_exchange_size

        # as in DecoderAttention: x's rows over "tensor", the products exchange them
        exchange = tp_exchange_size(self.mesh, x.shape[1], (m,), use_cache=self.use_cache,
                                    use_fp8=getattr(cfg, "use_fp8", False))
        if exchange:
            w_in = (wg.astype(dt), wu.astype(dt)) if gated else (wu.astype(dt),)
            down = mlp_exchange(x, w_in, wd.astype(dt), swiglu if gated else _relu2, self.mesh)
        else:
            up = module_fp8_dot(self, "up", x, wu.astype(dt), cfg)
            if gated:
                up = swiglu(module_fp8_dot(self, "gate", x, wg.astype(dt), cfg), up)
            else:
                up = _relu2(up)
            hidden = _constrain(up, ("batch", "seq", "mlp"), self.mesh)
            down = module_fp8_dot(self, "down", hidden, wd.astype(dt), cfg)
        return _constrain_stream(down, self.mesh, exchange)


class DecoderBlock(nn.Module):
    """Returns (x, aux_loss) — aux_loss is the MoE router load-balancing
    term (0.0 for dense MLP blocks). A block is ``x + mixer(norm(x))`` then
    ``x + ffn(norm(x))``; a kind with ``mixer`` "none" or ``mlp_kind`` "none"
    is the other half alone, with its one norm (the nemotron_h family's
    layers: a mixer layer followed by an expert layer is one block of both)."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False

    @nn.compact
    def __call__(self, x, sin, cos, deterministic: bool = True, cache_positions=None,
                 page_table=None, ragged_slots=None, slot_hist=None, kv_lengths=None,
                 token_mask=None, cache_layer=None, expert_stack=None):
        cfg = self.config
        mixer = getattr(cfg, "mixer", "attention")
        if mixer != "none":
            x = self._mix(x, mixer, sin, cos, deterministic, cache_positions, page_table, ragged_slots,
                          slot_hist, kv_lengths, cache_layer)
        if getattr(cfg, "mlp_kind", "swiglu") == "none":
            return x, jnp.float32(0.0)
        ln2 = self.param("ln_mlp", nn.with_logical_partitioning(_norm_init(cfg), ("norm",)), (cfg.embed_dim,))
        y_stream = _norm(x, ln2, cfg)  # in the stream's dtype
        y = y_stream.astype(cfg.dtype)
        if cfg.moe_num_experts > 1:
            from .moe import MoeMLP

            # the router reads the normed stream as it is carried (float32
            # with residual_dtype): its choice is discrete
            y, aux = MoeMLP(cfg, self.mesh, self.decode, name="moe_mlp")(
                y, token_mask, router_input=y_stream, stack=expert_stack)
        else:
            y = DecoderMLP(cfg, self.mesh, self.use_cache, name="mlp")(y)
            aux = jnp.float32(0.0)
        if cfg.dropout_rate > 0.0:
            y = nn.Dropout(cfg.dropout_rate)(y, deterministic=deterministic)
        return x + y.astype(x.dtype), aux

    def _mix(self, x, mixer, sin, cos, deterministic, cache_positions, page_table, ragged_slots,
             slot_hist, kv_lengths, cache_layer):
        """``x + mixer(norm(x))``, the mixer by the kind's ``mixer``."""
        cfg = self.config
        ln1 = self.param("ln_attn", nn.with_logical_partitioning(_norm_init(cfg), ("norm",)), (cfg.embed_dim,))
        # the stream may be carried wider than the activations
        # (config.residual_dtype); the layers' inputs are cfg.dtype either way
        y_stream = _norm(x, ln1, cfg)  # in the stream's dtype
        y = y_stream.astype(cfg.dtype)
        if mixer in ("ssm", "ssd", "gdn"):
            from .ssm import GatedDeltaNet, Mamba2Mixer, SelectiveSSM

            # (the mixer with heads reads the normed stream as it is carried)
            y = {"ssm": SelectiveSSM, "ssd": Mamba2Mixer, "gdn": GatedDeltaNet}[mixer](
                cfg, self.mesh, self.use_cache, self.decode, name="ssm")(
                y if mixer == "ssm" else y_stream, cache_positions=cache_positions, ragged_slots=ragged_slots,
                slot_hist=slot_hist, kv_lengths=kv_lengths, cache_layer=cache_layer)
        elif getattr(cfg, "kv_lora_rank", None) is not None:
            y = LatentAttention(cfg, self.mesh, self.use_cache, self.decode, name="attn")(
                y, sin, cos, cache_positions=cache_positions, page_table=page_table,
                ragged_slots=ragged_slots, slot_hist=slot_hist, kv_lengths=kv_lengths,
                cache_layer=cache_layer)
        else:
            y = DecoderAttention(cfg, self.mesh, self.use_cache, self.decode, name="attn")(
                y, sin, cos, deterministic, cache_positions=cache_positions,
                page_table=page_table, ragged_slots=ragged_slots,
                slot_hist=slot_hist, kv_lengths=kv_lengths, cache_layer=cache_layer,
            )
        if cfg.dropout_rate > 0.0:
            y = nn.Dropout(cfg.dropout_rate)(y, deterministic=deterministic)
        return x + y.astype(x.dtype)


class _ScanBlock(nn.Module):
    """DecoderBlock adapted to lax.scan carry protocol. ``deterministic``
    is a STATIC module attribute, not a carry leaf — in the carry it would
    trace to bool[] and nn.Dropout's python branch cannot take a tracer
    (latent until dropout_rate > 0 met scan_layers)."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, _):
        # cpos/ptab/rslots/shist/klens/tmask ride the carry like sin/cos
        # (broadcast inputs every layer reads unchanged); None when the
        # slot-arena / ragged-prefill paths are off. ``layer`` counts the
        # blocks where the "cache" collection is carried whole
        # (arena_in_place), else None. ``experts``: the run's stacked expert
        # leaves and a count of the blocks likewise (expert_stacks), else None
        x, aux, sin, cos, cpos, ptab, rslots, shist, klens, tmask, layer, experts = carry
        x, block_aux = DecoderBlock(self.config, self.mesh, self.use_cache, self.decode, name="block")(
            x, sin, cos, self.deterministic, cache_positions=cpos, page_table=ptab,
            ragged_slots=rslots, slot_hist=shist, kv_lengths=klens, token_mask=tmask,
            cache_layer=layer, expert_stack=experts,
        )
        if layer is not None:
            layer = layer + 1
        if experts is not None:
            experts = (*experts[:3], experts[3] + 1)
        return (x, aux + block_aux, sin, cos, cpos, ptab, rslots, shist, klens, tmask, layer, experts), None


class StageStack(nn.Module):
    """One pipeline stage: the layer-scan over num_layers/pipeline_stages
    blocks. Used as the stage body of parallel/pipeline.PipelineStages."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, sin, cos, deterministic: bool = True):
        cfg = self.config
        body = _ScanBlock
        if cfg.remat:
            body = nn.remat(body, prevent_cse=False, static_argnums=(), policy=_remat_policy(cfg))
        Stack = nn.scan(
            body,
            variable_axes={"params": 0, "fp8_stats": 0},
            split_rngs={"params": True, "dropout": True},
            length=cfg.num_layers // cfg.pipeline_stages,
            metadata_params={nn.PARTITION_NAME: "layer"},
        )
        (x, aux, *_), _ = Stack(
            cfg, self.mesh, deterministic=deterministic, name="layers"
        )((x, jnp.float32(0.0), sin, cos, None, None, None, None, None, None, None, None), None)
        if cfg.moe_num_experts > 1:
            # per-(stage, microbatch) router load-balance sum over this
            # stage's layers; the schedule accumulates and renormalizes
            return x, aux
        return x


class DecoderLM(nn.Module):
    """Causal LM. __call__(input_ids[, labels]) -> {"logits"|"loss", ...}.

    When ``labels`` is given, logits are never materialized — the fused
    chunked LM-head CE (ops/losses.py) runs instead.
    """

    config: DecoderConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        labels: Optional[jax.Array] = None,
        positions: Optional[jax.Array] = None,
        deterministic: bool = True,
        use_cache: bool = False,
        decode: bool = False,
        cache_positions: Optional[jax.Array] = None,
        page_table: Optional[jax.Array] = None,
        ragged_slots: Optional[jax.Array] = None,
        slot_hist: Optional[jax.Array] = None,
        kv_lengths: Optional[jax.Array] = None,
    ):
        cfg = self.config
        b, s = input_ids.shape
        if cache_positions is not None and not (use_cache and decode):
            raise ValueError(
                "cache_positions (slot-arena decode) requires use_cache=True "
                "and decode=True"
            )
        if page_table is not None and cache_positions is None:
            raise ValueError(
                "page_table (paged slot-arena decode) requires cache_positions"
            )
        if (ragged_slots is not None) != (slot_hist is not None):
            raise ValueError(
                "ragged_slots and slot_hist (packed ragged prefill) must be "
                "set together"
            )
        if ragged_slots is not None and page_table is None:
            raise ValueError(
                "ragged_slots (packed ragged prefill) requires page_table "
                "and cache_positions"
            )
        if use_cache and self._effective_stages() > 1:
            raise NotImplementedError(
                "KV-cache decode through the GPipe schedule is not supported "
                "(a decode step is serial across stages by construction); use "
                "accelerate_tpu.generation.generate / depipeline(), which fold "
                "the stage-stacked layers back into the layer scan"
            )
        if use_cache and cfg.remat:
            raise ValueError("generation needs remat=False (mutable KV cache under jax.checkpoint)")
        embedding = self.param(
            "embedding",
            nn.with_logical_partitioning(nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.embed_dim),
        )
        x = _embed_lookup(embedding, input_ids, cfg, self.mesh)
        from ..parallel.context import tp_exchange_size

        if tp_exchange_size(self.mesh, s, use_cache=use_cache, use_fp8=cfg.use_fp8):
            # the blocks' products carry the stream with its rows over "tensor"
            x = _constrain_stream(x, self.mesh, True)
        if getattr(cfg, "residual_dtype", None) is not None:
            x = x.astype(cfg.residual_dtype)

        if positions is None:
            positions = jnp.arange(s)
        sin, cos = _rotary_tables(positions, cfg, cfg.dtype)
        # the tokens that are real, for the experts' routing and load: a
        # packed prefill's rows (pads carry position -1), a decode step's
        # live slots (an idle slot's live length is 0)
        token_mask = None
        if ragged_slots is not None:
            token_mask = jnp.reshape(cache_positions, (b, s)) >= 0
        elif kv_lengths is not None:
            token_mask = jnp.broadcast_to((kv_lengths > 0)[:, None], (b, s))

        block_cls = DecoderBlock
        moe_aux = jnp.float32(0.0)  # router load-balance loss, summed over layers
        num_stages = self._effective_stages()
        if num_stages > 1:
            from ..parallel.pipeline import (
                PipelineStages,
                merge_microbatches,
                split_microbatches,
            )

            if (
                cfg.use_fp8
                and cfg.fp8_recipe == "delayed"
                and cfg.pipeline_schedule == "1f1b"
            ):
                # gpipe carries the amax histories through the schedule scan
                # (PipelineStages variable_carry); the manual 1f1b backward
                # cannot return mutated collections, so the engine would
                # silently train a different schedule than configured —
                # reject instead
                raise NotImplementedError(
                    "delayed fp8 scaling + the 1f1b schedule is not wired "
                    "(the manual backward cannot thread the amax-history "
                    "collection); use pipeline_schedule='gpipe' or "
                    "fp8_recipe='current'"
                )
            if cfg.pipeline_stages <= 1:
                cfg = dataclasses.replace(cfg, pipeline_stages=num_stages)
            num_micro = _adapt_microbatches(
                b, cfg.pipeline_microbatches or num_stages, num_stages
            )
            x_mb = split_microbatches(x, num_micro, mesh=self.mesh)
            moe = cfg.moe_num_experts > 1
            out = PipelineStages(
                stage_module=StageStack,
                stage_args=(cfg, self.mesh),
                num_stages=num_stages,
                num_microbatches=num_micro,
                mesh=self.mesh,
                stage_returns_aux=moe,
                name="pipeline",
            )(x_mb, sin, cos, deterministic)
            if moe:
                out, aux_total = out
                # sum over (stage, mb) of per-mb means == M x full-batch
                # mean (even split), so /M recovers the dense-path aux
                moe_aux = aux_total / num_micro
            x = merge_microbatches(out)
        elif cfg.scan_layers:
            scan_body = _maybe_streaming(_ScanBlock, cfg)
            if cfg.remat:
                scan_body = nn.remat(
                    scan_body,
                    prevent_cse=False,
                    static_argnums=(),
                    policy=_remat_policy(cfg),
                )
            # one scanned stack a run of consecutive layers of one kind, in
            # published order: a model of one kind is the one stack
            # "layers" of before; layer kinds give "layers_0", "layers_1",
            # ..., each with its kind's widths, rotary tables and, where
            # the cache is paged, its kind's page table. Where the
            # moe_experts kernel runs, a run's stacked expert leaves ride
            # its scan beside the tables and the kernel reads them there
            stacks = expert_stacks(cfg, decode, self.variables.get("params", {}))
            for i, run_cfg in enumerate(cfg.run_configs()):
                if cfg.layer_kinds:
                    sin, cos = _rotary_tables(positions, run_cfg, cfg.dtype)
                ptab = page_table
                if isinstance(page_table, dict):  # (a state or no mixer at all has no table)
                    ptab = page_table.get(run_cfg.cache_kind)
                # a paged decode step or packed prefill the kernel serves
                # carries the stacked arena through the scan whole and the
                # kernel updates it in place; every other call splits it by
                # layer, as ever (not the call that shapes the arena: a
                # carry has to exist). A state-space run's states are
                # carried whole in both serving programs: a pack advances a
                # few slots of a state that is of all of them.
                name = _run_name(cfg, i)
                in_place = (use_cache and decode and page_table is not None
                            and (run_cfg.has_state
                                 or arena_in_place(run_cfg, s, packed=ragged_slots is not None))
                            and name in self.variables.get("cache", {}))
                split = {"params": 0, "cache": 0, "fp8_stats": 0, MOE_LOAD: 0}
                if in_place:
                    del split["cache"]
                ScanStack = nn.scan(
                    scan_body,
                    variable_axes=split,
                    variable_carry="cache" if in_place else False,
                    split_rngs={"params": True, "dropout": True},
                    length=run_cfg.num_layers,
                    metadata_params={nn.PARTITION_NAME: "layer"},
                )
                (x, run_aux, *_), _ = ScanStack(
                    run_cfg, self.mesh, use_cache, decode, deterministic, name=name,
                )((x, jnp.float32(0.0), sin, cos, cache_positions, ptab,
                   ragged_slots, slot_hist, kv_lengths, token_mask,
                   jnp.int32(0) if in_place else None,
                   (*stacks[name], jnp.int32(0)) if stacks.get(name) is not None else None), None)
                moe_aux = moe_aux + run_aux
        else:
            block_cls = _maybe_streaming(DecoderBlock, cfg)
            if cfg.remat:
                block_cls = nn.remat(block_cls, prevent_cse=True, policy=_remat_policy(cfg))
            for i in range(cfg.num_layers):
                x, block_aux = block_cls(cfg, self.mesh, use_cache, decode, name=f"layer_{i}")(
                    x, sin, cos, deterministic, cache_positions=cache_positions,
                    page_table=page_table, ragged_slots=ragged_slots,
                    slot_hist=slot_hist, kv_lengths=kv_lengths, token_mask=token_mask,
                )
                moe_aux = moe_aux + block_aux

        ln_f = self.param("ln_final", nn.with_logical_partitioning(_norm_init(cfg), ("norm",)), (cfg.embed_dim,))
        lm_head = None
        if not cfg.tie_embeddings:
            lm_head = self.param(
                "lm_head",
                nn.with_logical_partitioning(_dense_init(), ("embed", "vocab")),
                (cfg.embed_dim, cfg.vocab_size * cfg.num_pred_heads),
            )

        if labels is not None:
            loss = _head_ce_loss(x, ln_f, embedding, lm_head, labels, cfg, self.mesh)
            if cfg.moe_num_experts > 1:  # (a model of layer kinds is served, not trained)
                aux = cfg.moe_aux_loss_weight * moe_aux / cfg.num_layers
                return {"loss": loss + aux, "lm_loss": loss, "aux_loss": aux}
            return {"loss": loss}
        x = _norm(x, ln_f, cfg).astype(cfg.dtype)
        vocab_kernel = _tied_vocab_kernel(embedding, lm_head, cfg)
        if cfg.fp32_logits:
            # the product leaves the unit in float32, not rounded to cfg.dtype
            logits = jnp.matmul(x, vocab_kernel, preferred_element_type=jnp.float32)
        else:
            logits = (x @ vocab_kernel).astype(jnp.float32)
        if cfg.num_pred_heads > 1:
            # every block of rows is multiplied; the next token's is block 0
            logits = logits[..., :cfg.vocab_size]
        out = {"logits": _constrain(logits, ("batch", "seq", "vocab"), self.mesh)}
        if cfg.moe_num_experts > 1:
            out["aux_loss"] = cfg.moe_aux_loss_weight * moe_aux / cfg.num_layers
        return out

    def pipeline_value_and_grad(self):
        """Manual ``(params, input_ids, labels) -> (loss, grads)`` for the
        1F1B pipeline schedule (``config.pipeline_schedule == "1f1b"``).

        Reverse-mode AD through the GPipe belt stashes O(M) microbatch
        activations per stage; ``parallel/pipeline.one_f_one_b`` interleaves
        each microbatch's backward into the same scan, bounding the stash at
        O(S). This builder decomposes the model exactly as ``__call__``'s
        pipeline path does — embedding in front, the stage-vmapped
        ``StageStack`` in the middle, ``ln_final`` + (tied) LM head + fused
        CE behind — computes the head/embedding grads with local ``jax.vjp``
        and the stage grads with the scheduler. Each microbatch's mean CE is
        weighted by its valid-token share, so the summed loss equals the
        GLOBAL non-ignored-token mean ``__call__`` computes — gpipe and 1f1b
        agree even with uneven -100 padding across microbatches. Returns
        None when the schedule is not "1f1b" (the engine then uses plain
        AD).
        """
        cfg = self.config
        num_stages = self._effective_stages()
        if cfg.pipeline_schedule != "1f1b" or num_stages <= 1:
            return None
        from ..parallel.pipeline import one_f_one_b, split_microbatches

        mesh = self.mesh
        if cfg.pipeline_stages > 1:
            cfg_staged = cfg
        else:
            cfg_staged = dataclasses.replace(cfg, pipeline_stages=num_stages)

        def value_and_grad(params, input_ids, labels, scale=None, rng=None):
            # ``scale`` (fp16 loss scale) seeds the head-vjp cotangent so the
            # whole manual backward — head, stages, embedding — runs in the
            # scaled domain, matching AD's underflow protection. Grads are
            # returned SCALED; the caller divides by ``scale`` afterwards.
            # ``rng`` enables dropout: the scheduler gives each (stage,
            # microbatch) one key, used identically by its forward and its
            # remat backward (Megatron per-microbatch RNG parity).
            b, s = input_ids.shape
            M = _adapt_microbatches(
                b, cfg_staged.pipeline_microbatches or num_stages, num_stages
            )
            positions = jnp.arange(s)
            sin, cos = rotary_embedding_tables(
                positions, cfg.head_dim, theta=cfg.rope_theta, dtype=cfg.dtype
            )
            stage_params = params["pipeline"]["schedule"]["stages"]
            outer = {k: v for k, v in params.items() if k != "pipeline"}
            labels_mb = split_microbatches(labels, M, mesh=mesh)
            # per-microbatch valid-token share of the global mean (shifted
            # labels: position i predicts token i+1, so column 0 never counts)
            counts = jnp.sum(labels_mb[:, :, 1:] != -100, axis=(1, 2)).astype(jnp.float32)
            weights = counts / jnp.maximum(jnp.sum(counts), 1.0)

            def embed_fn(outer_p, ids):
                x = _embed_lookup(outer_p["embedding"], ids, cfg, mesh)
                return split_microbatches(x, M, mesh=mesh)

            with_dropout = cfg.dropout_rate > 0 and rng is not None

            if with_dropout:

                def stage_fn(p_s, x, key):
                    return StageStack(cfg_staged, mesh).apply(
                        {"params": p_s}, x, sin, cos, False,
                        rngs={"dropout": key},
                    )
            else:

                def stage_fn(p_s, x):
                    return StageStack(cfg_staged, mesh).apply(
                        {"params": p_s}, x, sin, cos, True
                    )

            def make_dy(m, y):
                tgt = jax.lax.dynamic_index_in_dim(labels_mb, m, 0, keepdims=False)
                w = jax.lax.dynamic_index_in_dim(weights, m, 0, keepdims=False)
                loss_m, vjp = jax.vjp(
                    lambda op, yy: _head_ce_loss(
                        yy, op["ln_final"], op["embedding"], op.get("lm_head"),
                        tgt, cfg, mesh, weight=w,
                    ),
                    outer, y,
                )
                seed = jnp.ones((), loss_m.dtype)
                if scale is not None:
                    seed = seed * jnp.asarray(scale, loss_m.dtype)
                douter_h, dy = vjp(seed)
                # fp32 accumulators: the scheduler sums aux over M microbatches
                douter_h = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), douter_h
                )
                return {"loss": loss_m.astype(jnp.float32), "douter": douter_h}, dy

            x_mb = embed_fn(outer, input_ids)
            moe = cfg.moe_num_experts > 1
            sched_kwargs = {}
            if moe:
                # router aux: dense loss carries weight * (sum of per-layer
                # batch-mean aux) / num_layers; the schedule sums per-mb
                # means over (stage, mb), so the seed is weight/(layers*M)
                # — x scale to keep the whole backward in the scaled domain
                aux_seed = cfg.moe_aux_loss_weight / (cfg.num_layers * M)
                if scale is not None:
                    aux_seed = aux_seed * jnp.asarray(scale, jnp.float32)
                sched_kwargs["stage_aux_weight"] = aux_seed
            out = one_f_one_b(
                stage_fn, stage_params, x_mb, make_dy,
                num_stages=num_stages, num_microbatches=M, mesh=mesh,
                rng=rng if with_dropout else None,
                **sched_kwargs,
            )
            if moe:
                aux, stage_grads, dx_mb, aux_stage = out
            else:
                aux, stage_grads, dx_mb = out
            # embedding backward: re-run the (cheap) embed under vjp and pull
            # the pipeline-input cotangents through it
            _, embed_vjp = jax.vjp(lambda op: embed_fn(op, input_ids), outer)
            (douter_e,) = embed_vjp(dx_mb.astype(x_mb.dtype))
            douter = jax.tree_util.tree_map(
                lambda a, b_: a.astype(jnp.float32) + b_.astype(jnp.float32),
                aux["douter"], douter_e,
            )
            grads = dict(douter)
            grads["pipeline"] = {"schedule": {"stages": stage_grads}}
            if moe:
                # same outputs contract as the AD path's MoE model outputs
                aux_term = cfg.moe_aux_loss_weight * aux_stage / (
                    cfg.num_layers * M
                )
                return {
                    "loss": aux["loss"] + aux_term,
                    "lm_loss": aux["loss"],
                    "aux_loss": aux_term,
                }, grads
            return aux["loss"], grads

        return value_and_grad

    def host_streamable_prefixes(self) -> list:
        """Param-path prefixes this model streams host->HBM internally (the
        dispatch layer leaves these in pinned host instead of transferring
        them wholesale before apply). Only meaningful when
        ``config.stream_layer_weights`` is on."""
        cfg = self.config
        if not cfg.stream_layer_weights or self._effective_stages() > 1:
            return []
        if cfg.scan_layers:
            return ["layers"]
        return [f"layer_{i}" for i in range(cfg.num_layers)]

    def _effective_stages(self) -> int:
        """Pipeline degree: explicit config wins; otherwise a mesh with a
        real "stage" axis (ShardingConfig(pipeline_parallel=k)) turns the
        pipeline path on automatically."""
        cfg = self.config
        if cfg.pipeline_stages > 1:
            return cfg.pipeline_stages
        if (
            self.mesh is not None
            and cfg.scan_layers
            and self.mesh.shape.get("stage", 1) > 1
            and cfg.num_layers % self.mesh.shape["stage"] == 0
        ):
            return self.mesh.shape["stage"]
        return 1

    def init_variables(self, rng: jax.Array, batch_size: int = 1, seq_len: Optional[int] = None):
        seq_len = seq_len or min(self.config.max_seq_len, 128)
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return self.init(rng, dummy)
