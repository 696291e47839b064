"""Model configurations + size presets."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp


# the collection an expert layer writes its load vector to when the caller
# makes it mutable (models/moe.py writes it, the serving engine reads it)
MOE_LOAD_COLLECTION = "moe_load"


@dataclass
class DecoderConfig:
    """LLaMA-family causal LM config.

    ``attention_impl``: "auto" (pallas flash on TPU, XLA elsewhere),
    "flash", or "xla". ``remat``: checkpoint each block (trades FLOPs for
    HBM — the reference's FSDP activation-checkpointing analog,
    /root/reference/src/accelerate/accelerator.py:1485-1499).
    ``scan_layers``: roll blocks into one lax.scan — O(1) compile time in
    depth and a requirement for pipeline-stage splitting later.
    """

    vocab_size: int = 32_000
    num_layers: int = 12
    embed_dim: int = 768
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None -> MHA
    head_dim: Optional[int] = None  # None -> embed_dim // num_heads
    mlp_dim: Optional[int] = None  # None -> ~8/3 * embed, rounded to 256
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16  # compute dtype for activations
    attention_impl: str = "auto"
    remat: bool = True
    # remat_policy (only meaningful with remat=True):
    #   "save_attention" (default) — keep the flash kernel's out/lse
    #     residuals across the forward so the backward reuses them instead
    #     of re-running the kernel (the dominant recompute term at long
    #     context: +5pp MFU at 16k on v5e). Costs ~B*S*E bf16 per layer of
    #     extra HBM on top of the scan carry classic remat already saves —
    #     a constant factor, not a new asymptotic term. Memory-tight
    #     configs should set "full".
    #   "save_dots" — additionally keep every matmul output; the backward
    #     recomputes only elementwise ops. More HBM, fewest recomputed
    #     FLOPs: measured +3.8pp MFU over save_attention at S=2048 on v5e
    #     (the bench flagship policy). At 16k+ tokens/chip it goes
    #     bandwidth-bound — keep save_attention there.
    #   "full" — recompute everything (minimum memory, classic remat)
    remat_policy: str = "save_attention"
    scan_layers: bool = True
    fused_ce_chunks: int = 8
    # pipeline parallelism over the mesh "stage" axis: stage-stacked layer
    # params + microbatch schedule (parallel/pipeline.py)
    pipeline_stages: int = 1
    pipeline_microbatches: Optional[int] = None  # None -> pipeline_stages
    # training schedule for the stage loop:
    #   "gpipe" — the forward belt under reverse-mode AD (all-forward-then-
    #     all-backward; per-stage activation stash grows with M);
    #   "1f1b"  — manual interleaved fwd/bwd (parallel/pipeline.one_f_one_b):
    #     per-stage stash is O(S) regardless of M, so microbatch count can
    #     amortize the bubble at constant activation memory. Used by
    #     TrainEngine via DecoderLM.pipeline_value_and_grad; forward-only
    #     calls (eval/generation) are schedule-independent.
    pipeline_schedule: str = "gpipe"
    # KV-cache length for generation (None -> max_seq_len)
    max_cache_len: Optional[int] = None
    # paged KV cache (serving/pages.py): when both are set, decode-time
    # cache leaves are [kv_num_pages, KVH, kv_page_size, D] physical pages
    # addressed through a per-slot page table instead of a dense
    # [B, KVH, max_cache_len, D] arena — the slot's KV footprint tracks its
    # actual length, and pages can be shared copy-on-write across slots
    # (prefix cache). Only the slot-arena decode path supports paging;
    # prefill is the packed ragged dispatch over the same pages.
    kv_page_size: Optional[int] = None   # tokens per page, power of two
    kv_num_pages: Optional[int] = None   # physical pages in the arena
    # KV-cache storage precision (utils/quantization.quantize_kv /
    # dequantize_kv; serving/pages.py arena helpers): "bf16" stores K/V at
    # the compute dtype; "int8"/"int4" store quantized payloads plus a
    # small parallel fp32 scale arena (one symmetric scale per token per
    # kv head — a cache write quantizes only the token it writes, so
    # nothing ever re-quantizes and preempt/resume/prefix-hit round-trips
    # are drift-free). Reads dequantize in-register inside the pallas
    # decode kernels (HBM decode traffic shrinks 2-4x) or as the fused
    # astype*scale of the masked-dense reference. Applies to both
    # generate()'s dense cache and the paged arena.
    kv_cache_dtype: str = "bf16"
    # decode-attention implementation for the KV-cache decode paths
    # (ops/attention dispatch). None -> "paged": the length-aware pallas
    # decode kernel on TPU — HBM read ∝ live tokens — with a warn-once
    # masked-dense fallback elsewhere; "dense" forces the masked-dense reference path;
    # "interpret" runs the same kernel through the pallas interpreter
    # (the CPU test/CI mode).
    decode_kernel: Optional[str] = None
    # prefill-attention implementation for the packed ragged prefill over
    # the paged arena (ops/attention.ragged_prefill_attention). None ->
    # "ragged": the flash online-softmax pallas kernel on TPU — one
    # dispatch packs every pending admission tail, prefix pages already
    # in the arena are skipped at the block level — with a warn-once
    # dense fallback elsewhere; "dense" forces the reference path (the bit-exactness
    # oracle); "interpret" runs the same kernel through the pallas
    # interpreter (the CPU test/CI mode). ``prefill_kernel_block`` tunes
    # the token-block granule rows are packed to (default 8).
    prefill_kernel: Optional[str] = None
    prefill_kernel_block: Optional[int] = None
    # fp8 recipe (ops/fp8.py): every Linear-equivalent contraction (QKV/O + MLP) runs e4m3-fwd/e5m2-bwd.
    # Flipped on by Accelerator(mixed_precision="fp8"). ``fp8_recipe``:
    # "current" (per-tensor amax each step, XLA fuses the reduction) or
    # "delayed" (TE DelayedScaling parity: scales from a rolling amax
    # history threaded through the "fp8_stats" collection).
    use_fp8: bool = False
    fp8_recipe: str = "current"
    fp8_amax_history_len: int = 16
    # big-model inference: keep layer weights in pinned host RAM and
    # transfer each layer's slice to HBM inside the scan body, so peak HBM
    # is ~one layer + embedding, not the whole model (set automatically by
    # big_modeling.dispatch_model when layers land on the "cpu"/"disk" tier)
    stream_layer_weights: bool = False
    # mixture-of-experts FFN over the mesh "expert" axis (models/moe.py);
    # 0 = dense MLP
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_aux_loss_weight: float = 0.01
    # routing (models/moe.py: sorted pairs, grouped products, no capacity
    # and no dropped token). ``moe_scoring``: "softmax" over the router's
    # outputs, or "sigmoid" of each. ``moe_selection_bias``: a learned
    # [outputs] vector added to the scores for the choice of the top k
    # only, never to the weights. ``moe_router_outputs``: the router's
    # width where it is not the number of experts held (None: the same).
    # ``moe_experts_held``: (first, count) -- this program holds experts
    # first..first+count-1 of the router's outputs (``moe_num_experts`` is
    # that count) and computes their part of the result; the weights stay
    # normalised over all top k chosen.
    moe_scoring: str = "softmax"
    moe_selection_bias: bool = False
    moe_router_outputs: Optional[int] = None
    moe_experts_held: Optional[tuple] = None
    # ``moe_n_group`` / ``moe_topk_group``: a group stage before the choice
    # (group-limited routing): the router's outputs lie in ``moe_n_group``
    # equal groups, a group scores the sum of its two best biased scores,
    # and only experts of the ``moe_topk_group`` best groups can be chosen
    # (1 group: no stage). ``moe_routed_scale`` multiplies the normalised
    # weights. ``moe_shared_experts``: that many gated MLPs of the experts'
    # width, as one of their summed width, which every token passes through
    # and whose result is added to the routed one; it is computed once
    # whatever share of the routed experts is held.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scale: float = 1.0
    moe_shared_experts: int = 0
    # ``moe_shared_gate``: the shared expert's result is multiplied by a gate
    # of its own, ``sigmoid(x w)`` with ``w`` [embed_dim], one scalar a token,
    # before it is added (computed once, like the shared expert itself).
    moe_shared_gate: bool = False
    # ``moe_latent_dim``: the routed experts take and return this width
    # (LatentMoE): one projection ``embed_dim -> moe_latent_dim`` before the
    # dispatch and one back after the combine, each computed once whatever
    # share of the experts is held (the way back is linear, so the shares'
    # partial sums add up); the router and the shared expert read the full
    # width. ``moe_shared_dim``: the shared expert's width where it is not
    # ``moe_shared_experts x mlp_dim`` (it then exists without that count).
    moe_latent_dim: Optional[int] = None
    moe_shared_dim: Optional[int] = None
    # ``mlp_kind``: a layer's feed-forward part, dense or each expert:
    # "swiglu" (three matrices, ``W_down(silu(W_gate x) * W_up x)``), "relu2"
    # (two, ``W_down relu(W_up x)^2``, no gate matrix) or "none": the layer
    # is its mixer alone, with the one norm before it (a half-block, as the
    # nemotron_h family alternates them).
    mlp_kind: str = "swiglu"
    # -- attention by layer kind. The five fields describe every layer of
    # a model with one kind, and one kind's layers where ``layer_kinds``
    # states several. ``v_head_dim``: the values' width (None: head_dim).
    # ``rope_dim``: the leading dimensions of a head that are rotated
    # (None: all; 0: none). ``attn_window``: a query sees its own position and the
    # window - 1 before it. ``attn_sink``: a learned scalar per query head
    # in the softmax's denominator. ``attn_value_scale`` multiplies the
    # values.
    v_head_dim: Optional[int] = None
    rope_dim: Optional[int] = None  # 0: no rotation (the model carries the order elsewhere)
    # -- latent attention (MLA; models/decoder.LatentAttention).
    # ``kv_lora_rank``: keys and values are made from one latent of this
    # width a token, normed, beside one rotated key of ``qk_rope_head_dim``
    # that all heads share; a head's key is ``qk_nope_head_dim`` unrotated
    # dimensions made from the latent and that rotated key (``head_dim`` is
    # their sum), its value ``v_head_dim`` made from the latent.
    # ``q_lora_rank``: the queries pass a bottleneck of this width with a
    # norm inside (None: one projection). The serving cache keeps the latent
    # and the rotated key, one entry a token a layer (cache kind "latent"),
    # and attention over it runs absorbed: the keys' up-projection folded
    # into the query, the values' applied after the softmax. ``rope_yarn``:
    # ``(factor, original_max_position, beta_fast, beta_slow, mscale,
    # mscale_all_dim)``, YaRN on the rotated dimensions (ops/layers.py); the
    # softmax scale then carries ``yarn_mscale(factor, mscale_all_dim)^2``.
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    rope_yarn: Optional[tuple] = None
    attn_window: Optional[int] = None
    attn_sink: bool = False
    attn_value_scale: float = 1.0
    # ``attn_qk_norm``: an RMS norm over each query head and each key head
    # (one learned scale of ``head_dim`` for the queries, one for the keys, by
    # ``norm_unit_offset`` like every norm) before the rotation.
    # ``attn_output_gate``: the query projection is doubled, and the second
    # half, through a sigmoid, multiplies the attention's result element by
    # element before the output projection.
    attn_qk_norm: bool = False
    attn_output_gate: bool = False
    # ``residual_dtype``: the dtype the residual stream is carried and added
    # in between the layers (None: ``dtype``, as every model before). With
    # experts, float32: the top-k choice is discrete, and bfloat16's rounding
    # of the stream (2^-9 a layer, relative) is wide enough against the
    # spacing of 256 router scores to change the last chosen expert of about
    # one token in ten a layer, which moves that token's logits far more
    # than rounding does (PERF.md section 6, PR 28). The matrix
    # multiplications still take ``dtype`` inputs.
    residual_dtype: Optional[jnp.dtype] = None
    # ``layer_kinds``: ((name, {field: value, ...}), ...), each a set of
    # overrides of this config's fields (num_kv_heads, head_dim,
    # v_head_dim, rope_theta, rope_dim, attn_window, attn_sink,
    # attn_value_scale, mlp_dim, moe_num_experts, ...) for the layers of
    # that kind; ``layer_pattern`` gives each layer's kind by index, in
    # published order. Empty: one kind, the stack every caller has today.
    layer_kinds: tuple = ()
    layer_pattern: tuple = ()
    # ``mixer``: what a layer mixes the sequence with, "attention" or "ssm",
    # a selective state-space block (Mamba-1; models/ssm.py): an input
    # projection to ``ssm_expand x embed_dim`` channels and a gate, a causal
    # depthwise convolution of ``ssm_conv_width`` taps (``ssm_conv_bias``), a
    # step, B and C of ``ssm_dt_rank`` (None: embed_dim / 16, rounded up) and
    # ``ssm_state_dim`` each, each through an RMSNorm of its own where
    # ``ssm_inner_norms``, and the recurrence over a float32 state of
    # ``ssm_state_dim`` a channel. In the serving cache it keeps a state and
    # the convolution's last inputs a slot, not pages (cache kind "state").
    # ``ssm_kernel``: None (the ``ssm_scan`` kernel on a TPU, its
    # ``jax.numpy`` reference elsewhere), "scan", "reference" or "interpret".
    # "ssd" is the mixer with heads (Mamba-2; models/ssm.Mamba2Mixer):
    # ``ssm_num_heads`` heads of ``ssm_head_dim`` channels (their product the
    # inner width), one scalar decay and one step a head, the step from the
    # input projection itself, B and C of ``ssm_state_dim`` by group
    # (``ssm_n_groups``; a head reads group ``head // (heads / groups)``), the
    # convolution over the channels, B and C together, and a gated RMSNorm
    # within each group before the output projection. Its state is
    # ``heads x head_dim x ssm_state_dim`` float32 a slot a layer (the
    # ``ssd_scan`` kernel; cache kind "state" as well). "none": the layer has
    # no mixer and is its feed-forward part alone, with the one norm before
    # it, and keeps nothing in the cache. "gdn" is Gated DeltaNet
    # (models/ssm.GatedDeltaNet): ``ssm_num_heads`` value heads of
    # ``ssm_head_dim``, ``ssm_n_groups`` key heads of ``ssm_state_dim`` (value
    # head ``h`` reads key head ``h // (heads / groups)``), a convolution over
    # queries, keys and values together, a decay and a write strength of one
    # scalar a value head a token, l2-normed queries and keys, and a state
    # ``heads x ssm_state_dim x ssm_head_dim`` float32 a slot a layer that the
    # delta rule corrects (the ``gdn_scan`` kernel; cache kind "state").
    mixer: str = "attention"
    ssm_num_heads: Optional[int] = None
    ssm_head_dim: Optional[int] = None
    ssm_n_groups: int = 1
    ssm_state_dim: int = 16
    ssm_conv_width: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None
    ssm_inner_norms: bool = True
    ssm_conv_bias: bool = True
    ssm_kernel: Optional[str] = None
    # -- EVA attention (ops/eva.py; "Efficient Attention via Control
    # Variates", arXiv:2302.04542, causal and chunked). ``eva_window``: a
    # query sees the positions of its own window of this many exactly, and
    # every window before it as one pooled key and value a chunk of
    # ``eva_chunk`` positions, pooled under two learned vectors a kv head
    # (``eva_mu``, ``eva_phi``), all under one softmax. In the serving cache
    # its pages fall away when a window closes and the table gains the
    # window's pages of summaries (cache kind "closing<window>"); a page is a
    # chunk there. None: plain attention.
    eva_window: Optional[int] = None
    eva_chunk: Optional[int] = None
    # ``norm_unit_offset``: every RMS norm scales by ``1 + weight`` (weights
    # stored around 0). ``num_pred_heads``: the untied head has
    # ``vocab_size x num_pred_heads`` rows, block j the prediction of token
    # t + 1 + j; the logits returned are block 0's (the product is taken over
    # all rows). ``fp32_logits``: the head's product leaves the unit in
    # float32 and is not rounded to ``dtype`` on the way.
    norm_unit_offset: bool = False
    num_pred_heads: int = 1
    fp32_logits: bool = False

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.head_dim is None:
            self.head_dim = self.embed_dim // self.num_heads
        if self.mlp_dim is None:
            raw = int(self.embed_dim * 8 / 3)
            self.mlp_dim = (raw + 255) // 256 * 256
        if self.pipeline_stages > 1 and self.num_layers % self.pipeline_stages != 0:
            raise ValueError(
                f"pipeline_stages={self.pipeline_stages} must divide "
                f"num_layers={self.num_layers} evenly"
            )
        if self.fp8_recipe not in ("current", "delayed"):
            raise ValueError(
                f"fp8_recipe must be 'current' or 'delayed', got {self.fp8_recipe!r}"
            )
        if self.remat_policy not in ("save_attention", "save_dots", "full"):
            raise ValueError(
                f"remat_policy must be 'save_attention', 'save_dots' or "
                f"'full', got {self.remat_policy!r}"
            )
        if (
            self.fp8_recipe == "delayed"
            and self.pipeline_stages > 1
            and self.pipeline_schedule == "1f1b"
        ):
            # gpipe carries the stage-stacked amax histories through the
            # schedule scan (parallel/pipeline.PipelineStages
            # variable_carry); the manual 1f1b backward cannot return
            # mutated collections
            raise NotImplementedError(
                "delayed fp8 scaling + the 1f1b schedule is not wired "
                "(the manual backward cannot thread the amax-history "
                "collection); use pipeline_schedule='gpipe' or "
                "fp8_recipe='current'"
            )
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pipeline_schedule must be 'gpipe' or '1f1b', got "
                f"{self.pipeline_schedule!r}"
            )
        if (self.kv_page_size is None) != (self.kv_num_pages is None):
            raise ValueError(
                "kv_page_size and kv_num_pages must be set together "
                f"(got page_size={self.kv_page_size}, num_pages={self.kv_num_pages})"
            )
        if self.kv_page_size is not None:
            ps = self.kv_page_size
            if ps < 1 or (ps & (ps - 1)) != 0:
                raise ValueError(f"kv_page_size must be a power of two, got {ps}")
            if self.kv_num_pages < 1:
                raise ValueError(f"kv_num_pages must be >= 1, got {self.kv_num_pages}")
        if self.kv_cache_dtype not in ("bf16", "int8", "int4"):
            raise ValueError(
                "kv_cache_dtype must be 'bf16', 'int8' or 'int4', got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.kv_cache_dtype == "int4" and self.head_dim % 2:
            raise ValueError(
                f"int4 KV packing pairs head_dim values into bytes; head_dim "
                f"must be even, got {self.head_dim}"
            )
        if self.decode_kernel not in (None, "paged", "dense", "interpret"):
            raise ValueError(
                "decode_kernel must be None, 'paged', 'dense' or "
                f"'interpret', got {self.decode_kernel!r}"
            )
        if self.prefill_kernel not in (None, "ragged", "dense", "interpret"):
            raise ValueError(
                "prefill_kernel must be None, 'ragged', 'dense' or "
                f"'interpret', got {self.prefill_kernel!r}"
            )
        if self.prefill_kernel_block is not None and self.prefill_kernel_block < 1:
            raise ValueError(
                f"prefill_kernel_block must be a positive token-block size, "
                f"got {self.prefill_kernel_block}"
            )
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid', got {self.moe_scoring!r}")
        if self.moe_experts_held is not None:
            first, count = self.moe_experts_held
            outputs = self.moe_router_outputs or self.moe_num_experts
            if count != self.moe_num_experts or first < 0 or first + count > outputs:
                raise ValueError(
                    f"moe_experts_held={self.moe_experts_held!r} must be (first, "
                    f"moe_num_experts={self.moe_num_experts}) within the router's "
                    f"{outputs} outputs")
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"rope_dim must be even and at most head_dim {self.head_dim}, "
                f"got {self.rope_dim}")
        if self.mixer not in ("attention", "ssm", "ssd", "gdn", "none"):
            raise ValueError(f"mixer must be 'attention', 'ssm', 'ssd', 'gdn' or 'none', got {self.mixer!r}")
        if self.mlp_kind not in ("swiglu", "relu2", "none"):
            raise ValueError(f"mlp_kind must be 'swiglu', 'relu2' or 'none', got {self.mlp_kind!r}")
        if self.mixer == "none" and self.mlp_kind == "none":
            raise ValueError("a layer has a mixer, a feed-forward part or both")
        if self._with_heads:
            heads, groups = self.ssm_num_heads, self.ssm_n_groups
            if not heads or not self.ssm_head_dim or groups < 1 or heads % groups:
                raise ValueError(
                    f"a {self.mixer!r} mixer needs ssm_num_heads and ssm_head_dim >= 1 and ssm_n_groups "
                    f"that divides the heads; got {heads}, {self.ssm_head_dim}, {groups}")
        if self.moe_latent_dim is not None and self.moe_latent_dim < 1:
            raise ValueError(f"moe_latent_dim must be >= 1, got {self.moe_latent_dim}")
        if self.ssm_kernel not in (None, "scan", "reference", "interpret"):
            raise ValueError(
                "ssm_kernel must be None, 'scan', 'reference' or 'interpret', "
                f"got {self.ssm_kernel!r}")
        if self._with_heads and (self.ssm_state_dim < 1 or self.ssm_conv_width < 2):
            raise ValueError("a state-space mixer needs ssm_state_dim >= 1 and ssm_conv_width >= 2")
        if self.mixer == "ssm" and min(
                self.ssm_state_dim, self.ssm_conv_width - 1, self.ssm_expand, self.ssm_rank) < 1:
            raise ValueError(
                "a state-space mixer needs ssm_state_dim, ssm_expand and ssm_dt_rank >= 1 "
                "and ssm_conv_width >= 2")
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(f"attn_window must be >= 1, got {self.attn_window}")
        if self.kv_lora_rank is not None:
            n, p = self.qk_nope_head_dim, self.qk_rope_head_dim
            if not n or not p or p % 2 or n + p != self.head_dim or self.kv_lora_rank < 1:
                raise ValueError(
                    "latent attention needs kv_lora_rank >= 1 and head_dim = qk_nope_head_dim + "
                    f"qk_rope_head_dim (even); got {self.kv_lora_rank}, {self.head_dim} = {n} + {p}")
            if (self.attn_window is not None or self.attn_sink or self.attn_value_scale != 1.0
                    or self.eva_window is not None or self.mixer != "attention"
                    or self.attn_qk_norm or self.attn_output_gate):
                raise ValueError("latent attention takes no window, sink, value scale or closing window")
            if self.kv_cache_dtype != "bf16":
                raise NotImplementedError(
                    "quantized pages (kv_cache_dtype) are not supported for latent attention: "
                    "it keeps unquantized latents only")
            if self.use_fp8:
                raise NotImplementedError("latent attention has no fp8 recipe")
        elif self.q_lora_rank is not None or self.rope_yarn is not None:
            raise ValueError("q_lora_rank and rope_yarn are latent attention's (kv_lora_rank)")
        if self.attn_output_gate and self.value_dim != self.head_dim:
            raise ValueError("attn_output_gate doubles the query projection: it needs v_head_dim == head_dim")
        if self.moe_shared_gate and not self.shared_mlp_dim:
            raise ValueError("moe_shared_gate gates a shared expert (moe_shared_experts or moe_shared_dim)")
        if self.rope_yarn is not None:
            self.rope_yarn = tuple(float(x) for x in self.rope_yarn)
            if len(self.rope_yarn) != 6:
                raise ValueError(
                    "rope_yarn is (factor, original_max_position, beta_fast, beta_slow, mscale, mscale_all_dim)")
        if self.moe_n_group < 1 or not 1 <= self.moe_topk_group <= self.moe_n_group:
            raise ValueError(
                f"moe_topk_group={self.moe_topk_group} must be in [1, moe_n_group={self.moe_n_group}]")
        if self.moe_n_group > 1 and self.moe_num_experts > 1:
            outputs = self.moe_router_outputs or self.moe_num_experts
            if outputs % self.moe_n_group or outputs // self.moe_n_group < 2:
                raise ValueError(
                    f"the router's {outputs} outputs must lie in moe_n_group={self.moe_n_group} "
                    "equal groups of at least two")
            if self.moe_top_k > self.moe_topk_group * (outputs // self.moe_n_group):
                raise ValueError("moe_top_k exceeds the experts of the moe_topk_group best groups")
        if (self.eva_window is None) != (self.eva_chunk is None):
            raise ValueError("eva_window and eva_chunk must be set together")
        if self.eva_window is not None:
            w, c = self.eva_window, self.eva_chunk
            if c < 1 or w % (c * c):
                raise ValueError(
                    f"eva_window ({w}) must be a multiple of eva_chunk squared ({c}): a page "
                    "is a chunk, and a window's summaries fill whole pages")
            if self.attn_window is not None or self.attn_sink or self.mixer != "attention":
                raise ValueError("EVA attention takes no sliding window and no sink")
            if self.attn_qk_norm or self.attn_output_gate:
                raise ValueError("EVA attention takes no query and key norms and no output gate")
            if self.kv_page_size is not None and self.kv_page_size != c:
                raise ValueError(
                    f"EVA attention pools a filled page into one entry: kv_page_size "
                    f"({self.kv_page_size}) must equal eva_chunk ({c})")
            if self.kv_cache_dtype != "bf16":
                raise NotImplementedError("EVA attention pools unquantized pages only")
        if self.num_pred_heads < 1 or (self.num_pred_heads > 1 and self.tie_embeddings):
            raise ValueError("num_pred_heads > 1 needs an untied head (tie_embeddings=False)")
        self.layer_kinds = tuple((str(n), dict(o)) for n, o in self.layer_kinds)
        self.layer_pattern = tuple(int(i) for i in self.layer_pattern)
        if bool(self.layer_kinds) != bool(self.layer_pattern):
            raise ValueError("layer_kinds and layer_pattern must be set together")
        if self.layer_kinds:
            if len(self.layer_pattern) != self.num_layers:
                raise ValueError(
                    f"layer_pattern names {len(self.layer_pattern)} layers, "
                    f"num_layers is {self.num_layers}")
            if not all(0 <= i < len(self.layer_kinds) for i in self.layer_pattern):
                raise ValueError("layer_pattern indexes past layer_kinds")
            if not self.scan_layers or self.pipeline_stages > 1:
                raise NotImplementedError(
                    "layer kinds run as scanned stacks outside a pipeline "
                    "(scan_layers=True, pipeline_stages=1)")
            for i in range(len(self.layer_kinds)):
                self.kind_config(i)  # every kind's overrides validate now
        if self.moe_num_experts == 1:
            raise ValueError("moe_num_experts must be 0 (dense) or >= 2")
        outputs = self.moe_router_outputs or self.moe_num_experts
        if self.moe_num_experts > 1 and not (1 <= self.moe_top_k <= outputs):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, moe_num_experts="
                f"{outputs}]"
            )

    # -- layer kinds -------------------------------------------------------

    def kind_config(self, index: int, num_layers: int = 1) -> "DecoderConfig":
        """The config of ``num_layers`` consecutive layers of kind
        ``index``: this one with the kind's overrides, itself of one kind."""
        return dataclasses.replace(
            self, **self.layer_kinds[index][1], layer_kinds=(), layer_pattern=(),
            num_layers=num_layers)

    def kind_runs(self) -> list:
        """[(kind index, number of layers)] for each run of consecutive
        layers of one kind, in published order. A model of one kind is one
        run."""
        if not self.layer_kinds:
            return [(None, self.num_layers)]
        runs = []
        for k in self.layer_pattern:
            if runs and runs[-1][0] == k:
                runs[-1][1] += 1
            else:
                runs.append([k, 1])
        return [tuple(r) for r in runs]

    def run_configs(self) -> list:
        """One config a run of :meth:`kind_runs` (this one where there are
        no kinds)."""
        return [self if k is None else self.kind_config(k, n) for k, n in self.kind_runs()]

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def rotary_dim(self) -> int:
        return self.head_dim if self.rope_dim is None else self.rope_dim

    @property
    def latent_dim(self) -> int:
        """What a latent layer's cache entry holds: the latent and the one
        rotated key (512 + 64)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_sm_scale(self) -> float:
        """The softmax scale: ``head_dim^-1/2``, times YaRN's
        ``mscale(factor, mscale_all_dim)^2`` where the rotation is stretched."""
        scale = self.head_dim ** -0.5
        if self.rope_yarn is not None:
            from ..ops.layers import yarn_mscale

            scale *= yarn_mscale(self.rope_yarn[0], self.rope_yarn[5]) ** 2
        return scale

    @property
    def _with_heads(self) -> bool:
        """The mixers whose state is by head (``ssm_num_heads`` of
        ``ssm_head_dim``, ``ssm_n_groups`` groups or key heads of ``ssm_state_dim``)."""
        return self.mixer in ("ssd", "gdn")

    @property
    def ssm_inner_dim(self) -> int:
        if self._with_heads:
            return self.ssm_num_heads * self.ssm_head_dim
        return self.ssm_expand * self.embed_dim

    @property
    def ssm_conv_dim(self) -> int:
        """The channels the convolution runs over, of which a slot keeps the
        last ``ssm_conv_width - 1`` inputs: the inner width, and with heads
        every group's B and C (``ssd``) or every key head's query and key
        (``gdn``) beside it."""
        extra = 2 * self.ssm_n_groups * self.ssm_state_dim if self._with_heads else 0
        return self.ssm_inner_dim + extra

    @property
    def has_state(self) -> bool:
        """A mixer with a recurrent state (either state-space form, or the
        delta rule's): a state a slot, not pages."""
        return self.mixer in ("ssm", "ssd", "gdn")

    @property
    def state_slot_bytes(self) -> int:
        """Bytes one slot keeps in one layer of this (one-kind) config: the
        float32 state and the convolution's last inputs (in ``dtype``; float32
        for the mixers with heads)."""
        if not self.has_state:
            return 0
        conv_itemsize = jnp.dtype(self.dtype).itemsize if self.mixer == "ssm" else 4
        return (self.ssm_inner_dim * self.ssm_state_dim * 4
                + (self.ssm_conv_width - 1) * self.ssm_conv_dim * conv_itemsize)

    @property
    def shared_mlp_dim(self) -> int:
        """The shared expert's width (0: none)."""
        return self.moe_shared_dim or self.mlp_dim * self.moe_shared_experts

    @property
    def ssm_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.embed_dim // 16)

    @property
    def cache_kind(self) -> str:
        """Name of the kind of state this (one-kind) config's layers keep,
        for the serving cache: attention layers of one name share a page
        pool and a page table; "state" is a state-space mixer's, which is
        of a fixed size a slot and not paged."""
        if self.has_state:
            return "state"
        if self.mixer == "none":
            return "none"  # a layer without a mixer keeps nothing
        if self.eva_window is not None:
            return f"closing{self.eva_window}"
        if self.kv_lora_rank is not None:
            return "latent"
        return "full" if self.attn_window is None else f"window{self.attn_window}"

    def _layer_params(self, active: bool = False) -> int:
        e, h, kv = self.embed_dim, self.num_heads, self.num_kv_heads
        mats = {"swiglu": 3, "relu2": 2, "none": 0}[self.mlp_kind]
        if self.mixer == "none":
            attn = 0
        elif self.mixer == "ssd":
            # in and out projections, the convolution and its bias, a step's
            # bias, a decay and a skip a head, the gated norm's weight
            d, c = self.ssm_inner_dim, self.ssm_conv_dim
            attn = e * (d + c + self.ssm_num_heads) + d * e + self.ssm_conv_width * c \
                + (c if self.ssm_conv_bias else 0) + 3 * self.ssm_num_heads + d
        elif self.mixer == "gdn":
            # the projections to q, k, v, z and to the two scalars a head, the
            # convolution, a decay and a step's bias a head, the gated norm's
            # weight of one head's width, the output projection
            d, c, hv = self.ssm_inner_dim, self.ssm_conv_dim, self.ssm_num_heads
            attn = e * (c + d + 2 * hv) + self.ssm_conv_width * c + (c if self.ssm_conv_bias else 0) \
                + 2 * hv + self.ssm_head_dim + d * e
        elif self.mixer == "ssm":
            # in and out projections, the convolution, the step's, B's and
            # C's projection with their norms, the step's expansion and
            # bias, A and the skip
            d, n, r = self.ssm_inner_dim, self.ssm_state_dim, self.ssm_rank
            attn = e * 2 * d + d * e + self.ssm_conv_width * d + (d if self.ssm_conv_bias else 0) \
                + d * (r + 2 * n) + (r + 2 * n if self.ssm_inner_norms else 0) + r * d + d + d * n + d
        elif self.kv_lora_rank is not None:
            # the queries (through their bottleneck and its norm), the
            # latent's down-projection, norm and up-projection, the output
            r, rq, p = self.kv_lora_rank, self.q_lora_rank, self.qk_rope_head_dim
            q = e * h * self.head_dim if rq is None else e * rq + rq + rq * h * self.head_dim
            attn = q + e * (r + p) + r + r * h * (self.qk_nope_head_dim + self.value_dim) \
                + h * self.value_dim * e
        else:
            attn = e * h * self.head_dim * (2 if self.attn_output_gate else 1) \
                + e * kv * (self.head_dim + self.value_dim) \
                + h * self.value_dim * e + (h if self.attn_sink else 0) \
                + (2 * self.head_dim if self.attn_qk_norm else 0) \
                + (2 * kv * self.head_dim if self.eva_window is not None else 0)
        if self.mlp_kind == "none":
            mlp = 0
        elif self.moe_num_experts > 1:
            # per-expert matrices (in the latent where there is one, with its
            # two projections), the shared expert, the router (and its bias)
            outputs = self.moe_router_outputs or self.moe_num_experts
            experts = self.moe_top_k if active else self.moe_num_experts
            lat = self.moe_latent_dim
            mlp = experts * mats * (lat or e) * self.mlp_dim + (2 * e * lat if lat else 0) \
                + mats * e * self.shared_mlp_dim + e * outputs \
                + (outputs if self.moe_selection_bias else 0) + (e if self.moe_shared_gate else 0)
        else:
            mlp = mats * e * self.mlp_dim
        # + a norm before each part the layer has
        return attn + mlp + e * ((self.mixer != "none") + (self.mlp_kind != "none"))

    def _count_params(self, active: bool) -> int:
        layers = sum(c.num_layers * c._layer_params(active) for c in self.run_configs())
        head = 0 if self.tie_embeddings else self.embed_dim * self.vocab_size * self.num_pred_heads
        return layers + self.vocab_size * self.embed_dim + head + self.embed_dim  # + final norm

    @property
    def num_params(self) -> int:
        """Parameters held (for the estimate CLI): every layer by its kind,
        every expert held here."""
        return self._count_params(active=False)

    @property
    def num_active_params(self) -> int:
        """Parameters a token passes through: as :attr:`num_params` with
        ``moe_top_k`` experts a layer in place of those held (MFU math)."""
        return self._count_params(active=True)

    @classmethod
    def tiny(cls, **kw):
        """Test-size model (runs on the 8-device CPU sim)."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("num_layers", 2)
        kw.setdefault("embed_dim", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("mlp_dim", 128)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("dtype", jnp.float32)
        kw.setdefault("remat", False)
        return cls(**kw)

    @classmethod
    def small_1b(cls, **kw):
        """~1.2B bench model (fits one v5e chip in bf16 + Adam fp32)."""
        kw.setdefault("vocab_size", 32_000)
        kw.setdefault("num_layers", 16)
        kw.setdefault("embed_dim", 2048)
        kw.setdefault("num_heads", 16)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("max_seq_len", 2048)
        return cls(**kw)

    @classmethod
    def llama_7b(cls, **kw):
        kw.setdefault("vocab_size", 32_000)
        kw.setdefault("num_layers", 32)
        kw.setdefault("embed_dim", 4096)
        kw.setdefault("num_heads", 32)
        kw.setdefault("mlp_dim", 11_008)
        kw.setdefault("max_seq_len", 4096)
        kw.setdefault("tie_embeddings", False)
        return cls(**kw)


@dataclass
class EncoderConfig:
    """BERT-family encoder config (reference nlp_example target)."""

    vocab_size: int = 30_522
    num_layers: int = 12
    embed_dim: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_seq_len: int = 512
    type_vocab_size: int = 2
    num_labels: int = 2
    dropout_rate: float = 0.1
    norm_eps: float = 1e-12
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # fp8 on QKV/O + MLP contractions (ops/fp8.py), same knobs as DecoderConfig
    use_fp8: bool = False
    fp8_recipe: str = "current"
    fp8_amax_history_len: int = 16

    def __post_init__(self):
        if self.fp8_recipe not in ("current", "delayed"):
            raise ValueError(
                f"fp8_recipe must be 'current' or 'delayed', got {self.fp8_recipe!r}"
            )

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("num_layers", 2)
        kw.setdefault("embed_dim", 64)
        kw.setdefault("num_heads", 4)
        kw.setdefault("mlp_dim", 128)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("dtype", jnp.float32)
        return cls(**kw)

    @classmethod
    def bert_base(cls, **kw):
        return cls(**kw)


@dataclass
class VisionConfig:
    """ResNet-family config (reference cv_example target: ResNet-50 DP).

    TPU notes: NHWC layout (XLA's native conv layout on TPU), bf16 compute
    with fp32 BatchNorm statistics, stage widths in multiples of 128 so the
    im2col'd matmuls tile cleanly onto the MXU.
    """

    stage_sizes: tuple = (3, 4, 6, 3)  # ResNet-50
    num_filters: int = 64
    num_classes: int = 1000
    block: str = "bottleneck"  # "bottleneck" (50/101/152) or "basic" (18/34)
    image_size: int = 224
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    stem: str = "imagenet"  # "imagenet" = 7x7/2 + maxpool; "cifar" = 3x3/1

    @classmethod
    def tiny(cls, **kw):
        """Test-size model (runs on the 8-device CPU sim)."""
        kw.setdefault("stage_sizes", (1, 1))
        kw.setdefault("num_filters", 8)
        kw.setdefault("num_classes", 10)
        kw.setdefault("block", "basic")
        kw.setdefault("image_size", 32)
        kw.setdefault("stem", "cifar")
        kw.setdefault("dtype", jnp.float32)
        return cls(**kw)

    @classmethod
    def resnet18(cls, **kw):
        kw.setdefault("stage_sizes", (2, 2, 2, 2))
        kw.setdefault("block", "basic")
        return cls(**kw)

    @classmethod
    def resnet50(cls, **kw):
        return cls(**kw)

    @classmethod
    def resnet101(cls, **kw):
        kw.setdefault("stage_sizes", (3, 4, 23, 3))
        return cls(**kw)
