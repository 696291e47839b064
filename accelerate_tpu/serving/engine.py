"""Continuous-batching decode engine (the Orca/vLLM-style serving loop).

``generation.generate()`` is one prompt -> one prefill -> one private
decode loop; a server with N concurrent users would run N of those
serially and waste (N-1)/N of every decode step's HBM bandwidth. This
module decodes **many requests per device step** against one paged KV
cache and admits/evicts requests with no shape change, so a live engine
never recompiles:

- **paged slot KV cache** (``pages.py``) — the model's "cache" collection
  as fixed-size physical pages, a page table a slot, and a per-slot
  ``lengths`` vector. Admission maps and writes pages, eviction is host
  bookkeeping.
- **fused batched decode step** — ONE jitted fn
  ``(params, arena, last_tokens, lengths, active, rngs, page_tables)``
  with the arena (and the per-slot state vectors) **donated**, so the
  multi-hundred-MB cache updates in place instead of doubling HBM per
  step. The arena also stays whole inside the step where the decode
  kernel serves it, and inside the packed prefill where its kernel writes
  the pack's pages (``models/decoder.arena_in_place``; the
  ``arena_in_place`` and ``prefill_arena_in_place`` gauges).
- **packed prefill admission** — the pending prompts' tails are packed
  into one ragged dispatch of a fixed grid capacity per scheduler
  iteration, *interleaved* between decode steps: a 10k-token prompt never
  stalls in-flight decodes for more than one grid's worth of compute.
- **host-side scheduler** (``ServingEngine``) — request queue, slot
  allocator, per-request token-stream callbacks, serving metrics through
  the runtime telemetry pipeline.

Token-exactness: batched decode reuses the exact sampling helpers and the
exact masked-attention reference (``ops/attention.decode_attention``) the
single-stream loop uses, with per-request RNG chains split identically —
so ``generate_batched()`` output is token-for-token equal to sequential
``generate()`` calls with the same per-request seeds (tests/test_serving).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..generation import _sample, _sized_definition, depipeline
from ..telemetry.spans import emit as _emit_span
from ..telemetry.spans import record_gc as _record_gc
from ..telemetry.spans import span as _span
from ..models.decoder import MOE_LOAD, arena_in_place, expert_stacks
from ..models.moe import expert_chunks, expert_rows
from ..ops.attention import (
    decode_kernel_active,
    paged_decode_block_pages,
    paged_decode_gathers_rows,
    paged_decode_rows_per_product,
    prefill_kernel_active,
    prefill_token_block,
    prefill_walk_pages,
)
from ..ops.ssm import ssm_kernel_active
from .pages import (
    CacheKind,
    PrefixCache,
    arena_nbytes,
    state_nbytes,
    fork_page,
    gather_page,
    init_paged_arena,
    install_page,
    kv_cache_bits,
    kv_token_bytes,
    set_table_entry,
    set_table_row,
)
from .tiers import KV_WIRE_VERSION, TierConfig, TieredStore, TierEntry, entry_nbytes
from .scheduler import (
    SHED_DRAINING,
    SHED_PAGE_EXHAUSTED,
    SHED_PAGE_PRESSURE,
    MultiTenantScheduler,
    PrefillBudgetController,
    SchedulerConfig,
)


class PagePressure(RuntimeError):
    """Raised by the page allocator when nothing is left to evict —
    callers translate it into a scheduling decision (preempt a victim,
    shed a request) so a serving loop never wedges on it."""


@dataclass(eq=False)
class Request:
    """One generation request and its life-cycle state. ``tokens`` is the
    generated continuation (the prompt is not repeated); ``result()``
    returns prompt + continuation like ``generate()`` does.

    ``eq=False``: requests are identities, not values. The generated
    dataclass ``__eq__`` would compare the ``prompt`` arrays elementwise,
    making ``queue.remove(req)`` raise (ambiguous array truth) past any
    same-shape entry — which the scheduler's remove() would swallow as
    "not queued", silently breaking cancel/timeout/shed.

    Every submitted request reaches exactly one terminal ``outcome``:
    ``"finished"`` (eos or token budget), ``"shed"`` (admission control /
    load shedding / page exhaustion / drain — ``shed_reason`` says
    which), or ``"cancelled"`` (``cancel()``, ``timeout_s`` expiry, or a
    raising ``on_token`` callback). ``outcome`` is None while live;
    ``finish_reason`` carries the finer-grained cause."""

    prompt: np.ndarray
    max_new_tokens: int
    rng: jax.Array
    on_token: Optional[Callable] = None
    # engine-assigned int, or the caller's externally-supplied request_id
    # (int or str) — a router re-queuing a request across replicas keeps
    # one id so `accelerate-tpu trace` can stitch the hops back together
    id: object = -1
    tenant: str = "default"
    priority: int = 0
    deadline_s: Optional[float] = None   # scheduling hint (EDF within class)
    timeout_s: Optional[float] = None    # hard wall from submit to cancel
    replica: Optional[str] = None        # which engine served this hop

    # runtime state (engine-owned)
    tokens: list = field(default_factory=list)
    done: bool = False
    slot: Optional[int] = None
    submit_t: float = 0.0
    admit_t: Optional[float] = None      # popped from the queue and given a slot
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    prefill_dispatches: int = 0          # prefill dispatches that carried its rows
    outcome: Optional[str] = None        # finished | shed | cancelled
    finish_reason: Optional[str] = None  # eos | budget | timeout | ...
    shed_reason: Optional[str] = None
    preemptions: int = 0
    _last_token_t: float = 0.0
    # tokens whose production is enqueued on the device (the pack's first
    # and one a decode step since): the engine's positions, budget and
    # ``_active`` follow from this count, ``len(tokens)`` lags it by what
    # is in flight
    _dispatched: int = 0
    _cancel: bool = False
    _resume: Optional[dict] = None       # preempted: saved RNG row for re-admission
    # paged-arena attribution (request records carry these so
    # `accelerate-tpu trace`/`report` can attribute per-request TTFT wins)
    prefix_hit: int = 0        # prompt tokens served from the prefix cache
    pages_allocated: int = 0   # fresh pages this request consumed (forks incl.)
    # hierarchical KV tiering (serving/tiers.py): which tier the prefix
    # was restored from (None = HBM hit or cold), how long the restore
    # took, and how many pages it installed — the request-record hop the
    # latency waterfall's kv_restore stage attributes
    kv_restore_tier: Optional[str] = None
    kv_restore_ms: float = 0.0
    kv_restore_pages: int = 0
    # what attended this request's packed prefill dispatches: "ragged"
    # (the flash prefill kernel / its interpreter) or "dense" (the
    # kernel's dense reference) — the waterfall's prefill stage annotates
    # kernel-vs-dense from this field on the request record
    prefill_kernel: Optional[str] = None

    def result(self) -> np.ndarray:
        """[prompt + generated] token ids (the ``generate()`` contract)."""
        return np.concatenate([self.prompt, np.asarray(self.tokens, np.int32)])

    def cancel(self) -> bool:
        """Request cancellation; the engine frees the slot and pages at
        the next scheduler iteration and the request lands in the log
        with outcome ``cancelled``. False if already terminal."""
        if self.done:
            return False
        self._cancel = True
        return True


class ServingEngine:
    """Slot-based continuous-batching scheduler over one decoder model.

    ``temperature``/``top_k`` are engine-wide (they are *compiled into*
    the fused decode step; per-request sampling params would either force
    recompiles or a slower traced-sampling path). Per-request knobs are
    the prompt, ``max_new_tokens``, the RNG seed, and the streaming
    callback.

    The KV storage is the **paged arena** (``pages.py``): pages of
    ``page_size`` tokens (it must divide ``max_cache_len``) + per-slot
    page tables, with ``num_pages`` physical pages (default: every slot
    can reach ``max_cache_len``, plus the parking page; set it lower to
    overcommit — more slots per HBM byte when real lengths are below
    ``max_cache_len``). ``prefill_chunks`` gives the packed prefill
    dispatch its grid capacities (each rounded up to the token block) and
    the admit plan its unit. With ``prefix_cache`` on,
    admissions whose prompt prefix is cached map the shared pages
    (copy-on-write) and prefill only the tail.

    ``kv_cache_dtype`` ("int8"/"int4"; default: the config's, else bf16)
    stores the KV arena quantized — int8/packed-int4 payloads plus a
    per-(token, kv-head) fp32 scale arena that rides every page op
    (fork/share/page-out) beside its payload. Writes quantize only the
    fresh rows (fused into the cache scatter), reads dequantize inside the
    pallas decode kernel (or the masked-dense reference), so 2-4x more
    concurrent slots fit the same KV HBM budget at an accuracy cost the
    drift harness (``serving.drift``) quantifies. Compile set and the
    zero-recompile invariant are unchanged — quantization is a cache-leaf
    dtype, not a program shape.

    The decode step and every packed-prefill grid capacity compile exactly once;
    after ``mark_steady()`` the ``admission_recompiles`` property must
    stay 0 no matter what traffic arrives — admissions, prefix hits and
    page forks are all pure data changes — the recompile invariant the
    tests assert.
    """

    def __init__(
        self,
        definition,
        params,
        *,
        num_slots: int = 8,
        max_cache_len: Optional[int] = None,
        prefill_chunks=(64, 256),
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        param_placer=None,
        donate: Optional[bool] = None,
        telemetry=None,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        prefix_max_entries: Optional[int] = None,
        scheduler=None,
        faults=None,
        kv_cache_dtype: Optional[str] = None,
        replica: Optional[str] = None,
        kv_tiers=None,
        kind_pages: Optional[dict] = None,
    ):
        from ..utils.compile_cache import (
            compile_event_counters,
            ensure_persistent_compile_cache,
            install_compile_listeners,
        )

        ensure_persistent_compile_cache()
        install_compile_listeners()
        definition, params = depipeline(definition, params)
        cfg = getattr(definition, "config", None)
        if cfg is None or not hasattr(cfg, "max_cache_len"):
            raise ValueError(
                "ServingEngine needs a definition with a DecoderConfig-style "
                "config (max_cache_len/max_seq_len)"
            )
        # KV-cache storage precision: the engine knob wins, else whatever
        # the config already carries. Cloning the definition here (before
        # cache sizing) makes every program this engine compiles — the
        # packed prefill, the fused decode step —
        # create/consume the quantized payload + scale cache leaves.
        kvq = kv_cache_dtype or getattr(cfg, "kv_cache_dtype", "bf16") or "bf16"
        kv_cache_bits(kvq)  # validate early (raises on typos)
        self.kv_cache_dtype = kvq
        if kvq != getattr(cfg, "kv_cache_dtype", "bf16"):
            definition = definition.clone(
                config=dataclasses.replace(cfg, kv_cache_dtype=kvq)
            )
            cfg = definition.config
        cap = max_cache_len or cfg.max_cache_len or cfg.max_seq_len
        if cap != cfg.max_cache_len:
            definition = _sized_definition(definition, cap)
        self.definition = definition
        self.params = params
        self.num_slots = int(num_slots)
        self.max_cache_len = int(cap)
        self.prefill_chunks = tuple(sorted(set(int(c) for c in prefill_chunks)))
        if not self.prefill_chunks or self.prefill_chunks[0] < 1:
            raise ValueError(f"bad prefill_chunks {prefill_chunks!r}")
        self.temperature = float(temperature)
        self.top_k = top_k
        self.eos_token_id = eos_token_id
        if param_placer is None:
            from ..utils.quantization import dequantize_params as param_placer
        self._placer = param_placer
        # buffer donation: in-place arena updates on accelerator backends;
        # CPU-sim runs keep it off (pre-0.6 jaxlibs warn-and-copy there)
        self._donate = (
            donate if donate is not None else jax.default_backend() != "cpu"
        )

        # -- paged arena / prefix cache -------------------------------------
        if not page_size:
            raise ValueError(
                "ServingEngine: the flat slot arena is gone; the KV cache is "
                f"paged and page_size must be a positive integer, got {page_size!r}"
            )
        self.page_size = int(page_size)
        # a model that states layer kinds, a window, a sink, experts or a
        # recurrent state runs on the paged arena's normal path only; what
        # cannot yet be right for it refuses here, by the feature's name,
        # and never runs
        mcfg = definition.config
        run_cfgs = mcfg.run_configs() if hasattr(mcfg, "run_configs") else [mcfg]
        # a state-space mixer's state a slot (pages.CacheKind "state"): a
        # cached prefix would need its snapshot at the page boundary, a
        # page-out its copy
        has_state = any(getattr(c, "has_state", False) for c in run_cfgs)
        # a closing window with pooled summaries (EVA attention): a shared
        # prefix would have to end at a window's close, a page-out needs the
        # open window's summaries
        closing = [c for c in run_cfgs if getattr(c, "eva_window", None) is not None]
        self._closing = tuple(sorted({c.eva_window for c in closing}))  # the windows that close
        self._by_kind = bool(
            getattr(mcfg, "layer_kinds", ()) or getattr(mcfg, "attn_window", None)
            or getattr(mcfg, "attn_sink", False) or getattr(mcfg, "moe_num_experts", 0) > 1
            or has_state or closing or getattr(mcfg, "kv_lora_rank", None) is not None)
        if self._by_kind:
            refused = {
                "prefix_cache (page sharing across a window kind, past a closing window, "
                "without a state's snapshot at the page boundary, or of latent pages: it waits "
                "for sharing to go behind CacheKind, ROADMAP D4, and for the page references "
                "of S3)": bool(prefix_cache),
                "kv_tiers": kv_tiers is not None,
                "preemption by page-out and restore (scheduler.config.preemption)": (
                    scheduler is not None
                    and getattr(getattr(scheduler, "config", scheduler), "preemption", False)),
                "quantized pages (kv_cache_dtype)": kvq != "bf16",
            }
            for feature, asked in refused.items():
                if asked:
                    raise NotImplementedError(
                        f"ServingEngine: {feature} is not supported for a model with "
                        "layer kinds, a window, a closing window, a sink, experts, latent "
                        "attention or a recurrent state; it is refused rather than run and be wrong "
                        "(ROADMAP.md, Reach)")
        elif kind_pages:
            raise ValueError("kind_pages names pools of cache kinds; this model has one kind")
        # the programs of a model with experts return, with the tokens, the
        # pairs on each held expert of each expert layer
        moe_runs = [c for c in run_cfgs if getattr(c, "moe_num_experts", 0) > 1]
        self._expert_layers = sum(c.num_layers for c in moe_runs)
        # (top k, experts held, router outputs): what expert_rows takes
        self._expert_widths = next(((c.moe_top_k, c.moe_num_experts, c.moe_router_outputs or c.moe_num_experts)
                                    for c in moe_runs), None)
        self._pairs_per_token = sum(c.num_layers * c.moe_top_k for c in moe_runs)
        if self.max_cache_len % self.page_size:
            raise ValueError(
                f"page_size ({self.page_size}) must divide max_cache_len "
                f"({self.max_cache_len})"
            )
        self.pages_per_slot = self.max_cache_len // self.page_size
        # default: every slot can reach max_cache_len (+ the parking
        # page). Overcommit by passing a smaller num_pages.
        self.num_pages = (
            int(num_pages) if num_pages
            else 1 + self.num_slots * self.pages_per_slot
        )
        if self.num_pages < 2:
            raise ValueError(f"num_pages ({self.num_pages}) must be >= 2")
        # the packed prefill dispatch's token block follows from the
        # capacities compiled for it, unless the config names one;
        # the model hands the same block to the kernel
        self._ragged_bt = int(
            getattr(definition.config, "prefill_kernel_block", None)
            or prefill_token_block(self.prefill_chunks)
        )
        for c in closing:
            # a slot's rows of one pack end at a window's close, in whole
            # token blocks of whole chunks (the pages the pack fills)
            if c.eva_chunk != self.page_size or self._ragged_bt % c.eva_chunk or c.eva_window % self._ragged_bt:
                raise ValueError(
                    f"ServingEngine: a closing window of {c.eva_window} in chunks of {c.eva_chunk} "
                    f"needs page_size {c.eva_chunk} and a prefill token block ({self._ragged_bt}) "
                    "that is a multiple of the chunk and divides the window")
        self._paged_def = definition.clone(config=self._paged_config(
            definition.config, kind_pages or {}))
        # which state each layer kind keeps and how it is paged
        # (pages.CacheKind): a pool, a table a slot, the window's rule.
        # One kind: the allocator, tables and counts of before.
        self._kinds, self._state_kind = self._cache_kinds(self._paged_def.config)
        self._allocator = self._kinds[0].allocator
        self._tables_host = self._kinds[0].tables
        # hierarchical KV tiering (serving/tiers.py): demote-on-evict
        # host/disk/peer store under the prefix cache. A TierConfig
        # builds the store here (wired to the usage byte-seconds hook
        # and this replica's identity); a prebuilt TieredStore is
        # taken as-is; None = tiering off (evictions drop, as before)
        if isinstance(kv_tiers, TierConfig):
            self._tiers = TieredStore(
                kv_tiers, page_size=self.page_size,
                kv_cache_dtype=self.kv_cache_dtype,
                replica=replica, on_bytes=self._note_tier_bytes,
            )
        else:
            self._tiers = kv_tiers
            if self._tiers is not None and self._tiers.on_bytes is None:
                self._tiers.on_bytes = self._note_tier_bytes
        tier_entries = (
            self._tiers.config.entry_capacity() if self._tiers else 0
        )
        prefix_entries = (
            int(prefix_max_entries) if prefix_max_entries else 512
        )
        self._prefix = (
            PrefixCache(
                self._allocator, self.page_size,
                max_entries=prefix_entries,
                # tier-aware ghost shadows: headroom beyond the new
                # TOTAL (HBM+host+disk) capacity
                ghost_base_entries=(
                    prefix_entries + tier_entries if tier_entries else None
                ),
                on_evict=(
                    self._demote_entry if self._tiers is not None else None
                ),
            )
            if prefix_cache else None
        )
        self._arena = init_paged_arena(
            self._paged_def, params, self.num_slots, self.pages_per_slot,
            self._placer,
            kinds=[k.name for k in self._kinds] if len(self._kinds) > 1 else None,
        )
        # whether the decode step rides the paged pallas kernel (the
        # serving/decode_kernel_active gauge): in every layer kind
        pcfg = self._paged_def.config
        all_runs = pcfg.run_configs()
        state_runs = [c for c in all_runs if c.has_state]
        run_cfgs = [c for c in all_runs if c.mixer == "attention"]  # the attention kinds
        # a state-space kind: whether its recurrence runs its kernel (ssm_scan
        # for Mamba-1's, ssd_scan for the mixer with heads, gdn_scan for the
        # delta rule's: the <name>_kernel_active gauges say which is engaged),
        # and whether both programs carry the layers' states whole and update
        # them in place (the state_in_place gauge)
        by_mixer = lambda name: [c for c in state_runs if c.mixer == name]
        self._state_kernel_costed = {name: bool(by_mixer(name)) and all(ssm_kernel_active(c) for c in by_mixer(name))
                                     for name in ("ssm", "ssd", "gdn")}
        self._state_in_place = bool(state_runs) and all(c.scan_layers for c in state_runs)
        self._kernel_costed = all(decode_kernel_active(c) for c in run_cfgs)
        # ... and whether that step updates the arena in place, the
        # stacked leaves carried through the layer scan (the
        # arena_in_place gauge and count of serving/decode_dispatch)
        self._arena_in_place = all(arena_in_place(c) for c in run_cfgs)
        # ... and whether the packed prefill does, its kernel writing the
        # pack's pages (the prefill_arena_in_place gauge, arena_in_place
        # of serving/prefill_dispatch)
        self._prefill_in_place = all(arena_in_place(c, packed=True) for c in run_cfgs)
        # ... and whether both programs' moe_experts kernel reads the experts
        # out of their scanned stacks (the experts_from_stack gauge)
        stacks = expert_stacks(pcfg, True, params)
        self._experts_from_stack = bool(stacks) and None not in stacks.values()
        self._walk_block_pages = paged_decode_block_pages(run_cfgs[0], self.pages_per_slot)
        # ... and the query rows one of its products holds, and whether the
        # engaged kernel gathers every kv head's rows into one softmax (the
        # decode_rows_per_product and decode_narrow_form gauges beside
        # decode_block_pages: which form of the decode kernel this engine runs)
        self._decode_rows_per_product = paged_decode_rows_per_product(run_cfgs[0])
        self._decode_narrow_form = self._kernel_costed and paged_decode_gathers_rows(run_cfgs[0])
        # a latent kind (latent attention, read absorbed by both programs):
        # what the latent_* counts of the dispatch spans are reckoned for,
        # and whether both its kernels engage (the mla_kernel_active gauge)
        latent_runs = [c for c in run_cfgs if c.kv_lora_rank is not None]
        self._latent_kind = next((k for k in self._kinds if k.name == "latent"), None)
        self._mla_kernel_costed = bool(latent_runs) and all(
            decode_kernel_active(c) and prefill_kernel_active(c) for c in latent_runs)
        # packed ragged prefill (ops/attention.ragged_prefill_attention):
        # the admission planner packs every pending tail into ONE ragged
        # dispatch per scheduler iteration, token-block padding only.
        # Where the flash prefill kernel (or its interpreter) does not
        # engage, the packed dispatch runs its dense reference (the
        # serving/prefill_kernel_active gauge says which).
        self._prefill_kernel_costed = all(prefill_kernel_active(c) for c in run_cfgs)
        # fixed grid capacities compiled at warmup (the zero-recompile
        # invariant): each chunk bucket rounded up to the token block,
        # deduped. The packer picks the smallest capacity that fits
        # the round's packed tails.
        rb = self._ragged_bt
        self._ragged_caps = tuple(sorted(
            {-(-int(c) // rb) * rb for c in self.prefill_chunks}
        ))
        for kind in self._kinds:
            kind.device_tables = jnp.zeros(
                (self.num_slots, self.pages_per_slot), jnp.int32
            )
        table_donate = (0,) if self._donate else ()
        self._set_row = jax.jit(set_table_row, donate_argnums=table_donate)
        self._set_entry = jax.jit(set_table_entry, donate_argnums=table_donate)
        self._fork = jax.jit(
            fork_page, donate_argnums=(0,) if self._donate else ()
        )
        # KV-handoff import write (one page per dispatch, traced dst)
        self._install_page = jax.jit(
            install_page, donate_argnums=(0,) if self._donate else ()
        )
        # demote-on-evict read: install_page's mirror, traced src —
        # one compiled program gathers any page, so post-steady
        # demotions never recompile (a gather by a per-call id list
        # would compile per distinct page count)
        self._gather_page = jax.jit(gather_page)
        self.page_forks = 0
        self.kv_pages_exported = 0
        self.kv_pages_imported = 0
        # hierarchical-tiering accounting: committed admission hits per
        # tier (hbm = a plain prefix hit with no restore behind it), and
        # the restore batch counters behind kv_restore_overlap_frac
        self.kv_tier_hits = {"hbm": 0, "host": 0, "disk": 0, "peer": 0}
        self.kv_restore_batches = 0
        self.kv_restore_batches_overlapped = 0
        self.kv_restores = 0
        self.kv_restores_aborted = 0
        self._restore = None  # live restore state (see _plan_restore)
        self._restored_tier = None  # transient: which tier fed the
        self._kv_paths = None       # admission being planned right now
        self.prefill_chunks_skipped = 0
        # prefill padding-waste accounting (a dispatch has the FIXED row
        # count of its grid capacity, so waste = 1 - live/dispatched):
        # the prefill_pad_waste_frac gauge reads these
        self.prefill_packed_tokens = 0      # live tokens of the packs
        self._prefill_rows_dispatched = 0   # grid rows of the packs
        self.arena_bytes = arena_nbytes(self._arena)
        self.state_bytes = state_nbytes(self._arena)  # of arena_bytes: a slot's state, all slots
        self._tokens = jnp.zeros((self.num_slots,), jnp.int32)
        self._lengths = jnp.zeros((self.num_slots,), jnp.int32)
        self._rngs = jnp.zeros((self.num_slots, 2), jnp.uint32)
        self._active = np.zeros((self.num_slots,), bool)

        # -- multi-tenant scheduler / fault injection ----------------------
        # scheduler=None keeps the original FIFO deque; a SchedulerConfig
        # or MultiTenantScheduler switches submit()/step() to the policy
        # tier (weighted-fair queues, admission control, preemption, the
        # ITL-SLO prefill-budget feedback loop — scheduler.py)
        if isinstance(scheduler, SchedulerConfig):
            scheduler = MultiTenantScheduler(scheduler)
        self._sched: Optional[MultiTenantScheduler] = scheduler
        self._controller = None
        if scheduler is not None and scheduler.config.itl_slo_ms is not None:
            self._controller = PrefillBudgetController(
                scheduler.config.itl_slo_ms,
                budget=scheduler.config.prefill_budget,
                min_budget=scheduler.config.prefill_budget_min,
                max_budget=scheduler.config.prefill_budget_max,
            )
        self._faults = faults
        self._prefill_credit = 0.0
        self._draining = False
        # fleet identity: stamped onto every request record so the trace
        # CLI can stitch a re-queued request's hops across replicas
        # (ATT_REPLICA is how a launcher names its N engine processes)
        self.replica = (
            str(replica) if replica else (os.environ.get("ATT_REPLICA") or None)
        )

        self._queue: deque = deque()
        self._free = list(range(self.num_slots))[::-1]  # pop() -> slot 0 first
        self._slot_req: dict = {}
        self._admitting = None
        # request-id assignment: a plain counter under a lock (serve()
        # advertises submit() from another thread). Kept as an attribute
        # (not itertools.count) so an externally-supplied int request_id
        # can bump it PAST itself — the tracer/scheduler key per-request
        # state by id, and an auto id later colliding with a router's
        # int id would silently merge two requests' records
        import threading

        self._next_id = 0
        self._id_lock = threading.Lock()

        self._step_core = self._build_step_core()
        self._decode_step = jax.jit(self._step_core, donate_argnums=self._step_donate())
        self._ragged_fns: dict = {}
        # admission on the device: a request's two keys are staged into
        # these rows when it takes its slot (the pack program samples with
        # the first, the slot's decode chain starts from the second), and
        # one program a pack puts its slots live from the pack's own firsts
        self._prefill_keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        self._decode_keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        self._stage_keys = jax.jit(_stage_keys_fn)
        self._admit_state = jax.jit(_admit_state_fn)
        # one dispatch in flight: the decode step whose tokens the host has
        # not read yet (read after the next one is enqueued, or by
        # _settle()), and this iteration's packs, whose first tokens are
        # read behind the decode dispatch
        self._flight: Optional[_StepFlight] = None
        self._flight_packs: list = []
        self._last_result_t = 0.0  # when the host last saw a result complete

        # metrics
        self.iterations = 0  # scheduler iterations (calls of step() that had work)
        self.pages_allocated = 0  # pages handed out by _alloc_page, lifetime
        self.pages_released = 0   # pages given back behind a window or at a close, lifetime
        self.windows_closed = 0   # windows of a closing kind closed, lifetime
        self.pages_pooled = 0     # pages a dispatch filled and pooled into a summary, lifetime
        self.step_count = 0
        self.requests_completed = 0
        self.requests_shed = 0
        self.requests_cancelled = 0
        self.preemptions = 0
        self.resumptions = 0
        self.generated_tokens = 0
        self.rows_discarded = 0  # decode rows dispatched for a request whose eos was still in flight
        self._step_samples: deque = deque(maxlen=512)  # (wall_s, tokens) a decode step
        self._itl: deque = deque(maxlen=2048)  # inter-token gaps, seconds
        self._itl_emitted = 0   # lifetime gap count; the controller only
        self._itl_observed = 0  # observes when these differ (fresh data)
        self._counters = compile_event_counters
        self._steady_mark = None
        self._exe_mem: Optional[dict] = None
        self._capacity_model = None  # lazy CapacityModel (metrics())
        _record_gc()  # the collector's pauses as host/gc spans, once a process

        if telemetry is None:
            from ..telemetry import current_session

            telemetry = current_session()
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.attach_serving(self)

    # -- the cache by layer kind -------------------------------------------

    @property
    def _page_tables(self):
        """The first kind's device page tables (the only kind's, for a
        model of one kind)."""
        return self._kinds[0].device_tables

    @_page_tables.setter
    def _page_tables(self, value):
        self._kinds[0].device_tables = value

    def _tables_arg(self):
        """What the programs take as ``page_table``: the one table, or a
        table a cache kind by its name."""
        if len(self._kinds) == 1:
            return self._kinds[0].device_tables
        return {k.name: k.device_tables for k in self._kinds}

    def _paged_config(self, cfg, kind_pages: dict):
        """The model's config with the page geometry: ``num_pages`` is the
        pool of the full kind (or the only kind), ``kind_pages`` gives the
        other kinds' pools by cache-kind name (a window kind's default is
        its slots' windows plus one prefill pack each, and spare)."""
        paged = dict(kv_page_size=self.page_size, kv_num_pages=self.num_pages,
                     prefill_kernel_block=self._ragged_bt)
        kinds = []
        names = set()
        for name, over in cfg.layer_kinds:
            kcfg = dataclasses.replace(cfg, **over, layer_kinds=(), layer_pattern=())
            if kcfg.mixer == "attention":  # a state a slot has no pool to size, a layer without a mixer nothing
                names.add(kcfg.cache_kind)
            if kcfg.attn_window is not None:
                span = -(-(kcfg.attn_window + self.prefill_chunks[-1]) // self.page_size) + 1
                over = dict(over, kv_num_pages=int(kind_pages.get(
                    kcfg.cache_kind, 1 + self.num_slots * span)))
            kinds.append((name, over))
        unknown = set(kind_pages) - names
        if unknown:
            raise ValueError(
                f"kind_pages names {sorted(unknown)}; the model's cache kinds are {sorted(names)}")
        if not cfg.layer_kinds and cfg.attn_window is not None and kind_pages:
            paged["kv_num_pages"] = int(kind_pages.get(cfg.cache_kind, self.num_pages))
        return dataclasses.replace(cfg, **paged, layer_kinds=tuple(kinds))

    def _cache_kinds(self, pcfg) -> tuple:
        """``(paged kinds, state kind or None)``: one :class:`~.pages.CacheKind`
        a distinct kind of paged state among the model's runs of attention
        layers, in the order the layers first state it, and the one that is
        a state a slot where the model has state-space layers."""
        from ..ops.attention import cache_entry_widths, paged_key_lanes

        kinds = {}
        itemsize = jnp.dtype(pcfg.dtype).itemsize
        state_layers = slot_bytes = 0
        for c in pcfg.run_configs():
            if c.has_state:
                state_layers += c.num_layers
                slot_bytes += c.num_layers * c.state_slot_bytes
                continue
            if c.mixer == "none":  # a feed-forward part alone keeps nothing
                continue
            if c.kv_lora_rank is not None:
                # a latent kind: one entry a token for all heads, at the
                # lanes its pages store (576 -> 640), and no value page
                token_bytes = cache_entry_widths(c)[1] * itemsize
            elif self.kv_cache_dtype == "bf16":
                token_bytes = c.num_kv_heads * (paged_key_lanes(c.head_dim) + c.value_dim) * itemsize
            else:
                token_bytes = kv_token_bytes(c.num_kv_heads, c.head_dim, self.kv_cache_dtype)
            kind = kinds.get(c.cache_kind)
            if kind is None:
                closes = None if c.eva_window is None else (c.eva_window, c.eva_chunk)
                kinds[c.cache_kind] = CacheKind(
                    c.cache_kind, c.attn_window, c.kv_num_pages, self.num_slots,
                    self.pages_per_slot, self.page_size, c.num_layers, token_bytes,
                    closes=closes)
            else:
                if (kind.num_pages, kind.token_bytes) != (c.kv_num_pages, token_bytes):
                    raise ValueError(
                        f"layers of cache kind {c.cache_kind!r} disagree on their pages")
                kind.layers += c.num_layers
        if not kinds:
            raise NotImplementedError(
                "ServingEngine: a model with no attention layer is not supported: a slot's "
                "length, admission and growth are kept by the page tables of an attention kind")
        state = None
        if state_layers:
            state = CacheKind("state", None, 0, self.num_slots, 0, self.page_size,
                              state_layers, 0, slot_bytes=slot_bytes)
        return list(kinds.values()), state

    # -- compiled programs -------------------------------------------------

    def _step_donate(self) -> tuple:
        """What the decode step donates: the arena, the
        lengths and the key chains. Not the tokens: a step's tokens are
        read by the host after the next step, which takes them as its
        input, is enqueued."""
        return (1, 3, 5) if self._donate else ()

    def _build_step_core(self):
        placer = self._placer
        temperature, top_k = self.temperature, self.top_k
        definition = self._paged_def

        last_pos = self.max_cache_len - 1
        mutable = ["cache"] + ([MOE_LOAD] if self._expert_layers else [])

        def step(params, arena, tokens, lengths, active, rngs, page_tables):
            """One batched decode step -> (arena, tokens, lengths, rngs)."""
            # inactive slots still flow through the fused step (fixed batch)
            # but must NOT write at ``lengths``: a slot mid-admission has
            # prefill dispatches landing in the arena while decode steps run
            # interleaved, and a stray write there corrupts its prefix.
            # Park them on the LAST cache position instead — any request
            # that legitimately reaches it writes its own K/V there before
            # attending, so the garbage is unreachable. (A freed slot's
            # table row is reset to the parking page, so a parked write
            # can never land in another request's page.)
            write_pos = jnp.where(active, lengths, last_pos)
            out, mutated = definition.apply(
                {"params": placer(params), "cache": arena},
                tokens[:, None],
                positions=write_pos[:, None],
                use_cache=True,
                decode=True,
                cache_positions=write_pos,
                page_table=page_tables,
                # the paged decode kernel walks a slot's live pages only: an
                # inactive slot, parked at the end of the cache, has none
                kv_lengths=jnp.where(active, lengths + 1, 0),
                mutable=mutable,
            )
            logits = out["logits"][:, -1]  # [N, V]
            split = jax.vmap(jax.random.split)(rngs)  # [N, 2, 2]
            subs = split[:, 1]
            # mirror the single-stream _sample call shape ([1, V] per slot)
            # so the drawn bits — and therefore the tokens — are identical
            nxt = jax.vmap(lambda key, row: _sample(row[None], key, temperature, top_k)[0])(
                subs, logits
            )
            # frozen slots keep their token/length/rng: an inactive slot's
            # RNG chain must not advance, or a request admitted mid-flight
            # would diverge from its single-stream chain
            nxt = jnp.where(active, nxt, tokens)
            new_rngs = jnp.where(active[:, None], split[:, 0], rngs)
            new_lengths = jnp.where(active, lengths + 1, lengths)
            return (mutated["cache"], nxt, new_lengths, new_rngs) + _expert_load(mutated)

        return step

    def _ragged_prefill_fn(self, cap: int):
        fn = self._ragged_fns.get(cap)
        if fn is not None:
            return fn
        definition, placer = self._paged_def, self._placer
        temperature, top_k = self.temperature, self.top_k
        mutable = ["cache"] + ([MOE_LOAD] if self._expert_layers else [])

        def ragged_prefill(params, arena, ids, row_slot, row_pos, slot_hist,
                           page_tables, last_rows, rngs):
            # one packed flash-prefill dispatch over the paged arena: every
            # pending tail rides the same [1, cap] token pack, the ragged
            # kernel attends each row to its slot's arena prefix plus its
            # own packed causal history, and quantize-on-write scatters
            # payload+scales through the page table in the same program.
            # Pad rows (slot/pos = -1) route to the parking page. A first
            # token is sampled for EVERY slot from ``last_rows`` — the
            # host only reads the rows of slots that actually completed a
            # tail this dispatch, so the rest are dead lanes, not hazards.
            positions = jnp.maximum(row_pos, 0)[None, :]
            out, mutated = definition.apply(
                {"params": placer(params), "cache": arena},
                ids,  # [1, cap]
                positions=positions,
                use_cache=True,
                decode=True,
                cache_positions=row_pos[None, :],
                page_table=page_tables,
                ragged_slots=row_slot,
                slot_hist=slot_hist,
                mutable=mutable,
            )
            rows = jnp.take(out["logits"][0], last_rows, axis=0)  # [S, V]
            firsts = jax.vmap(
                lambda key, row: _sample(row[None], key, temperature, top_k)[0]
            )(rngs, rows)
            return (mutated["cache"], firsts) + _expert_load(mutated)

        fn = jax.jit(ragged_prefill,
                     donate_argnums=(1,) if self._donate else ())
        self._ragged_fns[cap] = fn
        return fn

    def _ragged_warm_args(self, rcap: int) -> tuple:
        """An all-pad pack of ``rcap`` rows for the packed prefill program.
        Safe to run on the idle arena: both kernel kv phases see zero live
        rows, quantize-on-write lands on the parking page (unreachable by
        construction), and the sampled firsts are discarded host-side."""
        ids = jnp.zeros((1, rcap), jnp.int32)
        pad = jnp.full((rcap,), -1, jnp.int32)
        per_slot = jnp.zeros((self.num_slots,), jnp.int32)
        return (self.params, self._arena, ids, pad, pad, per_slot, self._tables_arg(),
                per_slot, jnp.zeros((self.num_slots, 2), jnp.uint32))

    def _admit_warm_args(self) -> tuple:
        """``_admit_state`` with no slot going live: nothing changes."""
        meta = np.zeros((self.num_slots, 5), np.int32)
        return (self._tokens, self._lengths, self._rngs, self._tokens,
                self._decode_keys, jnp.asarray(meta))

    def warmup(self):
        """Compile every program this engine can ever dispatch — each
        packed-prefill grid capacity, the two admission programs (a request's
        keys staged on the device, a pack's slots put live) and the decode
        step — by running them once against the (idle) arena. After
        ``warmup(); mark_steady()``, ``admission_recompiles`` staying 0 is
        deterministic, not a function of what traffic happened to arrive.
        All-inactive decode steps park their writes (see the step body), so
        warmup leaves no observable state behind. The whole of it is one
        ``serving/warmup`` span, with how many programs were compiled or
        loaded from the persistent cache (``programs``), how many of them
        were compiled (``compiles``) and the compile seconds."""
        with _span("serving/warmup") as sp:
            mark = self._counters()
            self._warmup()
            now = self._counters()
            programs = now["count"] - mark["count"]
            sp.args["programs"] = programs
            sp.args["compiles"] = programs - (now["cache_hits"] - mark["cache_hits"])
            sp.args["compile_s"] = round(now["seconds"] - mark["seconds"], 3)
        return self

    def _warmup(self):
        if self._slot_req or self._queued_depth() or self._admitting is not None:
            raise RuntimeError("warmup() needs an idle engine")
        if self.telemetry is not None:
            from ..telemetry import forensics

            # registration + the warmup fingerprints below establish the
            # steady-state signatures, so any later diagnosed recompile
            # names what the admission path changed
            forensics.register(
                "decode_step", donate=self._step_donate(),
                statics={"num_slots": self.num_slots,
                         "max_cache_len": self.max_cache_len,
                         "temperature": self.temperature, "top_k": self.top_k},
            )
        costs = getattr(self.telemetry, "costs", None)
        # the page-table maintenance programs: row install (admission),
        # entry scatter (growth), page fork (copy-on-write). All traced-
        # index data ops — one compile each, any slot/page thereafter.
        # Warmup runs them as no-ops against the idle state (row 0 is
        # already parking; forking the parking page onto itself).
        self._page_tables = self._set_row(
            self._page_tables, 0, _row_upload(self._tables_host.rows[0])
        )
        self._page_tables = self._set_entry(self._page_tables, 0, 0, 0)
        if not self._by_kind:  # nothing forks, imports or demotes a page there
            self._arena = self._fork(self._arena, 0, 0)
            # the KV-handoff install program: write a zeros page into the
            # parking page (whose content is unreachable by construction),
            # so a post-steady import of handed-off pages never compiles
            self._arena = self._install_page(
                self._arena, self._page_slice_tree(), 0
            )
            # ... and its mirror, the demote-on-evict page gather (reads
            # the parking page; nothing observable), so a post-steady
            # eviction can demote into the host tier with zero recompiles
            jax.device_get(self._gather_page(self._arena, 0))
        # the packed ragged-prefill programs, one per fixed grid capacity
        for rcap in self._ragged_caps:
            warm = self._ragged_warm_args(rcap)
            self._note_forensics(f"ragged_prefill_{rcap}", {"ids": warm[2]})
            self._arena, *_ = self._ragged_prefill_fn(rcap)(*warm)
            if costs is not None:
                try:
                    costs.capture_lowered(
                        f"ragged_prefill_{rcap}",
                        self._ragged_prefill_fn(rcap).lower(*self._ragged_warm_args(rcap)))
                except Exception:
                    pass
        # the admission programs: a request's keys staged into slot 0's rows
        # (submit()'s own key program with them), and a pack of no slot put live
        self._prefill_keys, self._decode_keys = self._stage_keys(
            self._prefill_keys, self._decode_keys, 0, jax.random.PRNGKey(0))
        self._tokens, self._lengths, self._rngs = self._admit_state(
            *self._admit_warm_args())
        self._note_forensics(
            "decode_step",
            {"tokens": self._tokens, "lengths": self._lengths,
             "active": self._active, "rngs": self._rngs},
        )
        self._arena, self._tokens, self._lengths, self._rngs, *_ = self._decode_step(
            self.params, self._arena, self._tokens, self._lengths, self._active,
            self._rngs, self._tables_arg(),
        )
        jax.device_get(self._tokens)
        # snapshot the decode step's memory_analysis here on the engine
        # thread so a later flight dump never has to; the AOT re-lower hits
        # the persistent compile cache the jit call above just populated,
        # so this costs a deserialize, not a second compile
        self.executable_memory_stats()

    def audit_entrypoints(self) -> list:
        """Entry-point specs for the static program auditor
        (``accelerate_tpu.analysis.program_audit``): every program
        ``warmup()`` compiles — the packed prefill grids, the decode step,
        the page-table maintenance programs — with the example args warmup
        itself would pass and the *effective* donation sets. Trace-only consumers: building the
        specs executes nothing and compiles nothing, so this is safe on
        a live engine (the jitted-fn caches it touches are the ones
        warmup populates anyway). ``donate_expected`` mirrors
        ``self._donate`` so the CPU sim's deliberate no-donation policy
        is not reported as a donation miss."""
        dtype = np.dtype(self.definition.config.dtype).name
        donate_on = self._donate
        specs = []
        step_args = (self.params, self._arena, self._tokens, self._lengths,
                     self._active, self._rngs, self._page_tables)
        step_donate = self._step_donate()
        # NB: no shape_probe on the engine's own programs, deliberately.
        # The weak-shape check compares shape-derived scalar literals
        # between two traces, and the batched per-slot RNG chains bake
        # num_slots into threefry's counter math inside jax itself — a
        # library-inherent encoding every batched-RNG program has, not a
        # user bug. The engine's zero-recompile invariant holds by fixed
        # shapes (warmup + the compile-counter tests witness it); the
        # probe-based check is for shape-polymorphic USER programs.
        specs.append(dict(
            name="decode_step", fn=self._decode_step, args=step_args,
            donate=step_donate, donate_expected=donate_on, compute_dtype=dtype,
        ))
        table_donate = (0,) if donate_on else ()
        specs.append(dict(
            name="table_set_row", fn=self._set_row,
            args=(self._page_tables, 0,
                  _row_upload(self._tables_host.rows[0])),
            donate=table_donate, donate_expected=donate_on,
        ))
        specs.append(dict(
            name="table_set_entry", fn=self._set_entry,
            args=(self._page_tables, 0, 0, 0),
            donate=table_donate, donate_expected=donate_on,
        ))
        specs.append(dict(
            name="page_fork", fn=self._fork, args=(self._arena, 0, 0),
            donate=(0,) if donate_on else (), donate_expected=donate_on,
            compute_dtype=dtype,
        ))
        for rcap in self._ragged_caps:
            specs.append(dict(
                name=f"ragged_prefill_{rcap}",
                fn=self._ragged_prefill_fn(rcap),
                args=self._ragged_warm_args(rcap),
                donate=(1,) if donate_on else (),
                donate_expected=donate_on, compute_dtype=dtype,
            ))
        # the admission programs (a few words a slot: nothing worth donating)
        specs.append(dict(
            name="stage_keys", fn=self._stage_keys,
            args=(self._prefill_keys, self._decode_keys, 0,
                  jnp.zeros((2,), jnp.uint32)),
            donate=(), donate_expected=False,
        ))
        specs.append(dict(
            name="admit_state", fn=self._admit_state,
            args=self._admit_warm_args(), donate=(), donate_expected=False,
        ))
        return specs

    # -- request API -------------------------------------------------------

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int = 32,
        seed: int = 0,
        rng: Optional[jax.Array] = None,
        on_token: Optional[Callable] = None,
        tenant: str = "default",
        priority: int = 0,
        deadline_s: Optional[float] = None,
        timeout_s: Optional[float] = None,
        request_id=None,
    ) -> Request:
        """Queue one request; returns its live :class:`Request` handle.
        ``rng``/``seed`` match ``generate(..., rng=...)``: the same seed
        yields the same tokens the single-stream loop would produce.
        ``on_token(token_id, request)`` fires as each token is emitted.

        ``request_id`` (int or str) overrides the engine-assigned id: a
        router submitting one logical request to several replicas (e.g.
        a re-queue after a replica death) passes the same id to each hop
        so the per-replica request logs stitch back into one timeline
        (``accelerate-tpu trace summary --request-id``). The caller owns
        uniqueness among its own ids; an external *int* id also bumps the
        engine's auto counter past itself, so auto-assigned ids can never
        collide with it.

        With a scheduler attached, ``tenant``/``priority``/``deadline_s``
        drive the weighted-fair, priority-classed queue, and admission
        control applies: a submit past the queue watermarks returns a
        request **already terminal with outcome ``shed``** (check
        ``req.outcome``) instead of raising — backpressure is a value,
        not an exception. ``timeout_s`` cancels the request (freeing its
        slot and pages) if it has not finished that many seconds after
        submit."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        cover = self._plan_cover(prompt.size)
        if self._sched is not None and self._sched.config.preemption:
            # a preemptible request must be re-admittable at ANY progress
            # point: the worst-case replay (prompt + all generated tokens
            # but the last) must itself chunk-plan within the slot, or a
            # resume could fail to fit mid-flight when the prefix cache
            # has nothing for it
            cover = max(
                cover, self._plan_cover(prompt.size + max_new_tokens - 1)
            )
        need = prompt.size + max_new_tokens
        if need > self.max_cache_len or cover > self.max_cache_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens})"
                f" exceeds the slot KV capacity ({self.max_cache_len}); "
                "raise max_cache_len"
            )
        with self._id_lock:
            if request_id is None:
                rid = self._next_id
                self._next_id += 1
            else:
                rid = request_id
                if isinstance(rid, int) and rid >= self._next_id:
                    # never hand this id out as an auto id later
                    self._next_id = rid + 1
        req = Request(
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            rng=rng if rng is not None else jax.random.PRNGKey(seed),
            on_token=on_token,
            id=rid,
            tenant=str(tenant or "default"),
            priority=int(priority),
            deadline_s=deadline_s,
            timeout_s=timeout_s,
            replica=self.replica,
        )
        req.submit_t = time.perf_counter()
        tr = self._tracer()
        if tr is not None:
            # before the queue append: serve() admits from another thread,
            # and admission must find the tracer record already live
            tr.on_submit(req)
        usage = self._usage()
        if usage is not None:
            usage.note_submit(req.tenant)
        if self._draining:
            self._shed(req, SHED_DRAINING)
            return req
        if self._sched is not None:
            ok, reason = self._sched.admit(req)
            if not ok:
                self._shed(req, reason)
            return req
        self._queue.append(req)
        return req

    def generate_batched(self, prompts, *, max_new_tokens: int = 32, seeds=None):
        """Submit ``prompts`` (list of 1-D id arrays), run to completion,
        return the list of [prompt + generated] arrays — the batched
        counterpart of N sequential ``generate()`` calls."""
        if seeds is None:
            seeds = range(len(prompts))
        else:
            seeds = list(seeds)
            if len(seeds) != len(prompts):
                raise ValueError(
                    f"seeds ({len(seeds)}) must match prompts ({len(prompts)})"
                )
        reqs = [
            self.submit(p, max_new_tokens=max_new_tokens, seed=s)
            for p, s in zip(prompts, seeds)
        ]
        self.run()
        # the batch API promises every output or a loud error — a request
        # shed under page pressure (with no scheduler to preempt for it)
        # must not come back as a silently truncated sequence
        bad = [r for r in reqs if r.outcome != "finished"]
        if bad:
            raise RuntimeError(
                f"generate_batched: {len(bad)}/{len(reqs)} requests did not "
                f"finish ({sorted({r.outcome for r in bad})}; first: id="
                f"{bad[0].id} shed_reason={bad[0].shed_reason}) — the arena "
                "is overcommitted for this batch; raise num_pages/num_slots "
                "or serve through submit() with a scheduler"
            )
        return [r.result() for r in reqs]

    # -- scheduler ---------------------------------------------------------

    def _queued_depth(self) -> int:
        return self._sched.total_queued if self._sched is not None else len(self._queue)

    def _pending(self) -> bool:
        # a result in flight counts: after a late eos the last dispatch may
        # carry no live request, and run() still ends with nothing unread
        return bool(
            self._queued_depth() or self._admitting is not None or self._slot_req
            or self._flight is not None
        )

    def step(self) -> bool:
        """One scheduler iteration: reap cancels/timeouts, apply pressure
        decisions (shed, preempt), advance prefill admission within the
        ITL-budget, enqueue one batched decode step over every active slot,
        and only then read what the device has finished: the previous
        step's tokens and this iteration's first tokens. One dispatch is in
        flight while the host works; nothing else in here waits for the
        device. Returns whether any work happened (False = fully idle)."""
        if self._faults is None and not self._pending():
            # an idle poll (serve() between requests) does nothing below and
            # records no span: a thousand of them a second would wash the
            # last busy iterations out of the span ring
            return False
        with _span("serving/step") as sp:
            emitted0 = self.generated_tokens
            closed0, pooled0 = self.windows_closed, self.pages_pooled
            progressed = self._step_phases()
            self.iterations += 1
            args = sp.args
            args["iteration"] = self.iterations
            args["queued"] = self._queued_depth()
            args["live"] = len(self._slot_req)
            args["pages_in_use"] = self._allocator.in_use
            args["pages_free"] = self._allocator.free_count
            for kind in self._kinds[1:]:
                args[f"pages_in_use.{kind.name}"] = kind.allocator.in_use
            if self._by_kind:
                # what the cache holds against what it holds it for
                args["kv_bytes_in_use"] = sum(
                    k.allocator.in_use * k.page_bytes for k in self._kinds)
                # counted as dispatched, like the pages held for them
                args["live_tokens"] = sum(
                    r.prompt.size + r._dispatched for r in self._slot_req.values())
            if self._closing:
                # entries the live slots hold for those positions (a closed
                # window stands as one entry a chunk), and this iteration's
                # closes and pooled pages, packs and decode step together
                kind = self._kinds[0]
                args["entries_held"] = sum(
                    kind.entries(r.prompt.size + r._dispatched) for r in self._slot_req.values())
                args["windows_closed"] = self.windows_closed - closed0
                args["pages_pooled"] = self.pages_pooled - pooled0
            if self._state_kind is not None:
                # a slot's state is held from its admission on, whatever its length
                args["state_bytes_in_use"] = self._state_kind.slot_bytes * (
                    self.num_slots - len(self._free))
            args["emitted"] = self.generated_tokens - emitted0
        return progressed

    def _step_phases(self) -> bool:
        """The iteration's phases, each under its own ``serving/`` span
        (docs/telemetry.md lists them with their counts)."""
        with _span("serving/reap") as sp:
            gone0 = (self.requests_cancelled, self.requests_shed, self.preemptions)
            if self._faults is not None:
                # a fault script keys on step_count and on what the requests
                # hold: it sees the engine with nothing unread
                self._settle()
                self._faults.on_step(self)
            if self._draining and self._queued_depth():
                # request_drain() only sets the flag (it may fire from a
                # signal handler); the queue shed always runs here, on the
                # loop thread
                self._shed_queue_for_drain()
            progressed = self._reap()
            if self._sched is not None:
                progressed = self._shed_on_pressure() or progressed
                progressed = self._maybe_preempt() or progressed
            sp.args["reaped"] = self.requests_cancelled - gone0[0]
            sp.args["shed"] = self.requests_shed - gone0[1]
            sp.args["preempted"] = self.preemptions - gone0[2]
        if self._sched is not None:
            budget = (
                self._controller.budget if self._controller is not None
                else self._sched.config.prefill_budget
            )
            if not self._slot_req:
                # throttling prefill protects live decodes' ITL; with
                # none live there is nothing to protect — admit freely
                budget = max(budget, 1.0)
            self._prefill_credit = min(
                self._prefill_credit + budget, max(1.0, budget)
            )
            while self._prefill_credit >= 1.0:
                if not self._advance_admission():
                    break
                self._prefill_credit -= 1.0
                progressed = True
        else:
            progressed = self._advance_admission() or progressed
        progressed = self._decode_once() or progressed
        # the packs' first tokens, behind the decode dispatch: the step runs
        # while the host stamps and commits them
        progressed = self._read_packs(int(self._flight is not None)) or progressed
        if (
            self._controller is not None
            and self._itl_emitted != self._itl_observed
        ):
            # gate on fresh gaps: idle iterations (serve() polling an
            # empty engine) must not replay the last window's p99 into
            # the controller at wall-clock rate
            self._itl_observed = self._itl_emitted
            p99, n = self._recent_itl_p99_ms()
            self._controller.observe(p99, samples=n)
        return progressed

    def _recent_itl_p99_ms(self, window: int = 128):
        """p99 over the most recent ITL gaps — the live observation the
        prefill-budget controller acts on (the lifetime histograms would
        dilute a fresh regression under hours of healthy history)."""
        if not self._itl:
            return None, 0
        recent = list(self._itl)[-window:]
        return 1e3 * float(np.percentile(np.asarray(recent), 99)), len(recent)

    def run(self):
        """Drive :meth:`step` until queue, admissions and slots are idle."""
        try:
            while self._pending():
                self.step()
        except Exception:
            self._flight_dump("serving_exception")
            raise

    def serve(self, should_stop: Optional[Callable[[], bool]] = None, idle_sleep_s: float = 0.001):
        """Long-running loop: keep scheduling as requests arrive (from
        callbacks or another thread's ``submit``) until ``should_stop()``
        returns True; idle iterations sleep ``idle_sleep_s``. A drain
        request (:meth:`request_drain` — e.g. from the SIGTERM hook)
        finishes the in-flight requests and returns even when
        ``should_stop`` never fires."""
        try:
            while should_stop is None or not should_stop():
                busy = self.step()
                if self._draining and not self._pending():
                    return
                if not busy:
                    if should_stop is None and not self._pending():
                        return
                    time.sleep(idle_sleep_s)
            self._settle()  # stopped from outside: deliver what was computed
        except Exception:
            self._flight_dump("serving_exception")
            raise

    # -- drain / shutdown ---------------------------------------------------

    def request_drain(self):
        """Flag-only drain: stop admitting (subsequent ``submit`` sheds)
        and mark everything still queued for shedding at the top of the
        next scheduler iteration; in-flight requests finish under
        whatever loop is already driving :meth:`step`. Setting one flag
        is the entire effect, so this is safe from a signal handler or
        another thread even while the engine is mid-step — the queue
        mutation itself always happens on the loop thread."""
        self._draining = True

    def _shed_queue_for_drain(self):
        now = time.perf_counter()
        for req in (self._sched.queued() if self._sched is not None
                    else list(self._queue)):
            if self._sched is not None:
                self._sched.remove(req)
            else:
                try:
                    self._queue.remove(req)
                except ValueError:
                    continue
            req.shed_reason = SHED_DRAINING
            self._terminate(req, now, "shed", "shed")

    def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful shutdown: stop admitting, shed the queue, run the
        loop until every in-flight request finishes (or ``timeout_s``
        passes — the stragglers are then cancelled), and flush telemetry.
        Every request submitted before the drain ends with a definite
        outcome; none is abandoned. Returns a small summary dict."""
        self.request_drain()
        self._shed_queue_for_drain()  # owner thread: shed synchronously
        deadline = (
            time.perf_counter() + timeout_s if timeout_s is not None else None
        )
        while self._pending():
            if deadline is not None and time.perf_counter() > deadline:
                self._settle()  # the stragglers keep what was computed for them
                now = time.perf_counter()
                if self._admitting is not None:
                    self._abort_admission(now, "cancelled", "drain_timeout")
                for req in list(self._slot_req.values()):
                    self._terminate(req, now, "cancelled", "drain_timeout")
                break
            self.step()
        if self.telemetry is not None:
            try:
                self.telemetry.flush()
            except Exception:
                pass
        return {
            "completed": self.requests_completed,
            "shed": self.requests_shed,
            "cancelled": self.requests_cancelled,
        }

    # -- terminal transitions ----------------------------------------------

    def _release_slot(self, req: Request):
        if req.slot is None:
            return
        slot = req.slot
        self._slot_req.pop(slot, None)
        self._active[slot] = False
        self._release_slot_pages(slot, tenant=req.tenant)
        self._free.append(slot)
        req.slot = None

    def _terminate(self, req: Request, now: float, outcome: str, reason: str):
        """The single exit for every request: exactly one terminal
        outcome, slot+pages freed, counters and tracer fed."""
        if req.done:
            return
        req.done = True
        req.outcome = outcome
        req.finish_reason = reason
        req.finish_t = now
        self._release_slot(req)
        if outcome == "finished":
            self.requests_completed += 1
        elif outcome == "shed":
            self.requests_shed += 1
        else:
            self.requests_cancelled += 1
        usage = self._usage()
        if usage is not None:
            usage.note_outcome(req.tenant, outcome)
        tr = self._tracer()
        if tr is not None:
            tr.on_finish(req, reason)

    def _shed(self, req: Request, reason: str):
        req.shed_reason = reason
        self._terminate(req, time.perf_counter(), "shed", "shed")

    def _reap(self) -> bool:
        """Process cancellations and ``timeout_s`` expiries — queued,
        admitting and live alike. A cancelled/timed-out request frees its
        slot and pages *now*, not at engine close."""
        now = time.perf_counter()
        progressed = False

        def expired(req):
            return (
                req.timeout_s is not None and now - req.submit_t > req.timeout_s
            )

        for req in list(self._slot_req.values()):
            if req._cancel or expired(req):
                self._terminate(
                    req, now, "cancelled",
                    "cancelled" if req._cancel else "timeout",
                )
                progressed = True
        if self._admitting is not None:
            req = self._admitting[0]
            if req._cancel or expired(req):
                self._abort_admission(
                    now, "cancelled", "cancelled" if req._cancel else "timeout"
                )
                progressed = True
        queued = (
            self._sched.queued() if self._sched is not None else list(self._queue)
        )
        for req in queued:
            if req._cancel or expired(req):
                if self._sched is not None:
                    self._sched.remove(req)
                else:
                    try:
                        self._queue.remove(req)
                    except ValueError:
                        continue
                self._terminate(
                    req, now, "cancelled",
                    "cancelled" if req._cancel else "timeout",
                )
                progressed = True
        return progressed

    def _abort_admission(self, now: float, outcome: str, reason: str):
        """Tear down a mid-prefill admission (cancel/timeout/page
        exhaustion): the slot returns to the free list, its partially
        prefilled pages are released, the request terminates."""
        req, slot = self._admitting[0], self._admitting[1]
        self._admitting = None
        if self._restore is not None:
            # a mid-restore abort: the target pages were allocated but
            # never published — release them here or they leak
            for p in self._restore["pages"]:
                self._allocator.release(p)
            self._restore = None
            self.kv_restores_aborted += 1
        self._release_slot_pages(slot, tenant=req.tenant)
        self._free.append(slot)
        req.slot = None
        if outcome == "shed":
            req.shed_reason = reason if req.shed_reason is None else req.shed_reason
            self._terminate(req, now, "shed", "shed")
        else:
            self._terminate(req, now, outcome, reason)

    # -- pressure: shedding and preemption ----------------------------------

    def _page_free_frac(self) -> float:
        usable = self.num_pages - self._allocator.reserved
        return self._allocator.free_count / max(1, usable)

    def _shed_on_pressure(self) -> bool:
        """Watermark load shedding: when the paged arena's free fraction
        drops below the configured watermark, drop the newest
        lowest-priority queued request each step (queued work that could
        not be admitted anyway) with a telemetry event."""
        if self._sched.total_queued == 0:
            return False
        # prefix-cache-held pages are reclaimable, not pressure: evict LRU
        # entries first and only shed if the arena is still below the
        # watermark (i.e. the pages are pinned by live slots or a fault
        # injector, not the cache)
        while (
            self._page_free_frac() < self._sched.config.page_low_watermark
            and self._prefix is not None
            and self._prefix.evict_lru()
        ):
            pass
        if self._page_free_frac() >= self._sched.config.page_low_watermark:
            return False
        # only shed queued work that really "could not be admitted
        # anyway": a queued request that outranks a live slot is
        # preemption's job (_maybe_preempt runs right after), so bound
        # the pick to classes no live slot loses to — shedding the lone
        # high-priority interactive request while low-priority batch
        # slots pin the arena would invert priority
        live = [int(r.priority) for r in self._slot_req.values()]
        victim = self._sched.pick_shed(
            max_priority=(min(live) + 1) if live else None
        )
        if victim is None:
            return False
        self._sched.shed(victim)
        victim.shed_reason = SHED_PAGE_PRESSURE
        self._terminate(victim, time.perf_counter(), "shed", "shed")
        flight = getattr(self.telemetry, "flight", None)
        if flight is not None:
            flight.note("request_shed", request_id=victim.id,
                        reason=SHED_PAGE_PRESSURE,
                        free_frac=round(self._page_free_frac(), 4))
        return True

    def _maybe_preempt(self) -> bool:
        """Page out the lowest-priority victim slot when a strictly
        higher-priority request waits and no slot is free (at most one
        preemption per scheduler iteration)."""
        if (
            self._free or self._admitting is not None or not self._slot_req
            or self._sched.total_queued == 0
        ):
            return False
        best = self._sched.peek_priority()
        if best is None:
            return False
        victim = self._sched.pick_victim(self._slot_req.items(), best)
        if victim is None:
            return False
        if self._settle():
            # the tokens just read may have ended a request and freed a
            # slot: decide again, with nothing unread
            self._maybe_preempt()
            return True
        self._preempt(*victim)
        return True

    def _preempt(self, slot: int, req: Request):
        """Suspend a live request: save its decode-RNG chain (a host
        transfer — no compiled program), publish its KV pages to the
        prefix cache, release the slot, and requeue it at the front of
        its class. Re-admission replays prompt+generated via the prefix
        cache (mostly hits) and restores the saved chain — token-exact
        vs. an uninterrupted run, asserted in tests. The callers settle
        first: the saved chain stands behind every dispatched step, so
        ``req.tokens`` must hold every dispatched token too."""
        assert req._dispatched == len(req.tokens), "preempt with a token in flight"
        # whole-array device_get then host index: jnp fancy-indexing one
        # row would compile a gather, breaking the zero-recompile invariant
        rng_row = np.asarray(jax.device_get(self._rngs))[slot].copy()
        self._slot_req.pop(slot, None)
        self._active[slot] = False
        if self._prefix is not None and req.tokens:
            replay = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
            )
            # page out THROUGH the prefix cache: the entries hold the
            # refs, so re-admission maps them back as cache hits (and
            # LRU eviction can still reclaim them under real pressure)
            self._prefix.insert(
                replay, self._tables_host.rows[slot], tenant=req.tenant
            )
        self._release_slot_pages(slot, tenant=req.tenant)
        self._free.append(slot)
        req.slot = None
        req.preemptions += 1
        req._resume = {"rng": rng_row}
        self.preemptions += 1
        usage = self._usage()
        if usage is not None:
            usage.note_preempt(req.tenant)
        self._sched.requeue(req)
        tr = self._tracer()
        if tr is not None:
            tr.on_preempt(req)
        flight = getattr(self.telemetry, "flight", None)
        if flight is not None:
            flight.note("request_preempt", request_id=req.id, slot=slot,
                        tokens=len(req.tokens))

    def _relieve_pressure(self, req: Request, exclude_slot: int) -> bool:
        """A live slot could not grow its pages: preempt a strictly
        lower-priority victim (freeing its pages for this one) if the
        scheduler allows it. False when no victim qualifies — the caller
        sheds ``req`` instead of wedging."""
        if self._sched is None:
            return False
        self._settle()
        victim = self._sched.pick_victim(
            ((s, r) for s, r in self._slot_req.items() if s != exclude_slot),
            int(req.priority),
        )
        if victim is None:
            return False
        self._preempt(*victim)
        return True

    # -- internals ---------------------------------------------------------

    def _tracer(self):
        """The session's request tracer, or None — the whole per-request
        tracing layer costs one attribute check when telemetry is off."""
        if self.telemetry is None:
            return None
        return getattr(self.telemetry, "requests", None)

    def _usage(self):
        """The session's per-tenant usage accountant, or None — the same
        one-attribute-check contract as the tracer (telemetry/usage.py)."""
        if self.telemetry is None:
            return None
        return getattr(self.telemetry, "usage", None)

    def _note_forensics(self, fn: str, tree):
        """Fingerprint one compiled-program dispatch for recompile
        forensics; one attribute check when telemetry is off (the engine's
        no-recompile invariant means a diagnosed cause here IS a bug)."""
        if self.telemetry is None:
            return
        from ..telemetry import forensics

        forensics.note_call(fn, tree)

    def _flight_dump(self, reason: str):
        flight = getattr(self.telemetry, "flight", None)
        if flight is not None:
            try:
                flight.dump(reason)
            except Exception:
                pass

    def flight_dump(self, reason: str) -> bool:
        """Capture a flight-recorder debug bundle now (the public face of
        the internal hook — ``POST /v1/flight`` on a ReplicaServer and
        the canary's failing-probe action both land here). Returns
        whether a flight recorder exists to dump to."""
        has_flight = getattr(self.telemetry, "flight", None) is not None
        self._flight_dump(str(reason))
        return has_flight

    def _plan_chunks(self, prompt_len: int):
        """(start, bucket) list covering [0, prompt_len) from the fixed
        ``prefill_chunks`` — largest bucket that fits, smallest (padded)
        for the tail. The admit plan's unit: prefix-hit policy and the
        capacity guard count in these buckets (the packed dispatch itself
        takes rows up to its grid capacity, whatever the buckets)."""
        plan, start = [], 0
        while start < prompt_len:
            rem = prompt_len - start
            fit = [c for c in self.prefill_chunks if c <= rem]
            bucket = fit[-1] if fit else self.prefill_chunks[0]
            plan.append((start, bucket))
            start += bucket
        return plan

    def _plan_cover(self, prompt_len: int) -> int:
        plan = self._plan_chunks(prompt_len)
        start, bucket = plan[-1]
        return start + bucket

    # -- paged-arena bookkeeping -------------------------------------------

    def _alloc_page(self, kind=None) -> int:
        """One fresh page of ``kind``'s pool (the first kind's by default),
        evicting LRU prefix-cache entries under pressure. Exhaustion with
        nothing left to evict raises :class:`PagePressure`, which the
        admission/decode paths translate into a scheduling decision
        (preempt a victim, shed the request) — never an exception out of
        ``step()``."""
        kind = kind or self._kinds[0]
        page = kind.allocator.alloc()
        while (page is None and kind is self._kinds[0]
               and self._prefix is not None and self._prefix.evict_lru()):
            page = kind.allocator.alloc()
        if page is None:
            raise PagePressure(
                f"paged KV arena exhausted ({kind.num_pages} {kind.name} pages, "
                f"{len(self._slot_req)} live slots): raise num_pages or "
                "lower num_slots/max_new_tokens for this overcommit ratio"
            )
        self.pages_allocated += 1
        return page

    def _ensure_writable(self, req, slot: int, lo_pos: int, hi_pos: int):
        """Before a dispatch that writes positions [lo_pos, hi_pos] for
        ``slot``: grow its page table to cover hi_pos, and copy-on-write
        fork any page in the write range that is shared (prefix cache or
        another slot still references it). Pure data changes: a table-entry
        scatter per new page and one fork program per copy."""
        th = self._tables_host
        ps = self.page_size
        usage = self._usage()
        for kind in self._kinds:
            kt = kind.tables
            # the table entry of the last position written: its page, but for
            # a closing kind, whose table is in entry order
            p_hi = kind.entries(hi_pos) // ps
            if kt.alloc_count[slot] <= p_hi and kt.alloc_count[slot] == kt.released[slot]:
                # nothing live in this slot's table (a fresh slot of a window
                # kind whose write starts past its first pages): skip what
                # lies behind the window of the first position written
                kt.alloc_count[slot] = kt.released[slot] = max(
                    kt.alloc_count[slot], kind.first_live_entry(lo_pos))
            grown = []
            try:
                if kt.aside and not kt.aside_held[slot]:
                    # a closing kind: the pages the open window's summaries
                    # are pooled into, in the row's last columns (all of
                    # them or, under page pressure, none)
                    aside = []
                    try:
                        for _ in range(kt.aside):
                            aside.append(self._alloc_page(kind))
                    except PagePressure:
                        for page in aside:
                            kind.allocator.release(page)
                        self.pages_allocated -= len(aside)
                        raise
                    first = kt.pages_per_slot - kt.aside
                    kt.rows[slot][first:] = aside
                    kt.aside_held[slot] = True
                    grown += [(first + i, page) for i, page in enumerate(aside)]
                    req.pages_allocated += kt.aside
                    if usage is not None:
                        usage.note_pages(req.tenant, kt.aside)
                while kt.alloc_count[slot] <= p_hi:
                    idx = kt.alloc_count[slot]
                    page = self._alloc_page(kind)
                    kt.rows[slot][idx] = page
                    kt.alloc_count[slot] = idx + 1
                    grown.append((idx, page))
                    req.pages_allocated += 1
                    if usage is not None:
                        # growth: one more page held; a CoW fork below is held-
                        # count-neutral (fresh page replaces the shared claim)
                        usage.note_pages(req.tenant, 1)
            finally:
                # one table program a kind: the entry for a decode step's one
                # page, the whole row for a prefill pack's many (its stale
                # entries behind a window become parking entries on the way)
                if len(grown) == 1:
                    kind.device_tables = self._set_entry(kind.device_tables, slot, *grown[0])
                elif grown:
                    kind.device_tables = self._set_row(
                        kind.device_tables, slot, _row_upload(kt.rows[slot]))
        first = self._kinds[0]
        for idx in range(first.entries(lo_pos) // ps, first.entries(hi_pos) // ps + 1):
            page = int(th.rows[slot][idx])
            if not self._allocator.shared(page):
                continue
            fresh = self._alloc_page()
            self._arena = self._fork(self._arena, page, fresh)
            self._allocator.release(page)
            th.rows[slot][idx] = fresh
            self._page_tables = self._set_entry(self._page_tables, slot, idx, fresh)
            req.pages_allocated += 1
            self.page_forks += 1

    def _paged_admit_plan(self, req: Request, slot: int, seq: np.ndarray) -> list:
        """Map the longest cached prefix of ``seq`` into the slot's fresh
        page table (refcount++ per shared page) and return the chunk plan
        for the UNCACHED tail only — the prefix-cache TTFT win. ``seq``
        is the prompt on a fresh admission, or prompt+generated on a
        preemption resume (whose pages the page-out published, so the
        replay is mostly hits). At least the final token always prefills:
        its logits seed the first sampled token (discarded on resume).
        Returns [(global_start, bucket), ...]."""
        th = self._tables_host
        th.reset_slot(slot)
        cold_chunks = len(self._plan_chunks(seq.size))
        hit_len, entry = 0, None
        if self._prefix is not None:
            hit_len, entry = self._lookup_prefix(req, seq, cold_chunks)
        usage = self._usage()
        if entry is not None:
            n_map = -(-hit_len // self.page_size)
            for i in range(n_map):
                page = int(entry.pages[i])
                self._allocator.retain(page)
                th.rows[slot][i] = page
            th.alloc_count[slot] = n_map
            if usage is not None:
                usage.note_pages(req.tenant, n_map)
        if usage is not None and hit_len:
            usage.note_prefix_hit(req.tenant, hit_len)
        if hit_len:
            # tier attribution: a hit right after a restore belongs to
            # the tier that supplied the pages; every other committed
            # hit was HBM-resident all along
            self.kv_tier_hits[self._restored_tier or "hbm"] += 1
        req.prefix_hit = hit_len
        if hit_len:
            # prefill chunks the cached prefix made unnecessary (TTFT
            # attribution; the cold plan is what a miss would have run)
            self.prefill_chunks_skipped += cold_chunks - len(
                self._plan_chunks(seq.size - hit_len)
            )
        self._page_tables = self._set_row(
            self._page_tables, slot, _row_upload(th.rows[slot])
        )
        tail_plan = self._plan_chunks(seq.size - hit_len)
        return [(hit_len + start, bucket) for start, bucket in tail_plan]

    def _prefix_work(self) -> tuple:
        """The prefix cache's work counters now, for a span to difference:
        ``(probes, ghost_probes, hashed_tokens, entries_probed, evictions,
        evict_scanned)``, the first and third with the ghost shadows' own
        digests among them. Zeros in an engine without a prefix cache."""
        cache = self._prefix
        if cache is None:
            return (0, 0, 0, 0, 0, 0)
        ghost_n, ghost_tokens = (
            (cache.ghost.digests, cache.ghost.digested_tokens) if cache.ghost is not None else (0, 0))
        return (cache.digests + ghost_n, ghost_n, cache.digested_tokens + ghost_tokens,
                cache.entries_probed, cache.evictions, cache.evict_scanned)

    def _lookup_prefix(self, req: Request, seq: np.ndarray, cold_chunks: int):
        """The longest cached prefix of ``seq`` the admission commits to,
        as ``(hit_len, entry)``, under its own ``serving/prefix_lookup``
        span (the counts of the work at the same boundary)."""
        cache = self._prefix
        with _span("serving/prefix_lookup", request_id=req.id) as sp:
            work0 = self._prefix_work()
            hit_len, entry = cache.lookup(seq, limit=seq.size - 1)
            # the tail plan must still fit the slot (its padded cover can
            # exceed the whole-prompt cover when the tail is tiny)
            while hit_len and (
                hit_len + self._plan_cover(seq.size - hit_len)
                > self.max_cache_len
            ):
                hit_len = max(0, hit_len - self.page_size)
            # a hit whose tail needs MORE prefill dispatches than the cold
            # plan (e.g. cached 64 of a 256 prompt that cold-plans as one
            # 256 chunk but tail-plans as three 64s) is a TTFT loss, not a
            # win — decline it
            if hit_len and (
                len(self._plan_chunks(seq.size - hit_len)) > cold_chunks
            ):
                hit_len = 0
            if hit_len == 0:
                entry = None
            cache.record_hit(hit_len, entry)
            probes, ghost_probes, hashed, probed, *_ = (
                b - a for a, b in zip(work0, self._prefix_work()))
            # entries: those the lookup visited, one a length it probed
            sp.args.update(entries=probed, probes=probes, ghost_probes=ghost_probes,
                           hashed_tokens=hashed, hit_tokens=hit_len)
        return hit_len, entry

    def _insert_prefix(self, req: Request, slot: int):
        """Admission finished: publish this prompt's pages to the prefix
        cache (every page-aligned prefix + the full prompt), under a
        ``serving/prefix_insert`` span. The request's own boundary page
        becomes shared here — its first decode write into that page forks
        it, leaving the cached copy pristine."""
        cache = self._prefix
        if cache is None:
            return
        n_pages = -(-req.prompt.size // self.page_size)
        if n_pages > self._tables_host.alloc_count[slot]:
            return  # cannot happen post-prefill; guard for safety
        with _span("serving/prefix_insert", request_id=req.id) as sp:
            work0 = self._prefix_work()
            cache.insert(
                req.prompt, self._tables_host.rows[slot], tenant=req.tenant
            )
            probes, _, hashed, _, evictions, scanned = (
                b - a for a, b in zip(work0, self._prefix_work()))
            sp.args.update(probes=probes, hashed_tokens=hashed,
                           evictions=evictions, evict_scanned=scanned,
                           entries=len(cache.entries))

    def _release_slot_pages(self, slot: int, tenant: Optional[str] = None):
        """Eviction: drop the slot's page references (pages still retained
        by the prefix cache or another slot survive) and point its device
        table row back at the parking page, so a later all-inactive fused
        step can never write into a page that was reallocated."""
        held = 0
        for kind in self._kinds:
            th = kind.tables
            pages = th.slot_pages(slot)
            for page in pages:
                kind.allocator.release(page)
            held += len(pages)
            th.reset_slot(slot)
            kind.device_tables = self._set_row(
                kind.device_tables, slot, _row_upload(th.rows[slot])
            )
        if tenant is not None and held:
            usage = self._usage()
            if usage is not None:
                usage.note_pages(tenant, -held)

    def _release_behind_window(self, req: Request, slot: int, next_pos: int) -> int:
        """Give back the pages of every window kind that lie wholly behind
        the window of the slot's next query at ``next_pos``, and close the
        windows of a closing kind that lie wholly before it (after a prefill
        dispatch, and each round in ``serving/decode_grow``). Returns the
        pages released."""
        n = sum(kind.release_behind(slot, next_pos)
                for kind in self._kinds if kind.window is not None)
        for kind in self._kinds:
            if kind.closes is None:
                continue
            # a closing kind: the table is rewritten at a close, on the host
            # and (one row program) on the device; the pages given back are
            # read by nothing enqueued after this
            closed0 = kind.tables.closed[slot]
            given = kind.close_windows(slot, next_pos)
            if given:
                n += given
                self.windows_closed += kind.tables.closed[slot] - closed0
                kind.device_tables = self._set_row(
                    kind.device_tables, slot, _row_upload(kind.tables.rows[slot]))
        if n:
            self.pages_released += n
            usage = self._usage()
            if usage is not None:
                usage.note_pages(req.tenant, -n)
        return n

    # -- hierarchical KV tiering (HBM -> host -> disk -> peers) -------------

    def _note_tier_bytes(self, tenant: str, tier: str, delta: int):
        """TieredStore byte-movement hook -> the usage accountant's
        per-tenant tier byte-seconds meter (same symmetric contract as
        note_pages: every + has a matching -, held bytes drain to 0)."""
        if getattr(self, "telemetry", None) is None:
            # the disk-tier scan runs during __init__, before the
            # telemetry attribute lands — nothing to meter yet
            return
        usage = self._usage()
        if usage is not None:
            usage.note_tier_bytes(tenant, tier, delta)

    def _demote_entry(self, entry):
        """PrefixCache ``on_evict`` hook: gather the victim entry's
        pages off the arena (per-page through the warmup-compiled
        gather program — zero recompiles post-steady) and offer them to
        the host tier. Skips entries a tier already covers (a longer
        demoted entry serves every shorter aligned prefix), so the
        per-length cache entries never store the same pages twice."""
        tiers = self._tiers
        if tiers is None or entry.tokens is None or tiers.covers(entry.key):
            return
        if self._kv_paths is None:
            self._kv_paths = [p for p, _ in self._kv_leaf_specs()]
        from .pages import _page_axis as _pa

        per_page = [
            jax.device_get(self._gather_page(self._arena, int(p)))
            for p in entry.pages
        ]
        arrays = [
            np.concatenate([pp[i] for pp in per_page], axis=_pa(per_page[0][i]))
            for i in range(len(per_page[0]))
        ]
        tokens = np.asarray(entry.tokens, np.int32)
        tiers.put(TierEntry(
            key=entry.key, token_len=entry.token_len, tokens=tokens,
            n_pages=len(entry.pages), arrays=arrays, paths=self._kv_paths,
            nbytes=entry_nbytes(arrays, tokens), tenant=entry.tenant,
        ))

    def _plan_restore(self, req: Request, seq: np.ndarray) -> Optional[dict]:
        """Probe the lower tiers for a prefix of ``seq`` longer than the
        HBM cache's own best and, on a hit, allocate its target pages.
        Returns the restore state ``_advance_restore`` drives, or None
        (cold admission). Page pressure aborts the restore — a restore
        is an optimization, never worth shedding or preempting live
        work for — and the admission falls back to a cold prefill."""
        tiers = self._tiers
        if tiers is None or self._prefix is None or seq.size < 2:
            return None
        limit = seq.size - 1
        hbm_len, _ = self._prefix.peek(seq, limit)
        hit = tiers.probe(seq, limit, min_len=hbm_len)
        if hit is None:
            return None
        if hit["tier"] == "peer":
            try:
                tokens, token_len, _, arrays = self._handoff_arrays(
                    hit["handoff"]
                )
            except ValueError:
                self.kv_restores_aborted += 1
                return None
        else:
            tokens, arrays = hit["tokens"], hit["arrays"]
            token_len = hit["token_len"]
        # the same commit heuristics _paged_admit_plan applies to an HBM
        # hit, applied BEFORE paying for the restore: a hit the admit
        # plan would shrink or decline must not install pages first
        cold_chunks = len(self._plan_chunks(seq.size))
        hit_len = int(token_len)
        while hit_len and (
            hit_len + self._plan_cover(seq.size - hit_len) > self.max_cache_len
        ):
            hit_len = max(0, hit_len - self.page_size)
        if hit_len and (
            len(self._plan_chunks(seq.size - hit_len)) > cold_chunks
        ):
            hit_len = 0
        if hit_len <= hbm_len:
            return None
        n_pages = -(-hit_len // self.page_size)
        pages = []
        try:
            for _ in range(n_pages):
                pages.append(self._alloc_page())
        except PagePressure:
            for p in pages:
                self._allocator.release(p)
            self.kv_restores_aborted += 1
            return None
        return {
            "tier": hit["tier"],
            "tokens": np.asarray(tokens[:hit_len], np.int32),
            "arrays": arrays, "pages": pages, "next": 0,
            "t0": time.perf_counter(),
        }

    def _advance_restore(self, req: Request, slot: int, seq: np.ndarray):
        """One restore slice: install up to ``restore_batch_pages``
        pages through the warmup-compiled install program (async
        dispatches — the following ``_decode_once`` in the same
        scheduler iteration overlaps them with live slots' decode
        steps, the PR 2 dispatch-pipeline discipline). When the last
        page lands, the prefix registers in the HBM cache and the
        admission proceeds as a plain prefix hit — restored-hit ≡
        never-evicted hit, bit-for-bit."""
        r = self._restore
        batch = max(1, int(self._tiers.config.restore_batch_pages))
        overlapped = bool(self._slot_req)
        end = min(r["next"] + batch, len(r["pages"]))
        for i in range(r["next"], end):
            self._arena = self._install_page(
                self._arena, self._page_slice_tree(r["arrays"], i),
                r["pages"][i],
            )
        r["next"] = end
        self.kv_restore_batches += 1
        if overlapped:
            self.kv_restore_batches_overlapped += 1
        if end < len(r["pages"]):
            return
        # all pages installed: publish to the prefix cache (entries take
        # the refs), stamp the request's restore hop, and plan the
        # admission — whose lookup now takes the freshly restored hit
        self._prefix.insert(r["tokens"], r["pages"], tenant=req.tenant)
        for p in r["pages"]:
            self._allocator.release(p)
        if r["tier"] == "peer":
            self.kv_pages_imported += len(r["pages"])
        self.kv_restores += 1
        req.kv_restore_tier = r["tier"]
        req.kv_restore_pages = len(r["pages"])
        req.kv_restore_ms = round((time.perf_counter() - r["t0"]) * 1e3, 3)
        self._restore = None
        self._restored_tier = r["tier"]
        try:
            self._admitting[2] = self._paged_admit_plan(req, slot, seq)
        finally:
            self._restored_tier = None

    def kv_directory(self) -> dict:
        """Digest directory of this replica's exportable (HBM-cached)
        prefixes — what ``GET /v1/kv/directory`` serves and peers'
        TieredStores poll before pulling over ``/v1/kv/export``. Digest
        is the prefix cache's content key (blake2b-16 of the int32
        token bytes), hex-encoded; a peer holding the same tokens
        computes the same digest locally, so no token lists travel
        until a pull actually happens."""
        prefixes = []
        if self._prefix is not None:
            for entry in self._prefix.entries.values():
                prefixes.append({
                    "digest": entry.key.hex(),
                    "token_len": int(entry.token_len),
                })
        return {
            "version": 1, "replica": self.replica,
            "page_size": self.page_size,
            "kv_cache_dtype": self.kv_cache_dtype,
            "prefixes": prefixes,
        }

    # -- KV handoff (prefill -> decode replicas, session migration) ---------

    def _page_slice_tree(self, arrays=None, page_index: int = 0):
        """Pytree matching the arena where every K/V leaf is a size-1
        page slice — what the compiled install program consumes. With
        ``arrays`` (the per-leaf host arrays a handoff carries, arena
        flatten order), the slice is that payload's ``page_index``-th
        page; without, zeros (the warmup compile). Non-K/V leaves become
        fresh zeros so nothing aliases the donated arena."""
        from .pages import _page_axis, is_paged_leaf

        flat, treedef = jax.tree_util.tree_flatten_with_path(self._arena)
        it = iter(arrays) if arrays is not None else None
        leaves = []
        for path, leaf in flat:
            if is_paged_leaf(path):
                axis = _page_axis(leaf)
                if it is None:
                    shape = list(leaf.shape)
                    shape[axis] = 1
                    leaves.append(jnp.zeros(shape, leaf.dtype))
                else:
                    leaves.append(
                        jnp.asarray(np.take(next(it), [page_index], axis=axis))
                    )
            else:
                leaves.append(jnp.zeros(leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _kv_leaf_specs(self) -> list:
        """(path, leaf) for every K/V leaf, arena flatten order — the
        handoff wire format's leaf identity (payloads AND scale arenas:
        found by name, so they always travel together)."""
        from .pages import is_paged_leaf

        flat, _ = jax.tree_util.tree_flatten_with_path(self._arena)
        return [
            (jax.tree_util.keystr(path), leaf)
            for path, leaf in flat if is_paged_leaf(path)
        ]

    def _refuse_handoff(self):
        if self._by_kind:
            raise NotImplementedError(
                "ServingEngine: KV handoff (export_prefix_kv / import_prefix_kv) is not "
                "supported for a model with layer kinds, a window, a sink, experts or a "
                "recurrent state")

    def export_prefix_kv(self, tokens) -> Optional[dict]:
        """Export the longest cached prefix of ``tokens`` as a KV handoff:
        the quantized payload+scales pages shipped VERBATIM (bytes off the
        arena, no dequant/requant round trip — the PR 10 wire format), so
        an importing replica admits the prefix bit-identically to a local
        warm-cache hit. Returns None when nothing is cached. A prefill
        replica calls this for a finished prompt; a router calls it to
        migrate a session's KV off a draining replica. The probe uses
        ``PrefixCache.peek`` — exports never skew the hit gauges."""
        self._refuse_handoff()
        if self._prefix is None:
            raise ValueError("KV handoff needs the prefix cache (prefix_cache=True)")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size < 1:
            return None
        hit_len, entry = self._prefix.peek(tokens)
        if not hit_len:
            return None
        import base64

        n_pages = -(-hit_len // self.page_size)
        ids = [int(p) for p in entry.pages[:n_pages]]
        # per-page through the warmup-compiled gather (same as demotion):
        # a per-call id list would compile per distinct page count, and
        # a donor serving peer pulls exports in steady state
        from .pages import _page_axis as _pa

        per_page = [
            jax.device_get(self._gather_page(self._arena, p)) for p in ids
        ]
        gathered = [
            np.concatenate([pp[i] for pp in per_page],
                           axis=_pa(per_page[0][i]))
            for i in range(len(per_page[0]))
        ]
        leaves = []
        for (path, leaf), pages in zip(self._kv_leaf_specs(), gathered):
            leaves.append({
                "path": path,
                "dtype": pages.dtype.name,
                "shape": list(pages.shape),
                "data": base64.b64encode(
                    np.ascontiguousarray(pages).tobytes()
                ).decode("ascii"),
            })
        self.kv_pages_exported += n_pages
        return {
            "version": KV_WIRE_VERSION,
            "page_size": self.page_size,
            "kv_cache_dtype": self.kv_cache_dtype,
            "token_len": int(hit_len),
            "tokens": [int(t) for t in tokens[:hit_len]],
            "n_pages": n_pages,
            "replica": self.replica,
            "leaves": leaves,
        }

    def _handoff_arrays(self, handoff: dict):
        """Validate a KV handoff dict against this arena's wire
        identity (version, page size, KV dtype, leaf layout) and decode
        its payload. Returns ``(tokens, token_len, n_pages, arrays)``;
        raises ValueError on any mismatch. Shared by the import
        endpoint and the peer-tier restore path — one validator, so a
        peer pull can never install what an import would reject."""
        if handoff.get("version") != KV_WIRE_VERSION:
            raise ValueError(
                f"KV handoff version {handoff.get('version')!r} != {KV_WIRE_VERSION} "
                "(the stored bytes differ between versions)"
            )
        if int(handoff["page_size"]) != self.page_size:
            raise ValueError(
                f"KV handoff page_size {handoff['page_size']} != engine "
                f"page_size {self.page_size}"
            )
        if (handoff.get("kv_cache_dtype") or "bf16") != self.kv_cache_dtype:
            raise ValueError(
                f"KV handoff kv_cache_dtype {handoff.get('kv_cache_dtype')!r} "
                f"!= engine {self.kv_cache_dtype!r}"
            )
        tokens = np.asarray(handoff["tokens"], np.int32).reshape(-1)
        token_len = int(handoff["token_len"])
        n_pages = int(handoff["n_pages"])
        if tokens.size != token_len or n_pages != -(-token_len // self.page_size):
            raise ValueError("KV handoff token/page accounting is inconsistent")
        import base64

        from .pages import _page_axis

        specs = self._kv_leaf_specs()
        wire = handoff["leaves"]
        if len(wire) != len(specs):
            raise ValueError(
                f"KV handoff carries {len(wire)} K/V leaves, engine arena "
                f"has {len(specs)} — different model/cache layout"
            )
        arrays = []
        for (path, leaf), spec in zip(specs, wire):
            axis = _page_axis(leaf)
            expect = list(leaf.shape)
            expect[axis] = n_pages
            arr = np.frombuffer(
                base64.b64decode(spec["data"]), np.dtype(spec["dtype"])
            ).reshape(spec["shape"])
            if spec["path"] != path or list(arr.shape) != expect \
                    or arr.dtype != leaf.dtype:
                raise ValueError(
                    f"KV handoff leaf {spec['path']} "
                    f"({spec['dtype']}{spec['shape']}) does not match engine "
                    f"leaf {path} ({leaf.dtype.name}, page-gathered {expect})"
                )
            arrays.append(arr)
        return tokens, token_len, n_pages, arrays

    def import_prefix_kv(self, handoff: dict) -> int:
        """Install a peer's KV handoff into this arena's prefix cache:
        allocate pages, write each payload page through the (warmup-
        compiled) install program, register the token prefix — so the
        next admission of those tokens takes the prefix-hit path exactly
        as if this replica had prefilled them itself. Returns the token
        length now served from cache (0 when page pressure blocked the
        install — a handoff is an optimization, never worth shedding live
        work for). Raises ValueError on an incompatible wire format
        (page size, KV dtype, or leaf layout mismatch)."""
        self._refuse_handoff()
        if self._prefix is None:
            raise ValueError("KV handoff needs the prefix cache (prefix_cache=True)")
        tokens, token_len, n_pages, arrays = self._handoff_arrays(handoff)
        have, _ = self._prefix.peek(tokens)
        if have >= token_len:
            return have  # already cached at least this deep: nothing to do
        pages = []
        try:
            for _ in range(n_pages):
                pages.append(self._alloc_page())
        except PagePressure:
            for p in pages:
                self._allocator.release(p)
            return 0
        for i, dst in enumerate(pages):
            self._arena = self._install_page(
                self._arena, self._page_slice_tree(arrays, i), dst
            )
        self._prefix.insert(tokens, pages)
        # the cache entries hold the refs now; drop the allocation refs so
        # LRU eviction can reclaim the pages under real pressure
        for p in pages:
            self._allocator.release(p)
        self.kv_pages_imported += n_pages
        return token_len

    def _pop_next(self) -> Optional[Request]:
        """Next request to admit: the scheduler's WFQ/priority pick, or
        the FIFO head. Lazily skips requests that went terminal while
        queued (cancel racing the pop)."""
        while True:
            if self._sched is not None:
                req = self._sched.next_request()
            else:
                req = self._queue.popleft() if self._queue else None
            if req is None or not req.done:
                return req

    def _replay_seq(self, req: Request) -> np.ndarray:
        """The token sequence a preemption resume must re-prefill: the
        prompt plus every generated token except the last (whose K/V the
        next decode step writes — exactly the state the slot held when it
        was paged out)."""
        if not req.tokens:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens[:-1], np.int32)]
        )

    def _note_admission(self, req: Request, slot: int, tr):
        """``req`` left the queue for ``slot``: its ``admit_t`` stamp, the
        one ``serving/queue_wait`` span and the tracer's record."""
        now = time.perf_counter()
        req.admit_t = now
        wait = now - req.submit_t
        _emit_span("serving/queue_wait", req.submit_t, wait,
                   {"request_id": req.id, "slot": slot}, cat="serving")
        if tr is not None:
            tr.on_admission(req, slot, wait)

    def _note_prefill_chunk(self, req: Request, slot: int, start: int,
                            rows: int, t0: float, wall: float, tr):
        """One prefill dispatch carried ``rows`` of ``req`` from ``start``:
        the request's count, the one ``serving/prefill_chunk`` span (the
        dispatch's start and wall, shared by every request it packed) and
        the tracer's record."""
        req.prefill_dispatches += 1
        _emit_span("serving/prefill_chunk", t0, wall,
                   {"request_id": req.id, "slot": slot, "start": start,
                    "bucket": rows}, cat="serving")
        if tr is not None:
            tr.on_prefill_chunk(req, slot, start, rows, wall)

    def _note_first_token(self, req: Request, now: float, tr):
        req.first_token_t = now
        ttft = now - req.submit_t
        _emit_span("serving/first_token", req.submit_t, ttft,
                   {"request_id": req.id, "prompt_len": int(req.prompt.size),
                    "prefix_hit": int(req.prefix_hit),
                    "dispatches": req.prefill_dispatches,
                    "queue_wait_ms": round(1e3 * (req.admit_t - req.submit_t), 3)},
                   cat="serving")
        if tr is not None:
            tr.on_first_token(req, ttft)

    def _advance_admission(self) -> bool:
        """One admission dispatch: plan and pack on the host, enqueue the
        prefill program and put the slots it completes live on the device.
        Its first tokens stay in flight until ``_read_packs``."""
        tr = self._tracer()
        with _span("serving/admit_plan"):
            work = self._plan_dispatch(tr)
        if isinstance(work, bool):
            return work  # nothing to admit, or progress without a dispatch
        return self._ragged_dispatch(tr, *work)

    @contextlib.contextmanager
    def _page_grow_span(self, req: Request):
        """``serving/page_grow`` around the page growth for one packed
        request's rows (allocation, copy-on-write forks, the table programs),
        with what it did: pages allocated, prefix entries evicted under
        pressure and the entries ``evict_lru`` looked at to find them."""
        with _span("serving/page_grow", request_id=req.id) as sp:
            pages0 = self.pages_allocated
            *_, evictions0, scanned0 = self._prefix_work()
            try:
                yield
            finally:
                *_, evictions, scanned = self._prefix_work()
                sp.args.update(
                    pages_allocated=self.pages_allocated - pages0,
                    evictions=evictions - evictions0, evict_scanned=scanned - scanned0)

    def _retry_writable(self, req: Request, slot: int, lo: int, hi: int) -> bool:
        try:
            self._ensure_writable(req, slot, lo, hi)
            return True
        except PagePressure:
            return False

    def _admission_writable(self, req: Request, slot: int, lo: int, hi: int) -> bool:
        """Pages for the admission's next write range. Same ladder as
        live-slot growth (_grow_or_resolve): LRU eviction already failed
        inside _ensure_writable, so read what is in flight (its tokens may
        end requests and free their pages), then try paging out a strictly
        lower-priority victim before giving up — shedding the admission
        first would drop the highest-priority work under pressure. Only
        when no victim qualifies is the admission shed (never a raise out
        of step()); False then."""
        if self._retry_writable(req, slot, lo, hi):
            return True
        if self._settle() and self._retry_writable(req, slot, lo, hi):
            return True
        if self._relieve_pressure(req, slot) and self._retry_writable(req, slot, lo, hi):
            return True
        self._abort_admission(time.perf_counter(), "shed", SHED_PAGE_EXHAUSTED)
        flight = getattr(self.telemetry, "flight", None)
        if flight is not None:
            flight.note("request_shed", request_id=req.id,
                        reason=SHED_PAGE_EXHAUSTED)
        return False

    def _stage_request_keys(self, req: Request, slot: int):
        """``req``'s prefill and decode keys into its slot's rows, on the
        device (``jax.random.split`` inside one small program: the bits of
        the single-stream loop, and no host read of a key, which would wait
        for the step in flight)."""
        self._prefill_keys, self._decode_keys = self._stage_keys(
            self._prefill_keys, self._decode_keys, slot, req.rng)

    def _plan_dispatch(self, tr):
        """Everything of an admission step that comes before the prefill
        program is called: pop the next request, plan its pages, pack the
        rows on the host and upload them. Returns the dispatch's arguments,
        or a bool where there is none (False: nothing to admit)."""
        if self._admitting is None:
            if not self._free:
                return False
            req = self._pop_next()
            if req is None:
                return False
            slot = self._free.pop()
            if req._resume is not None:
                # preemption resume: replay prompt+generated (mostly
                # prefix-cache hits — the page-out published those pages),
                # discard the trailing sample (whatever key the slot's row
                # holds draws it), restore the saved RNG chain.
                seq = self._replay_seq(req)
            else:
                seq = req.prompt
                self._stage_request_keys(req, slot)
            # tier probe BEFORE the admit plan: a host/disk/peer hit
            # longer than HBM's best sets up a staged restore (plan
            # None until the pages land); otherwise plan immediately
            restore = self._plan_restore(req, seq)
            if restore is not None:
                self._restore = restore
                plan = None
            else:
                plan = self._paged_admit_plan(req, slot, seq)
            self._admitting = [req, slot, plan, 0, seq]
            if req._resume is not None:
                if tr is not None:
                    tr.on_resume(req, slot)
            else:
                self._note_admission(req, slot, tr)
        req, slot, plan, idx, seq = self._admitting
        if plan is None:
            # restore in flight: one page batch per scheduler iteration,
            # so the decode step right after overlaps the installs
            self._advance_restore(req, slot, seq)
            return True
        # one packed ragged dispatch (it may co-admit further queued
        # tails into the same grid)
        return self._ragged_pack(tr)

    def _ragged_pack(self, tr):
        """The host side of one packed ragged-prefill dispatch: the primary
        admission's next tail segment plus — when capacity remains — the
        WHOLE tails of further queued requests, packed token-block-aligned
        into the smallest compiled grid capacity that fits. Keeps the
        interleave discipline (one dispatch per scheduler iteration) and
        the zero-recompile invariant (grid capacities fixed at warmup).
        Returns ``_ragged_dispatch``'s arguments, or True where the
        admission was shed for pages."""
        req, slot, plan, idx, seq = self._admitting
        bt = self._ragged_bt
        cap_max = self._ragged_caps[-1]
        # ``idx`` is the next global position to prefill (0 = nothing
        # dispatched yet -> start past the prefix hit the admit plan
        # recorded; a first dispatch always advances past position 0,
        # so the sentinel is unambiguous)
        cur = plan[0][0] if idx == 0 else idx
        n = min(seq.size - cur, cap_max)
        # a closing kind: a slot's rows of one pack end at its window's close
        # (the summaries the rows behind the close read are pooled by this pack)
        for window in self._closing:
            n = min(n, window - cur % window)
        if self._faults is not None:
            self._faults.before_prefill(self)
        with self._page_grow_span(req):
            writable = self._admission_writable(req, slot, cur, cur + n - 1)
        if not writable:
            return True
        # packs: [request, slot, s0, s1, seq, primary]. The primary may be
        # mid-tail (longer than the largest grid); co-admitted tails are
        # always whole, so every co-admit completes in-dispatch and the
        # admission singleton invariant (_reap/_abort only ever see
        # self._admitting[0]) holds.
        packs = [[req, slot, cur, cur + n, seq, True]]
        used = -(-n // bt) * bt
        # co-admission: pull further queued requests into the same grid.
        # FIFO only (a scheduler's WFQ/priority pick must stay one-at-a-
        # time so its accounting observes each admission), no KV tiers
        # (a tier probe can stage a restore, which needs the singleton),
        # and a conservative no-hit fit check — a prefix hit only ever
        # shrinks the tail, so fitting cold guarantees fitting planned.
        if self._sched is None and self._tiers is None:
            while self._free and self._queue and used + bt <= cap_max:
                nxt = self._queue[0]
                if nxt.done:
                    self._queue.popleft()
                    continue
                if nxt._resume is not None:
                    # resumes restore a saved RNG chain and emit nothing;
                    # they admit alone through the singleton path
                    break
                if used + -(-int(nxt.prompt.size) // bt) * bt > cap_max:
                    break
                if any(int(nxt.prompt.size) > window for window in self._closing):
                    break  # a whole tail that crosses a close admits alone, window by window
                self._queue.popleft()
                slot2 = self._free.pop()
                plan2 = self._paged_admit_plan(nxt, slot2, nxt.prompt)
                hit2 = plan2[0][0]
                n2 = int(nxt.prompt.size) - hit2
                try:
                    with self._page_grow_span(nxt):
                        self._ensure_writable(nxt, slot2, hit2, hit2 + n2 - 1)
                except PagePressure:
                    # back out this co-admission and requeue at the head:
                    # it re-admits alone next iteration, where the full
                    # relieve/shed pressure ladder applies
                    self._release_slot_pages(slot2, nxt.tenant)
                    self._free.append(slot2)
                    if nxt.prefix_hit:
                        self.kv_tier_hits["hbm"] -= 1
                        nxt.prefix_hit = 0
                    self._queue.appendleft(nxt)
                    break
                self._stage_request_keys(nxt, slot2)
                self._note_admission(nxt, slot2, tr)
                packs.append([nxt, slot2, hit2, hit2 + n2, nxt.prompt, False])
                used += -(-n2 // bt) * bt
        rcap = next(c for c in self._ragged_caps if c >= used)
        with _span("serving/pack_upload", rows=rcap):
            ids = np.zeros((1, rcap), np.int32)
            row_slot = np.full((rcap,), -1, np.int32)
            row_pos = np.full((rcap,), -1, np.int32)
            hist = np.zeros((self.num_slots,), np.int32)
            last_rows = np.zeros((self.num_slots,), np.int32)
            fresh = 0
            r = 0
            for preq, psl, s0, s1, pseq, _ in packs:
                nseg = s1 - s0
                nb = -(-nseg // bt)
                ids[0, r:r + nseg] = pseq[s0:s1]
                # pad rows of a pack's LAST block keep the slot id (the
                # kernel reads the block's first row to name its slot; pads
                # are dead through pos = -1, not slot = -1)
                row_slot[r:r + nb * bt] = psl
                row_pos[r:r + nseg] = np.arange(s0, s1)
                hist[psl] = s0
                last_rows[psl] = r + nseg - 1
                r += nb * bt
                fresh += nseg
            ids_dev, *rest = map(jnp.asarray, (ids, row_slot, row_pos, hist, last_rows))
            self._note_forensics(f"ragged_prefill_{rcap}", {"ids": ids_dev})
        return (packs, rcap, fresh, ids_dev, *rest)

    def _ragged_dispatch(self, tr, packs: list, rcap: int, fresh: int, ids_dev,
                         row_slot, row_pos, hist, last_rows) -> bool:
        """Enqueue one packed grid and put every pack it completes into its
        slot, on the device and in the host's books: from here on the slot
        is live, and the decode step of this same iteration carries it. The
        first tokens themselves stay on the device (``_admit_state`` takes
        them from the pack program's result) until ``_read_packs`` fetches,
        stamps and emits them, behind that decode dispatch."""
        with _span("serving/prefill_dispatch", rows=rcap, tokens=fresh,
                   requests=len(packs), arena_in_place=int(self._prefill_in_place),
                   **self._pages_walked(packs), **self._latent_pairs(packs),
                   **self._state_advanced(packs, fresh), **self._pages_pooled(packs)) as sp:
            self._arena, firsts, *load = self._ragged_prefill_fn(rcap)(
                self.params, self._arena, ids_dev, row_slot, row_pos, hist,
                self._tables_arg(), last_rows, self._prefill_keys,
            )
            self.prefill_packed_tokens += fresh
            self._prefill_rows_dispatched += rcap
            # (goes live, the token a resume continues from or -1 for the
            # pack's own first, length, a resume's saved chain in two words)
            meta = np.zeros((self.num_slots, 5), np.int32)
            meta[:, 1] = -1
            rows = []  # what _read_packs notes and emits for each pack
            for preq, psl, s0, s1, pseq, primary in packs:
                # a window kind's pages behind the next row's window go
                # back while the pack that reads them is still in flight:
                # whoever writes such a page next is a later dispatch on
                # the same stream, so the pack has read it by then
                self._release_behind_window(preq, psl, s1)
                if primary and s1 < pseq.size:
                    # mid-tail: the primary stays the admission singleton
                    # and resumes at position s1 next scheduler iteration
                    # (a mid-tail primary fills the whole grid, so it never
                    # coexists with co-admits)
                    self._admitting[3] = s1
                    rows.append((preq, psl, s0, s1, False))
                    continue
                if primary:
                    self._admitting = None
                if preq._resume is not None:
                    # the replayed slot continues where it was paged out:
                    # last emitted token, restored chain, no new emission
                    meta[psl, :3] = 1, preq.tokens[-1], pseq.size
                    meta[psl, 3:] = np.asarray(preq._resume["rng"], np.uint32).view(np.int32)
                    preq._dispatched = len(preq.tokens)
                    preq._resume = None
                    preq._last_token_t = 0.0
                    self.resumptions += 1
                    rows.append((preq, psl, s0, s1, False))
                else:
                    self._insert_prefix(preq, psl)
                    meta[psl, 0], meta[psl, 2] = 1, preq.prompt.size
                    preq._dispatched = 1
                    rows.append((preq, psl, s0, s1, True))
                preq.slot = psl
                preq.prefill_kernel = "ragged" if self._prefill_kernel_costed else "dense"
                self._slot_req[psl] = preq
                self._active[psl] = preq._dispatched < preq.max_new_tokens
            if meta[:, 0].any():
                self._tokens, self._lengths, self._rngs = self._admit_state(
                    self._tokens, self._lengths, self._rngs, firsts,
                    self._decode_keys, jnp.asarray(meta))
        self._flight_packs.append(_PackFlight(firsts, tuple(load), rows, rcap, fresh, sp))
        return True

    def _read_packs(self, in_flight: int) -> bool:
        """Fetch the first tokens of this iteration's packs, oldest first,
        and stamp and emit them. ``in_flight``: 1 where a decode step was
        enqueued behind them (the chip runs it while the host commits), 0
        where they are read with nothing behind."""
        if not self._flight_packs:
            return False
        flights, self._flight_packs = self._flight_packs, []
        for flight in flights:
            self._read_pack(flight, in_flight)
        return True

    def _read_pack(self, flight: "_PackFlight", in_flight: int):
        sp, tr, rcap, fresh = flight.span, self._tracer(), flight.rcap, flight.fresh
        with _span("serving/prefill_fetch", in_flight=in_flight) as sp_f:
            firsts_h, *load = jax.device_get((flight.firsts, *flight.load))  # one fetch
            firsts_h = np.asarray(firsts_h)
        if load:
            _load_args(sp, np.asarray(load[0]), fresh * self._pairs_per_token,
                       self._expert_rows(rcap))
        # a request waited from the dispatch on; the device worked on the
        # pack from when it had finished what lay before it
        t0, wall = sp.t0, sp_f.t1 - sp.t0
        device_wall = sp_f.t1 - max(sp.t0, self._last_result_t)
        self._last_result_t = sp_f.t1
        with _span("serving/prefill_commit") as sp_c:
            costs = (getattr(self.telemetry, "costs", None)
                     if self.telemetry is not None else None)
            if costs is not None:
                costs.note_wall(f"ragged_prefill_{rcap}", device_wall)
            usage = self._usage()
            now = time.perf_counter()
            first_tokens = 0
            for preq, psl, s0, s1, first in flight.rows:
                self._note_prefill_chunk(preq, psl, s0, s1 - s0, t0, wall, tr)
                if usage is not None:
                    usage.note_prefill(preq.tenant, s1 - s0)
                    # the shared dispatch wall is billed proportionally to
                    # each tenant's live tokens in the pack
                    usage.note_compute(
                        preq.tenant, device_wall * 1e3 * (s1 - s0) / max(fresh, 1)
                    )
                if not first or preq.done:
                    continue
                self._note_first_token(preq, now, tr)
                self._emit(preq, int(firsts_h[psl]), now)
                first_tokens += 1
            sp_c.args["first_tokens"] = first_tokens

    def _expert_rows(self, tokens: int) -> int:
        """Rows a grouped product of the expert layers multiplies at once in
        a program of ``tokens`` rows (``models/moe.expert_rows``; the expert
        runs of one model share their routing's widths)."""
        return expert_rows(tokens, *self._expert_widths)

    def _pages_walked(self, packs: list) -> dict:
        """What the ragged prefill kernel is handed in this pack, for the
        ``serving/prefill_dispatch`` span: ``pages_walked`` is the sum over
        the pack's token blocks of the live pages the arena walk of one
        layer of the first cache kind visits (``prefill_walk_pages``: the
        kernel's own count, from each pack's history and first positions);
        a model of several kinds adds ``pages_walked.<kind>`` for the
        others (a window kind walks its window's pages only). 0 where the
        kernel does not engage: the dense reference walks nothing."""
        bt, ps = self._ragged_bt, self.page_size

        def pages(kind):
            if not self._prefill_kernel_costed:
                return 0
            return sum(prefill_walk_pages(kind.entries(s0), kind.entries(pos), ps, kind.window)
                       for _, _, s0, s1, *_ in packs for pos in range(s0, s1, bt))

        first, *others = self._kinds
        return {"pages_walked": pages(first),
                **{f"pages_walked.{kind.name}": pages(kind) for kind in others}}

    def _latent_pairs(self, packs: list) -> dict:
        """What a pack asks of latent attention, for the
        ``serving/prefill_dispatch`` span (one layer's counts):
        ``latent_pairs`` the visible (row, entry) pairs, a row at position p
        seeing p + 1 entries, cached and the pack's own; ``latent_entries``
        the entries those rows see between them, each once; ``latent_expanded``
        the cached entries the pack up-projected into keys and values, 0:
        the pack program reads them absorbed, as they are stored. Nothing
        where the model has no latent kind."""
        if self._latent_kind is None:
            return {}
        return {"latent_pairs": sum((s1 * (s1 + 1) - s0 * (s0 + 1)) // 2 for _, _, s0, s1, *_ in packs),
                "latent_entries": sum(s1 for _, _, _, s1, *_ in packs), "latent_expanded": 0}

    def _pages_pooled(self, packs: list) -> dict:
        """``pages_pooled`` for the ``serving/prefill_dispatch`` span: the
        pages of a closing kind this pack fills, each pooled into one entry
        of its window's summaries inside the program (one layer's count).
        Nothing where the model has no such kind."""
        if not self._closing:
            return {}
        ps = self.page_size
        n = sum(s1 // ps - s0 // ps for _, _, s0, s1, *_ in packs)
        self.pages_pooled += n
        return {"pages_pooled": n}

    def _state_advanced(self, packs: list, rows: int) -> dict:
        """What a pack hands the state-space layers, for the
        ``serving/prefill_dispatch`` span: ``ssm_rows`` the pack's live rows,
        on which their recurrence advances (the grid's padding rows advance
        nothing, and the blocks the kernel copies for them are not counted),
        ``ssm_slots`` the slots whose state it advances and
        ``ssm_fresh_slots`` those of them it zeroes first, inside the program
        (a request's first chunk starts at position 0). Nothing where the
        model keeps no such state."""
        if self._state_kind is None:
            return {}
        return {"ssm_rows": rows, "ssm_slots": len(packs),
                "ssm_fresh_slots": sum(1 for _, _, s0, *_ in packs if s0 == 0)}

    def _next_write_pos(self, req: Request) -> int:
        """The slot's next cache write position: the latest dispatched
        token's K/V has not been written yet (prefill samples the first
        token, each decode step writes the PREVIOUS token before sampling
        the next). Counted in tokens dispatched, which the host knows
        without reading any."""
        return req.prompt.size + req._dispatched - 1

    def _note_walk(self, sp, walked: list) -> None:
        """What the decode kernel is handed this round, on the
        ``serving/decode_grow`` span: ``walked`` holds each grown slot's
        last write position, and ``walked_tokens`` the page-rounded tokens
        one layer of the first cache kind walks for them (a model of
        several kinds adds ``walked_tokens.<kind>`` for the others: a
        window kind walks its window's pages only). A block is
        ``_walk_block_pages`` table entries; a slot that is free,
        mid-admission or waiting for its last token to be read has no live
        tokens and is skipped whole."""
        block = self._walk_block_pages * self.page_size
        first, *others = self._kinds
        tokens = [first.walked_tokens(p) for p in walked]
        sp.args["walked_tokens"] = sum(tokens)
        sp.args["walked_blocks"] = sum(-(-w // block) for w in tokens)
        sp.args["skipped_slots"] = self.num_slots - int(self._active.sum())
        for kind in others:
            sp.args[f"walked_tokens.{kind.name}"] = sum(kind.walked_tokens(p) for p in walked)

    def _grow_or_resolve(self, req: Request, slot: int, pos: int) -> bool:
        """Grow a live slot's pages for its next write position, resolving
        page pressure by reading what is in flight (its tokens may end
        requests, this one too, and free their pages), then by preempting
        a strictly-lower-priority victim (its pages move here) or, when
        none qualifies, shedding ``req`` itself — the one request
        outgrowing capacity pays, the loop never raises. True when the
        slot is still live and writable."""
        while True:
            try:
                self._ensure_writable(req, slot, pos, pos)
                return True
            except PagePressure:
                if self._settle():
                    if req.done:
                        return False
                    continue
                if self._relieve_pressure(req, slot):
                    continue
                req.shed_reason = SHED_PAGE_EXHAUSTED
                self._terminate(req, time.perf_counter(), "shed", "shed")
                flight = getattr(self.telemetry, "flight", None)
                if flight is not None:
                    flight.note("request_shed", request_id=req.id,
                                reason=SHED_PAGE_EXHAUSTED)
                return False

    def _decode_once(self) -> bool:
        """Enqueue the next decode step over the active slots, then read the
        tokens of the step before it: the device runs one while the host
        emits the other. Where there is nothing to enqueue, what is in
        flight is read with nothing behind it."""
        if not self._active.any():
            return self._settle_step()
        with _span("serving/decode_grow") as sp:
            pages0, released0 = self.pages_allocated, self.pages_released
            # each grown slot's last write position of this round: the
            # decode kernel walks its pages up to that one (a slot
            # preempted later in this same loop, for another's pages,
            # stays counted)
            walked = []
            for slot, req in list(self._slot_req.items()):
                if self._slot_req.get(slot) is not req or not self._active[slot]:
                    # shed/preempted while relieving another slot, or its
                    # whole budget is dispatched: it waits for its last token
                    continue
                pos = self._next_write_pos(req)
                self._release_behind_window(req, slot, pos)
                if self._grow_or_resolve(req, slot, pos):
                    walked.append(pos)
            sp.args["pages_allocated"] = self.pages_allocated - pages0
            self._note_walk(sp, walked)
            if self._by_kind:
                sp.args["pages_released"] = self.pages_released - released0
            if self._closing:
                # the slots whose write fills a page: the step pools it
                pooled = sum(1 for p in walked if (p + 1) % self.page_size == 0)
                self.pages_pooled += pooled
                sp.args["pages_pooled"] = pooled
            roster = [(slot, req) for slot, req in self._slot_req.items() if self._active[slot]]
        if not roster:
            # every live slot was shed under page pressure, or ended by
            # the tokens that pressure made the engine read
            self._settle_step()
            return True
        if self._faults is not None:
            self._faults.before_decode(self)
        load = ()
        with _span("serving/decode_dispatch", slots=len(roster),
                   arena_in_place=int(self._arena_in_place),
                   # the page-rounded entries one latent layer's kernel reads
                   **({"latent_tokens": sum(self._latent_kind.walked_tokens(p) for p in walked)}
                      if self._latent_kind else {}),
                   # the live slots' states advance one token each (an idle
                   # slot's state is copied in and out unchanged: not counted)
                   **({"ssm_slots": len(roster), "ssm_rows": len(roster)} if self._state_kind else {})) as sp_d:
            self._note_forensics(
                "decode_step",
                {"tokens": self._tokens, "lengths": self._lengths,
                 "active": self._active, "rngs": self._rngs},
            )
            # the mask the program reads is its own copy: the engine changes
            # its own below, before the step has run
            active = self._active.copy()
            self._arena, self._tokens, self._lengths, self._rngs, *load = self._decode_step(
                self.params, self._arena, self._tokens, self._lengths, active,
                self._rngs, self._tables_arg(),
            )
            for slot, req in roster:
                req._dispatched += 1
                if req._dispatched >= req.max_new_tokens:
                    # its budget is on the device: it rides no further step (no
                    # wasted row), and keeps slot and pages until the host has
                    # read and emitted its last token
                    self._active[slot] = False
        before, self._flight = self._flight, _StepFlight(self._tokens, tuple(load), roster, sp_d)
        if before is not None:
            self._read_step(before, in_flight=1)
        return True

    def _settle_step(self) -> bool:
        """Read the decode step in flight, if any, with nothing behind it."""
        flight, self._flight = self._flight, None
        if flight is None:
            return False
        self._read_step(flight, in_flight=0)
        return True

    def _settle(self) -> bool:
        """Read everything in flight now, oldest first: the serial order,
        for whoever must know the tokens before acting (preemption, which
        saves a slot's chain; page pressure; ``drain()``; a fault script).
        Returns whether anything was read."""
        step = self._settle_step()
        return self._read_packs(0) or step

    def _read_step(self, flight: "_StepFlight", in_flight: int):
        """Fetch a decode step's tokens and emit them to the requests that
        rode it. ``in_flight``: the decode dispatches enqueued behind it
        when the host began to wait (1 overlapped, 0 settled)."""
        roster, sp_d = flight.roster, flight.span
        with _span("serving/token_fetch", in_flight=in_flight) as sp_f:
            host, *load = jax.device_get((flight.toks, *flight.load))  # forces the step; one fetch
            host = np.asarray(host)  # [N]
        if load:
            # the step's own span, one iteration after it closed
            _load_args(sp_d, np.asarray(load[0]), len(roster) * self._pairs_per_token,
                       self._expert_rows(self.num_slots))
        # the device took the step up when it had finished what lay before it
        wall = sp_f.t1 - max(sp_d.t0, self._last_result_t)
        self._last_result_t = sp_f.t1
        with _span("serving/emit") as sp_e:
            done0 = self.requests_completed
            self.step_count += 1
            self._usage_note_step(wall, roster)
            # rows computed for nothing: the request's eos was still in flight
            # when this step was enqueued (the write landed in its own page)
            discarded = sum(1 for _, req in roster
                            if req.done and req.finish_reason == "eos")
            emitted = 0
            for slot, req in roster:
                if req.done:
                    # ended since the dispatch: by that late eos, or
                    # cancelled, timed out or shed with this token in
                    # flight (it is dropped)
                    continue
                self._emit(req, int(host[slot]), sp_f.t1)
                emitted += 1
            self.rows_discarded += discarded
            # count DELIVERED tokens, not the roster: tokens/s must not
            # claim a row whose request had ended
            self._step_samples.append((wall, emitted))
            if self.telemetry is not None:
                self.telemetry.on_step(self, wall, tokens=emitted)
                costs = getattr(self.telemetry, "costs", None)
                if costs is not None:
                    costs.note_wall("decode_step", wall)
            sp_e.args["emitted"] = emitted
            sp_e.args["discarded"] = discarded
            sp_e.args["finished"] = self.requests_completed - done0

    def _usage_note_step(self, wall_s: float, roster):
        """Attribute one batched decode dispatch's wall across the
        tenants of the requests that rode it, evenly."""
        usage = self._usage()
        if usage is None or not roster:
            return
        share = wall_s * 1e3 / len(roster)
        for _, req in roster:
            usage.note_compute(req.tenant, share)

    def _emit(self, req: Request, token: int, now: float):
        req.tokens.append(token)
        self.generated_tokens += 1
        if self._sched is not None:
            self._sched.note_tokens(req.tenant, 1)
        usage = self._usage()
        if usage is not None:
            # the conservation law: per-tenant decode tokens sum exactly
            # to generated_tokens — both increment here and only here
            usage.note_decode(req.tenant)
        gap = (now - req._last_token_t) if req._last_token_t else None
        if gap is not None:
            self._itl.append(gap)
            self._itl_emitted += 1
            tr = self._tracer()
            if tr is not None:
                tr.on_token(req, gap, len(req.tokens) - 1)
        req._last_token_t = now
        if req.on_token is not None:
            try:
                req.on_token(token, req)
            except Exception:
                # a poisoned request (raising downstream consumer) must
                # cost exactly one request, never the serving loop
                self._terminate(req, now, "cancelled", "callback_error")
                return
        if self.eos_token_id is not None and token == self.eos_token_id:
            self._finish(req, now, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, now, "budget")

    def _finish(self, req: Request, now: float, reason: str = "budget"):
        self._terminate(req, now, "finished", reason)

    # -- metrics -----------------------------------------------------------

    def mark_steady(self):
        """Snapshot the compile counters: every compile AFTER this call
        counts as an admission recompile (the invariant says there are
        none). Call once the engine has seen each prefill grid + the
        decode step — e.g. after a warmup wave."""
        self._steady_mark = self._counters()

    @property
    def admission_recompiles(self) -> Optional[int]:
        """Backend compiles since :meth:`mark_steady` (None before it)."""
        if self._steady_mark is None:
            return None
        return self._counters()["count"] - self._steady_mark["count"]

    def executable_memory_stats(self, cached_only: bool = False) -> dict:
        """``memory_analysis`` of the live fused decode step — argument /
        output / temp / generated-code bytes, the flight-recorder bundle's
        "what was the compiled program actually holding" section. Computed
        ON THE ENGINE THREAD (at ``warmup()``, or the first direct call)
        and cached: a flight dump passes ``cached_only=True`` because its
        caller may be the watchdog thread diagnosing a WEDGED backend, and
        a fresh lower+compile there would hang exactly when the evidence
        matters. Backends without memory_analysis report {}."""
        if self._exe_mem is not None or cached_only:
            return self._exe_mem or {}
        try:
            compiled = self._decode_step.lower(
                self.params, self._arena, self._tokens, self._lengths,
                self._active, self._rngs, self._page_tables,
            ).compile()
            costs = getattr(self.telemetry, "costs", None)
            if costs is not None:
                # same AOT object feeds the roofline registry: the fused
                # decode step is almost always the memory-bound poster
                # child (per-token HBM traffic ~= whole KV arena + params)
                costs.capture("decode_step", compiled)
            ma = compiled.memory_analysis()
            out = {}
            for key in ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes"):
                v = getattr(ma, key, None)
                if isinstance(v, (int, float)):
                    out[key] = int(v)
            self._exe_mem = out
        except Exception:
            self._exe_mem = {}
        return self._exe_mem

    def metrics(self) -> dict:
        """Serving gauges, ``serving/``-namespaced for the telemetry rollup
        (TelemetrySession.attach_serving feeds these into every flush)."""
        out = {
            "serving/queue_depth": self._queued_depth(),
            "serving/slot_occupancy": len(self._slot_req) / self.num_slots,
            "serving/requests_completed": self.requests_completed,
            "serving/generated_tokens": self.generated_tokens,
            "serving/arena_bytes": self.arena_bytes,
            # storage bits per K/V value (16 = unquantized) — the capacity
            # dashboards read this beside arena_bytes/pages_total to tell
            # a quantized arena from a shrunk one
            "serving/kv_cache_bits": kv_cache_bits(self.kv_cache_dtype),
        }
        if (
            self._sched is not None
            or self.requests_shed or self.requests_cancelled or self.preemptions
        ):
            out["serving/shed"] = self.requests_shed
            out["serving/cancelled"] = self.requests_cancelled
            out["serving/preemptions"] = self.preemptions
            out["serving/resumptions"] = self.resumptions
        if self._sched is not None:
            out.update(self._sched.metrics())
        if self._controller is not None:
            out["serving/itl_budget"] = round(self._controller.budget, 4)
            out["serving/itl_slo_breaches"] = self._controller.breaches
            out["serving/itl_budget_adjustments"] = self._controller.adjustments
        if self._draining:
            out["serving/draining"] = True
        if self._step_samples:
            wall = sum(w for w, _ in self._step_samples)
            toks = sum(n for _, n in self._step_samples)
            if wall > 0:
                out["serving/tokens_per_s"] = toks / wall
            out["serving/decode_step_ms_p50"] = 1e3 * float(
                np.median([w for w, _ in self._step_samples])
            )
        # the terminal-outcome denominator the shed-rate burn alert
        # divides by (telemetry/alerts.py): every request that reached an
        # outcome, whatever it was
        out["serving/requests_terminal"] = (
            self.requests_completed + self.requests_shed
            + self.requests_cancelled
        )
        if self._itl:
            itl = np.asarray(self._itl)
            out["serving/itl_p50_ms"] = 1e3 * float(np.percentile(itl, 50))
            out["serving/itl_p95_ms"] = 1e3 * float(np.percentile(itl, 95))
            # recent-window p99 (same observation the AIMD controller
            # acts on): the live gauge the ITL burn-rate alert samples —
            # unlike the lifetime histogram p99, it decays once the
            # regression clears
            p99, _ = self._recent_itl_p99_ms()
            if p99 is not None:
                out["serving/itl_recent_p99_ms"] = round(p99, 3)
        out["serving/pages_in_use"] = self._allocator.in_use
        out["serving/pages_total"] = self.num_pages
        out["serving/page_size"] = self.page_size
        out["serving/page_forks"] = self.page_forks
        # dispatches enqueued whose results the host has not read: 1 between
        # the iterations of an engine that overlaps, 0 idle or settled
        out["serving/dispatch_depth"] = int(self._flight is not None) + len(self._flight_packs)
        out["serving/rows_discarded"] = self.rows_discarded
        out["serving/decode_kernel_active"] = bool(self._kernel_costed)
        out["serving/arena_in_place"] = int(self._arena_in_place)
        out["serving/decode_rows_per_product"] = self._decode_rows_per_product
        out["serving/decode_block_pages"] = self._walk_block_pages
        out["serving/decode_narrow_form"] = int(self._decode_narrow_form)
        out["serving/prefill_arena_in_place"] = int(self._prefill_in_place)
        out["serving/experts_from_stack"] = int(self._experts_from_stack)
        out["serving/prefill_kernel_active"] = bool(self._prefill_kernel_costed)
        if self._latent_kind is not None:
            # what a token costs one latent layer's pages as stored, and
            # whether both programs read them with the latent kernels
            out["serving/latent_bytes_per_token"] = self._latent_kind.token_bytes
            out["serving/mla_kernel_active"] = int(self._mla_kernel_costed)
        if self._state_kind is not None:
            # the state a slot keeps beside its pages (of arena_bytes), which
            # of the recurrences' kernels is engaged (ssm_scan, ssd_scan, gdn_scan),
            # and whether the programs hold one copy of the state
            out["serving/state_bytes"] = self.state_bytes
            out["serving/state_bytes_per_slot"] = self._state_kind.slot_bytes
            for name, costed in self._state_kernel_costed.items():
                out[f"serving/{name}_kernel_active"] = int(costed)
            out["serving/state_in_place"] = int(self._state_in_place)
        for kind in self._kinds[1:]:
            out[f"serving/pages_in_use.{kind.name}"] = kind.allocator.in_use
            out[f"serving/pages_total.{kind.name}"] = kind.num_pages
        if self._by_kind:
            out["serving/pages_released"] = self.pages_released
        if self._closing:
            out["serving/windows_closed"] = self.windows_closed
            out["serving/pages_pooled"] = self.pages_pooled
        out["serving/prefill_packed_tokens"] = int(
            self.prefill_packed_tokens
        )
        if self.kv_pages_exported or self.kv_pages_imported:
            out["serving/kv_pages_exported"] = self.kv_pages_exported
            out["serving/kv_pages_imported"] = self.kv_pages_imported
        if self._prefix is not None:
            out["serving/prefix_hit_ratio"] = self._prefix.hit_ratio
            out["serving/prefix_hit_tokens"] = self._prefix.hit_tokens
            out["serving/prefill_chunks_skipped"] = self.prefill_chunks_skipped
            if self._prefix.ghost is not None:
                # ghost-cache economics: the hit ratio the prefix
                # cache WOULD have at 2x/4x/10x entry capacity, plus
                # reuse-after-evict distances — the evidence base for
                # a host/disk KV tier (ROADMAP item 2)
                out.update(self._prefix.ghost.gauges())
        if self._tiers is not None:
            out.update(self._tiers.gauges())
            lookups = self._prefix.lookups if self._prefix else 0
            for tier, hits in self.kv_tier_hits.items():
                out[f"serving/kv_tier_hits_{tier}"] = hits
                out[f"serving/kv_tier_hit_ratio_{tier}"] = (
                    hits / lookups if lookups else 0.0
                )
            out["serving/kv_restores"] = self.kv_restores
            out["serving/kv_restores_aborted"] = self.kv_restores_aborted
            out["serving/kv_restore_batches"] = self.kv_restore_batches
            out["serving/kv_restore_overlap_frac"] = (
                self.kv_restore_batches_overlapped / self.kv_restore_batches
                if self.kv_restore_batches else 0.0
            )
        if self._prefill_rows_dispatched:
            # fraction of dispatched prefill rows that were padding
            out["serving/prefill_pad_waste_frac"] = (
                1.0 - self.prefill_packed_tokens / self._prefill_rows_dispatched
            )
        if self._steady_mark is not None:
            out["serving/admission_recompiles"] = self.admission_recompiles
        # the placement-signal contract (telemetry/fleet.py, documented in
        # docs/telemetry.md "Fleet view"): one comparable scalar a router
        # ranks replicas by, plus the raw components it folds — exported
        # by EVERY engine, scheduler or not
        from ..telemetry.fleet import load_score

        out["serving/num_slots"] = self.num_slots
        out["serving/free_slots"] = self.num_slots - len(self._slot_req)
        out["serving/free_pages"] = self._allocator.free_count
        out["serving/load_score"] = load_score(
            queue_depth=out["serving/queue_depth"],
            num_slots=self.num_slots,
            slot_occupancy=out["serving/slot_occupancy"],
            free_pages=out["serving/free_pages"],
            pages_total=self.num_pages,
            itl_recent_p99_ms=out.get("serving/itl_recent_p99_ms"),
            itl_slo_ms=(
                self._sched.config.itl_slo_ms if self._sched is not None else None
            ),
            draining=self._draining,
        )
        # sustainable-rate estimate + headroom (telemetry/capacity.py):
        # the autoscaler's scale decision inputs, fed the serving gauges
        # above plus the roofline registry's decode-step attribution
        from ..telemetry.capacity import CapacityModel

        if self._capacity_model is None:
            self._capacity_model = CapacityModel()
        costs = getattr(self.telemetry, "costs", None)
        if costs is not None:
            cap_in = dict(out)
            cap_in.update(costs.rollup_keys(probe=False))
        else:
            cap_in = out
        out.update(self._capacity_model.observe(cap_in))
        return out

    @classmethod
    def from_dispatched(cls, dispatched, **kwargs):
        """Engine over a DispatchedModel (offloaded / quantized params +
        its in-graph placement transform) — the serving counterpart of
        ``generation.generate_dispatched``."""
        params = dispatched._concrete(dispatched.params)
        return cls(
            dispatched.definition, params,
            param_placer=dispatched.param_placer(), **kwargs,
        )


def _row_upload(row):
    """A slot's host page-table row for a table program, as its own copy: an
    upload may alias the host's buffer or read it later than the call (the
    CPU backend does the first for an aligned array), and the engine writes
    the row again while earlier dispatches are still in flight."""
    return jnp.asarray(row.copy())


def _expert_load(mutated) -> tuple:
    """``(pairs [expert layers, held experts],)`` from what the expert
    layers wrote to their load collection, in layer order; ``()`` for a
    model without experts, whose programs return what they always did."""
    load = mutated.get(MOE_LOAD)
    if not load:
        return ()
    leaves = jax.tree_util.tree_leaves(load)
    return (jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in leaves], axis=0),)


def _load_args(sp, load, pairs_all: int, rows: int) -> None:
    """Expert load on a dispatch span: ``expert_pairs`` (pairs on held
    experts), ``expert_pairs_all`` (tokens x k over the expert layers),
    ``expert_load_max`` (most pairs on one expert of one layer),
    ``experts_idle`` (held experts of a layer that got no token),
    ``experts_touched`` (those that got one: the experts whose weights the
    step reads) and ``expert_chunks`` (grouped products of ``rows`` rows the program made,
    over the expert layers: one a layer with a held pair where the load fits
    ``models/moe.expert_rows``, more where a burst overflowed it)."""
    sp.args["expert_pairs"] = int(load.sum())
    sp.args["expert_pairs_all"] = int(pairs_all)
    sp.args["expert_load_max"] = int(load.max())
    sp.args["experts_idle"] = int((load == 0).sum())
    sp.args["experts_touched"] = int((load > 0).sum())
    sp.args["expert_chunks"] = sum(expert_chunks(n, rows) for n in load.sum(axis=-1))


class _StepFlight(NamedTuple):
    """A decode step enqueued and not read: its tokens and
    expert load on the device, the requests that rode it by slot, and its
    ``serving/decode_dispatch`` span."""

    toks: jax.Array
    load: tuple
    roster: list
    span: object


class _PackFlight(NamedTuple):
    """A pack enqueued and not read: its first tokens and expert load on
    the device, ``(request, slot, s0, s1, emits a first token)`` for each
    request it carried, and its ``serving/prefill_dispatch`` span."""

    firsts: jax.Array
    load: tuple
    rows: list
    rcap: int
    fresh: int
    span: object


def _stage_keys_fn(prefill_keys, decode_keys, slot, rng):
    """One request's two keys into its slot's rows (traced ``slot``: one
    compile): ``jax.random.split`` as the single-stream loop splits."""
    prefill_rng, decode_rng = jax.random.split(rng)
    return prefill_keys.at[slot].set(prefill_rng), decode_keys.at[slot].set(decode_rng)


def _admit_state_fn(tokens, lengths, rngs, firsts, decode_keys, meta):
    """One pack's slots go live, without a token crossing the host. ``meta``
    is ``[slots, 5]`` int32 from the host: whether the slot goes live, the
    token a resumed request continues from (-1: a fresh one takes the pack
    program's ``firsts``), its length, and a resume's saved key chain as two
    words (a fresh request's chain starts from its staged decode key)."""
    live, last, length = meta[:, 0] > 0, meta[:, 1], meta[:, 2]
    resumed = last >= 0
    saved = jax.lax.bitcast_convert_type(meta[:, 3:], jnp.uint32)
    return (
        jnp.where(live, jnp.where(resumed, last, firsts), tokens),
        jnp.where(live, length, lengths),
        jnp.where(live[:, None], jnp.where(resumed[:, None], saved, decode_keys), rngs),
    )


def generate_batched(
    definition,
    params,
    prompts,
    *,
    max_new_tokens: int = 32,
    num_slots: Optional[int] = None,
    seeds=None,
    **engine_kwargs,
):
    """One-shot batched generation: build a :class:`ServingEngine`, submit
    every prompt, run to completion. Returns a list of [prompt + generated]
    id arrays, token-exact vs. per-prompt ``generate()`` with the same
    seeds. For a long-lived server keep an engine instead — this helper
    rebuilds (and recompiles) per call."""
    engine = ServingEngine(
        definition, params,
        num_slots=num_slots or min(max(len(prompts), 1), 8),
        **engine_kwargs,
    )
    return engine.generate_batched(prompts, max_new_tokens=max_new_tokens, seeds=seeds)
