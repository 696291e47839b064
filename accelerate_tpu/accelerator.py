"""The Accelerator façade + TrainEngine (the jit-fused training core).

Parity target: /root/reference/src/accelerate/accelerator.py (3,562 LoC).
The reference keeps the torch eager loop and interposes wrappers (DDP, AMP
autocast, GradScaler). Here the same *user loop shape*

    model, optimizer, dataloader, scheduler = accelerator.prepare(...)
    for batch in dataloader:
        with accelerator.accumulate(model):
            outputs = model(**batch)
            accelerator.backward(outputs["loss"])
            optimizer.step(); scheduler.step(); optimizer.zero_grad()

is executed by staging onto XLA:

- ``model(**batch)`` runs ONE fused jit computing outputs AND gradients
  (grads stashed for the coming ``backward``) — same FLOPs as torch's
  fwd+bwd, no eager/grad-tape machinery;
- ``backward`` folds the stashed grads into the accumulation buffer
  (scaled 1/num_steps — the reference divides the loss instead,
  accelerator.py:2186);
- ``optimizer.step()`` applies one fused optax update (grad-clip + fp16
  loss-scale handling via lax.cond inside the jit);
- data-parallel gradient reduction is IMPLICIT: params are replicated /
  sharded over the mesh and the batch is sharded on dim0, so XLA inserts
  the psum over ICI — there is no DDP bucket machinery to configure.

For peak performance `accelerator.build_train_step(loss_fn)` fuses the whole
micro-batch loop (lax.scan) + update into a single XLA computation.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .data import DataLoaderDispatcher, DataLoaderShard, prepare_data_loader, skip_first_batches as _skip_first_batches
from .logging import get_logger
from .optimizer import AcceleratedOptimizer
from .parallel.sharding import (
    batch_spec,
    infer_param_sharding,
    replicate,
    shard_params,
    sharding_of,
    unbox_params,
)
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .utils.dataclasses import (
    AutocastKwargs,
    CompilePlugin,
    DataLoaderConfiguration,
    GradScalerKwargs,
    GradientAccumulationPlugin,
    InitProcessGroupKwargs,
    KwargsHandler,
    MixedPrecisionConfig,
    PrecisionType,
    ProfileKwargs,
    ProjectConfiguration,
    ShardingConfig,
)
from .utils.operations import (
    convert_outputs_to_fp32,
    convert_to_fp32,
    gather,
    gather_object,
    pad_across_processes,
    recursively_apply,
    reduce,
    send_to_device,
)
from .utils.random import default_keychain

logger = get_logger(__name__)


def _is_flax_module(obj) -> bool:
    try:
        import flax.linen as nn

        return isinstance(obj, nn.Module)
    except Exception:
        return False


def _default_loss_selector(outputs):
    """Find the scalar loss in model outputs (dict['loss'] / .loss / scalar /
    first element of a tuple)."""
    if isinstance(outputs, jax.Array) and outputs.ndim == 0:
        return outputs
    if isinstance(outputs, dict) and "loss" in outputs:
        return outputs["loss"]
    if hasattr(outputs, "loss"):
        return outputs.loss
    if isinstance(outputs, (tuple, list)) and len(outputs) > 0:
        return outputs[0]
    raise ValueError(
        "Could not locate a scalar loss in the model outputs; return a dict "
        "with a 'loss' key (or a scalar), or pass loss_fn= to prepare()."
    )


class Model:
    """Bundles a model definition with its variables — the unit `prepare()`
    accepts (torch modules carry params internally; JAX separates them).

    ``definition`` is either a flax linen Module or a pure
    ``apply(params, *args, **kwargs)`` callable. ``variables`` for flax is
    the full variables dict ({'params': ..., possibly 'batch_stats': ...});
    for a callable it is the params pytree itself.
    """

    def __init__(self, definition, variables, loss_fn: Optional[Callable] = None):
        self.definition = definition
        self.is_flax = _is_flax_module(definition)
        if self.is_flax and not (isinstance(variables, dict) and "params" in variables):
            variables = {"params": variables}
        self.variables = variables
        self.loss_fn = loss_fn

    @property
    def params(self):
        return self.variables["params"] if self.is_flax else self.variables

    @property
    def extra_collections(self) -> dict:
        if not self.is_flax:
            return {}
        return {k: v for k, v in self.variables.items() if k != "params"}


class PreparedModel:
    """What `prepare(model)` returns: callable like the original, running the
    fused forward(+grad) jit. ``train()``/``eval()`` toggle gradient
    computation and mutable-state updates (torch-parity)."""

    def __init__(self, engine: "TrainEngine"):
        self._engine = engine
        self.training = True

    def __call__(self, *args, **kwargs):
        return self._engine.model_call(self.training, *args, **kwargs)

    def train(self, mode: bool = True):
        self.training = mode
        return self

    def eval(self):
        self.training = False
        return self

    @property
    def params(self):
        return self._engine.params

    @property
    def variables(self):
        return self._engine.current_variables()

    def state_dict(self):
        return self._engine.current_variables()

    def unwrap(self) -> Model:
        m = Model(self._engine.model.definition, self._engine.current_variables(),
                  loss_fn=self._engine.model.loss_fn)
        return m


def _roll_fp8_stats(extra_state):
    """Advance the delayed-fp8 amax histories one optimizer step (forwards
    max-accumulate into the current slot; the engine rolls the slot HERE so
    accumulation microsteps / pipeline ticks share one slot and the window
    spans real steps — TE's per-iteration roll). No-op without a live
    "fp8_stats" collection. Callers must NOT roll on paths that cannot
    record amaxes (a user loss_fn cannot update mutable collections — its
    forwards discard the writes, and rolling anyway would drain a restored
    history to zeros within history_len steps)."""
    from collections.abc import Mapping

    if isinstance(extra_state, Mapping) and "fp8_stats" in extra_state:
        from .ops.fp8 import roll_amax_histories

        return {
            **extra_state,
            "fp8_stats": roll_amax_histories(extra_state["fp8_stats"]),
        }
    return extra_state


def _make_scale_state(kwargs: GradScalerKwargs) -> dict:
    """Dynamic loss scale (GradScaler analog) as a device pytree."""
    return {
        "scale": jnp.asarray(kwargs.init_scale, jnp.float32),
        "growth_tracker": jnp.asarray(0, jnp.int32),
    }


class TrainEngine:
    """Owns the device state (params/opt_state/accum grads/loss scale) and
    the jitted computations for one model+optimizer pair."""

    def __init__(
        self,
        model: Model,
        accelerator: "Accelerator",
    ):
        self.model = model
        self.accelerator = accelerator
        self.state = accelerator.state
        self.mesh = accelerator.state.mesh
        self.precision: MixedPrecisionConfig = accelerator.state.precision
        self.sharding_config: ShardingConfig = accelerator.state.sharding_config
        self.gradient_state = accelerator.gradient_state

        # --- shard parameters over the mesh (the FSDP/DDP-wrap analog) ---
        raw_params, logical_axes = unbox_params(model.params)
        self.param_sharding = infer_param_sharding(
            raw_params, self.mesh, self.sharding_config, logical_axes
        )
        if self.sharding_config.offload_params_to_host:
            # FSDP cpu_offload analog: master params live in pinned host;
            # every compute path streams them to HBM in-graph (_cast_params).
            # Scalar params stay on device (rank-0 placement rejected by SPMD).
            from .parallel.sharding import with_memory_kind

            self.param_sharding = jax.tree_util.tree_map(
                lambda sh, p: with_memory_kind(sh, "pinned_host") if getattr(p, "ndim", 0) >= 1 else sh,
                self.param_sharding,
                raw_params,
            )
        with jax.transfer_guard("allow"):
            self.params = shard_params(
                jax.tree_util.tree_map(
                    lambda p: jnp.asarray(p, self.precision.param_dtype)
                    if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating)
                    else jnp.asarray(p),
                    raw_params,
                ),
                self.param_sharding,
            )
        self.extra_state = replicate(model.extra_collections, self.mesh) if model.extra_collections else {}

        self.optimizer: Optional[optax.GradientTransformation] = None
        self.opt_state = None
        self.schedule: Optional[Callable] = None
        self.step_count = 0
        self._accum_grads = None
        self._accum_finite = None
        self._pending_grads = None
        self._pending_loss = None
        self._last_skipped = False
        self._clip_max_norm = None
        self.scale_state = (
            _make_scale_state(self.precision.grad_scaler)
            if self.precision.needs_loss_scaling
            else None
        )
        self.loss_fn = model.loss_fn or _default_loss_selector
        self._jit_cache: dict = {}
        self.donate_state = accelerator.compile_plugin.donate_state
        # telemetry session (set by Accelerator.prepare when enabled); the
        # step paths guard on `is not None` so disabled runs pay one check
        self.telemetry = None
        self._pipeline_fallback_warned = False
        # models can own their backward schedule (DecoderLM 1f1b pipeline:
        # interleaved per-microbatch fwd/bwd that reverse-mode AD cannot
        # express). Only usable when the loss comes from the model itself —
        # a user loss_fn would be silently ignored by the manual path.
        self._manual_vag = None
        self._manual_vag_wants_rng = False
        # model call-signature facts, resolved once: positional parameter
        # order (binds tuple batches by NAME in _extract_lm_batch) and
        # whether training should default flax `deterministic` to False —
        # only when the config actually carries dropout, so models without
        # it keep bit-identical traces
        self._call_argnames = ("input_ids", "labels")
        self._train_dropout_default = False
        self._det_argpos = -1
        if model.is_flax:
            import inspect

            try:
                sig_params = inspect.signature(model.definition.__call__).parameters
                self._call_argnames = tuple(sig_params)
                self._train_dropout_default = (
                    "deterministic" in sig_params
                    and getattr(
                        getattr(model.definition, "config", None), "dropout_rate", 0
                    )
                    > 0
                )
                if self._train_dropout_default:
                    self._det_argpos = self._call_argnames.index("deterministic")
            except (TypeError, ValueError):
                pass
        if model.loss_fn is None:
            getter = getattr(model.definition, "pipeline_value_and_grad", None)
            if getter is not None:
                self._manual_vag = getter()
                # dropout models need the per-step key threaded into the
                # schedule (per-(stage, microbatch) masks); gate on BOTH the
                # config needing it and the hook's signature accepting it, so
                # duck-typed hooks without an rng parameter keep working
                import inspect

                wants = (
                    getattr(getattr(model.definition, "config", None), "dropout_rate", 0) > 0
                )
                if wants:
                    hook_takes_rng = False
                    try:
                        hook_takes_rng = "rng" in inspect.signature(self._manual_vag).parameters
                    except (TypeError, ValueError):
                        pass
                    if not hook_takes_rng:
                        # the AD path would train WITH dropout for this
                        # config, so an rng-less duck-typed hook silently
                        # toggles regularization per-batch-routing (ADVICE r5)
                        logger.warning(
                            "model config has dropout_rate > 0 but its "
                            "pipeline_value_and_grad hook accepts no 'rng' "
                            "parameter: batches routed through the manual "
                            "pipeline schedule will train WITHOUT dropout. "
                            "Add an `rng=` kwarg to the hook to receive the "
                            "per-step dropout key."
                        )
                    wants = hook_takes_rng
                self._manual_vag_wants_rng = wants

    # ------------------------------------------------------------------
    # model apply plumbing
    # ------------------------------------------------------------------

    def _apply(self, params, extra_state, training: bool, rng_key, args, kwargs):
        """Pure forward: returns (outputs, new_extra_state)."""
        if self.model.is_flax:
            # training means dropout: a config with dropout_rate > 0 trains
            # non-deterministic by default (torch .train() parity) — the same
            # semantics the manual 1f1b path has, so flipping
            # pipeline_schedule never toggles regularization. An explicit
            # deterministic= in the call always wins.
            if (
                training
                and rng_key is not None
                and self._train_dropout_default
                and "deterministic" not in kwargs
                and len(args) <= self._det_argpos  # not already positional
            ):
                kwargs = {**kwargs, "deterministic": False}
            variables = {"params": params, **extra_state}
            mutable = list(extra_state.keys()) if (training and extra_state) else False
            rngs = {"dropout": rng_key} if (training and rng_key is not None) else None
            out = self.model.definition.apply(
                variables, *args, rngs=rngs, mutable=mutable, **kwargs
            )
            if mutable:
                outputs, new_state = out
                return outputs, new_state
            return out, extra_state
        else:
            return self.model.definition(params, *args, **kwargs), extra_state

    def _cast_params(self, params):
        if self.sharding_config.offload_params_to_host:
            from .parallel.sharding import transfer_tree

            params = transfer_tree(params, jax.memory.Space.Device)
        c = self.precision.compute_dtype
        return jax.tree_util.tree_map(
            lambda p: p.astype(c) if jnp.issubdtype(p.dtype, jnp.floating) else p, params
        )

    def _warn_pipeline_fallback(self, args, kwargs, reason: str = None):
        """One-time notice that a 1F1B-capable model is training through the
        AD/GPipe fallback: gradients are equivalent, but the O(M) activation
        stash silently replaces the configured O(S) schedule's memory
        profile — a model sized for 1F1B can OOM the moment a batch key
        forces this path (ADVICE r5). Names the offending key(s)."""
        if self._pipeline_fallback_warned:
            return
        self._pipeline_fallback_warned = True
        if reason is None:
            named = {}
            extra_positional = 0
            for i, a in enumerate(args):
                if i < len(self._call_argnames):
                    named[self._call_argnames[i]] = a
                else:
                    extra_positional += 1
            named.update(kwargs)
            offending = sorted(k for k in named if k not in ("input_ids", "labels"))
            if extra_positional:
                offending.append(f"{extra_positional} extra positional arg(s)")
            if offending:
                reason = f"batch key(s) {', '.join(offending)} forced the fallback"
            elif "labels" not in named:
                reason = "the batch carries no labels"
            else:
                reason = "the batch does not match the (input_ids, labels) signature"
        logger.warning(
            "model exposes pipeline_value_and_grad (1f1b schedule) but this "
            "training step runs through the AD/GPipe fallback: %s. The "
            "fallback computes identical gradients but stashes activations "
            "for ALL microbatches (O(M) memory instead of the schedule's "
            "O(S)) — a model sized for 1F1B can OOM here. Feed plain "
            "(input_ids, labels) batches to use the configured schedule.",
            reason,
        )

    # ------------------------------------------------------------------
    # staged computations
    # ------------------------------------------------------------------

    def _fwd_bwd_fn(self, params, extra_state, scale, rng_key, args, kwargs):
        """outputs + grads in one computation (see module docstring)."""
        if self._manual_vag is not None and not extra_state:
            ids, labels = _extract_lm_batch(args, kwargs, self._call_argnames)
            if labels is not None:
                # scale seeds the manual backward (scaled-domain grads, same
                # underflow protection as the AD path below), then unscale
                # before the finite check. scale=/rng= are passed only when
                # needed: the hook is duck-typed, and a 3-arg implementation
                # keeps working without fp16/dropout.
                extra = {}
                if scale is not None:
                    extra["scale"] = scale
                if self._manual_vag_wants_rng and rng_key is not None:
                    extra["rng"] = rng_key
                out, grads = self._manual_vag(
                    self._cast_params(params), ids, labels, **extra
                )
                # hooks return a scalar loss, or an outputs dict with "loss"
                # (MoE surfaces {"loss","lm_loss","aux_loss"} — same contract
                # as the AD path's model outputs)
                outputs = (
                    {k: v.astype(jnp.float32) for k, v in out.items()}
                    if isinstance(out, dict)
                    else {"loss": out.astype(jnp.float32)}
                )
                loss = outputs["loss"]
                if scale is not None:
                    grads = jax.tree_util.tree_map(
                        lambda g: (g.astype(jnp.float32) / scale), grads
                    )
                    finite = jnp.all(
                        jnp.asarray(
                            [jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)]
                        )
                    )
                else:
                    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
                    finite = jnp.asarray(True)
                return outputs, extra_state, grads, finite, loss

        if self._manual_vag is not None:
            self._warn_pipeline_fallback(
                args, kwargs,
                reason="live mutable collections cannot thread through the "
                       "manual backward" if extra_state else None,
            )

        def loss_of(p):
            outputs, new_state = self._apply(
                self._cast_params(p), extra_state, True, rng_key, args, kwargs
            )
            loss = self.loss_fn(outputs)
            loss = loss.astype(jnp.float32)
            scaled = loss * scale if scale is not None else loss
            return scaled, (outputs, new_state, loss)

        grads, (outputs, new_state, loss) = jax.grad(loss_of, has_aux=True)(params)
        if scale is not None:
            grads = jax.tree_util.tree_map(lambda g: (g / scale).astype(jnp.float32), grads)
            finite = jnp.all(
                jnp.asarray(
                    [jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)]
                )
            )
        else:
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
            finite = jnp.asarray(True)
        outputs = _cast_float_outputs(outputs, self.precision.output_dtype)
        return outputs, new_state, grads, finite, loss

    def _get_jit(self, name: str, fn, **jit_kwargs):
        if name not in self._jit_cache:
            self._jit_cache[name] = jax.jit(fn, **jit_kwargs)
        return self._jit_cache[name]

    def model_call(self, training: bool, *args, **kwargs):
        # bool/str/None call-args (flax `deterministic`, BatchNorm `train`
        # flags) feed Python control flow in the module, so they enter the
        # jit as statics, not tracers.
        t_args, s_args, t_kw, s_kw = _split_static_call(args, kwargs)
        if not training:
            fwd = self._get_jit(
                "eval_fwd",
                lambda p, es, a, kw, sa, skw: _cast_float_outputs(
                    self._apply(
                        self._cast_params(p), es, False, None, *_merge_static_call(a, kw, sa, skw)
                    )[0],
                    self.precision.output_dtype,
                ),
                static_argnums=(4, 5),
            )
            return fwd(self.params, self.extra_state, t_args, t_kw, s_args, s_kw)

        rng_key = default_keychain().next_key("dropout")
        scale = self.scale_state["scale"] if self.scale_state is not None else None
        if self.telemetry is not None:
            self.telemetry.note_batch(args, kwargs, self._call_argnames)
            from .telemetry import forensics as _forensics

            _forensics.note_call(
                "train_fwd_bwd",
                {"args": t_args, "kwargs": t_kw, "statics": (s_args, s_kw)},
            )

        fwd_bwd = self._get_jit(
            "fwd_bwd",
            lambda p, es, s, k, a, kw, sa, skw: self._fwd_bwd_fn(
                p, es, s, k, *_merge_static_call(a, kw, sa, skw)
            ),
            static_argnums=(6, 7),
        )
        outputs, new_state, grads, finite, loss = fwd_bwd(
            self.params, self.extra_state, scale, rng_key, t_args, t_kw, s_args, s_kw
        )
        self.extra_state = new_state
        self._pending_grads = (grads, finite)
        self._pending_loss = loss
        return outputs

    def backward(self, loss=None):
        """Fold pending grads into the accumulation buffer."""
        if self._pending_grads is None:
            raise RuntimeError(
                "accelerator.backward() called but no forward pass is pending. "
                "Call the prepared model first (in train mode)."
            )
        grads, finite = self._pending_grads
        self._pending_grads = None
        # inv_steps is a traced argument (not a closure constant) so changing
        # accelerator.gradient_accumulation_steps mid-run takes effect.
        inv_steps = jnp.asarray(1.0 / self.gradient_state.num_steps, jnp.float32)
        if self._accum_grads is None:
            scale_fn = self._get_jit(
                "accum_init", lambda g, inv: jax.tree_util.tree_map(lambda x: x * inv, g)
            )
            self._accum_grads = scale_fn(grads, inv_steps)
            self._accum_finite = finite
        else:
            add_fn = self._get_jit(
                "accum_add",
                lambda acc, g, inv, f_acc, f: (
                    jax.tree_util.tree_map(lambda a, x: a + x * inv, acc, g),
                    jnp.logical_and(f_acc, f),
                ),
                donate_argnums=(0,),
            )
            self._accum_grads, self._accum_finite = add_fn(
                self._accum_grads, grads, inv_steps, self._accum_finite, finite
            )

    # ------------------------------------------------------------------
    # optimizer wiring
    # ------------------------------------------------------------------

    def attach_optimizer(self, optimizer: optax.GradientTransformation, schedule=None):
        from .parallel.sharding import (
            infer_opt_state_sharding,
            transfer_tree,
            tree_with_memory_kind,
        )

        self.optimizer = optimizer
        self.schedule = schedule
        # opt shardings derive from the DEVICE view of the param shardings:
        # memory kinds in a jit's out_shardings must be uniform per memory
        # space or the SPMD partitioner rejects the rank-0 annotations
        base_param_sharding = (
            tree_with_memory_kind(self.param_sharding, "device")
            if self.sharding_config.offload_params_to_host
            else self.param_sharding
        )
        self.opt_state_sharding = infer_opt_state_sharding(
            optimizer, self.params, base_param_sharding, self.mesh
        )
        device_space = jax.memory.Space.Device
        init = self._get_jit(
            "opt_init",
            lambda p: optimizer.init(transfer_tree(p, device_space)),
            out_shardings=self.opt_state_sharding,
        )
        self.opt_state = init(self.params)
        if self.sharding_config.offload_optimizer_state:
            # ZeRO-offload analog: Adam moments (2x params in fp32 — usually
            # the single biggest HBM line item) live in pinned host between
            # steps; _update_fn streams them to HBM per update and the step
            # wrappers re-place them host-side after. Scalar leaves (step
            # counts) stay on device — the SPMD partitioner rejects
            # placement annotations on rank-0 buffers.
            from .parallel.sharding import with_memory_kind

            self.opt_state_sharding = jax.tree_util.tree_map(
                lambda sh, leaf: with_memory_kind(sh, "pinned_host") if getattr(leaf, "ndim", 0) >= 1 else sh,
                self.opt_state_sharding,
                self.opt_state,
            )
            self.opt_state = self._replace_offloaded_opt(self.opt_state)

    def _replace_offloaded_opt(self, opt_state):
        return jax.tree_util.tree_map(
            lambda x, sh: jax.device_put(x, sh) if getattr(x, "ndim", 0) >= 1 else x,
            opt_state,
            self.opt_state_sharding,
        )

    def _replace_offloaded_params(self, params):
        return jax.tree_util.tree_map(
            lambda x, sh: jax.device_put(x, sh) if getattr(x, "ndim", 0) >= 1 else x,
            params,
            self.param_sharding,
        )

    def _update_fn(self, params, opt_state, grads, scale_state, finite, max_norm):
        """One optimizer update: clip -> optax -> apply; fp16 skip via cond.
        Host-offloaded state streams HBM-ward here and back at the end."""
        from .parallel.sharding import transfer_tree

        offload_opt = self.sharding_config.offload_optimizer_state
        offload_p = self.sharding_config.offload_params_to_host
        if offload_opt:
            opt_state = transfer_tree(opt_state, jax.memory.Space.Device)
        if offload_p:
            params = transfer_tree(params, jax.memory.Space.Device)
        if max_norm is not None:
            gnorm = optax.global_norm(grads)
            clip_scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * clip_scale, grads)

        def do_update(operand):
            params, opt_state, grads = operand
            updates, new_opt = self.optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt

        if scale_state is None:
            new_params, new_opt = do_update((params, opt_state, grads))
            return new_params, new_opt, None, jnp.asarray(False)

        def skip(operand):
            params, opt_state, grads = operand
            return params, opt_state

        new_params, new_opt = jax.lax.cond(
            finite, do_update, skip, (params, opt_state, grads)
        )
        new_scale = self._scale_state_update(scale_state, finite)
        return new_params, new_opt, new_scale, jnp.logical_not(finite)

    def _scale_state_update(self, scale_state, finite):
        """GradScaler growth/backoff (shared by the GSPMD update and the
        compressed shard_map step): grow after growth_interval consecutive
        finite steps, back off (floored at 1.0) on overflow."""
        gk = self.precision.grad_scaler
        return jax.lax.cond(
            finite,
            lambda s: {
                "scale": jnp.where(
                    s["growth_tracker"] + 1 >= gk.growth_interval,
                    s["scale"] * gk.growth_factor,
                    s["scale"],
                ),
                "growth_tracker": jnp.where(
                    s["growth_tracker"] + 1 >= gk.growth_interval,
                    0,
                    s["growth_tracker"] + 1,
                ),
            },
            lambda s: {
                "scale": jnp.maximum(s["scale"] * gk.backoff_factor, 1.0),
                "growth_tracker": jnp.zeros((), jnp.int32),
            },
            scale_state,
        )

    def optimizer_step(self):
        if self.optimizer is None:
            raise RuntimeError("optimizer not attached; prepare(model, optimizer) together")
        if self._accum_grads is None:
            logger.warning("optimizer.step() called with no accumulated gradients; skipping")
            return
        max_norm = self._clip_max_norm
        use_clip = max_norm is not None
        key = "update_clip" if use_clip else "update"
        if key not in self._jit_cache:
            if use_clip:
                fn = lambda p, o, g, s, f, mn: self._update_fn(p, o, g, s, f, mn)
            else:
                fn = lambda p, o, g, s, f: self._update_fn(p, o, g, s, f, None)
            self._jit_cache[key] = jax.jit(
                fn, donate_argnums=(0, 1, 2) if self.donate_state else (2,)
            )
        finite = self._accum_finite if self._accum_finite is not None else jnp.asarray(True)
        call_args = [self.params, self.opt_state, self._accum_grads, self.scale_state, finite]
        if use_clip:
            call_args.append(jnp.asarray(max_norm, jnp.float32))
        new_params, new_opt, new_scale, skipped = self._jit_cache[key](*call_args)
        if self.sharding_config.offload_params_to_host:
            new_params = self._replace_offloaded_params(new_params)
        if self.sharding_config.offload_optimizer_state:
            new_opt = self._replace_offloaded_opt(new_opt)
        self.params = new_params
        self.opt_state = new_opt
        if self.scale_state is not None:
            self.scale_state = new_scale
            self._last_skipped = skipped
        else:
            self._last_skipped = False
        self._accum_grads = None
        self._accum_finite = None
        self.extra_state = _roll_fp8_stats(self.extra_state)
        self.step_count += 1
        if self.telemetry is not None:
            self.telemetry.on_optimizer_step(self)

    def last_step_skipped(self) -> bool:
        if isinstance(self._last_skipped, bool):
            return self._last_skipped
        return bool(jax.device_get(self._last_skipped))

    def zero_grad(self):
        self._accum_grads = None
        self._accum_finite = None

    def clip_grad_norm(self, max_norm: float):
        """Record the clip threshold for the coming update and return the
        current global grad norm (reference clip_grad_norm_ returns it).

        Before any backward there are no accumulated grads and the returned
        norm is 0.0 — the same value torch.nn.utils.clip_grad_norm_ returns
        when no parameter has a .grad; the threshold still applies to the
        next update."""
        self._clip_max_norm = float(max_norm)
        if self._accum_grads is None:
            return jnp.asarray(0.0)
        norm_fn = self._get_jit("grad_norm", optax.global_norm)
        return norm_fn(self._accum_grads)

    def current_learning_rate(self):
        if self.schedule is not None:
            return float(self.schedule(self.step_count))
        # try to find a scalar lr hyperparam in the opt state
        try:
            hp = getattr(self.opt_state, "hyperparams", None)
            if hp and "learning_rate" in hp:
                return float(jax.device_get(hp["learning_rate"]))
        except Exception:
            pass
        return None

    def current_variables(self):
        if self.model.is_flax:
            return {"params": self.params, **self.extra_state}
        return self.params

    # ------------------------------------------------------------------
    # checkpointing
    # ------------------------------------------------------------------

    def state_dict(self) -> dict:
        out = {
            "params": self.params,
            "opt_state": self.opt_state,
            "step_count": self.step_count,
        }
        if self.extra_state:
            out["extra_state"] = self.extra_state
        if self.scale_state is not None:
            out["scale"] = dict(self.scale_state)
        return out

    @staticmethod
    def _own_restored_buffers(tree):
        """Re-materialize restored leaves as executable outputs.

        The step/update programs donate params and opt_state. A donated
        buffer must be exclusively owned by its array; ``device_put``
        results restored from a checkpoint do not always satisfy that
        (scalar leaves can come out of jax's shared constant pool), and an
        executable deserialized from the persistent compilation cache will
        honor the donation where a freshly compiled CPU executable refuses
        it — the runtime then reuses the donated storage for an unrelated
        allocation while the aliased output still reads it (observed: adam
        ``mu`` clobbered to the backward seed 1.0 one step after
        ``load_state``). Copying through a compiled program yields
        uniquely-owned buffers that are safe to donate.
        """
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        idx = [i for i, leaf in enumerate(leaves) if isinstance(leaf, jax.Array)]
        if idx:
            picked = [leaves[i] for i in idx]
            copier = jax.jit(
                lambda xs: [jnp.copy(x) for x in xs],
                out_shardings=[x.sharding for x in picked],
            )
            for i, fresh in zip(idx, copier(picked)):
                leaves[i] = fresh
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def load_state_dict(self, state: dict):
        self.params = self._own_restored_buffers(jax.tree_util.tree_map(
            lambda like, v: jax.device_put(jnp.asarray(v, like.dtype), like.sharding),
            self.params, state["params"],
        ))
        if self.opt_state is not None and state.get("opt_state") is not None:
            self.opt_state = self._own_restored_buffers(jax.tree_util.tree_map(
                lambda like, v: jax.device_put(jnp.asarray(v, like.dtype), like.sharding)
                if isinstance(like, jax.Array)
                else v,
                self.opt_state, state["opt_state"],
            ))
        self.step_count = int(state.get("step_count", 0))
        if "extra_state" in state:
            self.extra_state = replicate(state["extra_state"], self.mesh)
        if "scale" in state and self.scale_state is not None:
            self.scale_state = {
                "scale": jnp.asarray(state["scale"]["scale"], jnp.float32),
                "growth_tracker": jnp.asarray(state["scale"]["growth_tracker"], jnp.int32),
            }

    def load_optimizer_state(self, state: dict):
        if state.get("opt_state") is not None and self.opt_state is not None:
            self.opt_state = self._own_restored_buffers(jax.tree_util.tree_map(
                lambda like, v: jax.device_put(jnp.asarray(v, like.dtype), like.sharding)
                if isinstance(like, jax.Array)
                else v,
                self.opt_state, state["opt_state"],
            ))
        if "step_count" in state:
            self.step_count = int(state["step_count"])

    # ------------------------------------------------------------------
    # fully-fused train step (the perf path)
    # ------------------------------------------------------------------

    def build_train_step(
        self,
        loss_fn: Optional[Callable] = None,
        micro_steps: Optional[int] = None,
        steps_per_call: Optional[int] = None,
    ):
        """One jit: split batch into micro-batches, lax.scan fwd+bwd
        accumulating grads, clip, update. Returns step(batch)->metrics.

        ``steps_per_call=K`` fuses K FULL optimizer steps (each with its own
        batch and RNG stream) into ONE executable via lax.scan — the
        MaxText-style train loop. The returned runner then takes a batch
        whose leaves carry a leading [K, ...] axis (K stacked per-step
        batches) and returns the LAST step's metrics plus ``loss_mean`` over
        the K steps. This amortizes per-dispatch host latency, which is
        a visible share of sub-50ms steps."""
        micro = micro_steps or self.gradient_state.num_steps
        if (
            (
                getattr(self.sharding_config, "grad_compression_dtype", None)
                or getattr(self.sharding_config, "grad_compression_rank", None)
            )
            and self.mesh is not None
            and self.mesh.shape.get("replica", 1) > 1
        ):
            if steps_per_call and steps_per_call > 1:
                raise NotImplementedError(
                    "steps_per_call>1 is not supported together with gradient "
                    "compression (the compressed step runs under shard_map)"
                )
            return self._build_compressed_replica_step(loss_fn, micro)
        user_loss = loss_fn
        max_norm = self._clip_max_norm

        def loss_and_state(params, extra_state, rng_key, batch):
            """-> (loss, new_extra_state). user_loss path can't update
            mutable collections (no handle to return them) — documented."""
            if user_loss is not None:
                return (
                    user_loss(self._make_apply(extra_state, rng_key), params, batch),
                    extra_state,
                )
            args, kwargs = _batch_to_call(batch)
            outputs, new_state = self._apply(
                self._cast_params(params), extra_state, True, rng_key, args, kwargs
            )
            return self.loss_fn(outputs).astype(jnp.float32), new_state

        manual_vag = self._manual_vag if user_loss is None else None

        def step_fn(params, opt_state, extra_state, scale_state, rng_key, batch):
            scale = scale_state["scale"] if scale_state is not None else None

            def one_micro(carry, mb):
                acc, loss_acc, key, es = carry
                key, sub = jax.random.split(key)

                args, kwargs = _batch_to_call(mb)
                ids, labels = _extract_lm_batch(args, kwargs, self._call_argnames)
                if manual_vag is not None and (es or labels is None):
                    # trace-time notice (the routing is static per compile)
                    self._warn_pipeline_fallback(
                        args, kwargs,
                        reason="live mutable collections cannot thread "
                               "through the manual backward" if es else None,
                    )
                if manual_vag is not None and not es and labels is not None:
                    # model-owned backward schedule (1f1b pipeline): the loss
                    # scale seeds the manual backward's cotangent, so the
                    # whole backward runs scaled (fp16 underflow protection,
                    # same as AD) and grads arrive scaled for the post-scan
                    # /scale + finite check. scale=/rng= only when needed
                    # (duck-typed hook: 3-arg implementations stay valid).
                    extra = {}
                    if scale is not None:
                        extra["scale"] = scale
                    if self._manual_vag_wants_rng:
                        extra["rng"] = sub
                    out, g = manual_vag(self._cast_params(params), ids, labels, **extra)
                    # dict-returning hooks (MoE) -> the scalar for the scan
                    l = (out["loss"] if isinstance(out, dict) else out).astype(
                        jnp.float32
                    )
                    new_es = es
                else:

                    def scaled_loss(p):
                        l, new_es = loss_and_state(p, es, sub, mb)
                        return (l * scale if scale is not None else l), (l, new_es)

                    g, (l, new_es) = jax.grad(scaled_loss, has_aux=True)(params)
                acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32) / micro, acc, g
                )
                return (acc, loss_acc + l / micro, key, new_es), None

            zero = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            carry0 = (zero, jnp.asarray(0.0), rng_key, extra_state)
            if micro > 1:
                mbs = jax.tree_util.tree_map(
                    lambda x: x.reshape((micro, x.shape[0] // micro) + x.shape[1:]), batch
                )
                (grads, loss, _, new_extra), _ = jax.lax.scan(one_micro, carry0, mbs)
            else:
                (grads, loss, _, new_extra), _ = one_micro(carry0, batch)
            if scale is not None:
                grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
                finite = jnp.all(
                    jnp.asarray([jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)])
                )
            else:
                finite = jnp.asarray(True)
            new_params, new_opt, new_scale, skipped = self._update_fn(
                params, opt_state, grads, scale_state, finite,
                jnp.asarray(max_norm, jnp.float32) if max_norm is not None else None,
            )
            if user_loss is None:
                # the user-loss path cannot record amaxes (no handle to
                # return mutated collections) — rolling there would drain
                # the history; see _roll_fp8_stats
                new_extra = _roll_fp8_stats(new_extra)
            metrics = {"loss": loss, "grad_norm": optax.global_norm(grads)}
            return new_params, new_opt, new_extra, new_scale, skipped, metrics

        if steps_per_call and steps_per_call > 1:

            def multi_fn(params, opt_state, extra_state, scale_state, rng_key, batches):
                def body(carry, mb):
                    p, o, es, ss, key = carry
                    key, sub = jax.random.split(key)
                    p, o, es, ss, skipped, metrics = step_fn(p, o, es, ss, sub, mb)
                    return (p, o, es, ss, key), (metrics, skipped)

                (p, o, es, ss, _), (ms, sk) = jax.lax.scan(
                    body, (params, opt_state, extra_state, scale_state, rng_key), batches
                )
                metrics = jax.tree_util.tree_map(lambda x: x[-1], ms)
                metrics["loss_mean"] = jnp.mean(ms["loss"])
                # ANY skipped step inside the fused window must surface
                # through optimizer_step_was_skipped, not just the last one
                skipped_any = jnp.any(jnp.asarray(sk)) if sk is not None else sk
                return p, o, es, ss, skipped_any, metrics

            fused_fn = multi_fn
        else:
            fused_fn = step_fn
        donate = (0, 1) if self.donate_state else ()
        jitted = jax.jit(fused_fn, donate_argnums=donate)
        if self.telemetry is not None:
            from .telemetry import forensics as _forensics

            _forensics.register(
                "train_step", donate=donate,
                statics={"micro_steps": micro, "steps_per_call": steps_per_call},
            )
        cost_captured = []
        from .parallel.context import record_exchanged_products

        def run(batch):
            tm = self.telemetry
            t0 = time.perf_counter() if tm is not None else None
            rng_key = default_keychain().next_key("train_step")
            if tm is not None:
                from .telemetry import forensics as _forensics

                # fingerprint BEFORE dispatch: a changed batch signature
                # here is the recompile this very call is about to pay
                _forensics.note_call("train_step", {"batch": batch})
            with record_exchanged_products() as exchanged:  # (filled by the call that traces)
                new_params, new_opt, new_extra, new_scale, skipped, metrics = jitted(
                    self.params, self.opt_state, self.extra_state, self.scale_state, rng_key, batch
                )
            if exchanged:
                run._audit_tp_products = tuple(sorted(exchanged))
            if self.sharding_config.offload_params_to_host:
                new_params = self._replace_offloaded_params(new_params)
            if self.sharding_config.offload_optimizer_state:
                new_opt = self._replace_offloaded_opt(new_opt)
            self.params, self.opt_state = new_params, new_opt
            self.extra_state = new_extra
            if self.scale_state is not None:
                self.scale_state = new_scale
                self._last_skipped = skipped
            self.step_count += steps_per_call if steps_per_call else 1
            if tm is not None:
                from .telemetry.metrics import batch_token_count

                tokens, samples, seq_len = batch_token_count(batch)
                tm.on_step(
                    self, time.perf_counter() - t0, tokens=tokens,
                    samples=samples, seq_len=seq_len,
                    steps=steps_per_call if steps_per_call else 1,
                    metrics=metrics, exe="train_step",
                )
                if tm.costs is not None and not cost_captured:
                    # once, on the (warmup) first step: re-lower against
                    # the live avals (one trace, no backend compile — the
                    # compiled-form memory analysis is added only when the
                    # persistent cache can serve it) so the roofline row
                    # exists from step 1
                    cost_captured.append(True)
                    try:
                        tm.costs.capture_lowered("train_step", jitted.lower(
                            self.params, self.opt_state, self.extra_state,
                            self.scale_state, rng_key, batch,
                        ))
                    except Exception:
                        pass
            return metrics

        # expose the underlying jitted executable to the static program
        # auditor (`accelerate-tpu audit`): the runner closure hides it,
        # and the auditor needs the fn + effective donation set to trace
        run._audit_fn = jitted
        run._audit_donate = donate
        # the tensor-parallel products of a block that exchange their rows
        # while they multiply (parallel/context.gather_einsum), by their einsum
        run._audit_tp_products = ()
        return run

    def audit_entrypoints(self, step, batch) -> list:
        """Entry-point specs for ``accelerate_tpu.analysis.program_audit``
        covering the fused train step ``build_train_step`` returned:
        the underlying jitted fn, the live optimizer/param state as
        example args, and the effective ``donate_argnums``. Trace-only —
        nothing executes. ``batch`` is one example batch shaped like the
        real traffic (what the signature forensics fingerprint too)."""
        import jax as _jax

        fn = getattr(step, "_audit_fn", None)
        if fn is None:
            return []
        donate = tuple(getattr(step, "_audit_donate", ()) or ())
        return [dict(
            name="train_step", fn=fn,
            args=(self.params, self.opt_state, self.extra_state,
                  self.scale_state, _jax.random.PRNGKey(0), batch),
            donate=donate, donate_expected=bool(donate),
            compute_dtype=("bfloat16"
                           if self.state.mixed_precision == "bf16" else None),
        )]

    def _make_apply(self, extra_state, rng_key):
        def apply_fn(params, *args, **kwargs):
            out, _ = self._apply(self._cast_params(params), extra_state, True, rng_key, args, kwargs)
            return out

        return apply_fn

    def _build_compressed_replica_step(self, loss_fn, micro):
        """Train step with a COMPRESSED cross-slice gradient all-reduce — the
        TPU analog of the reference's DDP comm hooks (fp16/bf16/powerSGD on
        the gradient bucket all-reduce, reference utils/dataclasses.py:
        111-208). The step runs under an explicit shard_map over the mesh so
        the reduction hops are separate collectives:

          1. fp32 reduction over the intra-slice axes — rides ICI, cheap.
             With ``fsdp > 1`` the param shards enter sharded, are
             all-gathered before the forward, and AD's transpose of that
             gather IS the ZeRO reduce-scatter — grads leave fsdp-sharded.
          2. the "replica" hop — DCN-crossing on a multi-slice HYBRID mesh —
             carries either ``grad_compression_dtype`` words (bf16/fp16
             halve, int8 quarters the bytes) or, with
             ``grad_compression_rank``, PowerSGD low-rank factors
             ((m+n)*rank floats instead of m*n, warm-started Q, per-replica
             error feedback).

        int8 uses a cross-replica-consistent per-tensor scale with headroom
        so the on-wire psum cannot overflow (max |q| <= 127/num_replicas).
        fp16 loss scaling composes: the backward runs scaled, grads unscale
        before compression, and the finite check gates the update exactly
        like the GSPMD path."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from .utils.serialization import flatten_pytree, unflatten_to_like

        mesh = self.mesh
        comp_name = self.sharding_config.grad_compression_dtype
        rank = self.sharding_config.grad_compression_rank
        optimizer = self.optimizer
        user_loss = loss_fn
        n_replica = mesh.shape["replica"]
        fsdp_size = mesh.shape.get("fsdp", 1)
        data_axes = tuple(a for a in ("data",) if mesh.shape.get(a, 1) > 1)
        batch_axes = ("replica",) + data_axes + (("fsdp",) if fsdp_size > 1 else ())

        param_specs = jax.tree_util.tree_map(
            lambda s: s.spec, self.param_sharding
        )
        opt_specs = jax.tree_util.tree_map(
            lambda s: s.spec, self.opt_state_sharding
        )

        def _fsdp_dim(spec):
            for i, part in enumerate(spec):
                names = (part,) if isinstance(part, str) else tuple(part or ())
                if "fsdp" in names:
                    return i
            return None

        def _gather_full(p, spec):
            d = _fsdp_dim(spec)
            if d is None or fsdp_size == 1:
                return p
            return jax.lax.all_gather(p, "fsdp", axis=d, tiled=True)

        if rank:
            comp_state = self._init_powersgd_state(rank)
        else:
            comp_state = {}
        comp_paths = set(comp_state)

        def _dtype_hop(g):
            """The plain compressed replica-mean for one fp32 grad leaf."""
            if comp_name == "int8":
                cap = 127 // n_replica  # sum over R replicas stays <= 127
                absmax = jax.lax.pmax(jnp.max(jnp.abs(g)), "replica")
                scale = absmax / cap + 1e-30
                q = jnp.clip(jnp.round(g / scale), -cap, cap).astype(jnp.int8)
                summed = jax.lax.psum(q, "replica")  # int8 on the wire
                return summed.astype(jnp.float32) * scale / n_replica
            if comp_name is None:
                return jax.lax.pmean(g, "replica")
            comp = jnp.dtype(comp_name)
            return jax.lax.pmean(g.astype(comp), "replica").astype(jnp.float32)

        def _powersgd_hop(g, state):
            """PowerSGD rank-r replica mean with error feedback (reference
            powerSGD_hook): M = g + error; P = MQ -> pmean -> orthonormalize;
            Q' = M^T P -> pmean; ghat = P Q'^T; error' = M - ghat. Leaves
            with >2 dims run per-slice along dim 0 (layer-scanned stacks).
            State leaves carry a leading replica dim (sliced to 1 inside the
            shard_map): the error buffer GENUINELY differs per replica —
            declaring it replicated would be an SPMD lie that any reshard
            could collapse."""
            q, err = state["q"][0], state["err"][0]

            def one(m2d, q2d):
                p = jax.lax.pmean(m2d @ q2d, "replica")
                p, _ = jnp.linalg.qr(p)
                q_new = jax.lax.pmean(m2d.T @ p, "replica")
                return p @ q_new.T, q_new

            m = (g + err).astype(jnp.float32)
            if g.ndim == 2:
                ghat, q_new = one(m, q)
            else:
                flat = m.reshape(m.shape[0], m.shape[1], -1)
                ghat, q_new = jax.vmap(one)(flat, q)
                ghat = ghat.reshape(g.shape)
            return ghat, {"q": q_new[None], "err": (m.reshape(g.shape) - ghat)[None]}

        def body(params, opt_state, extra_state, scale_state, comp_state, rng_key, batch):
            scale = scale_state["scale"] if scale_state is not None else None
            idx = jax.lax.axis_index(batch_axes)
            base_key = jax.random.fold_in(rng_key, idx)

            def one_micro(carry, mb):
                acc, loss_acc, key, es = carry
                key, sub = jax.random.split(key)

                def local_loss(p_shards):
                    p = jax.tree_util.tree_map(_gather_full, p_shards, param_specs)
                    # same loss_fn contract as the normal path: a user-
                    # supplied fn receives (apply_fn, params, batch)
                    if user_loss is not None:
                        l = user_loss(self._make_apply(es, sub), p, mb).astype(jnp.float32)
                        new_es = es
                    else:
                        args, kwargs = _batch_to_call(mb)
                        outputs, new_es = self._apply(
                            self._cast_params(p), es, True, sub, args, kwargs
                        )
                        l = self.loss_fn(outputs).astype(jnp.float32)
                    return (l * scale if scale is not None else l), (l, new_es)

                g, (l, new_es) = jax.grad(local_loss, has_aux=True)(params)
                acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32) / micro, acc, g
                )
                return (acc, loss_acc + l / micro, key, new_es), None

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            carry0 = (zero, jnp.asarray(0.0), base_key, extra_state)
            if micro > 1:
                mbs = jax.tree_util.tree_map(
                    lambda x: x.reshape((micro, x.shape[0] // micro) + x.shape[1:]), batch
                )
                (grads, loss, _, new_es), _ = jax.lax.scan(one_micro, carry0, mbs)
            else:
                (grads, loss, _, new_es), _ = one_micro(carry0, batch)

            # unscale + finite check BEFORE the lossy compression (a saturated
            # fp16 grad must trigger the skip, not silently clip)
            if scale is not None:
                grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
            finite = jnp.all(
                jnp.asarray([jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)])
            )
            finite = jax.lax.pmin(finite.astype(jnp.int32), batch_axes).astype(bool)

            # intra-slice (ICI) fp32 reduction, PER LEAF by its sharding:
            # - fsdp-sharded leaves: the fsdp sum already happened in AD
            #   (all_gather transpose = psum_scatter) — normalize to a mean;
            # - replicated leaves (norms, leaves under the size threshold):
            #   AD inserted NO fsdp collective, each member only saw its own
            #   sub-batch — pmean over fsdp alongside data.
            def _ici_mean(g, spec):
                sharded = _fsdp_dim(spec) is not None and fsdp_size > 1
                axes = data_axes + (
                    ("fsdp",) if (fsdp_size > 1 and not sharded) else ()
                )
                if axes:
                    g = jax.lax.pmean(g, axes)
                return g / fsdp_size if sharded else g

            grads = jax.tree_util.tree_map(_ici_mean, grads, param_specs)

            # the replica (DCN) hop, compressed
            flat_g = flatten_pytree(grads)
            new_comp = {}
            for path in flat_g:
                if path in comp_paths:
                    flat_g[path], new_comp[path] = _powersgd_hop(
                        flat_g[path], comp_state[path]
                    )
                else:
                    flat_g[path] = _dtype_hop(flat_g[path])
            grads = unflatten_to_like(flat_g, grads)

            loss = jax.lax.pmean(loss, batch_axes)
            # mutable collections (e.g. BatchNorm stats) were updated from
            # each shard's local batch: average float leaves so every shard
            # leaves with the same, global-batch-equivalent statistics
            new_es = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, batch_axes)
                if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                new_es,
            )
            # pre-clip norm, global across fsdp shards (each member must
            # apply the SAME clip factor or shards drift apart). Only the
            # fsdp-SHARDED leaves psum over fsdp — replicated leaves would
            # double-count.
            flat_for_norm = flatten_pytree(grads)
            flat_specs = flatten_pytree(param_specs)
            sq_sharded = sum(
                jnp.sum(jnp.square(g)) for p, g in flat_for_norm.items()
                if _fsdp_dim(flat_specs[p]) is not None
            ) if fsdp_size > 1 else 0.0
            sq_rep = sum(
                jnp.sum(jnp.square(g)) for p, g in flat_for_norm.items()
                if fsdp_size == 1 or _fsdp_dim(flat_specs[p]) is None
            )
            if fsdp_size > 1:
                sq_sharded = jax.lax.psum(sq_sharded, "fsdp")
            grad_norm = jnp.sqrt(sq_rep + sq_sharded)
            max_norm = self._clip_max_norm
            if max_norm is not None:
                factor = jnp.minimum(1.0, max_norm / (grad_norm + 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * factor, grads)

            def do_update(operand):
                params, opt_state, grads = operand
                updates, new_opt = optimizer.update(grads, opt_state, params)
                new_params = jax.tree_util.tree_map(
                    lambda p, u: p + u.astype(p.dtype), params, updates
                )
                return new_params, new_opt

            if scale_state is None:
                new_params, new_opt = do_update((params, opt_state, grads))
                new_scale, skipped = None, jnp.asarray(False)
            else:
                new_params, new_opt = jax.lax.cond(
                    finite, do_update, lambda op: (op[0], op[1]),
                    (params, opt_state, grads),
                )
                new_scale = self._scale_state_update(scale_state, finite)
                skipped = jnp.logical_not(finite)
                if new_comp:
                    # an overflow step's PowerSGD state was computed from
                    # non-finite grads — keep the old state or NaN poisons
                    # every later step (the scaler backoff can't recover it)
                    new_comp = jax.lax.cond(
                        finite, lambda op: op[0], lambda op: op[1],
                        (new_comp, comp_state),
                    )
            metrics = {"loss": loss, "grad_norm": grad_norm}
            return new_params, new_opt, new_es, new_scale, new_comp, skipped, metrics

        rep = P()
        scale_specs = None if self.scale_state is None else jax.tree_util.tree_map(
            lambda _: rep, self.scale_state
        )
        # comp-state leaves carry a leading replica dim (error feedback is
        # per-replica by construction) — shard it honestly
        comp_specs = jax.tree_util.tree_map(lambda _: P("replica"), comp_state)
        stepped = shard_map(
            body,
            mesh=mesh,
            in_specs=(param_specs, opt_specs, rep, scale_specs, comp_specs, rep, P(batch_axes)),
            out_specs=(param_specs, opt_specs, rep, scale_specs, comp_specs, rep, rep),
            axis_names=set(mesh.axis_names),
            check_vma=False,
        )
        jitted = jax.jit(stepped, donate_argnums=(0, 1, 4) if self.donate_state else ())
        self._comp_state = comp_state

        def run(batch):
            tm = self.telemetry
            t0 = time.perf_counter() if tm is not None else None
            rng_key = default_keychain().next_key("train_step")
            if tm is not None:
                from .telemetry import forensics as _forensics

                _forensics.note_call("train_step", {"batch": batch})
            new_params, new_opt, new_es, new_scale, new_comp, skipped, metrics = jitted(
                self.params, self.opt_state, self.extra_state, self.scale_state,
                self._comp_state, rng_key, batch
            )
            self.params, self.opt_state = new_params, new_opt
            if user_loss is None:
                new_es = _roll_fp8_stats(new_es)
            self.extra_state = new_es
            self._comp_state = new_comp
            if self.scale_state is not None:
                self.scale_state = new_scale
                self._last_skipped = skipped
            self.step_count += 1
            if tm is not None:
                from .telemetry.metrics import batch_token_count

                tokens, samples, seq_len = batch_token_count(batch)
                tm.on_step(
                    self, time.perf_counter() - t0, tokens=tokens,
                    samples=samples, seq_len=seq_len, metrics=metrics,
                    exe="train_step",
                )
            return metrics

        return run

    @staticmethod
    def _powersgd_matrix_view(shape, rank):
        """The ONE owner of PowerSGD's per-leaf eligibility + matrix-view
        rule, shared by the state init and the wire-bytes estimator so they
        can never disagree. Returns ``(m, n, stack, q_shape)`` for an
        eligible leaf, else None. >=3D leaves (layer-scanned stacks) view as
        ``stack`` independent [m, n] matrices along dim 0."""
        if len(shape) < 2:
            return None
        if len(shape) == 2:
            m, n, stack = shape[0], shape[1], 1
            q_shape = (n, rank)
        else:
            m, n, stack = shape[1], int(np.prod(shape[2:])), shape[0]
            q_shape = (shape[0], n, rank)
        if min(m, n) <= 2 * rank:
            return None
        return m, n, stack, q_shape

    @staticmethod
    def replica_wire_bytes(params, grad_compression_dtype=None, grad_compression_rank=None):
        """Bytes each replica puts on the DCN wire per optimizer step under
        the configured gradient compression — the number that makes the
        rank/dtype choice concrete (the reference documents its powerSGD
        hook's tradeoffs qualitatively, utils/dataclasses.py:111-130; this
        quantifies them for YOUR param tree). Mirrors the compressed step's
        per-leaf ROUTING (shared _powersgd_matrix_view): PowerSGD-eligible
        leaves (>=2D, min(m, n) > 2r, stacked leaves per dim-0 slice) send
        the rank-r P and Q factors in fp32; everything else sends the leaf
        at the dtype hop's width (int8 adds one fp32 scale per leaf).

        Byte counts assume the replicated intra-slice layout PowerSGD
        targets (fsdp == 1). On a hybrid fsdp>1 mesh, per-DEVICE traffic
        differs: fsdp-sharded leaves send 1/fsdp shares while replicated
        small leaves are reduced from every mesh position — use the
        Accelerator method, which reports the active config, and treat
        hybrid numbers as the aggregate across the fsdp group. Returns
        {"bytes": int, "compressed_leaves": int, "total_leaves": int}."""
        from .utils.serialization import flatten_pytree

        rank = grad_compression_rank
        comp = grad_compression_dtype
        aliases = {"bf16": "bfloat16", "fp16": "float16", "none": None}
        comp = aliases.get(comp, comp)
        widths = {None: 4, "bfloat16": 2, "float16": 2, "int8": 1}
        if comp not in widths:
            raise ValueError(
                f"grad_compression_dtype {comp!r} not recognized; pick one of "
                "None/'bfloat16'/'float16'/'int8' (aliases bf16/fp16/none)"
            )
        dtype_width = widths[comp]
        total = 0
        n_comp = 0
        n_leaves = 0
        for path, p in flatten_pytree(params).items():
            shape = tuple(getattr(p, "shape", ()))
            size = int(np.prod(shape)) if shape else 1
            n_leaves += 1
            view = TrainEngine._powersgd_matrix_view(shape, rank) if rank else None
            if view is not None:
                m, n, stack, _ = view
                total += stack * (m + n) * rank * 4  # P + Q, fp32
                n_comp += 1
            else:
                total += size * dtype_width + (4 if comp == "int8" else 0)
        return {"bytes": total, "compressed_leaves": n_comp, "total_leaves": n_leaves}

    def _init_powersgd_state(self, rank: int):
        """Warm-start Q + error-feedback buffers for every grad the PowerSGD
        hop will compress: >=2D params whose matrix view is worth rank-r
        (min(m, n) > 2r). 3+D leaves (layer-scanned stacks) compress
        per-dim-0 slice. Keyed by flat path; everything else uses the dtype
        hop. Every leaf gets a leading replica dim — the error buffers are
        genuinely per-replica (sharded P("replica") through the step)."""
        from .utils.serialization import flatten_pytree

        n_replica = self.mesh.shape["replica"]
        state = {}
        key = jax.random.PRNGKey(17)
        for path, p in flatten_pytree(self.params).items():
            shape = tuple(getattr(p, "shape", ()))
            view = self._powersgd_matrix_view(shape, rank)
            if view is None:
                continue
            _, _, _, q_shape = view
            key, sub = jax.random.split(key)
            q = jax.random.normal(sub, q_shape, jnp.float32)
            state[path] = {
                "q": jnp.broadcast_to(q[None], (n_replica,) + q_shape),
                "err": jnp.zeros((n_replica,) + shape, jnp.float32),
            }
        return state


_fp8_mxu_warned = False


def _device_has_fp8_mxu(device) -> bool:
    """fp8 MXU throughput arrives with v6e (Trillium); v5e/v5p and older
    emulate fp8 matmuls via convert-to-bf16 (docs/fp8.md)."""
    import re

    kind = getattr(device, "device_kind", "") or ""
    m = re.search(r"tpu\s*v(\d+)", kind.lower())
    return bool(m) and int(m.group(1)) >= 6


def _warn_fp8_without_mxu_once(device) -> None:
    """One loud notice when mixed_precision='fp8' lands on hardware that
    only emulates fp8: the user just bought overhead, not speed (measured
    ~11pp MFU below bf16 on v5e — BENCH fp8 row), and nothing else at
    runtime says so. The recipe itself stays numerically valid, so this is
    a warning, not an error; the same code path speeds up on v6e+."""
    global _fp8_mxu_warned
    if _fp8_mxu_warned or _device_has_fp8_mxu(device):
        return
    _fp8_mxu_warned = True
    import warnings

    kind = getattr(device, "device_kind", "unknown device")
    warnings.warn(
        f"mixed_precision='fp8' on {kind!r}: this chip has no fp8 MXU, so "
        "XLA emulates fp8 matmuls via convert and training runs SLOWER "
        "than bf16 (see docs/fp8.md, 'When to use it'). The recipe is "
        "numerically faithful and transfers to v6e+/Ironwood unchanged; "
        "use mixed_precision='bf16' here if you want throughput.",
        stacklevel=3,
    )


def _enable_fp8(definition):
    """Flip ``config.use_fp8`` on a model definition that supports the fp8
    recipe (ops/fp8.py); definitions without the knob pass through — their
    matmuls simply stay bf16 (the reference likewise only converts layers
    TE has fp8 kernels for)."""
    import dataclasses as _dc

    cfg = getattr(definition, "config", None)
    if cfg is None or not hasattr(cfg, "use_fp8") or cfg.use_fp8:
        return definition
    try:
        return definition.copy(config=_dc.replace(cfg, use_fp8=True))
    except Exception:  # pragma: no cover - exotic module types
        return definition


def _split_static_call(args, kwargs):
    """Partition call inputs: bool/str/bytes/None/enum values become jit
    statics (they feed Python control flow in user modules); arrays, numbers,
    and containers stay traced."""
    import enum

    is_static = lambda v: isinstance(v, (bool, str, bytes, enum.Enum)) or v is None
    traced_args = tuple(None if is_static(a) else a for a in args)
    static_args = tuple((i, a) for i, a in enumerate(args) if is_static(a))
    traced_kw = {k: v for k, v in kwargs.items() if not is_static(v)}
    static_kw = tuple(sorted((k, v) for k, v in kwargs.items() if is_static(v)))
    return traced_args, static_args, traced_kw, static_kw


def _merge_static_call(args, kwargs, static_args, static_kw):
    args = list(args)
    for i, v in static_args:
        args[i] = v
    return tuple(args), dict(kwargs, **dict(static_kw))


def _looks_like_schedule(fn) -> bool:
    """True if ``fn`` behaves like an optax schedule: step -> scalar lr.
    Guards prepare()'s pass 3 from silently wrapping stray callables (e.g. a
    loss function passed positionally) as schedulers.

    Detection order (to avoid executing user code where possible):
    1. the signature is checked, so multi-arg callables (loss functions,
       factories) are rejected without executing them;
    2. single-arg callables whose ``__module__``/``__wrapped__`` come from
       optax are accepted without probing (covers every optax.schedules
       factory);
    3. remaining single-argument callables ARE probed with ``fn(0)`` — a
       side-effecting closure will observe a fake step-0 call. Pass such
       callables through ``Accelerator.prepare_scheduler`` explicitly to
       skip prepare()'s probing entirely."""
    import inspect

    try:
        sig = inspect.signature(fn)
        sig.bind(0)  # must accept exactly one positional argument
    except TypeError:
        return False
    except (ValueError, RuntimeError):  # builtins without signatures: probe
        pass
    # single-arg callables minted by optax (schedule factories return
    # closures from optax.schedules.*) are schedules — skip the probe. The
    # signature check above still ran, so optax LOSS functions (2+ args)
    # were already rejected without this fast path ever seeing them.
    probed = fn.func if isinstance(fn, functools.partial) else getattr(fn, "__wrapped__", fn)
    if (getattr(probed, "__module__", "") or "").split(".")[0] == "optax":
        return True
    try:
        out = fn(0)
    except Exception:
        return False
    if isinstance(out, bool):  # a predicate, not a learning rate
        return False
    if isinstance(out, (int, float)):
        return True
    return hasattr(out, "shape") and tuple(getattr(out, "shape", (1,))) == ()


def _cast_float_outputs(outputs, dtype):
    return recursively_apply(
        lambda t: t.astype(dtype) if jnp.issubdtype(t.dtype, jnp.floating) else t, outputs
    )


def _batch_to_call(batch):
    if isinstance(batch, dict):
        return (), batch
    if isinstance(batch, (tuple, list)):
        return tuple(batch), {}
    return (batch,), {}


def _extract_lm_batch(args, kwargs, argnames=("input_ids", "labels")):
    """(input_ids, labels) from an LM call, or (None, None) when the call
    carries ANYTHING else (positions, deterministic, masks…) — a manual
    pipeline backward only covers the plain (input_ids, labels) signature,
    and silently dropping extra inputs would diverge from AD.

    ``argnames`` is the MODEL's positional parameter order (taken from its
    call signature at engine init): positional args are bound by name
    before the check, so a tuple batch against Seq2SeqLM's
    (input_ids, decoder_input_ids, ...) signature maps args[1] to
    decoder_input_ids — and is routed to AD — instead of being misread as
    labels."""
    named = {}
    for i, a in enumerate(args):
        if i >= len(argnames):
            return None, None
        named[argnames[i]] = a
    named.update(kwargs)
    if any(k not in ("input_ids", "labels") for k in named):
        return None, None
    return named.get("input_ids"), named.get("labels")


class Accelerator:
    """The user façade (reference accelerator.py:160)."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        sharding_config: Optional[ShardingConfig] = None,
        compile_plugin: Optional[CompilePlugin] = None,
        step_scheduler_with_optimizer: bool = True,
        kwargs_handlers: Optional[list] = None,
        rng_types: Optional[list] = None,
        loss_fn: Optional[Callable] = None,
        telemetry=None,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # kwargs handlers (reference accelerator.py:347-381)
        self.scaler_handler = None
        self.init_handler = None
        self.autocast_handler = None
        self.profile_handler = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_handler = handler
            elif isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler

        self.compile_plugin = compile_plugin or CompilePlugin()
        from .utils.compile_cache import ensure_persistent_compile_cache

        ensure_persistent_compile_cache()

        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            sharding_config=sharding_config,
            _from_accelerator=True,
        )
        if self.scaler_handler is not None:
            self.state.precision.grad_scaler = self.scaler_handler
        if self.state.mixed_precision == "fp8":
            _warn_fp8_without_mxu_once(self.state.device)

        if gradient_accumulation_plugin is None:
            gradient_accumulation_plugin = GradientAccumulationPlugin(
                num_steps=int(os.environ.get("ACCELERATE_TPU_GRADIENT_ACCUMULATION_STEPS",
                                             gradient_accumulation_steps))
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)

        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        self.device_placement = device_placement
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["jax"]
        self.loss_fn = loss_fn

        self._engines: list[TrainEngine] = []
        self._models: list[PreparedModel] = []
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list = []
        self._custom_objects: list = []
        self._load_model_state_pre_hook = {}
        self._save_model_state_pre_hook = {}
        self.step = 0
        self.flag_tensor = None

        from .tracking import filter_trackers

        self.log_with = filter_trackers(log_with, self.logging_dir)
        self.trackers: list = []

        # runtime telemetry (docs/telemetry.md): `telemetry=` takes a
        # TelemetryConfig (or True for defaults); None defers to the
        # ATT_TELEMETRY env gate. Disabled -> self.telemetry is None and the
        # engine step paths stay on their zero-overhead fast path.
        from .telemetry import TelemetrySession, resolve_config

        tcfg = resolve_config(telemetry)
        self.telemetry = TelemetrySession(tcfg, accelerator=self) if tcfg else None

    # ------------------------------------------------------------------
    # state passthroughs (reference accelerator.py properties)
    # ------------------------------------------------------------------

    @property
    def distributed_type(self):
        return self.state.distributed_type

    @property
    def num_processes(self):
        return self.state.num_processes

    @property
    def process_index(self):
        return self.state.process_index

    @property
    def local_process_index(self):
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def is_main_process(self):
        return self.state.is_main_process

    @property
    def is_local_main_process(self):
        return self.state.is_local_main_process

    @property
    def is_last_process(self):
        return self.state.is_last_process

    @property
    def mixed_precision(self):
        return self.state.mixed_precision

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self):
        return self.project_configuration.iteration

    @property
    def sync_gradients(self):
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self):
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value):
        self.gradient_state.plugin_kwargs.update({"num_steps": value})

    @property
    def optimizer_step_was_skipped(self):
        return any(opt.step_was_skipped for opt in self._optimizers)

    def on_main_process(self, function):
        return self.state.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state.on_process(function, process_index)

    def on_last_process(self, function):
        return self.state.on_last_process(function)

    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state.local_main_process_first():
            yield

    def split_between_processes(self, inputs, apply_padding=False):
        return self.state.split_between_processes(inputs, apply_padding=apply_padding)

    def print(self, *args, **kwargs):
        self.state.print(*args, **kwargs)

    # ------------------------------------------------------------------
    # prepare (reference accelerator.py:1211)
    # ------------------------------------------------------------------

    def prepare(self, *args, device_placement=None):
        """Dispatch each object to its _prepare_* (two-pass like the
        reference: models first so optimizers can attach to engines)."""
        result = list(args)
        # pass 1: models
        for i, obj in enumerate(result):
            if isinstance(obj, Model) or _is_flax_module(obj):
                result[i] = self.prepare_model(obj)
        # pass 2: everything else
        for i, obj in enumerate(result):
            if isinstance(obj, optax.GradientTransformation):
                result[i] = self.prepare_optimizer(obj)
            elif _is_dataloader_like(obj):
                result[i] = self.prepare_data_loader(obj)
        # pass 3: schedules (need prepared optimizers)
        for i, obj in enumerate(result):
            if callable(obj) and not isinstance(
                obj, (PreparedModel, AcceleratedOptimizer, AcceleratedScheduler, Model)
            ) and not _is_dataloader_like(obj) and not isinstance(obj, optax.GradientTransformation):
                if not _looks_like_schedule(obj):
                    raise TypeError(
                        f"prepare() received a callable ({obj!r}) that is not an "
                        "optax schedule (schedule(step:int) must return a scalar "
                        "learning rate; single-argument candidates are probed "
                        "with step=0). Loss functions belong on the model "
                        "(Model(..., loss_fn=...)) or Accelerator(loss_fn=...), "
                        "not in prepare()."
                    )
                result[i] = self.prepare_scheduler(obj)
        return result[0] if len(result) == 1 else tuple(result)

    def prepare_model(self, model: Union[Model, Any], device_placement=None, evaluation_mode=False) -> PreparedModel:
        if _is_flax_module(model):
            raise ValueError(
                "Pass `accelerate_tpu.Model(flax_module, variables)` so prepare() "
                "has the parameters (JAX separates module and params)."
            )
        if model.loss_fn is None and self.loss_fn is not None:
            model.loss_fn = self.loss_fn
        if self.mixed_precision == "fp8":
            model.definition = _enable_fp8(model.definition)
        engine = TrainEngine(model, self)
        self._engines.append(engine)
        if self.telemetry is not None:
            self.telemetry.attach_engine(engine)
        prepared = PreparedModel(engine)
        if evaluation_mode:
            prepared.eval()
        self._models.append(prepared)
        return prepared

    def prepare_optimizer(self, optimizer: optax.GradientTransformation, device_placement=None) -> AcceleratedOptimizer:
        engine = self._engines[len(self._optimizers)] if len(self._engines) > len(self._optimizers) else (
            self._engines[-1] if self._engines else None
        )
        wrapped = AcceleratedOptimizer(optimizer, engine=engine)
        if engine is not None:
            engine.attach_optimizer(optimizer)
        self._optimizers.append(wrapped)
        return wrapped

    def prepare_scheduler(self, schedule: Callable) -> AcceleratedScheduler:
        wrapped = AcceleratedScheduler(
            schedule,
            optimizers=self._optimizers,
            split_batches=self.dataloader_config.split_batches,
            step_with_optimizer=self.step_scheduler_with_optimizer,
        )
        for engine in self._engines:
            if engine.schedule is None:
                engine.schedule = schedule
        self._schedulers.append(wrapped)
        return wrapped

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        prepared = prepare_data_loader(
            data_loader,
            mesh=self.state.mesh if (device_placement if device_placement is not None else self.device_placement) else None,
            rng_types=self.rng_types,
            config=self.dataloader_config,
        )
        self._dataloaders.append(prepared)
        return prepared

    # ------------------------------------------------------------------
    # the training contract
    # ------------------------------------------------------------------

    def backward(self, loss=None, **kwargs):
        """Reference accelerator.py:2164. The loss value is informational
        (grads were computed at the model call); accumulation scaling by
        1/num_steps happens here like the reference's loss division."""
        for engine in self._engines:
            if engine._pending_grads is not None:
                engine.backward(loss)

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: int = 2):
        """Reference accelerator.py:2292. Returns the global grad norm."""
        if norm_type != 2:
            raise ValueError("only L2 grad clipping is supported on TPU")
        norms = [e.clip_grad_norm(max_norm) for e in self._engines]
        return norms[0] if len(norms) == 1 else norms

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0):
        raise NotImplementedError(
            "clip_grad_value_ is not supported; use clip_grad_norm_ "
            "(value clipping breaks GSPMD gradient fusion)."
        )

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Reference accelerator.py:931-1088: toggles sync_gradients based on
        the step counter / dataloader end."""
        self._do_sync()
        yield

    def _do_sync(self):
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_state.num_steps) == 0
                or self.gradient_state.sync_each_batch
            )

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Under GSPMD grad reduction happens inside the fused update, so
        accumulating locally is already communication-free; this context just
        forces sync_gradients False for parity (reference accelerator.py:994)."""
        old = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(old)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """DDP Join parity (reference accelerator.py:1091). With global-batch
        SPMD feeding every process always sees the same number of batches, so
        this is a no-op wrapper (even_batches override included for parity)."""
        if even_batches is not None:
            for dl in self._dataloaders:
                dl.even_batches = even_batches
        yield

    @contextlib.contextmanager
    def autocast(self, autocast_handler: Optional[AutocastKwargs] = None):
        """Parity context (reference accelerator.py:3386): precision is a
        property of the staged computation, so nothing to switch here."""
        yield

    def replica_wire_bytes(self):
        """Per-step DCN wire bytes under the active gradient-compression
        config (see TrainEngine.replica_wire_bytes). Compare configs:

        >>> acc.replica_wire_bytes()                     # {"bytes": ...}
        >>> TrainEngine.replica_wire_bytes(params, "bfloat16")
        >>> TrainEngine.replica_wire_bytes(params, grad_compression_rank=4)
        """
        if not self._engines:
            raise RuntimeError("prepare(model, optimizer) before replica_wire_bytes")
        eng = self._engines[-1]
        sc = self.state.sharding_config
        return eng.replica_wire_bytes(
            eng.params,
            getattr(sc, "grad_compression_dtype", None),
            getattr(sc, "grad_compression_rank", None),
        )

    def build_train_step(
        self,
        loss_fn: Optional[Callable] = None,
        micro_steps: Optional[int] = None,
        steps_per_call: Optional[int] = None,
    ):
        """The fused-perf path: one XLA computation for the whole optimizer
        step (micro-batch scan + clip + update). Idiomatic-JAX users should
        prefer this over the eager-parity loop. ``steps_per_call=K`` scans K
        full optimizer steps in one executable (batch leaves gain a leading
        [K, ...] axis) — amortizes per-dispatch latency for small models."""
        if not self._engines:
            raise RuntimeError("prepare(model, optimizer) before build_train_step")
        return self._engines[-1].build_train_step(
            loss_fn=loss_fn, micro_steps=micro_steps, steps_per_call=steps_per_call
        )

    def audit_entrypoints(self, step, batch) -> list:
        """Static-audit specs for a step built by :meth:`build_train_step`
        (see :meth:`TrainEngine.audit_entrypoints`)."""
        if not self._engines:
            return []
        return self._engines[-1].audit_entrypoints(step, batch)

    # ------------------------------------------------------------------
    # collectives façade (reference accelerator.py:2408-2608)
    # ------------------------------------------------------------------

    def gather(self, tensor):
        return gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather + drop the tail samples duplicated by even_batches padding
        (reference accelerator.py:2408-2480, driven by GradientState.remainder)."""
        try:
            recursively_apply(lambda x: x, input_data, error_on_other_type=True)
            all_tensors = True
        except TypeError:
            all_tensors = False
        if use_gather_object or not all_tensors:
            data = gather_object(input_data)
        else:
            data = self.gather(input_data)
        if self.gradient_state.end_of_dataloader and self.gradient_state.remainder > 0:
            def _trim(t):
                # only batched leaves carry padding; scalars (e.g. a mean
                # loss) pass through untouched
                if getattr(t, "ndim", 0) == 0:
                    return t
                return t[: self.gradient_state.remainder]

            return recursively_apply(_trim, data)
        return data

    def reduce(self, tensor, reduction="sum", scale=1.0):
        return reduce(tensor, reduction, scale)

    def pad_across_processes(self, tensor, dim=0, pad_index=0, pad_first=False):
        return pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        if isinstance(model, PreparedModel):
            return model.unwrap()
        return model

    def prepare_for_eval(self, batch, batch_dim: int = 0):
        """Place an eval batch the same way prepared dataloaders do.
        ``batch_dim=1`` for a stacked [K, batch, ...] multi-step batch
        (``build_train_step(steps_per_call=K)``): steps axis replicated,
        batch axis sharded over the data mesh axes."""
        from .utils.operations import make_global_batch

        return make_global_batch(batch, self.state.mesh, batch_dim=batch_dim)

    # ------------------------------------------------------------------
    # trigger (coordinated breakpoint; reference accelerator.py:2198-2255)
    # ------------------------------------------------------------------

    def set_trigger(self):
        self.flag_tensor = True

    def check_trigger(self) -> bool:
        flags = gather_object([1 if self.flag_tensor else 0])
        if any(flags):
            self.flag_tensor = False
            return True
        return False

    # ------------------------------------------------------------------
    # trackers (reference accelerator.py:2610-2737)
    # ------------------------------------------------------------------

    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = {}):
        from .tracking import resolve_trackers

        self.trackers = resolve_trackers(self.log_with, project_name, self.logging_dir, init_kwargs)
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        from .tracking import GeneralTracker

        return GeneralTracker(_blank=True)

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = {}):
        for tracker in self.trackers:
            tracker.log(values, step=step, **log_kwargs.get(tracker.name, {}))

    def log_system_metrics(self, step: Optional[int] = None, extra: Optional[dict] = None,
                           log_kwargs: dict = {}) -> dict:
        """Flush the telemetry rollup (step time, tokens/s, MFU, data-wait
        split, compile/cache activity, memory, precision health — see
        docs/telemetry.md for the glossary) through every configured
        tracker, and return it. Requires ``telemetry=`` to be enabled."""
        if self.telemetry is None:
            raise RuntimeError(
                "telemetry is not enabled; pass telemetry=TelemetryConfig(...) "
                "(or True) to Accelerator, or set ATT_TELEMETRY=1."
            )
        values = self.telemetry.rollup()
        if extra:
            values = {**values, **extra}
        if values:
            if step is None:
                step = values.get("sys/step")
            self.log(values, step=step, log_kwargs=log_kwargs)
        return values

    def prometheus_metrics(self) -> str:
        """The live telemetry rollup + SLO histograms as Prometheus text
        exposition — what the scrape thread serves
        (``TelemetryConfig(exporter_port=...)`` / ``ATT_TELEMETRY_PORT``);
        exposed directly for custom health endpoints. Requires
        ``telemetry=`` to be enabled."""
        if self.telemetry is None:
            raise RuntimeError(
                "telemetry is not enabled; pass telemetry=TelemetryConfig(...) "
                "(or True) to Accelerator, or set ATT_TELEMETRY=1."
            )
        from .telemetry.exporter import prometheus_text

        return prometheus_text(self.telemetry)

    def end_training(self):
        if self.telemetry is not None:
            self.telemetry.close()
        for tracker in self.trackers:
            tracker.finish()

    # ------------------------------------------------------------------
    # save / load (reference accelerator.py:2739-3218) — checkpointing.py
    # ------------------------------------------------------------------

    def save(self, obj, f, safe_serialization: bool = True):
        from .utils.other import save as _save

        _save(obj, f, save_on_each_node=self.project_configuration.save_on_each_node,
              safe_serialization=safe_serialization)

    def save_model(self, model, save_directory, max_shard_size="10GB", safe_serialization=True):
        from .checkpointing import save_model_weights

        save_model_weights(model, save_directory, max_shard_size=max_shard_size,
                           safe_serialization=safe_serialization)

    def register_for_checkpointing(self, *objects):
        invalid = [obj for obj in objects if not (hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"All `objects` must include a `state_dict` and `load_state_dict` function to be stored: {invalid}"
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook):
        import uuid

        key = uuid.uuid4()
        self._save_model_state_pre_hook[key] = hook
        return _RemovableHandle(self._save_model_state_pre_hook, key)

    def register_load_state_pre_hook(self, hook):
        import uuid

        key = uuid.uuid4()
        self._load_model_state_pre_hook[key] = hook
        return _RemovableHandle(self._load_model_state_pre_hook, key)

    def save_state(self, output_dir: Optional[str] = None, safe_serialization: bool = True, **save_model_func_kwargs):
        from .checkpointing import save_accelerator_state

        if self.project_configuration.automatic_checkpoint_naming:
            output_dir = os.path.join(self.project_dir, "checkpoints")
        os.makedirs(output_dir, exist_ok=True)
        if self.project_configuration.automatic_checkpoint_naming:
            folders = [os.path.join(output_dir, folder) for folder in os.listdir(output_dir)]
            if (
                self.project_configuration.total_limit is not None
                and (len(folders) + 1 > self.project_configuration.total_limit)
                and self.is_main_process
            ):
                folders.sort(key=lambda f: int(f.rsplit("_", 1)[-1]) if f.rsplit("_", 1)[-1].isdigit() else -1)
                for folder in folders[: len(folders) + 1 - self.project_configuration.total_limit]:
                    import shutil

                    shutil.rmtree(folder, ignore_errors=True)
            output_dir = os.path.join(output_dir, f"checkpoint_{self.save_iteration}")
            if os.path.exists(output_dir):
                raise ValueError(
                    f"Checkpoint directory {output_dir} ({self.save_iteration}) already "
                    "exists. Please manually override `self.save_iteration` with what "
                    "iteration to start with."
                )
            self.wait_for_everyone()
        os.makedirs(output_dir, exist_ok=True)
        logger.info(f"Saving current state to {output_dir}")

        for hook in self._save_model_state_pre_hook.values():
            hook(self._models, [], output_dir)

        path = save_accelerator_state(
            output_dir,
            engines=self._engines,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
            step=self.step,
            safe_serialization=safe_serialization,
        )
        self.project_configuration.iteration += 1
        return path

    def load_state(self, input_dir: Optional[str] = None, **load_model_func_kwargs):
        from .checkpointing import load_accelerator_state

        if input_dir is None and self.project_configuration.automatic_checkpoint_naming:
            base = os.path.join(self.project_dir, "checkpoints")
            folders = sorted(
                os.listdir(base), key=lambda f: int(f.rsplit("_", 1)[-1]) if f.rsplit("_", 1)[-1].isdigit() else -1
            )
            input_dir = os.path.join(base, folders[-1])
        logger.info(f"Loading states from {input_dir}")

        for hook in self._load_model_state_pre_hook.values():
            hook(self._models, [], input_dir)

        override_step = load_accelerator_state(
            input_dir,
            engines=self._engines,
            schedulers=self._schedulers,
            dataloaders=self._dataloaders,
            custom_objects=self._custom_objects,
        )
        if override_step is not None:
            self.step = override_step

    def get_state_dict(self, model, unwrap=True):
        """Full (host-replicated) variables of a prepared model — the
        FSDP FULL_STATE_DICT consolidation analog (reference :3291-3348)."""
        if isinstance(model, PreparedModel):
            variables = model.state_dict()
        elif isinstance(model, Model):
            variables = model.variables
        else:
            variables = model
        from .utils.serialization import _to_numpy

        return jax.tree_util.tree_map(_to_numpy, variables)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        return _skip_first_batches(dataloader, num_batches)

    def free_memory(self, *objects):
        """Reference :3219. Drops engine/device state references + caches."""
        from .utils.memory import release_memory

        objects = release_memory(*objects)
        if self.telemetry is not None:
            self.telemetry._engines.clear()
        self._engines.clear()
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self.step = 0
        jax.clear_caches()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    def profile(self, profile_handler: Optional[ProfileKwargs] = None):
        handler = profile_handler or self.profile_handler or ProfileKwargs()
        return handler.build(suffix=str(self.process_index))

    @contextlib.contextmanager
    def local_sgd(self, *args, **kwargs):  # pragma: no cover - see local_sgd.py
        from .local_sgd import LocalSGD

        with LocalSGD(self, *args, **kwargs) as ctx:
            yield ctx

    def __repr__(self):
        return f"Accelerator(state={self.state!r})"


class _RemovableHandle:
    def __init__(self, registry, key):
        self.registry = registry
        self.key = key

    def remove(self):
        self.registry.pop(self.key, None)


def _is_dataloader_like(obj) -> bool:
    from .data import DataLoader

    if isinstance(obj, (DataLoader, DataLoaderShard, DataLoaderDispatcher)):
        return True
    return type(obj).__module__.startswith("torch.utils.data")
