"""LocalSGD (parity: /root/reference/src/accelerate/local_sgd.py, 103 LoC).

Run N optimizer steps with *per-replica* parameter copies, then average
parameters across the data-parallel dimension. The reference raises on TPU
(local_sgd.py:36-38); here it is supported natively with a real per-replica
engine mode:

- entering the context stacks params and optimizer state with a leading
  replica dim R (the product of the data-ish mesh axes), sharded over those
  axes — each replica group owns its own copy;
- ``build_local_step()`` returns a fused step that runs under ``shard_map``
  over the data axes: every replica computes gradients from ITS batch shard
  and applies the optax update to ITS copy — no cross-replica collective in
  the step, which is the entire point of LocalSGD (no per-step DCN/ICI
  gradient traffic on multi-slice meshes);
- every ``local_sgd_steps`` (and on exit) ``step()`` triggers the real
  synchronization: a parameter (and optimizer-moment) mean across the
  replica dim — one collective per N steps instead of per step;
- on exit the synced copy collapses back into the engine with its original
  shardings, so checkpointing and further (synchronous) training continue
  seamlessly.

Models with internal mesh sharding constraints (tensor/pipeline parallel)
are out of scope — LocalSGD is a data-parallel technique; pass a
``mesh=None`` model (the reference has the same restriction via DDP-only
support).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

_DATA_AXES = ("replica", "data", "fsdp")


class LocalSGD:
    def __init__(self, accelerator, model=None, local_sgd_steps: int = 8, enabled: bool = True):
        self.accelerator = accelerator
        self.model = model
        self.num_steps = local_sgd_steps
        self.mesh = accelerator.state.mesh
        self.axes = tuple(
            a for a in _DATA_AXES if self.mesh is not None and self.mesh.shape.get(a, 1) > 1
        )
        self.replicas = 1
        for a in self.axes:
            self.replicas *= self.mesh.shape[a]
        self.enabled = enabled and self.replicas > 1
        self.step_qty = 0
        self._stacked = None  # (params, opt_state) with leading replica dim
        self._active = False

    # ------------------------------------------------------------------
    @property
    def _engine(self):
        if self.model is not None and hasattr(self.model, "_engine"):
            return self.model._engine
        engines = getattr(self.accelerator, "_engines", [])
        return engines[0] if engines else None

    def __enter__(self):
        self.step_qty = 0
        if self.enabled:
            self._stack_state()
            engine = self._engine
            self._enter_step_count = engine.step_count if engine is not None else 0
            self._active = True
        return self

    def __exit__(self, *exc):
        if not self._active:
            return False
        if exc and exc[0] is not None:
            # an exception is already unwinding: don't collapse the snapshot
            # over the engine (and don't raise the misuse guard over it) —
            # drop the per-replica copies and leave engine state untouched
            self._active = False
            self._stacked = None
            return False
        self._check_engine_untouched()
        self._sync_and_avg_model_params()
        self._collapse_state()
        self._active = False
        return False

    def step(self):
        """Advance the LocalSGD step counter and sync every ``local_sgd_steps``.

        Must be paired with the step function returned by
        :meth:`build_local_step` — while the context is active the engine's
        own train step must NOT run (its updates would be overwritten by the
        stacked per-replica copies on exit; this raises if it did).
        """
        self.step_qty += 1
        if not self._active:
            return
        self._check_engine_untouched()
        if self.step_qty % self.num_steps == 0:
            self._sync_and_avg_model_params()

    def _check_engine_untouched(self):
        engine = self._engine
        if engine is not None and engine.step_count != self._enter_step_count:
            raise RuntimeError(
                "LocalSGD: the prepared engine advanced "
                f"{engine.step_count - self._enter_step_count} step(s) while the "
                "per-replica snapshot was active; those updates would be lost on "
                "exit. Inside the LocalSGD context, drive training with the step "
                "returned by build_local_step(), not the engine's train step."
            )

    # ------------------------------------------------------------------
    def _spec(self):
        return P(self.axes if len(self.axes) > 1 else self.axes[0])

    def _stack_sharding(self):
        return NamedSharding(self.mesh, self._spec())

    def _stack_state(self):
        engine = self._engine
        if engine is None:
            raise RuntimeError("LocalSGD needs a prepared model (accelerator.prepare first)")
        if engine.optimizer is None:
            raise RuntimeError("LocalSGD needs a prepared optimizer")
        R = self.replicas
        sharding = self._stack_sharding()

        def stack(leaf):
            if not hasattr(leaf, "shape"):
                return leaf
            return jax.device_put(
                jnp.broadcast_to(leaf[None], (R,) + tuple(leaf.shape)), sharding
            )

        self._stacked = (
            jax.tree_util.tree_map(stack, engine.params),
            jax.tree_util.tree_map(stack, engine.opt_state),
        )

    def _collapse_state(self):
        """Fold the (already synced) stacked copies back into the engine."""
        engine = self._engine
        params, opt_state = self._stacked

        def collapse(leaf, like):
            if not hasattr(leaf, "shape"):
                return leaf
            mean = jnp.mean(leaf.astype(jnp.float32), axis=0).astype(like.dtype)
            return jax.device_put(mean, like.sharding) if hasattr(like, "sharding") else mean

        engine.params = jax.tree_util.tree_map(collapse, params, engine.params)
        engine.opt_state = jax.tree_util.tree_map(collapse, opt_state, engine.opt_state)
        engine.step_count += self.step_qty
        self._stacked = None

    def _sync_and_avg_model_params(self):
        """The real LocalSGD synchronization (reference local_sgd.py:95):
        mean the per-replica parameter (and moment) copies across the
        replica dim — one allreduce per sync window."""
        if not self._active:
            self.accelerator.wait_for_everyone()
            return

        @jax.jit
        def avg(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True), x.shape
                ).astype(x.dtype)
                if hasattr(x, "shape")
                else x,
                tree,
            )

        params, opt_state = self._stacked
        self._stacked = (avg(params), avg(opt_state))

    # ------------------------------------------------------------------
    def build_local_step(self, loss_fn=None):
        """Fused per-replica train step: each replica group updates its own
        copy from its own batch shard, with NO cross-replica collective.
        Use inside the context instead of the engine's build_train_step."""
        engine = self._engine
        if not self._active:
            return engine.build_train_step(loss_fn=loss_fn)
        mesh = self.mesh
        axes = self.axes
        optimizer = engine.optimizer
        user_loss = loss_fn or engine.loss_fn

        from .accelerator import _batch_to_call

        def per_replica(params_blk, opt_blk, key, batch_blk):
            # block shapes carry a leading local-replica dim of 1
            params = jax.tree_util.tree_map(lambda x: x[0], params_blk)
            opt_state = jax.tree_util.tree_map(lambda x: x[0], opt_blk)
            idx = jax.lax.axis_index(axes[0]) if len(axes) == 1 else jax.lax.axis_index(axes)
            key = jax.random.fold_in(key, idx)

            def local_loss(p):
                args, kwargs = _batch_to_call(batch_blk)
                outputs, _ = engine._apply(engine._cast_params(p), engine.extra_state, True, key, args, kwargs)
                return user_loss(outputs).astype(jnp.float32)

            loss, grads = jax.value_and_grad(local_loss)(params)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates
            )
            expand = lambda t: jax.tree_util.tree_map(lambda x: x[None] if hasattr(x, "shape") else x, t)
            return expand(new_params), expand(new_opt), loss[None]

        spec = self._spec()
        replicated = P()
        stepped = shard_map(
            per_replica,
            mesh=mesh,
            in_specs=(spec, spec, replicated, spec),
            out_specs=(spec, spec, spec),
            check_vma=False,
        )
        jitted = jax.jit(stepped)

        def run(batch):
            from .utils.random import default_keychain

            key = default_keychain().next_key("local_sgd")
            params, opt_state = self._stacked
            new_params, new_opt, losses = jitted(params, opt_state, key, batch)
            self._stacked = (new_params, new_opt)
            return {"loss": jnp.mean(losses), "per_replica_loss": losses}

        return run
