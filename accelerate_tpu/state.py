"""Process/device runtime singletons: PartialState, AcceleratorState,
GradientState.

Parity target: /root/reference/src/accelerate/state.py (1,234 LoC). Same
singleton-shared-``__dict__`` design (state.py:82,153) so every instance
anywhere in the program sees one runtime. What changes on TPU:

- backend selection + ``init_process_group`` (state.py:709-766) becomes
  `jax.distributed.initialize` (only on multi-host) + `jax.Mesh` construction;
- "device" is a mesh of devices, not one cuda index; rank topology comes from
  `jax.process_index/process_count` (hosts) and `jax.device_count` (chips);
- `wait_for_everyone` (state.py:342) becomes a sync over global devices.
"""

from __future__ import annotations

import logging
import os
import time
from contextlib import contextmanager
from functools import wraps
from typing import Any, Callable, Optional

import jax

from .parallel.mesh import build_mesh, mesh_shape_dict
from .utils.dataclasses import (
    DistributedType,
    GradientAccumulationPlugin,
    MixedPrecisionConfig,
    PrecisionType,
    ShardingConfig,
    ShardingStrategy,
)
from .utils.environment import (
    get_coordinator_address,
    get_env,
    get_flag,
    get_num_processes_env,
    get_process_id,
    parse_choice_from_env,
)

logger = logging.getLogger(__name__)

_jax_distributed_initialized = False


def _maybe_init_jax_distributed():
    """Initialize jax.distributed exactly once, iff launch env asks for it.

    The launcher writes COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID
    (utils/launch env contract, ≙ reference MASTER_ADDR/WORLD_SIZE/RANK).
    Single-host runs skip this entirely — jax sees local devices directly.
    """
    global _jax_distributed_initialized
    if _jax_distributed_initialized:
        return
    coord = get_coordinator_address()
    nproc = get_num_processes_env()
    if coord and nproc and nproc > 1:
        # Cross-process collectives on the CPU backend need gloo (the
        # debug/gloo-on-localhost test path, reference launchers.py:269).
        # Setting it only configures the CPU client, so it is safe to set
        # unconditionally — also covers hosts where CPU is the default
        # platform without JAX_PLATFORMS being set.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nproc,
            process_id=get_process_id() or 0,
        )
        _jax_distributed_initialized = True


class _SharedDict(dict):
    """All instances of a state class share one dict (reference state.py:38-82;
    we use a plain class-level dict — the reference's thread-local variant
    existed only for torch_xla's one-process-per-device spawn model, which JAX
    does not use: one process drives all local chips)."""


class PartialState:
    """Topology + process-control singleton (reference state.py:114).

    Knows nothing about mixed precision or sharding strategy — just who we
    are (process_index / num_processes), what devices exist, and process
    coordination primitives.
    """

    _shared_state = _SharedDict()

    def __init__(self, cpu: bool = False, **kwargs):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        self._cpu = cpu or parse_choice_from_env("JAX_PLATFORMS", "") == "cpu"
        self.debug = get_flag("DEBUG_MODE")
        _maybe_init_jax_distributed()

        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        # All processes on one host would need distinct local indices; JAX
        # runs one process per host, so local index is 0 unless the launcher
        # says otherwise (CPU-sim multi-proc testing).
        self.local_process_index = int(get_env("LOCAL_PROCESS_ID", 0))
        self.devices = jax.local_devices()
        self.device = self.devices[0]
        backend = jax.default_backend()
        self.backend = backend
        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif backend == "cpu":
            self.distributed_type = (
                DistributedType.CPU_SIM if jax.device_count() > 1 else DistributedType.NO
            )
        elif jax.device_count() > 1:
            self.distributed_type = DistributedType.TPU
        else:
            self.distributed_type = DistributedType.NO
        self.fork_launched = get_flag("FORK_LAUNCHED")

    # -- lifecycle ---------------------------------------------------------

    @property
    def initialized(self) -> bool:
        return "distributed_type" in self.__dict__

    @classmethod
    def _reset_state(cls):
        """Tear down for tests (reference state.py:1189)."""
        cls._shared_state.clear()

    # -- topology ----------------------------------------------------------

    @property
    def num_devices(self) -> int:
        return jax.device_count()

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    @property
    def use_distributed(self) -> bool:
        return self.num_devices > 1 or self.num_processes > 1

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # -- coordination ------------------------------------------------------

    def wait_for_everyone(self):
        """Cross-host barrier (reference state.py:342). On a single process
        this is a device sync (flush pending async dispatch)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")
        else:
            (jax.device_put(0) + 0).block_until_ready()

    @contextmanager
    def main_process_first(self):
        """Main process runs the body first, others wait (state.py:477)."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self):
        if not self.is_local_main_process:
            self.wait_for_everyone()
        yield
        if self.is_local_main_process:
            self.wait_for_everyone()

    def on_main_process(self, function: Callable = None):
        """Decorator: run only on the main process (state.py:518)."""

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable = None):
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable):
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable = None, process_index: int = None):
        if function is None:
            return lambda f: self.on_process(f, process_index)

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    def on_local_process(self, function: Callable = None, local_process_index: int = None):
        if function is None:
            return lambda f: self.on_local_process(f, local_process_index)

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return wrapper

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/dict/array evenly across processes (state.py:388).

        With ``apply_padding`` the last process's share is padded with the
        final element so all shares are equal-length (needed before gather).
        """
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs)
        if isinstance(inputs, dict):
            length = len(inputs[list(inputs.keys())[0]])
            if not all(len(v) == length for v in inputs.values()):
                raise ValueError("All dict values must have the same length")
        num_samples_per_process, num_extras = divmod(length, self.num_processes)
        start = self.process_index * num_samples_per_process + min(self.process_index, num_extras)
        end = start + num_samples_per_process + (1 if self.process_index < num_extras else 0)

        def _split(obj):
            if isinstance(obj, dict):
                return {k: _split(v) for k, v in obj.items()}
            result = obj[start:end]
            if apply_padding:
                whole = num_samples_per_process + (1 if num_extras > 0 else 0)
                if hasattr(result, "shape"):
                    import numpy as np

                    while result.shape[0] < whole:
                        result = np.concatenate([result, result[-1:]], axis=0)
                else:
                    result = list(result) + [result[-1]] * (whole - len(result))
            return result

        yield _split(inputs)

    # -- telemetry heartbeat ----------------------------------------------

    def publish_heartbeat(self, step: int):
        """Record this process's training progress in the shared state dict.

        The slot lives in ``_shared_state`` (the dict every PartialState
        instance aliases), so the telemetry watchdog's monitor thread — or
        any other observer — reads the latest beat through a fresh
        ``PartialState()`` with zero coupling to the training loop. The
        step counter is monotonic per run; the timestamp is
        ``time.monotonic()`` (immune to wall-clock jumps)."""
        self.__dict__["telemetry_heartbeat"] = (int(step), time.monotonic())

    @property
    def heartbeat(self):
        """``(step, monotonic_time)`` of the last published heartbeat, or
        None when nothing has beaten yet."""
        return self.__dict__.get("telemetry_heartbeat")

    def set_device(self):  # pragma: no cover - parity no-op
        """JAX owns device selection; kept for API parity."""

    def print(self, *args, **kwargs):
        if self.is_local_main_process:
            print(*args, **kwargs)

    def __repr__(self):
        return (
            f"Distributed environment: {self.distributed_type}\n"
            f"Num processes: {self.num_processes}\n"
            f"Process index: {self.process_index}\n"
            f"Local process index: {self.local_process_index}\n"
            f"Device count: {self.num_devices}\n"
            f"Backend: {self.backend}\n"
        )


class AcceleratorState:
    """Adds mixed precision + sharding/mesh on top of PartialState
    (reference state.py:815)."""

    _shared_state = _SharedDict()

    def __init__(
        self,
        mixed_precision: str | None = None,
        cpu: bool = False,
        sharding_config: Optional[ShardingConfig] = None,
        _from_accelerator: bool = False,
        **kwargs,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if mixed_precision is not None and mixed_precision != self.mixed_precision:
                raise ValueError(
                    "AcceleratorState already initialized with "
                    f"mixed_precision={self.mixed_precision!r}; create the Accelerator "
                    "before any other AcceleratorState() use, or _reset_state() first."
                )
            if sharding_config is not None and sharding_config != self.sharding_config:
                raise ValueError(
                    "AcceleratorState already initialized with a different "
                    f"sharding_config ({self.sharding_config}); create the Accelerator "
                    "before any other AcceleratorState() use, or _reset_state() first."
                )
            return
        self._partial = PartialState(cpu, **kwargs)
        mp = mixed_precision or get_env("MIXED_PRECISION", "no")
        self.precision = MixedPrecisionConfig(mode=PrecisionType(mp))
        self.sharding_config = sharding_config or _sharding_config_from_env()
        self.mesh = build_mesh(self.sharding_config.resolve(jax.device_count()))
        self.initialized_from_accelerator = _from_accelerator

    @property
    def initialized(self) -> bool:
        return "precision" in self.__dict__

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False):
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()
            GradientState._reset_state()

    @property
    def mixed_precision(self) -> str:
        return self.precision.mode.value

    @property
    def mesh_shape(self) -> dict:
        return mesh_shape_dict(self.mesh)

    def __getattr__(self, name):
        # Delegate topology/coordination to PartialState (reference does the
        # same via shared dict; we compose instead).
        if name in ("_partial",) or name.startswith("__"):
            raise AttributeError(name)
        partial = self.__dict__.get("_partial")
        if partial is None:
            raise AttributeError(
                f"AcceleratorState has no attribute {name!r} (not initialized)"
            )
        return getattr(partial, name)

    def __repr__(self):
        return (
            repr(self._partial)
            + f"Mixed precision: {self.mixed_precision}\n"
            + f"Mesh: {self.mesh_shape}\n"
        )


def _sharding_config_from_env() -> ShardingConfig:
    """Build ShardingConfig from launcher env vars (config cascade level 2;
    reference plugins read FSDP_*/MEGATRON_LM_* envs in __post_init__)."""
    kwargs = {}
    mapping = {
        "STRATEGY": ("strategy", str),
        "DATA_PARALLEL": ("data_parallel", int),
        "FSDP": ("fsdp", int),
        "TENSOR_PARALLEL": ("tensor_parallel", int),
        "SEQUENCE_PARALLEL": ("sequence_parallel", int),
        "EXPERT_PARALLEL": ("expert_parallel", int),
        "PIPELINE_PARALLEL": ("pipeline_parallel", int),
        "REPLICA": ("replica", int),
        "GRAD_COMPRESSION": ("grad_compression_dtype", str),
    }
    for env_name, (field_name, cast) in mapping.items():
        v = get_env(env_name)
        if v:  # unset AND empty both mean "not configured" (launcher stomps
            #    GRAD_COMPRESSION with "" to kill stale inherited values)
            kwargs[field_name] = cast(v)
    return ShardingConfig(**kwargs)


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference state.py:1111).

    ``sync_gradients`` tells wrappers whether this micro-step is a boundary;
    ``remainder`` records how many tail samples of the last batch are padding
    (consumed by ``gather_for_metrics``); active dataloaders register here so
    end-of-epoch forces a sync (reference state.py:1216-1229).
    """

    _shared_state = _SharedDict()

    def __init__(self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = [None]
            self.plugin_kwargs = (
                gradient_accumulation_plugin.to_kwargs()
                if gradient_accumulation_plugin is not None
                else {}
            )
            self._is_xla_gradients_synced = True
        if gradient_accumulation_plugin is not None and self.plugin_kwargs != gradient_accumulation_plugin.to_kwargs():
            self.plugin_kwargs = gradient_accumulation_plugin.to_kwargs()

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self.__dict__

    @classmethod
    def _reset_state(cls):
        cls._shared_state.clear()

    @property
    def num_steps(self) -> int:
        return self.plugin_kwargs.get("num_steps", 1)

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin_kwargs.get("adjust_scheduler", True)

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin_kwargs.get("sync_with_dataloader", True)

    @property
    def sync_each_batch(self) -> bool:
        return self.plugin_kwargs.get("sync_each_batch", False)

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _add_dataloader(self, dataloader):
        self.dataloader_references.append(dataloader)
        self.active_dataloader = dataloader

    def _remove_dataloader(self, dataloader):
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    def _set_sync_gradients(self, value: bool):
        self.sync_gradients = value

    def __repr__(self):
        return (
            f"Sync Gradients: {self.sync_gradients}\n"
            f"At end of current dataloader: {self.end_of_dataloader}\n"
            f"Extra samples added: {self.remainder}\n"
            f"Gradient accumulation plugin: {self.plugin_kwargs}\n"
        )
