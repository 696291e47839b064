"""notebook_launcher / debug_launcher
(parity: reference launchers.py, 302 LoC).

The torch version must xmp.spawn 8 processes on TPU (one per core) or fork
CUDA workers; JAX drives every local chip from ONE process, so
``notebook_launcher`` on a single host is just "call the function" after
setting launch env. Multi-process remains for the CPU/gloo debug path and
multi-host notebooks (each host runs its own kernel).
"""

from __future__ import annotations

import multiprocessing
import os
import subprocess
import sys
import tempfile
import textwrap
import time
from typing import Optional

from .utils.environment import env_var


def notebook_launcher(
    function,
    args=(),
    num_processes: Optional[int] = None,
    mixed_precision: str = "no",
    use_port: str = "29500",
    master_addr: str = "127.0.0.1",
    node_rank: int = 0,
    num_nodes: int = 1,
    **kwargs,
):
    """Run ``function(*args)`` under the launch env contract.

    - single host (the TPU case): executes inline — one process already
      sees all chips, nothing to spawn (reference must xmp.spawn instead);
    - ``num_processes > 1``: spawns CPU/gloo workers like debug_launcher
      (reference notebook GPU path).
    """
    if num_processes is None or num_processes <= 1:
        os.environ[env_var("MIXED_PRECISION")] = mixed_precision
        return function(*args)
    return _spawn_and_run(
        function, args, num_processes, mixed_precision, master_addr, use_port
    )


def debug_launcher(function, args=(), num_processes: int = 2):
    """Fork a world of ``num_processes`` CPU workers over gloo-on-localhost
    (reference debug_launcher:269 — world_size=2 CPU fork)."""
    return _spawn_and_run(function, args, num_processes, "no", "127.0.0.1", _free_port())


def _free_port() -> str:
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return str(s.getsockname()[1])


def _worker_env(rank, num_processes, mixed_precision, addr, port):
    return {
        "JAX_PLATFORMS": "cpu",
        env_var("MIXED_PRECISION"): mixed_precision,
        env_var("COORDINATOR_ADDRESS"): f"{addr}:{port}",
        env_var("NUM_PROCESSES"): str(num_processes),
        env_var("PROCESS_ID"): str(rank),
        env_var("LOCAL_PROCESS_ID"): str(rank),
        env_var("FORK_LAUNCHED"): "1",
    }


def _fork_worker(function, args, overrides):
    os.environ.update(overrides)
    function(*args)


def _jax_backends_initialized() -> bool:
    mods = sys.modules
    if "jax" not in mods:
        return False
    try:
        import jax._src.xla_bridge as xla_bridge

        return xla_bridge.backends_are_initialized()
    except Exception:
        return True  # unknown jax internals: assume live, take the safe path


def monitor_group(procs, *, poll, terminate, kill, wait, interval=0.05, grace=5.0) -> int:
    """Poll a worker group until all exit 0; on the first non-zero exit,
    terminate the rest (survivors blocked in collectives would hang forever),
    escalating to kill() if a worker ignores SIGTERM for ``grace`` seconds.
    Returns the first non-zero exit code, or 0. Shared by the notebook/debug
    launchers (mp.Process and subprocess workers) and `accelerate-tpu launch`.
    """
    while True:
        codes = [poll(p) for p in procs]
        bad = [c for c in codes if c not in (None, 0)]
        if bad:
            for p, c in zip(procs, codes):
                if c is None:
                    terminate(p)
            deadline = time.monotonic() + grace
            for p in procs:
                if not wait(p, max(0.0, deadline - time.monotonic())):
                    kill(p)
                    wait(p, grace)
            return bad[0]
        if all(c == 0 for c in codes):
            return 0
        time.sleep(interval)


def _mp_group_kwargs():
    return dict(
        poll=lambda p: None if p.is_alive() else p.exitcode,
        terminate=lambda p: p.terminate(),
        kill=lambda p: p.kill(),
        wait=lambda p, t: (p.join(t), not p.is_alive())[1],
    )


def _subprocess_group_kwargs():
    def _wait(p, timeout):
        try:
            p.wait(timeout=timeout)
            return True
        except subprocess.TimeoutExpired:
            return False

    return dict(
        poll=lambda p: p.poll(),
        terminate=lambda p: p.terminate(),
        kill=lambda p: p.kill(),
        wait=_wait,
    )


_WORKER_TEMPLATE = """
import cloudpickle, sys
with open({payload!r}, "rb") as f:
    function, args = cloudpickle.load(f)
function(*args)
"""


def _spawn_and_run(function, args, num_processes, mixed_precision, addr, port):
    """Run ``num_processes`` gloo-on-localhost workers.

    Default path: ``fork`` — children inherit ``__main__``, so functions
    defined in a notebook or a directly-run script work without any pickling
    (reference uses fork-based start_processes for the same reason). If jax
    backends are already initialized in this process, forking would inherit
    live runtime state, so fall back to fresh subprocesses with the function
    serialized by value via cloudpickle (which, unlike pickle, survives
    ``__main__``-defined functions and closures).
    """
    if not _jax_backends_initialized():
        ctx = multiprocessing.get_context("fork")
        procs = []
        for rank in range(num_processes):
            overrides = _worker_env(rank, num_processes, mixed_precision, addr, port)
            p = ctx.Process(target=_fork_worker, args=(function, tuple(args), overrides))
            p.start()
            procs.append(p)
        code = monitor_group(procs, **_mp_group_kwargs())
    else:
        import cloudpickle

        with tempfile.TemporaryDirectory() as td:
            payload = os.path.join(td, "fn.pkl")
            with open(payload, "wb") as f:
                cloudpickle.dump((function, tuple(args)), f)
            script = os.path.join(td, "worker.py")
            with open(script, "w") as f:
                f.write(textwrap.dedent(_WORKER_TEMPLATE).format(payload=payload))
            procs = []
            for rank in range(num_processes):
                env = dict(os.environ)
                env.update(_worker_env(rank, num_processes, mixed_precision, addr, port))
                procs.append(subprocess.Popen([sys.executable, script], env=env))
            code = monitor_group(procs, **_subprocess_group_kwargs())
    if code:
        raise RuntimeError(f"launcher worker failed with exit code {code}")
