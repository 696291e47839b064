"""`accelerate-tpu launch` — start a training script on TPU hosts.

Parity target: /root/reference/src/accelerate/commands/launch.py (1,184 LoC).
The torch version multiplexes over torchrun/deepspeed/sagemaker/xmp.spawn;
on TPU the topology is simpler — ONE process per host drives all local
chips — so the dispatch collapses to three launchers:

  simple_launcher      single host: exec the script with env set
                       (reference simple_launcher:762)
  multi_process_launcher
                       N processes on THIS machine with the
                       COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID env
                       contract; used for multi-host-style testing on
                       localhost (the reference's gloo-on-localhost test
                       strategy, SURVEY §4) and by pod fan-out re-entry
  tpu_pod_launcher     gcloud ssh to every TPU-VM worker re-invoking this
                       CLI (reference tpu_pod_launcher:893 = xla_dist)

Precedence: CLI flag > config yaml > default (reference
_validate_launch_command:972 merge semantics).
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
from typing import Optional

from ..utils.environment import env_var
from .config_args import ClusterConfig, load_config_from_file


def register(subparsers):
    parser = subparsers.add_parser("launch", help="Launch a script on this host / a TPU pod")
    parser.add_argument("--config_file", default=None)
    parser.add_argument("--num_processes", type=int, default=None, help="Number of host processes")
    parser.add_argument("--num_machines", type=int, default=None, help="Alias of --num_processes (reference parity)")
    parser.add_argument("--mixed_precision", choices=["no", "fp16", "bf16"], default=None)
    parser.add_argument("--cpu", action="store_true", help="Force CPU (with gloo collectives when multi-process)")
    parser.add_argument("--main_process_ip", default=None)
    parser.add_argument("--main_process_port", type=int, default=None)
    parser.add_argument("--gradient_accumulation_steps", type=int, default=None)
    # sharding degrees (the FSDP/DeepSpeed/Megatron arg-group analog)
    for axis in ("data_parallel", "fsdp", "tensor_parallel", "sequence_parallel",
                 "expert_parallel", "pipeline_parallel", "replica"):
        parser.add_argument(f"--{axis}", type=int, default=None)
    parser.add_argument("--sharding_strategy", default=None)
    parser.add_argument("--grad_compression_dtype", default=None,
                        choices=["bfloat16", "float16", "int8", "bf16", "fp16", "none"],
                        help="Compress the cross-slice (DCN) gradient all-reduce; 'none' disables")
    # pod fan-out
    parser.add_argument("--tpu_name", default=None)
    parser.add_argument("--tpu_zone", default=None)
    parser.add_argument("--tpu_project", default=None)
    parser.add_argument("--tpu_use_sudo", action="store_true")
    parser.add_argument("--downcast_bf16", action="store_true")
    parser.add_argument("-m", "--module", action="store_true", help="Run script as a python module")
    parser.add_argument("--no_python", action="store_true", help="Exec script directly (not via python)")
    parser.add_argument("--quiet", "-q", action="store_true")
    parser.add_argument("--debug", action="store_true")
    parser.add_argument("--max_restarts", type=int, default=0,
                        help="Relaunch the whole world up to N times after a worker failure (elastic parity)")
    parser.add_argument("--monitor_interval", type=float, default=0.1,
                        help="Seconds between worker health polls")
    parser.add_argument("training_script", help="Script (or module) to launch")
    parser.add_argument("training_script_args", nargs=argparse_remainder(), help="Script args")
    parser.set_defaults(func=launch_command)
    return parser


def argparse_remainder():
    import argparse

    return argparse.REMAINDER


def _merge(args, config: ClusterConfig) -> ClusterConfig:
    """CLI overrides config file (reference _validate_launch_command:972)."""
    merged = ClusterConfig(**config.to_dict())
    if args.num_processes is not None:
        merged.num_processes = args.num_processes
    elif args.num_machines is not None:
        merged.num_processes = args.num_machines
    if args.mixed_precision is not None:
        merged.mixed_precision = args.mixed_precision
    if args.main_process_ip is not None:
        merged.main_process_ip = args.main_process_ip
    if args.main_process_port is not None:
        merged.main_process_port = args.main_process_port
    if args.sharding_strategy is not None:
        merged.sharding_strategy = args.sharding_strategy
    for axis in ("data_parallel", "fsdp", "tensor_parallel", "sequence_parallel",
                 "expert_parallel", "pipeline_parallel", "replica"):
        v = getattr(args, axis)
        if v is not None:
            setattr(merged, axis, v)
    if args.grad_compression_dtype is not None:
        merged.grad_compression_dtype = (
            None if args.grad_compression_dtype == "none" else args.grad_compression_dtype
        )
    for flag in ("tpu_name", "tpu_zone", "tpu_project"):
        v = getattr(args, flag)
        if v is not None:
            setattr(merged, flag, v)
    if args.debug:
        merged.debug = True
    if args.downcast_bf16:
        merged.downcast_bf16 = True
    return merged


def prepare_launch_env(config: ClusterConfig, args=None) -> dict:
    """The ACCELERATE_TPU_* env contract consumed by state.py
    (reference prepare_simple_launcher_cmd_env:91 writes ACCELERATE_*)."""
    env = dict(os.environ)
    env[env_var("MIXED_PRECISION")] = config.mixed_precision
    env[env_var("STRATEGY")] = str(config.sharding_strategy)
    for axis, name in (
        ("data_parallel", "DATA_PARALLEL"),
        ("fsdp", "FSDP"),
        ("tensor_parallel", "TENSOR_PARALLEL"),
        ("sequence_parallel", "SEQUENCE_PARALLEL"),
        ("expert_parallel", "EXPERT_PARALLEL"),
        ("pipeline_parallel", "PIPELINE_PARALLEL"),
        ("replica", "REPLICA"),
    ):
        env[env_var(name)] = str(getattr(config, axis))
    # always stomp (like the axis vars): a stale inherited value must not
    # resurrect compression the current config doesn't ask for
    env[env_var("GRAD_COMPRESSION")] = config.grad_compression_dtype or ""
    if config.debug:
        env[env_var("DEBUG_MODE")] = "1"
    if config.downcast_bf16:
        env[env_var("DOWNCAST_BF16")] = "1"
    if config.compilation_cache_dir:
        # jax's own variable: the one place utils/compile_cache.py reads
        env["JAX_COMPILATION_CACHE_DIR"] = config.compilation_cache_dir
    if args is not None and getattr(args, "gradient_accumulation_steps", None):
        env[env_var("GRADIENT_ACCUMULATION_STEPS")] = str(args.gradient_accumulation_steps)
    return env


def _script_cmd(args) -> list:
    if args.no_python:
        cmd = [args.training_script]
    elif args.module:
        cmd = [sys.executable, "-m", args.training_script]
    else:
        cmd = [sys.executable, args.training_script]
    return cmd + list(args.training_script_args)


def simple_launcher(args, config: ClusterConfig) -> int:
    """One process on this host drives all its chips (the normal TPU case)."""
    env = prepare_launch_env(config, args)
    if args.cpu:
        _force_cpu(env)
    process = subprocess.Popen(_script_cmd(args), env=env)
    process.wait()
    return process.returncode


def multi_process_launcher(args, config: ClusterConfig) -> int:
    """Spawn num_processes local processes with the distributed env contract
    (COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID). With --cpu this is the
    debug/gloo-on-localhost path; on a pod worker it re-enters per host.

    Monitors the whole world: the first worker to exit non-zero gets the rest
    killed (survivors would otherwise hang in collectives), and with
    ``--max_restarts`` the world is relaunched on a fresh port — the
    torchrun-elastic restart semantic (reference launch.py:774-806)."""
    n = config.num_processes
    ip = config.main_process_ip or "127.0.0.1"
    base_env = prepare_launch_env(config, args)
    max_restarts = getattr(args, "max_restarts", 0) or 0
    interval = getattr(args, "monitor_interval", 0.1) or 0.1
    for attempt in range(max_restarts + 1):
        # fresh port each attempt: the old coordinator socket may linger
        port = config.main_process_port if (config.main_process_port and attempt == 0) else _free_port()
        procs = []
        for rank in range(n):
            env = dict(base_env)
            env[env_var("COORDINATOR_ADDRESS")] = f"{ip}:{port}"
            env[env_var("NUM_PROCESSES")] = str(n)
            env[env_var("PROCESS_ID")] = str(rank)
            env[env_var("LOCAL_PROCESS_ID")] = str(rank)
            env[env_var("RESTART_COUNT")] = str(attempt)
            if args.cpu:
                _force_cpu(env)
            procs.append(subprocess.Popen(_script_cmd(args), env=env))
        from ..launchers import _subprocess_group_kwargs, monitor_group

        code = monitor_group(procs, interval=interval, **_subprocess_group_kwargs())
        if code == 0:
            return 0
        if attempt < max_restarts:
            print(f"[accelerate-tpu launch] worker failed (exit {code}); "
                  f"restart {attempt + 1}/{max_restarts}", file=sys.stderr)
    return code


def tpu_pod_launcher(args, config: ClusterConfig) -> int:
    """gcloud ssh fan-out: run the same launch on every TPU-VM worker
    (reference tpu_pod_launcher:893). jax.distributed auto-discovers the
    pod topology from TPU metadata, so workers need no rank env."""
    script_cmd = " ".join(shlex.quote(c) for c in _script_cmd(args))
    env_exports = " ".join(
        f"{k}={shlex.quote(v)}"
        for k, v in prepare_launch_env(config, args).items()
        if k.startswith(env_var(""))
    )
    remote = f"cd {shlex.quote(os.getcwd())} && {env_exports} {script_cmd}"
    if args.tpu_use_sudo:
        remote = "sudo " + remote
    cmd = [
        "gcloud", "compute", "tpus", "tpu-vm", "ssh", config.tpu_name,
        f"--zone={config.tpu_zone}",
        "--worker=all",
        f"--command={remote}",
    ]
    if config.tpu_project:
        cmd.append(f"--project={config.tpu_project}")
    process = subprocess.Popen(cmd)
    process.wait()
    return process.returncode


def _force_cpu(env: dict) -> None:
    """Hold the child processes to the CPU."""
    env["JAX_PLATFORMS"] = "cpu"


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def launch_command(args) -> int:
    config = _merge(args, load_config_from_file(args.config_file))
    if config.tpu_name:
        return tpu_pod_launcher(args, config)
    if config.num_processes and config.num_processes > 1:
        return multi_process_launcher(args, config)
    return simple_launcher(args, config)


def main():  # pragma: no cover - direct entry
    import argparse

    parser = argparse.ArgumentParser("accelerate-tpu launch")
    sub = parser.add_subparsers()
    register(sub)
    args = parser.parse_args(["launch"] + sys.argv[1:])
    sys.exit(args.func(args))
