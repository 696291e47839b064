"""CPU rehearsal of ``chip_smoke.py`` (the on-chip-measurement guide's first
two rehearsals): the same phases and checks at tiny widths, kernels through
the pallas interpreter, in a subprocess as the chip tool would run it. It
guards control flow only; the chip run is the proof (README, "On the chip").
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK = "[CPU REHEARSAL, not a chip run] "


RUNS = {
    "one_chip": ("--cpu-rehearsal", "--chips", "1"),
    "four_chips": ("--cpu-rehearsal", "--chips", "4"),
    "no_chip": (),
    "no_chip_chips4": ("--chips", "4"),
}


@pytest.fixture(scope="module")
def runs():
    """{name: (returncode, stdout, stderr)} of every run above, started
    together: each is a process of its own, as the chip tool would start it,
    so the module costs what its slowest run costs (the one-chip rehearsal)
    and not their sum. The suite's compile-time lever would make the
    interpreted kernels run ~6x slower than they compile faster; the smoke
    runs as a user would run it. JAX_PLATFORMS=cpu is pinned for all of them:
    for the two runs without --cpu-rehearsal that is the point."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_DISABLE_MOST_OPTIMIZATIONS")}
    env["JAX_PLATFORMS"] = "cpu"
    procs = {
        name: subprocess.Popen(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flags],
            env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for name, flags in RUNS.items()
    }
    try:
        done = {}
        for name, proc in procs.items():
            out, err = proc.communicate(timeout=600)
            done[name] = (proc.returncode, out, err)
        yield done
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()


@pytest.mark.parametrize("name, chips, phases", [
    ("one_chip", 1, ("kernels vs references", "state-space scan vs reference", "closing-window pooling vs reference",
                    "latent attention's kernels vs their dense reads",
                    "ssd: the recurrence with heads and two-matrix experts vs their jax.numpy reads",
                    "gdn: the delta rule's two forms and many narrow experts vs their jax.numpy reads", "train", "serve")),
    ("four_chips", 4, ("four chips: fsdp2 x tp2 trainer vs one device",)),
], ids=["one_chip", "four_chips"])
def test_cpu_rehearsal_runs_every_phase(runs, name, chips, phases):
    returncode, stdout, stderr = runs[name]
    assert returncode == 0, stderr[-3000:]
    lines = stdout.strip().splitlines()
    # it says so on every line, so no line can pass for a chip result
    assert lines and all(l.startswith(MARK) for l in lines)
    for phase in phases:
        assert f"{MARK}== {phase}: ok in" in stdout
    assert sum(": ok in" in l for l in lines) == len(phases)
    assert f'"platform": "cpu", "kind": "cpu", "count": {chips}' in lines[-1]
    if chips == 1:
        assert "0 compile events after step 1" in stdout
        assert "0 compile events after warmup()" in stdout
        assert "first token agrees on" in stdout
    else:
        assert "4 distinct shards of 1/4 on 4 devices" in stdout


@pytest.mark.parametrize("name", ["no_chip", "no_chip_chips4"])
def test_without_a_chip_it_fails_and_prints_no_result(runs, name):
    """No TPU and no explicit --cpu-rehearsal: a non-zero exit and nothing on
    stdout — JAX_PLATFORMS=cpu in the environment (this sandbox has it) is
    not a request for the rehearsal, and there is no falling through."""
    returncode, stdout, stderr = runs[name]
    assert returncode != 0
    assert stdout == ""
    assert "need" in stderr and "tpu" in stderr
