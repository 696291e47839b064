"""Compile-cache resolution in utils/compile_cache.py: one directory,
placed from outside. ``JAX_COMPILATION_CACHE_DIR`` set -> that directory
and nothing set in code; unset -> the fixed in-checkout path."""

import os
import subprocess
import sys

import jax
import pytest

import accelerate_tpu.utils.compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_state(monkeypatch, tmp_path):
    """Snapshot/restore the jax config state these tests mutate (conftest
    points the whole suite at one shared cache dir through the env)."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = {
        k: getattr(jax.config, k)
        for k in ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")
    }
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    yield monkeypatch, tmp_path
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_env_dir_is_the_cache_dir(cache_state):
    monkeypatch, tmp_path = cache_state
    user = str(tmp_path / "usercache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", user)
    assert cc.ensure_persistent_compile_cache() == user
    # applied, not just reported: jax only reads the env var at import time
    assert jax.config.jax_compilation_cache_dir == user
    assert os.path.isdir(user)


def test_env_dir_keeps_the_users_thresholds(cache_state):
    monkeypatch, tmp_path = cache_state
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 7.0)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    cc.ensure_persistent_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 7.0


def test_env_dir_outranks_a_dir_set_in_code(cache_state):
    """The acceptance rule: with the variable set, no other directory is
    used — even one some code configured after import."""
    monkeypatch, tmp_path = cache_state
    env_dir = str(tmp_path / "from_env")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "from_code"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert cc.ensure_persistent_compile_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert not os.path.exists(tmp_path / "from_code")


def test_unset_means_the_fixed_in_checkout_dir(cache_state):
    assert cc.REPO_CACHE_DIR == os.path.join(REPO, ".xla_cache")
    assert cc.ensure_persistent_compile_cache() == cc.REPO_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cc.REPO_CACHE_DIR
    # our directory, our thresholds
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5
    # idempotent: generate() calls this on every invocation
    assert cc.ensure_persistent_compile_cache() == cc.REPO_CACHE_DIR


def test_unusable_dir_raises_naming_the_path(cache_state):
    """A cache dir that cannot be created must fail loudly — carrying on
    uncached was every restart paying full recompiles with nothing in the
    logs."""
    monkeypatch, tmp_path = cache_state
    blocker = tmp_path / "a_file"
    blocker.write_text("not a dir")
    target = str(blocker / "cache")  # parent is a regular file
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
    with pytest.raises(OSError, match="a_file/cache"):
        cc.ensure_persistent_compile_cache()


def test_disabled_by_jax_switch_returns_none(cache_state):
    jax.config.update("jax_enable_compilation_cache", False)
    assert cc.ensure_persistent_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_accelerator_leaves_jax_on_the_env_dir(cache_state):
    """``Accelerator()`` turns the cache on, at the directory the environment
    names — also when some code had configured another one before."""
    from accelerate_tpu import Accelerator

    monkeypatch, tmp_path = cache_state
    env_dir = str(tmp_path / "outside")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "from_code"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    Accelerator()
    assert jax.config.jax_compilation_cache_dir == env_dir


_PROBE = (
    "import jax; from accelerate_tpu.utils.compile_cache import "
    "ensure_persistent_compile_cache as ensure; "
    "print('DIR=' + str(ensure()) + '|' + str(jax.config.jax_compilation_cache_dir))"
)


def test_same_fixed_dir_from_two_processes(tmp_path):
    """The directory is part of what an entry is found by: with the variable
    unset, two fresh processes started in different places must resolve the
    same in-checkout one."""
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE], env=env, cwd=str(cwd),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for cwd in (tmp_path, REPO)  # both at once: they share nothing but the answer
    ]
    seen = []
    for proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err[-2000:]
        seen.append(out.strip().splitlines()[-1])
    assert seen == [f"DIR={cc.REPO_CACHE_DIR}|{cc.REPO_CACHE_DIR}"] * 2
