"""Test harness: simulate an 8-device TPU slice on CPU.

This is the analog of the reference's debug_launcher/gloo-on-localhost
strategy (SURVEY §4): `--xla_force_host_platform_device_count=8` gives a real
8-device mesh so every sharding/collective path runs for real, single-process.

XLA reads these settings at *backend initialization* (first device query), so
this works even if a pytest plugin imported jax already — as long as no
backend is live yet.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# The suite is compile-bound: hundreds of tiny GSPMD programs, each a few
# seconds of XLA work. Budget on a SINGLE CPU core: full non-slow suite
# ~9 min (was >20 min before these levers); per-file runs are seconds to a
# minute. On multicore hosts pytest-xdist (-n auto) divides the compile
# bill. Two levers keep wall time sane:
# - skip XLA's optimization pipeline: tests assert semantics, not speed
#   (~35-65% off the worst tests' compile time)
# - persist compiled executables across runs, so re-runs (CI retries, local
#   iteration, review) skip backend compiles. The directory is the one the
#   package resolves (utils/compile_cache.py): an outside
#   JAX_COMPILATION_CACHE_DIR stays; unset, the fixed .xla_cache/ of the
#   checkout. It goes through the ENVIRONMENT (not jax.config.update) so
#   LAUNCHED SUBPROCESSES — the most compile-heavy tests — inherit it too;
#   JAX_ENABLE_COMPILATION_CACHE=0 turns it off.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_repo_root, ".xla_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

sys.path.insert(0, _repo_root)

if "jax" in sys.modules:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized(), (
        "JAX backend initialized before conftest could force the 8-device CPU sim"
    )

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def reset_state():
    """Reset all runtime singletons between tests (reference
    AccelerateTestCase, test_utils/testing.py:478-489)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    PartialState._reset_state()
    GradientState._reset_state()


# tests/benchmark/test_bench_manifest.py::_alter builds three of its faults
# from ``entry["reduced"][0]`` and ``values["rope_theta"]``. A configuration
# with nothing reduced and no rope key (jamba2-3b-serve-28l, PR 36) has
# neither, so ``_alter`` itself raises there, after the architectures before
# it in the manifest have been checked. That file is the benchmark's and only
# a ``benchmark`` PR may edit it (PERF.md section 7 asks for that repair:
# delete this block with it). Until then the three cases are expected to end
# in ``_alter``'s IndexError or KeyError, and in nothing else (a check that
# stops refusing a fault still fails), and the same three faults are held
# against every architecture, the uncut one and the toy one too, in
# tests/benchmark/test_bench_uncut_widths.py.
_ALTER_NEEDS_A_CUT_AND_A_ROPE_KEY = (
    "reduced_key_lacks_its_public_value",
    "reduced_key_states_another_public_value",
    "reduced_names_a_key_that_is_no_cut_of_scale",
)


def pytest_collection_modifyitems(items):
    test = "test_bench_manifest.py::test_an_altered_configuration_fails_the_published_widths"
    for item in items:
        if test in item.nodeid and any(f"[{case}]" in item.nodeid for case in _ALTER_NEEDS_A_CUT_AND_A_ROPE_KEY):
            item.add_marker(pytest.mark.xfail(
                raises=(IndexError, KeyError), strict=False,
                reason="_alter needs a reduced key and a rope key; an uncut configuration without rotation has "
                       "neither (tests/benchmark/test_bench_uncut_widths.py holds the same faults)"))
