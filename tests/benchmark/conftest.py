"""The benchmark's tests import its modules by their plain names, as
``benchmarks/run.py`` does."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture
def optimized_xla():
    """The suite compiles with most XLA optimizations off (tests/conftest.py);
    the interpreted pallas kernels of a whole engine are then some hundred
    times slower. Tests that drive the engine turn them back on."""
    import jax

    before = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", before)
