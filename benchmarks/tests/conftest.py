"""The benchmark's own tests run on the CPU (``pytest benchmarks/tests``;
not part of ``tests/``): platform pinned before the first backend touch."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def release_compiled_programs():
    """Each rehearsal run compiles the tiny cell's programs anew; a dozen
    runs' executables kept in one process abort the CPU compiler here."""
    yield
    import jax
    jax.clear_caches()
