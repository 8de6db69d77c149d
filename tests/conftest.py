"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding/collective
tests run on a virtual 8-device CPU platform, mirroring how the driver
dry-runs the multi-chip path.  Both selections happen here, before the
first backend touch: the forced host-device count (read once, at backend
creation) and the CPU platform — so the suite never claims a chip.
"""
import os
import sys

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from gsc_tpu.runtime import enable_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# exact float32 matmuls so implementation-parity tests compare numerics,
# not matmul precision modes
jax.config.update("jax_default_matmul_precision", "highest")
# persistent compilation cache (the repo's one rule): the suite is
# compile-bound and most programs are identical run to run — repeat runs
# skip those compiles
enable_compile_cache()


@pytest.fixture(autouse=True)
def _propagate_package_logs():
    """caplog captures via root-logger propagation, which setup_logging
    turns off for the ``gsc_tpu`` tree (console handler instead).  Tests
    run in any order, so re-enable propagation around each test — without
    this, any test using caplog on package loggers passes in isolation
    and fails after whichever test calls setup_logging."""
    import logging

    logger = logging.getLogger("gsc_tpu")
    old = logger.propagate
    logger.propagate = True
    yield
    logger.propagate = old


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Release compiled executables after each test module.

    The full suite compiles ~100 XLA programs in one process; letting them
    accumulate has segfaulted XLA's CPU compiler near the end of the run
    (in whichever module happened to compile around position ~90 — seen in
    two different modules).  Per-module cache clearing caps the live
    executable count; modules recompile their own programs anyway."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()
