"""Device mesh + sharding helpers.

The reference has no distributed execution of any kind (SURVEY.md §5: one
process, one env, CPU) — this module is the TPU-native scaling layer the
rebuild adds (BASELINE.json north_star): a 1-D ``dp`` mesh over which env
replicas, replay shards and learner batches are sharded, with parameters
replicated; XLA inserts the cross-chip collectives (grad psum) from the
sharding annotations.  The same code drives 1 chip, a v5e pod slice, or a
virtual ``xla_force_host_platform_device_count`` CPU mesh (tests/CI).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.extend  # explicit: clear_backends lives here, not on bare jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def force_virtual_cpu(n_devices: int) -> None:
    """Select an ``n_devices``-device virtual CPU platform — BEFORE any
    backend touch.

    The dry-run/CI entry point, and the ONLY code in the package that
    changes the platform: call it explicitly, before the first
    ``jax.devices()``/``jit`` of the process.  It sets
    ``xla_force_host_platform_device_count`` and switches
    ``jax_platforms`` to cpu via ``jax.config.update`` — an order of
    operations that never initializes the default (possibly TPU) backend,
    so a dry run never claims a chip.  If a CPU backend predating the
    flag is already live, falls back to ``clear_backends`` surgery."""
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n_devices:
        jax.extend.backend.clear_backends()
    if len(jax.devices()) < n_devices:
        raise ValueError(
            f"virtual CPU platform has {len(jax.devices())} devices, "
            f"need {n_devices}")


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host runtime initialization (``jax.distributed.initialize``).

    Call ONCE per process, before any backend touch.  With no arguments,
    coordinates from the environment (``JAX_COORDINATOR_ADDRESS``,
    ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``, or the cloud autodetection
    jax ships).  After this, ``jax.devices()`` is GLOBAL across all
    processes and ``make_mesh()``/``make_hybrid_mesh()`` build pod-wide
    meshes; each process addresses only ``jax.local_devices()``.

    The reference has nothing comparable (SURVEY §5: one process, one CPU);
    this is the entry point BASELINE config 5's data-parallel v5e-16 run
    crosses hosts through."""
    kw = {}
    if coordinator is not None:
        kw["coordinator_address"] = coordinator
    if num_processes is not None:
        kw["num_processes"] = num_processes
    if process_id is not None:
        kw["process_id"] = process_id
    jax.distributed.initialize(**kw)


def make_hybrid_mesh(outer_axis: str = "dcn", axis: str = "dp") -> Mesh:
    """2-D (process, local-device) mesh: the outer axis crosses hosts (DCN
    on a multi-slice pod, ICI within a slice), the inner axis crosses each
    process's local chips.  Shard replicas over BOTH axes and keep
    parameters replicated: the gradient psum then reduces over ICI first
    and crosses DCN once per step — the standard DCN-last layout.

    Falls back to a [1, n] grid in single-process runs, so code written
    against (outer, inner) axis names runs unchanged on one host."""
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    n_proc = jax.process_count()
    local = len(devs) // max(n_proc, 1)
    grid = np.asarray(devs).reshape(n_proc, local)
    return Mesh(grid, (outer_axis, axis))


def require_devices(n_devices: int, what: str):
    """The backend's devices, or a ``ValueError`` naming how many ``what``
    needs and what the backend has.  Mesh builders never change the
    platform: a run that asked for a chip mesh must not become a CPU run.
    Dry runs select the virtual CPU platform explicitly
    (:func:`force_virtual_cpu`, or ``JAX_PLATFORMS=cpu`` with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before start)."""
    devs = jax.devices()
    if len(devs) < n_devices:
        raise ValueError(
            f"{what} needs {n_devices} devices and the "
            f"{devs[0].platform!r} backend has {len(devs)}.  For a CPU dry "
            "run start the process with JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices}")
    return devs


def make_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all).
    Raises ``ValueError`` when the backend has fewer
    (:func:`require_devices`)."""
    devs = jax.devices() if n_devices is None else \
        require_devices(n_devices, f"make_mesh({n_devices})")[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded_axis0(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def put_replicated(tree, mesh: Mesh):
    """Replicate a pytree onto every device of the mesh."""
    return jax.device_put(tree, replicated(mesh))


def put_sharded(tree, mesh: Mesh, axis: str = "dp"):
    """Shard every leaf's leading (replica) axis across the mesh."""
    return jax.device_put(tree, sharded_axis0(mesh, axis))
