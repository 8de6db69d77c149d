"""Process-level JAX set-up every entry point shares.

Three things live here and nowhere else:

- **the compile-cache rule** (:func:`enable_compile_cache`): where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and no code
  sets another directory; where it is not, the cache is
  ``<checkout>/.jax_cache`` — a fixed path (the path is part of the cache
  key, so a directory that moves never hits).  ``cli train|infer|serve``,
  ``chip_smoke.py``, ``tests/conftest.py`` and the tools all
  call it, so the cache is ON by default on the product path;
- **the device identity** every result carries (:func:`device_summary`):
  platform, ``device_kind`` and device count exactly as JAX reports them;
- **the platform guards**: :func:`require_tpu` for measurement entry
  points (a CPU run must never print a device metric) and
  :func:`require_cpu_env` for tools whose parent configures JAX and then
  starts JAX children — one process owns a chip, so those are CPU-only.

Importing this module does not import jax.
"""
from __future__ import annotations

import os
from typing import Dict, List

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# entries cheaper to recompile than to read are not written; -1 lifts the
# size floor so the small-but-slow scan programs are kept
MIN_COMPILE_TIME_S = 1.0
MIN_ENTRY_SIZE_BYTES = -1


def compile_cache_dir() -> str:
    """The directory the rule selects (no jax import, no side effect)."""
    return os.environ.get(CACHE_ENV) or os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the compile-cache rule to this process; returns the
    directory in use.  Call before the first compile."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_TIME_S)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      MIN_ENTRY_SIZE_BYTES)
    return compile_cache_dir()


def device_summary() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` as JAX reports the backend
    (initialises it)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_fields() -> Dict[str, object]:
    """The device summary as the flat ``platform`` / ``device_kind`` /
    ``device_count`` fields every benchmark row carries."""
    dev = device_summary()
    return {"platform": dev["platform"], "device_kind": dev["kind"],
            "device_count": dev["count"]}


def tree_platforms(tree) -> List[str]:
    """Sorted platforms of the devices holding ``tree``'s array leaves;
    host (numpy) leaves count as ``"host"``."""
    import jax

    found = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            found.update(d.platform for d in leaf.devices())
        else:
            found.add("host")
    return sorted(found)


def require_tpu(what: str) -> Dict[str, object]:
    """Device summary, or exit non-zero naming what JAX found instead of
    a TPU — before anything compiles."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU and JAX found {dev['count']} x "
            f"{dev['kind']!r} (platform {dev['platform']!r}) — nothing "
            "was compiled and no result is printed")
    return dev


def require_cpu_env(tool: str) -> None:
    """Refuse to start a CPU-only tool unless the environment pins JAX to
    the CPU.  Checked on the environment, not the backend: asking the
    backend would claim the chip in the parent, and the tool's JAX
    children would then fail or hang on it."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            f"{tool} is CPU-only: its parent process configures JAX and "
            "then starts JAX child processes, and a chip belongs to one "
            "process at a time.  Start it with JAX_PLATFORMS=cpu")
