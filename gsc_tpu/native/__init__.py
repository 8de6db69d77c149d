"""Native (C++) host-path accelerators, loaded via ctypes.

The TPU compute path is JAX/XLA/Pallas; this package holds the *host* hot
paths in C++ — currently the per-episode traffic pre-generation
(traffic_gen.cpp), which the pure-numpy fallback implements as a per-flow
Python loop (gsc_tpu/sim/traffic.py).  The shared object is not tracked:
it is built on first use with g++ (no pip/pybind dependencies) next to its
source.  A build or load failure selects the numpy generator (identical
schedules on deterministic configs, a different random stream on
stochastic ones) — and WHICH generator is in use, and why, is logged once
per process.  Set ``GSC_TPU_NO_NATIVE=1`` to force the fallback.
Host path only (``--replicas 1``); replica-parallel runs sample on device.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("gsc_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "traffic_gen.cpp")
_SO = os.path.join(_DIR, "_traffic.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False


def _build() -> None:
    subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-o", _SO, _SRC],
        check=True, capture_output=True, timeout=120)


def _load() -> ctypes.CDLL:
    if (not os.path.exists(_SO)
            or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
        _build()
    lib = ctypes.CDLL(_SO)
    lib.gsc_generate_flows.restype = ctypes.c_int
    lib.gsc_generate_flows.argtypes = [
        ctypes.c_uint64,
        ctypes.c_int, ctypes.c_double,
        ctypes.c_int, ctypes.POINTER(ctypes.c_double),
        ctypes.c_double, ctypes.c_double,
        ctypes.c_double, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
    ]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library, or None.  The
    outcome is decided — and logged — once per process."""
    global _lib, _failed
    if _lib is not None or _failed:
        return _lib
    with _lock:
        if _lib is not None or _failed:
            return _lib
        if os.environ.get("GSC_TPU_NO_NATIVE") == "1":
            _failed = True
            log.info("traffic generator: numpy (GSC_TPU_NO_NATIVE=1)")
            return None
        try:
            _lib = _load()
            log.info("traffic generator: native (%s)", _SO)
        except (OSError, subprocess.SubprocessError, AttributeError) as e:
            # no g++, a failed or timed-out build, an unloadable object,
            # a missing symbol: the numpy generator takes over
            _failed = True
            log.warning("traffic generator: numpy — native build/load "
                        "failed (%s: %s)", type(e).__name__, e)
    return _lib


def generate_flows_native(seed: int, means: np.ndarray, run_duration: float,
                          dr_mean: float, dr_stdev: float, size_shape: float,
                          det_arrival: bool, det_size: bool,
                          ttl_choices: np.ndarray, n_sfcs: int,
                          egress_nodes: np.ndarray, capacity: int):
    """-> (times, ingress, drs, durs, ttls, sfcs, egs) ndarrays of length n,
    or None when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    steps, n_nodes = means.shape
    means = np.ascontiguousarray(means, np.float64)
    ttl = np.ascontiguousarray(ttl_choices, np.float64)
    eg = np.ascontiguousarray(egress_nodes, np.int32)
    times = np.empty(capacity, np.float64)
    ingress = np.empty(capacity, np.int32)
    drs = np.empty(capacity, np.float64)
    durs = np.empty(capacity, np.float64)
    ttls = np.empty(capacity, np.float64)
    sfcs = np.empty(capacity, np.int32)
    egs = np.empty(capacity, np.int32)
    pd = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    pi = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int))
    n = lib.gsc_generate_flows(
        ctypes.c_uint64(seed), steps, run_duration, n_nodes, pd(means),
        dr_mean, dr_stdev, size_shape, int(det_arrival), int(det_size),
        pd(ttl), len(ttl), n_sfcs, pi(eg), len(eg), capacity,
        pd(times), pi(ingress), pd(drs), pd(durs), pd(ttls), pi(sfcs),
        pi(egs))
    return (times[:n], ingress[:n], drs[:n], durs[:n], ttls[:n], sfcs[:n],
            egs[:n])
