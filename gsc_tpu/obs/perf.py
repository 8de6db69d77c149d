"""Device-cost ledger — compile-time FLOPs/bytes/fusions per entry point.

The round-5 MFU/roofline table that proved the substep regime (op-count
bound, ~100x above the HBM roof) was assembled BY HAND from one-off
scripts and went stale the moment it landed in BENCH_NOTES.  This module
makes that evidence a per-run artifact: every watched jitted entry point
(``episode_step``, ``chunk_step``, ``learn_burst``,
``serve_policy_b<B>``) is AOT-lowered once at setup time and its
``Compiled`` object mined for

- XLA's own cost model (``compiled.cost_analysis()``): FLOPs and bytes
  accessed per call;
- HLO structure (:mod:`gsc_tpu.analysis.hlo`): fusion count — the
  op-count perf proxy — plus a small op histogram
  (while/dot/scatter/gather), the device operations per
  ``jax.named_scope`` layer (``scopes``: ops, fusions, copies, result
  bytes for each of ``obs.trace.DEVICE_SCOPES``), the map from each
  operation's instruction name to its scope path, with the module name
  and each operation's signature that identify the program in a device
  trace (``op_map``: what :func:`gsc_tpu.obs.trace.layer_times` joins a
  trace's events through) and the collective-op stats
  (all-reduce/all-gather/reduce-scatter count + payload bytes) that
  make the ``tp``-vs-``sharded`` interconnect comparison machine-read
  (on a sharded dispatch the trainer additionally captures the
  PARTITIONED executable as ``<entry>_sharded`` — the plain entry stays
  the carving-comparable number);
- executable memory residency (``compiled.memory_analysis()``).

Wall timings arrive separately via :meth:`CostLedger.note_timing` — fed
from the trainer's **existing deferred drains** (PhaseTimer totals) and
the serve latency histograms, so the ledger adds ZERO host syncs to the
dispatch path (the ``no_host_sync`` sentinel contract: everything here
happens before the episode loop or after it, never inside a dispatch).

Combining the two yields per-dispatch achieved FLOP/s and bytes/s and —
on a device :data:`DEVICE_PEAKS` knows — MFU against that chip's
published peaks and the roofline position (arithmetic intensity vs the
ridge point, attainable-roof multiple).  A device that is not in the
table gets NO ``mfu``/``bw_util``/``roofline`` fields and the document
says so (``peaks: null`` plus ``peaks_note``): a CPU run, or a chip whose
datasheet nobody entered, never borrows another part's peaks.  The whole
ledger serializes as a schema-versioned ``perf.json`` next to
``metrics.json`` (``RunObserver.close`` writes it), naming ``backend``,
``device_kind`` and ``device_count``; each capture also emits one
structured ``compile_cost`` event into events.jsonl.

The FLOPs and bytes are XLA's cost model for the compiled program, not
the operations the algorithm needs, and the wall is host wall around the
drain — so ``mfu`` here is a utilization of the cost model's work, to be
read next to a device trace, not instead of one.
"""
from __future__ import annotations

import functools
import logging
import time
from typing import Dict, Optional

from ..analysis.hlo import (collective_stats, count_fusions, module_name,
                            op_histogram, scope_map)
from .trace import DEVICE_SCOPES

log = logging.getLogger("gsc_tpu.obs.perf")

# bump on any breaking change to the perf.json layout; readers
# (tools/obs_report.py, tools/bench_diff.py) key on it
PERF_SCHEMA_VERSION = 1

# THE peaks table: published per-chip peaks keyed by the string
# ``jax.devices()[0].device_kind`` prints.  One row per part somebody has
# actually run this repo on, each with its source; a device that is not
# here gets no MFU/roofline (``device_peaks`` returns None) — never a
# default.
DEVICE_PEAKS = {
    # one TPU v5e chip reports itself as "TPU v5 lite" (chip run, PR 21)
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16 MXU
        "bytes_per_s": 819e9,       # HBM2e
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: 197 TFLOP/s bf16, 819 GB/s HBM per chip",
    },
}


def device_peaks(device_kind: str) -> Optional[Dict]:
    """The :data:`DEVICE_PEAKS` row for ``device_kind``, or None."""
    return DEVICE_PEAKS.get(device_kind)

# ops worth a per-entry histogram next to the fusion count: `while` is
# the serial-scatter tell on CPU, `dot` the MXU share, scatter/gather
# the layout-sensitive movers (analysis/hlo.py docstrings)
_OP_HISTOGRAM = ("while", "dot", "scatter", "gather")


def _unwrap_partial(fn, args, kwargs):
    """Peel ``functools.partial`` layers (the ``donated_jit`` wrapper
    shape: ``partial(jit(fn, ...), bound_self)``) down to the jit object,
    folding the partial's bound arguments in front of the caller's."""
    while isinstance(fn, functools.partial):
        args = tuple(fn.args) + tuple(args)
        kwargs = {**fn.keywords, **kwargs}
        fn = fn.func
    return fn, args, kwargs


def resolve_lowerable(owner, name: str):
    """(fn, prefix_args) for capturing entry point ``name`` on ``owner``
    (a DDPG/ParallelDDPG): the instance attribute when it unwraps to a
    lowerable jit — the ``donated_jit`` partial, i.e. the EXECUTABLE
    actually dispatched, whose backend compile seeds the persistent
    cache for the first real dispatch — else the class-level jit with
    the owner passed explicitly (``donate=False``, where the class jit
    IS the dispatched program, and the sharded-plan wrappers, where the
    unsharded class jit is the carving-comparable stand-in).  The single
    resolver behind the Trainer's capture sites, so the
    donated-wrapper shape is interpreted in exactly one place."""
    fn = owner.__dict__.get(name)
    inner = fn
    while isinstance(inner, functools.partial):
        inner = inner.func
    if fn is not None and hasattr(inner, "lower"):
        return fn, ()
    return getattr(type(owner), name), (owner,)


# The persistent compile cache keys a program on its operations alone, so
# a hit hands back the executable with the ``op_name`` metadata of
# whichever source compiled it first.  Compiling the same lowering under a
# compiler option that changes nothing but the cache key (what the
# compiler logs) gives an executable of its own: the names of THIS source
# the first time, a cache hit after that.
OWN_CACHE_KEY = {"xla_detailed_logging": False}


def scope_ledger(hlo: str):
    """``(operations by named scope, op_map)`` of a compiled program's
    text, from one walk (:func:`gsc_tpu.analysis.hlo.scope_map`).
    ``op_map`` is ``{"module", "paths", "anchors", "signatures"}``: the
    module's name, as a device trace names its executions, each scope
    path's operations, the operations that count its executions, and
    each operation's result-type-and-opcode check, which tells programs
    of one name apart in a trace (``obs.trace.join_scopes``)."""
    walked = scope_map(hlo, DEVICE_SCOPES)
    return walked["stats"], {
        "module": module_name(hlo), "paths": walked["paths"],
        "anchors": walked["anchors"], "signatures": walked["signatures"]}


def _mine_scopes(compiled):
    """``(hlo text, operations by named scope, op_map)`` of a compiled
    program (:func:`scope_ledger`); ``("", {}, None)`` on a backend
    without HLO text access."""
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    return (hlo, *scope_ledger(hlo)) if hlo else ("", {}, None)


def _cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain ``{metric: value}`` dict."""
    return dict(compiled.cost_analysis() or {})


class CostLedger:
    """Per-run compile-time cost ledger + wall-timing merge.

    ``hub`` (a :class:`~gsc_tpu.obs.MetricsHub`) is optional; with one,
    every capture emits a ``compile_cost`` event.  Capture failures are
    recorded (``{"available": False, "error": ...}``) and logged, never
    raised — a missing cost model must not fail a training run.
    """

    def __init__(self, hub=None, device_kind: Optional[str] = None):
        self.hub = hub
        # ``device_kind`` overrides what JAX reports (tests pin a table
        # row without owning the part); None = ask the backend, lazily
        self._device_kind = device_kind
        self._device: Optional[Dict] = None
        self._entries: Dict[str, Dict] = {}
        self._timings: Dict[str, Dict[str, float]] = {}
        self._phases: Dict[str, Dict[str, float]] = {}

    # -------------------------------------------------------------- device
    def device(self) -> Dict:
        """``{"platform", "kind", "count"}`` of the backend (resolved
        once, lazily — constructing a ledger never touches jax)."""
        if self._device is None:
            from ..runtime import device_summary
            self._device = device_summary()
            if self._device_kind is not None:
                self._device = {**self._device, "kind": self._device_kind}
        return self._device

    def backend(self) -> str:
        return self.device()["platform"]

    def peaks(self) -> Optional[Dict]:
        """The peaks-table row for this device, or None when the device
        is not in :data:`DEVICE_PEAKS`."""
        return device_peaks(self.device()["kind"])

    # ------------------------------------------------------------- capture
    def has(self, name: str) -> bool:
        return name in self._entries

    def capture(self, name: str, fn, args=(), kwargs=None,
                recapture: bool = False) -> Optional[Dict]:
        """AOT-lower ``fn`` (a jit object, possibly wrapped in
        ``functools.partial``) on ``args``/``kwargs`` and record its
        static cost.  Arguments may be live arrays OR
        ``jax.ShapeDtypeStruct``s — lowering never executes the program,
        so donated buffers are safe to pass.  Idempotent per name unless
        ``recapture``."""
        if self.has(name) and not recapture:
            return self._entries[name]
        kwargs = dict(kwargs or {})
        t0 = time.perf_counter()
        try:
            fn, args, kwargs = _unwrap_partial(fn, args, kwargs)
            lowered = fn.lower(*args, **kwargs)
            compiled = lowered.compile()
            mined = _mine_scopes(compiled)
            scopes = mined[1]
            if scopes and not any(rec["ops"] for scope, rec in scopes.items()
                                  if scope != "unscoped"):
                # no scope name at all: a cache hit compiled from a source
                # that had none
                log.info("cost-ledger capture of %r: the cached executable "
                         "carries no scope names, compiling the lowering "
                         "under a cache key of its own", name)
                compiled = lowered.compile(compiler_options=OWN_CACHE_KEY)
                mined = _mine_scopes(compiled)
            entry = self.capture_compiled(name, compiled, mined)
            entry["capture_s"] = round(time.perf_counter() - t0, 3)
            return entry
        except Exception as e:  # noqa: BLE001 - observability must not kill
            log.warning("cost-ledger capture of %r failed: %s: %s",
                        name, type(e).__name__, e)
            self._entries[name] = {"available": False,
                                   "error": f"{type(e).__name__}: {e}"}
            return self._entries[name]

    def capture_compiled(self, name: str, compiled, mined=None) -> Dict:
        """Record an already-compiled ``jax.stages.Compiled`` (the serve
        path holds one per bucket after warmup).  ``mined`` is
        :func:`_mine_scopes`'s result where the caller already has it."""
        cost = _cost_dict(compiled)
        hlo, scopes, op_map = mined or _mine_scopes(compiled)
        entry: Dict = {
            "available": True,
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "fusions": count_fusions(hlo) if hlo else None,
            "ops": op_histogram(hlo, _OP_HISTOGRAM) if hlo else {},
            # cross-device movers (all-reduce/all-gather/reduce-scatter
            # ... count + payload bytes per call): 0/{} on single-device
            # programs; on a partitioned executable this is the
            # machine-read side of the tp-vs-sharded interconnect claim
            "collectives": (collective_stats(hlo) if hlo
                            else {"ops": {}, "count": 0, "bytes": 0}),
            # device operations by named scope (obs.trace.DEVICE_SCOPES
            # plus `unscoped`): the program's op count put down to layers
            "scopes": scopes,
            # every operation's scope path, and what identifies the
            # program in a device trace: the join from events to layers
            "op_map": op_map,
        }
        if entry["flops"] and entry["bytes_accessed"]:
            entry["arithmetic_intensity"] = round(
                entry["flops"] / entry["bytes_accessed"], 4)
        try:
            mem = compiled.memory_analysis()
            entry["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
            }
        except Exception:
            pass
        self._entries[name] = entry
        if self.hub is not None:
            self.hub.event("compile_cost", fn=name,
                           flops=entry["flops"],
                           bytes_accessed=entry["bytes_accessed"],
                           fusions=entry["fusions"],
                           ops=entry["ops"],
                           collectives=entry["collectives"],
                           scopes=entry["scopes"],
                           op_map=entry["op_map"])
            if entry["fusions"] is not None:
                self.hub.gauge("compile_fusions", entry["fusions"], fn=name)
        return entry

    # ------------------------------------------------------------- timings
    def note_timing(self, name: str, total_s: float, count: int):
        """Merge host-wall attribution for ``name``'s dispatches —
        sourced from the trainer's PhaseTimer totals / the serve latency
        histograms AFTER the run, never from inside the dispatch path."""
        if count <= 0:
            return
        self._timings[name] = {"total_s": round(float(total_s), 6),
                               "count": int(count)}

    def note_phases(self, phases: Dict[str, Dict[str, float]]):
        """Attach the run's cumulative PhaseTimer summary (the
        device-vs-host time split obs_report renders)."""
        self._phases = dict(phases or {})

    # ------------------------------------------------------------- summary
    def _derived(self, entry: Dict, timing: Optional[Dict]) -> Dict:
        """MFU + roofline position from static cost x measured wall."""
        out = dict(entry)
        if timing:
            out["dispatches"] = timing["count"]
            out["wall_s_total"] = timing["total_s"]
            mean_s = timing["total_s"] / max(timing["count"], 1)
            out["wall_s_mean"] = round(mean_s, 6)
            peaks = self.peaks()
            if entry.get("available") and entry.get("flops") and mean_s > 0:
                achieved = entry["flops"] / mean_s
                out["achieved_flops_per_s"] = round(achieved, 1)
                bytes_a = entry.get("bytes_accessed") or 0.0
                if bytes_a:
                    bw = bytes_a / mean_s
                    out["achieved_bytes_per_s"] = round(bw, 1)
                if peaks is None:
                    # unknown device: achieved rates only — no MFU, no
                    # roofline, never another part's peaks
                    return out
                out["mfu"] = round(achieved / peaks["flops_per_s"], 6)
                if bytes_a:
                    out["bw_util"] = round(bw / peaks["bytes_per_s"], 6)
                    intensity = entry["flops"] / bytes_a
                    ridge = peaks["flops_per_s"] / peaks["bytes_per_s"]
                    attainable = min(peaks["flops_per_s"],
                                     intensity * peaks["bytes_per_s"])
                    out["roofline"] = {
                        "intensity": round(intensity, 4),
                        "ridge": round(ridge, 4),
                        "regime": ("memory_bound" if intensity < ridge
                                   else "compute_bound"),
                        # how far BELOW the attainable roof the measured
                        # rate sits (>=1; the round-5 table's "~100x
                        # above the HBM roof" phrasing, inverted to a
                        # stable ratio)
                        "roof_multiple": round(
                            attainable / max(achieved, 1e-30), 1),
                    }
        return out

    def entry(self, name: str) -> Optional[Dict]:
        e = self._entries.get(name)
        if e is None:
            return None
        return self._derived(e, self._timings.get(name))

    def summary(self) -> Dict:
        """The full schema-versioned perf document."""
        dev = self.device()
        peaks = self.peaks()
        return {
            "schema_version": PERF_SCHEMA_VERSION,
            "ts": round(time.time(), 3),
            "backend": dev["platform"],
            "device_kind": dev["kind"],
            "device_count": dev["count"],
            "peaks": peaks,
            **({} if peaks is not None else {"peaks_note": (
                f"device_kind {dev['kind']!r} is not in obs.perf."
                "DEVICE_PEAKS: entries carry achieved rates only, no "
                "mfu / bw_util / roofline")}),
            "run": (self.hub.base_tags.get("run")
                    if self.hub is not None else None),
            "entries": {name: self._derived(e, self._timings.get(name))
                        for name, e in self._entries.items()},
            "phases": self._phases,
        }

    def write_json(self, path: str) -> str:
        """Atomic ``perf.json`` write (same contract as metrics.json).
        Named ``write_json`` rather than ``write`` on purpose: traced
        code paths call file ``.write()`` constantly, and gsc-lint's
        name-graph would fuse a method named ``write`` into the jit
        cone."""
        from .sinks import write_atomic_json
        return write_atomic_json(path, self.summary())
