"""Runtime sentinels: compile/retrace monitoring + host-sync guarding.

The static pass (astlint.py) over-approximates; these sentinels make the
same properties *testable at runtime*:

- :class:`CompileMonitor` hooks JAX's compile logging (the
  ``jax.log_compiles`` channel on the ``jax._src.dispatch`` logger) and
  counts traces / XLA compilations per jitted entry point.  Wired into a
  :class:`~gsc_tpu.obs.MetricsHub` it emits one ``compile`` event per
  watched entry point into the run's ``events.jsonl`` (rendered by
  ``tools/obs_report.py``), so a retrace storm is visible in run
  telemetry, not just in wall time.  Counting keys on TRACES, not backend
  compiles: the persistent compilation cache (tests/conftest.py) can skip
  the backend step, but a cache-missing jit call always re-traces.
- :func:`assert_no_retrace` — context manager that fails loudly when a
  watched entry point traces during the guarded region (the steady-state
  contract of the pipelined episode loop).
- :func:`no_host_sync` — wraps ``jax.transfer_guard_device_to_host`` so a
  guarded region performs ZERO unplanned device->host transfers; the
  XLA error is re-raised as :class:`HostSyncError` naming the region.

The monitor swallows the raw ``log_compiles`` WARNING spam while active
(the structured events replace it) and restores the previous logging /
config state on stop.
"""
from __future__ import annotations

import logging
import re
import threading
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Tuple

# entry points a training run cares about: the fused episode/chunk kernels
# and their two-call fallbacks (agents/ddpg.py, parallel/dp.py, env reset),
# plus the on-device scenario sampler (topology/factory.py), the device
# traffic sampler (sim/traffic_device.py: one trace per (topology, B)) and
# the async replay service insert (parallel/async_rl.py) — a factory or
# async run's stream contract is exactly one trace per entry point
DEFAULT_WATCH = ("episode_step", "rollout_episode", "learn_burst",
                 "chunk_step", "rollout_episodes", "reset_all", "reset",
                 "step", "factory_sample", "traffic_sample",
                 "replay_ingest")

_TRACE_RE = re.compile(
    r"Finished tracing \+ transforming (.+?) for pjit in ([0-9.eE+-]+) sec")
_XLA_RE = re.compile(
    r"Finished XLA compilation of jit\((.+?)\) in ([0-9.eE+-]+) sec")
_SWALLOW_PREFIXES = ("Finished tracing + transforming",
                     "Finished jaxpr to MLIR module conversion",
                     "Finished XLA compilation of", "Compiling ")


class RetraceError(AssertionError):
    """A watched jitted entry point re-traced inside a no-retrace region."""


class HostSyncError(AssertionError):
    """A guarded region performed a device->host transfer."""


class _CompileLogTap(logging.Filter):
    """ONE process-wide tap on the jax compile-log loggers, fanning each
    parsed record out to every active monitor.

    A per-monitor filter would blind stacked monitors:
    ``logging.Filterer.filter`` short-circuits on the first filter
    returning False, so a suppressing observer-owned monitor would
    swallow every record before a later-installed ``assert_no_retrace``
    monitor saw it.  Suppression is therefore decided ACROSS all active
    monitors, after all of them have counted the record."""

    def __init__(self):
        super().__init__()
        self.monitors: List["CompileMonitor"] = []   # guarded by _TAP_LOCK

    def filter(self, record: logging.LogRecord) -> bool:
        msg = record.getMessage()
        parsed = None
        m = _TRACE_RE.search(msg)
        if m:
            parsed = (m.group(1), "trace", float(m.group(2)))
        else:
            m = _XLA_RE.search(msg)
            if m:
                parsed = (m.group(1), "xla", float(m.group(2)))
        with _TAP_LOCK:
            monitors = list(self.monitors)
        if parsed is not None:
            for mon in monitors:
                mon._on_event(*parsed)
        if msg.startswith(_SWALLOW_PREFIXES) and any(
                mon.suppress_logs for mon in monitors):
            return False
        return True


_TAP = _CompileLogTap()
_TAP_LOCK = threading.Lock()
_PREV_LOG_COMPILES = [None]   # jax_log_compiles value before the first tap


def _register_monitor(mon: "CompileMonitor"):
    import jax

    with _TAP_LOCK:
        if not _TAP.monitors:
            for name in CompileMonitor._LOGGERS:
                logging.getLogger(name).addFilter(_TAP)
            _PREV_LOG_COMPILES[0] = jax.config.jax_log_compiles
            jax.config.update("jax_log_compiles", True)
        _TAP.monitors.append(mon)


def _unregister_monitor(mon: "CompileMonitor"):
    import jax

    with _TAP_LOCK:
        if mon in _TAP.monitors:
            _TAP.monitors.remove(mon)
        if not _TAP.monitors:
            jax.config.update("jax_log_compiles", _PREV_LOG_COMPILES[0])
            for name in CompileMonitor._LOGGERS:
                logging.getLogger(name).removeFilter(_TAP)


class CompileMonitor:
    """Counts jit traces / XLA compiles per function name while active.

    ``hub`` (a :class:`gsc_tpu.obs.MetricsHub`) is optional: with one,
    every trace/compile of a *watched* name emits a structured ``compile``
    event (the events.jsonl stream) plus ``jit_traces_total`` /
    ``jit_compiles_total{fn=...}`` counters; unwatched names only bump an
    aggregate ``jit_traces_other_total`` counter so tiny ``jnp`` op jits
    cannot flood the stream.  ``watch=None`` watches everything.
    """

    _LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla")

    def __init__(self, hub=None, watch: Optional[Iterable[str]] =
                 DEFAULT_WATCH, suppress_logs: bool = True):
        self.hub = hub
        self.watch = None if watch is None else set(watch)
        self.suppress_logs = suppress_logs
        self._lock = threading.Lock()
        self.trace_counts: Dict[str, int] = {}
        self.compile_counts: Dict[str, int] = {}
        # bounded: the durable record is the hub's events.jsonl stream;
        # this window only serves tests/interactive inspection, and a
        # retrace storm on a long run must not grow host memory with it
        self.events: deque = deque(maxlen=1024)
        # set when the start-time self-probe saw no trace record: the
        # jax log wording drifted and the monitor is blind.  Observability
        # paths log-and-continue; assert_no_retrace fails CLOSED on it.
        self.degraded = False
        self._started = False

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "CompileMonitor":
        if self._started:
            return self
        self._started = True
        _register_monitor(self)
        self._self_probe()
        return self

    def _self_probe(self):
        """Jit a throwaway function and check its trace was counted.  The
        regexes are pinned to jax's log_compiles wording; on format drift
        the monitor would otherwise count nothing and every no-retrace
        assertion would pass vacuously — fail loudly instead."""
        import jax

        def _gsc_compile_probe(x):   # fresh object every start: re-traces
            return x

        try:
            jax.jit(_gsc_compile_probe)(0)
        except Exception:   # no backend available: leave degraded unset
            return
        if self.traces("_gsc_compile_probe") == 0:
            self.degraded = True
            logging.getLogger("gsc_tpu.analysis").warning(
                "CompileMonitor self-probe saw no trace record — the jax "
                "log_compiles message format has drifted; compile events "
                "and retrace detection are BLIND until the sentinel "
                "regexes are updated")

    def stop(self):
        if not self._started:
            return
        self._started = False
        _unregister_monitor(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------ recording
    def _watched(self, fn: str) -> bool:
        return self.watch is None or fn in self.watch

    def _on_event(self, fn: str, kind: str, duration_s: float):
        with self._lock:
            counts = (self.trace_counts if kind == "trace"
                      else self.compile_counts)
            counts[fn] = counts.get(fn, 0) + 1
            n = counts[fn]
            if self._watched(fn):
                self.events.append({"fn": fn, "kind": kind,
                                    "duration_s": duration_s, "count": n})
        if self.hub is None:
            return
        if self._watched(fn):
            name = ("jit_traces_total" if kind == "trace"
                    else "jit_compiles_total")
            self.hub.counter(name, fn=fn)
            # field is `stage` (trace|xla), not `kind` — MetricsHub.event's
            # first parameter owns that name
            self.hub.event("compile", fn=fn, stage=kind,
                           duration_s=round(duration_s, 4), count=n)
        elif kind == "trace":
            self.hub.counter("jit_traces_other_total")

    # ------------------------------------------------------------- queries
    def snapshot(self) -> Dict[str, Tuple[int, int]]:
        """{fn: (traces, xla_compiles)} for every name seen so far."""
        with self._lock:
            names = set(self.trace_counts) | set(self.compile_counts)
            return {n: (self.trace_counts.get(n, 0),
                        self.compile_counts.get(n, 0)) for n in names}

    def traces(self, fn: str) -> int:
        with self._lock:
            return self.trace_counts.get(fn, 0)

    @contextmanager
    def assert_no_retrace(self, *names: str):
        """Fail with :class:`RetraceError` if any of ``names`` (default:
        the watch set) traces inside the region — the steady-state
        pipelined loop must compile each entry point exactly once, before
        this guard begins."""
        if self.degraded:
            raise RetraceError(
                "CompileMonitor is degraded (log-format drift: the "
                "start-time self-probe saw no trace record) — a "
                "no-retrace assertion would pass vacuously; update the "
                "sentinel regexes for this jax version")
        watched = set(names) or (self.watch or set())
        with self._lock:
            before = {n: self.trace_counts.get(n, 0) for n in watched} \
                if watched else dict(self.trace_counts)
        yield self
        with self._lock:
            after = {n: self.trace_counts.get(n, 0)
                     for n in (watched or self.trace_counts)}
        grew = {n: after.get(n, 0) - before.get(n, 0)
                for n in after if after.get(n, 0) > before.get(n, 0)}
        if grew:
            detail = ", ".join(f"{n} (+{k})" for n, k in sorted(grew.items()))
            raise RetraceError(
                f"jitted entry point(s) re-traced inside a no-retrace "
                f"region: {detail} — check for weak-type scalars, "
                "changing shapes, or fresh static args in the hot loop")


@contextmanager
def assert_no_retrace(*names: str, hub=None):
    """Standalone guard: monitors compiles only for the duration of the
    region and raises :class:`RetraceError` on any trace of ``names``
    (any trace at all when no names are given)."""
    mon = CompileMonitor(hub=hub, watch=set(names) or None)
    with mon:
        with mon.assert_no_retrace(*names):
            yield mon


@contextmanager
def no_host_sync(what: str = "guarded region"):
    """Zero unplanned device->host syncs inside the region.

    Two layers, because they catch different things on different
    backends:

    - ``jax.transfer_guard_device_to_host("disallow")`` — the XLA-level
      guard, authoritative on TPU/GPU where device buffers live off-host.
      On the CPU backend it is INERT (host-resident buffers convert
      zero-copy, no transfer is recorded), which is exactly where CI
      runs, hence:
    - a Python tripwire over the repo's host-sync entry points —
      ``np.asarray``/``np.array`` on a ``jax.Array``, ``jax.device_get``
      and ``jax.block_until_ready`` raise :class:`HostSyncError`
      immediately.  These are the R1 call forms (astlint) and cover every
      planned sync in the trainer/harness drain paths, so one sneaking
      into a dispatch region fails on any backend.  ``float()``/
      ``int()`` on a 0-d array cannot be intercepted from Python —
      that residual is the static pass's job.

    The numpy patch is process-global for the duration (raises only for
    jax.Array arguments) — test-scoped usage only, not for threaded
    production paths.  Host->device transfers (staging np.int32 args,
    prefetched traffic) remain allowed: the episode-loop contract is
    about the *device->host* syncs that serialize the pipeline."""
    import jax
    import numpy as np

    def _holds_jax_array(a):
        # containers sync too: np.asarray([stats["x"], stats["y"]]) is a
        # device->host materialization of every jax leaf inside
        try:
            return any(isinstance(leaf, jax.Array)
                       for leaf in jax.tree_util.tree_leaves(a))
        except Exception:   # unflattenable exotic object: not ours
            return False

    def _np_tripwire(name, orig):
        def wrapper(a, *args, **kwargs):
            if _holds_jax_array(a):
                raise HostSyncError(
                    f"{name}() materialized a jax.Array inside {what} — "
                    "an unplanned device->host sync")
            return orig(a, *args, **kwargs)
        return wrapper

    def _always_tripwire(name):
        def wrapper(*args, **kwargs):
            raise HostSyncError(
                f"{name}() inside {what} — an unplanned device->host "
                "sync")
        return wrapper

    patches = [
        (np, "asarray", _np_tripwire("np.asarray", np.asarray)),
        (np, "array", _np_tripwire("np.array", np.array)),
        (jax, "device_get", _always_tripwire("jax.device_get")),
        (jax, "block_until_ready",
         _always_tripwire("jax.block_until_ready")),
    ]
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    for mod, name, repl in patches:
        setattr(mod, name, repl)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    except HostSyncError:
        raise
    except Exception as e:  # noqa: BLE001 - classify, then re-raise
        msg = str(e)
        if "transfer" in msg.lower() and "disallow" in msg.lower():
            raise HostSyncError(
                f"unplanned device->host transfer inside {what}: {msg}"
            ) from e
        raise
    finally:
        for mod, name, orig in saved:
            setattr(mod, name, orig)
