"""Test-mode CSV telemetry — schema-compatible with the reference writer.

Reference: coordsim/writer/writer.py:16-235.  In test mode the reference
streams per-control-interval CSVs (placements, node_metrics, metrics,
run_flows, drop_reasons, runtimes, rl_state, optional scheduling) from a
SimPy process.  Here the same files with the same headers are written by the
evaluation driver from the metrics pytree after each control step — one
device→host transfer per interval, no process machinery.
"""
from __future__ import annotations

import csv
import os
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..config.schema import DROP_REASONS


class PhaseTimer:
    """Per-phase host wall timing for the asynchronous episode pipeline.

    The pipeline's win is OVERLAP — host traffic sampling and metric
    draining hidden behind device compute — which a single SPS number
    cannot attribute.  This accumulates host-side wall time per named phase
    (``host_sample``, ``dispatch``, ``drain``, ...): ``dispatch`` is the
    time the loop spends handing work to the device (async, so near-zero
    unless the dispatch queue is full — i.e. the device is the
    bottleneck), ``drain`` is time blocked on device→host metric syncs,
    and ``host_sample`` only appears on the serial path (the prefetch
    thread absorbs it on the pipelined path).  A pipelined run should show
    drain+host_sample collapsing toward zero while dispatch grows to cover
    the device wall.

    Accumulation is lock-protected: the async actor/learner path shares
    ONE ledger across the actor threads and the learner loop (that is
    what makes ``actor_idle`` vs ``learner_idle`` comparable on one
    clock), and an unlocked read-modify-write would drop increments under
    that interleaving."""

    # closed spans kept until the loop takes them: a few thousand covers
    # hundreds of episodes, so a loop that never takes them (the async
    # path) holds a bounded ring, not a leak
    MAX_SPANS = 4096

    def __init__(self, wall=time.time, perf=time.perf_counter):
        import collections
        import threading

        self._total: Dict[str, float] = {}
        self._count: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._wall, self._perf = wall, perf
        self._spans = collections.deque(maxlen=self.MAX_SPANS)
        # the identifier the spans of one episode share; the episode loop
        # sets it at the top of each iteration
        self.episode: Optional[int] = None

    @contextmanager
    def phase(self, name: str):
        t0 = self._perf()
        try:
            yield
        finally:
            self.add(name, self._perf() - t0)

    @contextmanager
    def span(self, name: str, parent: Optional[str] = None):
        """:meth:`phase` that also keeps the closed span itself:
        ``{name, parent, episode, t0, dur_s}`` — ``t0`` on the wall clock
        (the clock of the hub's ``ts``), ``dur_s`` on the monotonic one,
        ``episode`` as it stood when the span opened.  ``parent`` is the
        caller's to give: :func:`gsc_tpu.obs.trace.phase_span` keeps the
        per-thread stack of open spans."""
        t0, p0, episode = self._wall(), self._perf(), self.episode
        try:
            yield
        finally:
            dur = self._perf() - p0
            self.add(name, dur)
            with self._lock:
                self._spans.append({"name": name, "parent": parent,
                                    "episode": episode, "t0": t0,
                                    "dur_s": dur})

    def take_spans(self) -> List[Dict]:
        """The spans closed since the last call, oldest first."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def add(self, name: str, seconds: float):
        with self._lock:
            self._total[name] = self._total.get(name, 0.0) + seconds
            self._count[name] = self._count.get(name, 0) + 1

    def summary(self) -> Dict[str, Dict[str, float]]:
        """{phase: {total_s, count, mean_ms}} over everything recorded."""
        with self._lock:
            totals = dict(self._total)
            counts = dict(self._count)
        return {
            name: {"total_s": round(t, 4), "count": counts[name],
                   "mean_ms": round(1e3 * t / max(counts[name], 1), 3)}
            for name, t in sorted(totals.items())
        }


class TestModeWriter:
    """CSV suite with the reference's file names and headers
    (writer.py:26-110).

    ``flush_every`` batches the every-file flush to one in every N
    ``write_step`` calls (default 1 = the reference's flush-per-interval
    behavior, which the parity tests rely on; long evaluation sweeps pass
    ``Trainer.evaluate(telemetry_flush_every=N)`` so 8 file flushes stop
    gating every control interval).
    ``close`` always flushes whatever is buffered and is idempotent; the
    writer is also a context manager (``with TestModeWriter(...) as w:``).
    """

    def __init__(self, test_dir: str, write_schedule: bool = False,
                 write_flow_actions: bool = False,
                 sf_names: Sequence[str] = (), sfc_names: Sequence[str] = (),
                 flush_every: int = 1):
        if flush_every < 1:
            raise ValueError(f"flush_every must be >= 1, got {flush_every}")
        os.makedirs(test_dir, exist_ok=True)
        self.sf_names = list(sf_names)
        self.sfc_names = list(sfc_names)
        self.write_schedule = write_schedule
        self.write_flow_actions = write_flow_actions
        self.flush_every = flush_every
        self._steps_since_flush = 0
        self._closed = False
        self._files = {}
        self._writers = {}

        def w(name, header):
            f = open(os.path.join(test_dir, name), "w", newline="")
            self._files[name] = f
            wr = csv.writer(f)
            wr.writerow(header)
            self._writers[name] = wr
            return wr

        w("placements.csv", ["episode", "time", "node", "sf"])
        w("node_metrics.csv", ["episode", "time", "node", "node_capacity",
                               "used_resources", "ingress_traffic"])
        # trailing truncated_arrivals column is an extension over the
        # reference schema (writer.py:47): nonzero means flow-table slot
        # exhaustion / the per-substep arrival budget delayed arrivals and
        # generated-flow timing no longer matches the reference exactly
        w("metrics.csv", ["episode", "time", "total_flows", "successful_flows",
                          "dropped_flows", "in_network_flows",
                          "avg_end2end_delay", "truncated_arrivals"])
        w("run_flows.csv", ["episode", "time", "successful_flows",
                            "dropped_flows", "total_flows"])
        w("runtimes.csv", ["run", "runtime"])
        w("drop_reasons.csv", ["episode", "time", *DROP_REASONS])
        # rl_state.csv has no header row in the reference (writer.py:233-235)
        f = open(os.path.join(test_dir, "rl_state.csv"), "w", newline="")
        self._files["rl_state.csv"] = f
        self._writers["rl_state.csv"] = csv.writer(f)
        if write_schedule:
            w("scheduling.csv", ["episode", "time", "origin_node", "sfc",
                                 "sf", "schedule_node", "schedule_prob"])
        if write_flow_actions:
            # per-flow decision rows (writer.py:101-110 header)
            w("flow_actions.csv", ["episode", "time", "flow_id",
                                   "flow_rem_ttl", "flow_ttl", "curr_node_id",
                                   "dest_node", "cur_node_rem_cap",
                                   "next_node_rem_cap", "link_cap",
                                   "link_rem_cap"])
        self._run = 0

    def write_flow_action(self, episode: int, time: float, flow_id: int,
                          rem_ttl: float, ttl: float, cur_node, dest_node,
                          cur_node_rem_cap: float, next_node_rem_cap: float,
                          link_cap, link_rem_cap):
        """One per-flow decision row (writer.py:112-140)."""
        if self.write_flow_actions:
            self._writers["flow_actions.csv"].writerow(
                [episode, time, flow_id, rem_ttl, ttl, cur_node, dest_node,
                 cur_node_rem_cap, next_node_rem_cap, link_cap, link_rem_cap])
            if self.flush_every == 1:
                self._files["flow_actions.csv"].flush()

    def write_step(self, episode: int, time: float, metrics, placement,
                   node_cap, node_names: Optional[Sequence[str]] = None,
                   schedule=None, runtime: Optional[float] = None,
                   rl_state: Optional[Sequence[float]] = None,
                   truncated_arrivals: int = 0):
        """Log one control interval from device pytrees."""
        placement = np.asarray(placement)
        node_cap = np.asarray(node_cap)
        n = placement.shape[0]
        names = (list(node_names) if node_names
                 else [f"pop{i}" for i in range(n)])
        sfs = self.sf_names or [f"sf{i}" for i in range(placement.shape[1])]

        for node in range(n):
            for s in range(placement.shape[1]):
                if placement[node, s]:
                    self._writers["placements.csv"].writerow(
                        [episode, time, names[node], sfs[s]])

        # used_resources = peak demanded capacity this run
        # (run_max_node_usage, writer.py:183)
        used = np.asarray(metrics.run_max_node_usage)
        ingress = np.asarray(metrics.run_requested_node)
        for node in range(n):
            if node_cap[node] > 0 or used[node] > 0:
                self._writers["node_metrics.csv"].writerow(
                    [episode, time, names[node], node_cap[node], used[node],
                     ingress[node]])

        self._writers["metrics.csv"].writerow(
            [episode, time, int(metrics.generated), int(metrics.processed),
             int(metrics.dropped), int(metrics.active),
             float(metrics.avg_e2e()), int(truncated_arrivals)])
        self._writers["run_flows.csv"].writerow(
            [episode, time, int(metrics.run_processed),
             int(metrics.run_dropped), int(metrics.run_generated)])
        self._writers["drop_reasons.csv"].writerow(
            [episode, time, *np.asarray(metrics.drop_reasons).tolist()])
        if runtime is not None:
            self._run += 1
            self._writers["runtimes.csv"].writerow([self._run, runtime])
        if rl_state is not None:
            self._writers["rl_state.csv"].writerow(
                [episode, time] + [float(x) for x in rl_state])
        if schedule is not None and self.write_schedule:
            sched = np.asarray(schedule)
            sfcs = self.sfc_names or [f"sfc{i}" for i in range(sched.shape[1])]
            rows = []
            for src in range(n):
                for c in range(sched.shape[1]):
                    for s in range(sched.shape[2]):
                        for dst in range(n):
                            p = sched[src, c, s, dst]
                            if p > 0:
                                rows.append([episode, time, names[src],
                                             sfcs[c], sfs[s], names[dst], p])
            self._writers["scheduling.csv"].writerows(rows)
        self._steps_since_flush += 1
        if self._steps_since_flush >= self.flush_every:
            self._steps_since_flush = 0
            for f in self._files.values():
                f.flush()

    def close(self):
        """Flush and close every file; safe to call more than once (and
        called automatically when used as a context manager)."""
        if self._closed:
            return
        self._closed = True
        for f in self._files.values():
            f.close()   # close() flushes Python-buffered data itself

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
