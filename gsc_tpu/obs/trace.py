"""Profiler annotations, device time by layer from a profiler trace, and
the events.jsonl -> Perfetto trace exporter.

Three parts, one module (each is "how a run becomes a timeline"):

**Live annotations** — ``--profile`` traces of the pipelined trainer used
to be one opaque blob: the fused rollout+learn program, the prefetch
waits and the metric drains all interleave with nothing attributing
device time to pipeline phases.  :func:`phase_span` wraps the host-side
phases in ``jax.profiler.TraceAnnotation`` and :func:`episode_span` marks
each episode dispatch with ``jax.profiler.StepTraceAnnotation``.
Annotation names are stable API — tooling and docs reference them:
:data:`SPAN_NAMES` (the phase ranges: the root ``episode`` and its
children) and ``episode_step`` (the per-episode step marker).  With a
``PhaseTimer`` every span is also kept with its start, duration, parent
and episode, and the episode loops emit them as one ``episode_spans``
event per episode.  The device program's layers carry
``jax.named_scope`` names from :data:`DEVICE_SCOPES`; they reach the
compiled HLO as ``op_name`` metadata, where
:func:`gsc_tpu.analysis.hlo.scope_stats` counts operations by them.

**Device time by layer** — a profiler trace names each device operation
by its instruction and each program execution by its module;
:func:`layer_times` joins those events to the map from instruction to
scope path that the cost ledger keeps for every program it captured
(``obs.perf``: ``op_map``), and each device-idle gap to the innermost
host span open through it: device seconds per layer, whole executions of
each layer, each program's join coverage and own idle share
(``tools/obs_report.py`` renders them for a ``--profile`` run).  Only
:func:`load_profile` needs JAX; the join takes plain lists.

**Post-hoc export** — a run's ``events.jsonl`` already carries everything
a timeline needs (episode boundaries, cumulative PhaseTimer totals,
stalls, recovery ladders, compile events, serve stats), but reading a
stall out of log-line timestamp deltas is archaeology.
:func:`build_trace` renders the stream into Chrome trace-event JSON
(the format Perfetto / ``chrome://tracing`` open directly): one track
per logical thread — episode loop, prefetcher, serve, serve_request,
watchdog, compile — with watchdog stalls as instant events,
recovery/rollback ladders chained by flow arrows, batcher flushes as
complete slices on the serve track, and head-sampled
``serve_request_span`` events as slices on the serve_request track
whose flow arrows link each request through its batcher flush to the
device call that answered it.  The async flight-recorder records
(``async_actor_ep`` / ``async_learner_spans``, emitted deferred at run
end by ``run_async`` when the hub keeps series history) reconstruct the
decoupled fleet: one track per actor (rollout slices, backpressure-wait
``put`` slices, ``adopt`` marks), a channel track (each block's queued
put->pop residency), and a learner track (``replay_ingest`` /
``learn_burst`` slices, ``publish`` marks) — with put->pop flow arrows
carrying block size + staleness wait and publish->adopt arrows linking
every weight version to each actor that adopted it.
Phase sub-spans sit at their recorded start times where the stream holds
``episode_spans`` events; a stream without them (older runs) gets them
RECONSTRUCTED from the cumulative per-episode deltas, laid back-to-back
inside each episode's span and clamped to it.
:func:`validate_trace` is the strict schema check
(monotone ts per track, matched B/E pairs, pid/tid present) that CI and
the exporter gate on; ``tools/trace_export.py`` is the CLI.

The join and the export are deliberately jax-free (stdlib + the sibling
sinks reader) — they must run anywhere a trace or an events stream can
be copied to.
"""
from __future__ import annotations

import heapq
import json
import os
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

# Every ``phase_span`` name in the package, stable API.  ``episode`` is the
# root an episode loop opens at the top of each iteration; the rest are
# its children, in the order the replica loop runs them, then the
# serial/pipelined loop's own two.
SPAN_NAMES = (
    "episode", "preempt_check", "scenario_regen", "cost_capture",
    "reset_enqueue", "dispatch", "drain", "harness_observe", "episode_log",
    "publish", "ckpt", "host_sample", "host_sample_wait")

# Every ``jax.named_scope`` name in the package, stable API: the layer
# boundaries of the device program.  The innermost of these on an HLO
# instruction's ``op_name`` path is its scope (``analysis.hlo.scope_stats``).
# The last four stand only in a program whose networks have a looped
# torso (``AgentConfig.torso``, models/torso.py): one pass of the stack,
# its layers' two halves, and the exit gate.
TORSO_SCOPES = ("torso_pass", "torso_attention", "torso_mlp", "exit_gate")
DEVICE_SCOPES = (
    "rollout_step", "sim_substep", "traffic_arrivals", "policy_forward",
    "env_observe", "replay_write", "learn_burst", "replay_sample",
    "critic_update", "actor_update", "target_update", "gat_layer",
    "finite_guard") + TORSO_SCOPES

class _OpenSpans(threading.local):
    """Per-thread stack of the open ``phase_span`` names."""

    def __init__(self):
        self.stack = []


_open_spans = _OpenSpans()


@contextmanager
def phase_span(name: str, timer=None, hub=None):
    """One pipeline phase: profiler range + optional
    :class:`~gsc_tpu.utils.telemetry.PhaseTimer` span (totals, and the
    span itself with the enclosing ``phase_span`` of this thread as its
    parent) + hub last-phase bookkeeping (what a stall event reports
    being stuck in)."""
    import jax

    stack = _open_spans.stack
    parent = stack[-1] if stack else None
    if hub is not None:
        hub.note_phase(name, done=False)
    stack.append(name)
    with jax.profiler.TraceAnnotation(name):
        try:
            if timer is not None:
                with timer.span(name, parent):
                    yield
            else:
                yield
        finally:
            stack.pop()
            if hub is not None:
                hub.note_phase(name, done=True)


def emit_episode_spans(hub, timer) -> None:
    """One ``episode_spans`` event with every span the timer closed since
    the last emission; nothing without a hub or without spans."""
    spans = timer.take_spans()
    if hub is not None and spans:
        hub.event("episode_spans", spans=spans)


@contextmanager
def episode_span(step: int, name: str = "episode_step"):
    """Step marker around one episode's device dispatch, so profiler UIs
    attribute device time per episode instead of one run-length blob."""
    import jax

    with jax.profiler.StepTraceAnnotation(name, step_num=int(step)):
        yield


# ---------------------------------------------------- device time by layer
# A ``jax.profiler`` trace of a TPU has one plane per chip; on it the
# ``XLA Ops`` line holds one event per operation executed, named by its
# instruction's text, and the ``XLA Modules`` line one per program
# execution, named ``<module>(<program id>)``.  The functions below join
# those events to the map the cost ledger keeps for each program it
# captured (``obs.perf``: ``op_map``), and the device's idle gaps to the
# host's ``phase_span`` names, which the profiler records on its own
# clock.  Everything but :func:`load_profile` takes plain lists.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_MODULE = "(no module)"
# a program whose map covers less of its busy time is read as unmapped:
# a layer's seconds are never read off a partial join
MIN_COVERAGE = 0.99


def find_profile(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` a profiler trace wrote under
    ``trace_dir`` (``plugins/profile/<time>/``)."""
    import glob

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _is_device_plane(name: str) -> bool:
    """``/device:TPU:0``: one plane per chip (a chip's extra planes carry
    a suffix after the number)."""
    return name.startswith("/device:") and \
        name.rsplit(":", 1)[-1].strip().isdigit()


def load_profile(path: str, span_names=SPAN_NAMES) -> Dict:
    """A profiler trace as plain lists: ``{"devices": {plane: {"ops",
    "modules"}}, "spans"}``, each a list of ``(name, start_ns,
    duration_ns)`` — each chip's ``XLA Ops`` and ``XLA Modules`` events,
    and the host's events named in ``span_names``.  A CPU run's trace has
    no chip plane, so ``devices`` is empty there."""
    from jax.profiler import ProfileData

    lines = {OPS_LINE: "ops", MODULES_LINE: "modules"}
    wanted = frozenset(span_names)
    devices: Dict[str, Dict] = {}
    spans: List[tuple] = []
    for plane in ProfileData.from_file(path).planes:
        if _is_device_plane(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name in lines:
                    dev[lines[line.name]] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name in wanted]
    return {"devices": devices, "spans": spans}


def _module(event_name: str):
    """``jit_chunk_step(123)`` -> ``("jit_chunk_step", "123")``."""
    base, sep, rest = event_name.rpartition("(")
    if sep and rest.endswith(")"):
        return base, rest[:-1]
    return event_name, None


def _leaf_ops(ops) -> List[tuple]:
    """The operations that enclose no other recorded operation (a
    ``while`` spans its body's), sorted by start: the device is busy
    during these alone."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(ordered)
    stack: List[tuple] = []           # (index, end)
    for i, (_, start, dur) in enumerate(ordered):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            parent[stack[-1][0]] = True
        stack.append((i, start + dur))
    return [o for o, p in zip(ordered, parent) if not p]


def _choose_map(heads, candidates):
    """The one map among ``candidates`` (maps of the program's module
    name) under which every instruction ``heads`` shows — ``{name:
    signature}``, the program's distinct events — has that signature;
    None where no map, or more than one, passes."""
    passing = []
    for op_map in candidates:
        sigs = op_map.get("signatures") or {}
        seen = [sigs.get(name) for name in heads]
        if any(got is not None for got in seen) and all(
                got == sig for got, sig in zip(seen, heads.values())
                if got is not None):
            passing.append(op_map)
    return passing[0] if len(passing) == 1 else None


def join_scopes(device: Dict, maps) -> Dict:
    """One device's leaf operations joined to the programs' layers.

    ``device`` is one plane of :func:`load_profile`; ``maps`` the
    ``op_map`` of each captured program (a ``compile_cost`` event's, a
    ``perf.json`` entry's).  Each leaf operation goes to the module
    execution that holds its start.  A device trace names a program
    ``<module>(<id>)``, where the id is the runtime's own fingerprint,
    which the compiled object does not report; so a program's map is the
    one of its module name under which every operation the trace shows
    of the program has the same result type and opcode
    (:func:`gsc_tpu.analysis.hlo.instruction_head`) — two programs of one
    name, as ``chunk_step`` with and without its learn burst, share
    instruction names but not their types — and where no map, or more
    than one, passes that check the program stays unmapped.  Returns
    ``{"leaves": [(start_ns, end_ns, execution, instruction)],
    "executions": [(module name, start_ns, end_ns, map or None,
    {instruction: scope path} or None)]}``; an operation outside every
    recorded execution has execution -1.  Each distinct event name is
    parsed once."""
    from ..analysis.hlo import instruction_head

    order = sorted(device.get("modules") or [], key=lambda e: e[1])
    parsed: Dict[str, tuple] = {}
    heads: Dict[str, Dict[str, int]] = {}      # program -> {op: signature}
    leaves = []
    k = 0
    for name, start, dur in _leaf_ops(device.get("ops") or []):
        head = parsed.get(name)
        if head is None:
            head = parsed[name] = instruction_head(name) or (name, None)
        while k < len(order) and order[k][1] + order[k][2] <= start:
            k += 1
        inside = k < len(order) and order[k][1] <= start
        if inside and head[1] is not None:
            heads.setdefault(order[k][0], {})[head[0]] = head[1]
        leaves.append((start, start + dur, k if inside else -1, head[0]))
    maps = [m for m in maps if m]
    chosen = {}
    for program, seen in heads.items():
        op_map = _choose_map(seen, [m for m in maps if m.get("module")
                                    == _module(program)[0]])
        if op_map is not None:
            chosen[program] = (op_map, {
                op: path for path, ops in op_map["paths"].items()
                for op in ops})
    executions = [(_module(name)[0], start, start + dur,
                   *chosen.get(name, (None, None)))
                  for name, start, dur in order]
    return {"leaves": leaves, "executions": executions}


def scope_seconds(joined: Dict) -> Dict:
    """Device seconds of the leaf operations by layer: ``innermost``
    (each operation under the last scope of its path), ``inclusive``
    (under every scope of its path, as ``scope_stats``' ``ops_incl``
    counts), ``unscoped`` (operations of a mapped program under no
    scope), ``unmatched`` (by module: operations of a mapped program
    that its map lacks) and ``unmapped`` (by module: operations of a
    program with no map, and ``(no module)`` for those outside every
    recorded execution) — never guessed into a scope."""
    inner: Dict[str, float] = {}
    incl: Dict[str, float] = {}
    unmatched: Dict[str, float] = {}
    unmapped: Dict[str, float] = {}
    unscoped = 0.0
    execs = joined["executions"]
    for start, end, k, instr in joined["leaves"]:
        s = (end - start) * 1e-9
        op_map = execs[k][3] if k >= 0 else None
        if op_map is None:
            label = execs[k][0] if k >= 0 else NO_MODULE
            unmapped[label] = unmapped.get(label, 0.0) + s
            continue
        path = execs[k][4].get(instr)
        if path is None:
            unmatched[execs[k][0]] = unmatched.get(execs[k][0], 0.0) + s
        elif path == "unscoped":
            unscoped += s
        else:
            names = path.split("/")
            inner[names[-1]] = inner.get(names[-1], 0.0) + s
            for name in set(names):
                incl[name] = incl.get(name, 0.0) + s
    return {"innermost": inner, "inclusive": incl, "unscoped": unscoped,
            "unmatched": unmatched, "unmapped": unmapped}


def join_coverage(joined: Dict) -> Dict[str, float]:
    """By module name: the share of a mapped program's busy time (its
    leaf operations' seconds) whose instruction its map holds."""
    found: Dict[str, List[float]] = {}
    execs = joined["executions"]
    for start, end, k, instr in joined["leaves"]:
        if k < 0 or execs[k][3] is None:
            continue
        rec = found.setdefault(execs[k][0], [0.0, 0.0])
        rec[1] += end - start
        if instr in execs[k][4]:
            rec[0] += end - start
    return {m: (a / b if b else 0.0) for m, (a, b) in found.items()}


def _prefixes(path: str) -> List[str]:
    """``a/b/c`` -> ``["a", "a/b", "a/b/c"]``: the paths it lies under."""
    names = path.split("/")
    return ["/".join(names[:i]) for i in range(1, len(names) + 1)]


def scope_executions(joined: Dict, prefixes=None) -> Dict[str, Dict]:
    """Whole executions of scope paths in the trace, and the device
    seconds of the leaf operations under each (itself and every path
    nested in it) per execution: ``{path: {"executions", "seconds",
    "per_execution_s"}}`` for each of ``prefixes`` (every path a map
    anchors, by default) that some program shows twice.

    The rule: executions are counted between consecutive occurrences of
    the path's anchor — the first of its map's ``anchors`` (operations
    that run once per iteration of the loop that carries the layer) that
    the trace shows.  From the anchor's first occurrence to its last lie
    exactly k - 1 whole executions for k occurrences, and the seconds
    under the path that start in that interval are theirs: the execution
    a trace cuts at its start or at its end is never counted.
    Occurrences are counted per program (map) and summed over them."""
    execs = joined["executions"]
    wanted: Dict[int, Dict[str, List[str]]] = {}    # map -> op -> paths
    for e in execs:
        if e[3] is not None and id(e[3]) not in wanted:
            ops = wanted[id(e[3])] = {}
            for path, anchors in e[3].get("anchors", {}).items():
                if prefixes is None or path in prefixes:
                    for op in anchors:
                        ops.setdefault(op, []).append(path)
    marks: Dict[tuple, Dict[str, List[float]]] = {}  # (map, path) -> starts
    for start, _, k, instr in joined["leaves"]:
        if k >= 0 and execs[k][3] is not None:
            for path in wanted[id(execs[k][3])].get(instr, ()):
                marks.setdefault((id(execs[k][3]), path), {}).setdefault(
                    instr, []).append(start)
    windows: Dict[tuple, tuple] = {}
    for e in execs:
        for path, anchors in (e[3] or {}).get("anchors", {}).items():
            seen = marks.get((id(e[3]), path))
            if seen and (id(e[3]), path) not in windows:
                starts = seen[next(op for op in anchors if op in seen)]
                if len(starts) >= 2:
                    windows[(id(e[3]), path)] = (starts[0], starts[-1],
                                                 len(starts) - 1)
    # per map and operation, once: the windows of the paths it lies under
    plans: Dict[int, Dict[str, List[tuple]]] = {}
    seconds: Dict[str, float] = {}
    for start, end, k, instr in joined["leaves"]:
        if k < 0 or execs[k][3] is None:
            continue
        plan = plans.setdefault(id(execs[k][3]), {})
        todo = plan.get(instr)
        if todo is None:
            path = execs[k][4].get(instr)
            todo = plan[instr] = [
                (prefix, *windows[(id(execs[k][3]), prefix)][:2])
                for prefix in (_prefixes(path) if path and path != "unscoped"
                               else ())
                if (id(execs[k][3]), prefix) in windows]
        for prefix, w0, w1 in todo:
            if w0 <= start < w1:
                seconds[prefix] = seconds.get(prefix, 0.0) + \
                    (end - start) * 1e-9
    out: Dict[str, Dict] = {}
    for (_, path), (_, _, n) in windows.items():
        rec = out.setdefault(path, {"executions": 0, "seconds": 0.0})
        rec["executions"] += n
    for path, rec in out.items():
        rec["seconds"] = seconds.get(path, 0.0)
        rec["per_execution_s"] = rec["seconds"] / rec["executions"]
    return out


def _union(intervals) -> List[tuple]:
    merged: List[list] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def program_busy(joined: Dict) -> Dict[str, Dict]:
    """By module name: the recorded executions, the seconds they span
    (each clipped to the trace's first and last leaf operation, so an
    execution the trace cuts counts for its traced part) and the busy
    seconds of the leaf operations inside them: ``1 - busy_s / span_s``
    is the idle share inside the program, without the host's gaps
    between its executions."""
    leaves = joined["leaves"]
    if not leaves:
        return {}
    busy: Dict[int, float] = {}
    reach: Dict[int, float] = {}       # execution -> end of its busy run
    for start, end, k, _ in leaves:    # sorted by start
        if k < 0:
            continue
        last = reach.get(k, start)
        if end > last:
            busy[k] = busy.get(k, 0.0) + end - (start if start > last
                                                else last)
            reach[k] = end
    lo = leaves[0][0]
    hi = max(end for _, end, _, _ in leaves)
    out: Dict[str, Dict] = {}
    for k, (module, start, end, _, _) in enumerate(joined["executions"]):
        span = min(end, hi) - max(start, lo)
        if span <= 0:
            continue
        rec = out.setdefault(module, {"executions": 0, "span_s": 0.0,
                                      "busy_s": 0.0})
        rec["executions"] += 1
        rec["span_s"] += span * 1e-9
        rec["busy_s"] += busy.get(k, 0.0) * 1e-9
    return out


def idle_spans(leaves, spans, top: int = 5) -> List[List]:
    """The ``top`` longest device-idle gaps between leaf operations
    (``(start_ns, end_ns, ...)`` sorted by start, as :func:`join_scopes`
    gives them), each ``[span, seconds]`` under the host span that held
    most of it.  At each instant of a gap the host is in the innermost of
    the ``spans`` (``(name, start_ns, duration_ns)`` on the device's
    clock) open then — nesting decided by containment, so the shortest
    of those that cover the instant — or in ``none``; the gap goes to the
    name that held the most of its time."""
    found = []
    reach = None
    for start, end, *_ in leaves:      # sorted by start
        if reach is not None and start > reach:
            found.append((start - reach, reach, start))
        if reach is None or end > reach:
            reach = end
    out = []
    for length, g0, g1 in heapq.nlargest(top, found):
        open_ = [(max(start, g0), min(start + dur, g1), dur, name)
                 for name, start, dur in spans
                 if start < g1 and start + dur > g0]
        cuts = sorted({g0, g1} | {t for a, b, _, _ in open_ for t in (a, b)})
        held: Dict[str, float] = {}
        for a, b in zip(cuts[:-1], cuts[1:]):
            inner = min(((dur, name) for lo, hi, dur, name in open_
                         if lo <= a and b <= hi), default=(0, "none"))[1]
            held[inner] = held.get(inner, 0.0) + b - a
        out.append([max(held, key=held.get), length * 1e-9])
    return out


def _add(into: Dict, more: Dict) -> None:
    """Sum ``more``'s numbers into ``into``, nested dicts key by key."""
    for key, val in more.items():
        if isinstance(val, dict):
            _add(into.setdefault(key, {}), val)
        else:
            into[key] = into.get(key, 0) + val


def layer_times(loaded: Dict, maps, top: int = 5) -> Dict:
    """:func:`load_profile`'s trace read through the captured ``maps``,
    summed over the chips: device seconds by scope, whole executions of
    every anchored scope path, each mapped program's join coverage, the
    programs' own busy and span seconds and the longest idle gaps under
    the host's spans.  A program whose coverage is under
    :data:`MIN_COVERAGE` on a chip is read there as unmapped."""
    maps = [m for m in maps if m]
    out = {"scopes": {}, "executions": {}, "coverage": {}, "programs": {},
           "idle_spans": []}
    for device in loaded["devices"].values():
        joined = join_scopes(device, maps)
        coverage = join_coverage(joined)
        joined["executions"] = [
            e if e[3] is None or coverage[e[0]] >= MIN_COVERAGE
            else (*e[:3], None, None) for e in joined["executions"]]
        _add(out["scopes"], scope_seconds(joined))
        _add(out["executions"], scope_executions(joined))
        for module, share in coverage.items():
            out["coverage"][module] = min(share, out["coverage"].get(
                module, 1.0))
        _add(out["programs"], program_busy(joined))
        out["idle_spans"] += idle_spans(joined["leaves"], loaded["spans"],
                                        top)
    for rec in out["executions"].values():
        rec["per_execution_s"] = rec["seconds"] / rec["executions"]
    out["idle_spans"] = sorted(out["idle_spans"], key=lambda g: -g[1])[:top]
    return out


# --------------------------------------------------------------- exporter
# one pid per run stream; fixed tids = the logical threads of a run.
# Stable API: tools and tests reference these names.
TRACE_PID = 1
TRACE_TRACKS = {
    "episode": 1,        # training loop: episode spans + phase sub-spans
    "prefetcher": 2,     # producer-thread restarts
    "serve": 3,          # serve_start/serve_stats counters + flush slices
    "watchdog": 4,       # stalls, escalations, invariant violations
    "compile": 5,        # jit trace/XLA compile spans + compile_cost marks
    "recovery": 6,       # self-healing ladder, chained by flow arrows
    "serve_request": 7,  # head-sampled request spans, flow-linked to the
                         # batcher flush that answered them
    "channel": 8,        # async actor->learner conduit: one slice per
                         # block's queued residency (put -> pop)
    "learner": 9,        # async learner: ingest + learn_burst slices,
                         # publish marks (flow-linked to actor adopts)
}
# per-actor async tracks start here: actor a renders on tid BASE + a
ACTOR_TRACK_BASE = 16
# phase sub-span layout order inside an episode slice (the obs schema's
# cumulative PhaseTimer names)
_TRACE_PHASES = ("host_sample", "host_sample_wait", "dispatch", "drain")


def _event_ts(e) -> float:
    ts = e.get("ts") if isinstance(e, dict) else None
    return float(ts) if isinstance(ts, (int, float)) \
        and not isinstance(ts, bool) else float("-inf")


def sort_events(events: List[Dict]) -> List[Dict]:
    """Stable ts-sort WITHIN each run's slice of an (append-mode) stream.
    Runs are delimited by ``run_start`` in file order — a later run whose
    wall clock stepped backwards (NTP, VM resume) must never interleave
    into the previous run's tail, so the sort is per-run, not global.
    Within one run the reorder window is the emit race (ts stamped
    before the sink lock), which is same-run by construction."""
    out: List[Dict] = []
    seg: List[Dict] = []
    for e in events:
        if isinstance(e, dict) and e.get("event") == "run_start" and seg:
            seg.sort(key=_event_ts)
            out.extend(seg)
            seg = []
        seg.append(e)
    seg.sort(key=_event_ts)
    out.extend(seg)
    return out


def read_events(path: str) -> List[Dict]:
    """Load a run's event stream: accepts the run dir or the events.jsonl
    itself, walks rotated segments (``events.jsonl.N .. .1`` then the
    live file — the ``--obs-rotate-mb`` layout), skips torn tail lines.

    Events come back SORTED by ``ts`` within each run (stable — same-ts
    records keep file order; see :func:`sort_events`): the hub stamps
    ``ts`` before taking the sink lock, so concurrently-emitting threads
    (watchdog, prefetcher, main loop) can land out of order in the file,
    and a rotation can split an interleaving across segments.  Every
    consumer of this reader (trace builder, curves extraction) assumes
    one monotone stream per run — sorting here is what makes that
    assumption true, and keeping it per-run means appended runs never
    interleave even when the wall clock stepped backwards between
    them."""
    from .sinks import rotated_paths

    if os.path.isdir(path):
        path = os.path.join(path, "events.jsonl")
    segments = [p for p in rotated_paths(path) if os.path.exists(p)]
    if not segments:
        raise FileNotFoundError(f"no events stream at {path}")
    events = []
    for seg in segments:
        with open(seg) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue   # torn final line of a live segment
    return sort_events(events)


def _us(ts: float, t0: float) -> float:
    return round((ts - t0) * 1e6, 1)


def build_trace(events: List[Dict]) -> Dict:
    """Chrome trace-event JSON from an obs event stream.

    Where a run's stream holds ``episode_spans`` events, every recorded
    span is one complete slice at its own start time and duration (the
    root ``episode`` span is the episode's slice, its children nest
    inside it by containment).  A run without them keeps the older
    layout: episode slices back-to-back on the episode track (each ends
    at its event's wall ts), phase sub-spans reconstructed from the
    per-episode deltas of the cumulative PhaseTimer totals, laid
    sequentially inside the episode slice and scaled down if they would
    overflow it — faithful shares, synthetic start times.  Stalls /
    escalations / invariant violations are instants on the watchdog
    track; consecutive ``recovery`` events chain with flow arrows so a
    retry -> restart -> rollback ladder reads as one connected story."""
    events = [e for e in events if isinstance(e, dict) and "ts" in e]
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    # read_events already sorts, but the builder also accepts raw lists
    # (tests, in-memory sinks) — re-apply the SAME per-run sort so a
    # later run whose clock stepped backwards is never woven into the
    # previous run's slices here either.  (The trace is one timeline, so
    # the final output sort below still orders such streams globally —
    # a Chrome-format requirement; multi-run streams with non-monotone
    # clocks render best-effort.)  Stable: same-ts events keep caller
    # order.
    events = sort_events(events)
    # the async flight-recorder records (``async_actor_ep`` /
    # ``async_learner_spans``) are emitted DEFERRED at run end but carry
    # their own wall timestamps from mid-run — the trace origin must
    # include those payload times or every reconstructed span would land
    # at a negative offset and fail the strict validator
    t_min = [float(e["ts"]) for e in events]
    for e in events:
        k = e.get("event")
        if k == "async_actor_ep":
            t_min.extend(float(r[0]) for r in (e.get("chunks") or []))
            t_min.extend(float(r[0]) - float(r[1])
                         for r in (e.get("puts") or []))
            t_min.extend(float(r[0]) for r in (e.get("adopts") or []))
        elif k == "async_learner_spans":
            for field in ("ingests", "bursts", "publishes"):
                t_min.extend(float(r[0]) for r in (e.get(field) or []))
        elif k == "episode_spans":
            t_min.extend(float(sp["t0"]) for sp in (e.get("spans") or []))
    t0 = min(t_min)
    run = next((e.get("run") for e in events if e.get("run")), "run")
    out: List[Dict] = []

    # named `push`, not `emit`: a device-side scan body already owns
    # that name, and gsc-lint's name-graph would treat this host-only
    # helper as traced
    def push(ph, name, tid, ts_us, dur=None, args=None, **extra):
        ev = {"ph": ph, "name": name, "pid": TRACE_PID, "tid": tid,
              "ts": ts_us, "cat": "gsc"}
        if dur is not None:
            ev["dur"] = dur
        if args:
            ev["args"] = args
        ev.update(extra)
        out.append(ev)

    # track metadata (ph "M"): process + thread names
    out.append({"ph": "M", "name": "process_name", "pid": TRACE_PID,
                "tid": 0, "ts": 0.0, "args": {"name": f"gsc_tpu {run}"}})
    for label, tid in TRACE_TRACKS.items():
        out.append({"ph": "M", "name": "thread_name", "pid": TRACE_PID,
                    "tid": tid, "ts": 0.0, "args": {"name": label}})

    ep_tid = TRACE_TRACKS["episode"]
    prev_phase_totals: Dict[str, float] = {}
    prev_end = 0.0            # episode-track cursor (monotone)
    compile_end = 0.0         # compile-track cursor
    recoveries = [e for e in events if e.get("event") == "recovery"]
    rec_index = {id(e): i for i, e in enumerate(recoveries)}
    flow_id = 0
    # serving flushes index ((run-segment, flush_id) -> dispatch ts_us):
    # sampled request spans flow-arrow into the flush slice that
    # answered them; built up front because span events carry their
    # ENQUEUE wall time, which always precedes the flush's dispatch time
    # in the sorted stream.  Keyed per run_start segment, not by
    # flush_id alone — appended runs in a reused --obs-dir each restart
    # their flush ids at 0, and a run-1 span must never arrow into a
    # run-2 flush slice
    seg_of: Dict[int, int] = {}
    seg = 0
    for e in events:
        if e.get("event") == "run_start":
            seg += 1
        seg_of[id(e)] = seg
    # runs whose phases are recorded spans, and what their ``episode``
    # events say (the root span's slice carries it as args)
    span_segs = {seg_of[id(e)] for e in events
                 if e.get("event") == "episode_spans"}
    ep_args = {(seg_of[id(e)], e.get("episode")):
               {"episode": e.get("episode"), "sps": e.get("sps"),
                "return": e.get("episodic_return")}
               for e in events if e.get("event") == "episode"}
    flush_ts = {(seg_of[id(e)], e.get("flush_id")): _us(float(e["ts"]), t0)
                for e in events
                if e.get("event") == "serve_flush"
                and e.get("flush_id") is not None}
    # async flight-recorder indices (same per-segment keying): put->pop
    # flows need each block's ingest start by seq, publish->adopt flows
    # need each version's publish time; both live in deferred learner
    # records that can sort before OR after the actor records
    async_ingest: Dict[tuple, List] = {}
    async_pub: Dict[tuple, float] = {}
    actor_ids = set()
    for e in events:
        k = e.get("event")
        if k == "async_learner_spans":
            s = seg_of[id(e)]
            for row in (e.get("ingests") or []):
                async_ingest[(s, int(row[5]))] = row
            for p_ts, ver in (e.get("publishes") or []):
                async_pub.setdefault((s, int(ver)), float(p_ts))
        elif k == "async_actor_ep":
            actor_ids.add(int(e.get("actor") or 0))
    for a in sorted(actor_ids):
        out.append({"ph": "M", "name": "thread_name", "pid": TRACE_PID,
                    "tid": ACTOR_TRACK_BASE + a, "ts": 0.0,
                    "args": {"name": f"actor{a}"}})

    for ev in events:
        kind = ev.get("event")
        ts_us = _us(float(ev["ts"]), t0)
        if kind == "run_start":
            prev_phase_totals = {}
            prev_end = max(prev_end, ts_us)
            push("i", "run_start", ep_tid, ts_us, s="t",
                 args={k: v for k, v in ev.items()
                       if k in ("run", "episodes", "replicas", "pipeline",
                                "precision", "mesh")})
        elif kind == "episode_spans":
            for sp in (ev.get("spans") or []):
                root = sp.get("name") == "episode"
                start = _us(float(sp["t0"]), t0)
                dur = round(max(float(sp.get("dur_s") or 0.0), 0.0) * 1e6, 1)
                push("X", (f"episode {sp.get('episode')}" if root
                           else sp.get("name")), ep_tid, start, dur=dur,
                     args=(ep_args.get((seg_of[id(ev)], sp.get("episode")))
                           if root else {"episode": sp.get("episode"),
                                         "parent": sp.get("parent")}))
                prev_end = max(prev_end, round(start + dur, 1))
        elif kind == "episode" and seg_of[id(ev)] in span_segs:
            pass    # drawn from its recorded root span, above
        elif kind == "episode":
            start = max(prev_end, 0.0)
            end = max(ts_us, start)
            push("B", f"episode {ev.get('episode')}", ep_tid, start,
                 args={"episode": ev.get("episode"), "sps": ev.get("sps"),
                       "return": ev.get("episodic_return")})
            totals = {n: i.get("total_s", 0.0)
                      for n, i in (ev.get("phases") or {}).items()}
            deltas = {n: max(t - prev_phase_totals.get(n, 0.0), 0.0)
                      for n, t in totals.items()}
            prev_phase_totals = totals
            order = [p for p in _TRACE_PHASES if deltas.get(p, 0) > 0] + \
                sorted(set(deltas) - set(_TRACE_PHASES))
            total_us = sum(deltas.get(p, 0.0) for p in order) * 1e6
            span = end - start
            scale = (span / total_us) if total_us > span else 1.0
            cursor = start
            for p in order:
                d = round(deltas.get(p, 0.0) * 1e6 * scale, 1)
                if d <= 0:
                    continue
                push("B", p, ep_tid, cursor,
                     args={"delta_ms": round(deltas[p] * 1e3, 3)})
                cursor = round(min(cursor + d, end), 1)
                push("E", p, ep_tid, cursor)
            push("E", f"episode {ev.get('episode')}", ep_tid, end)
            prev_end = end
        elif kind == "eval_episode":
            start = max(prev_end,
                        ts_us - round(float(ev.get("runtime_s") or 0.0)
                                      * 1e6, 1))
            end = max(ts_us, start)
            push("B", f"eval {ev.get('episode')}", ep_tid, start,
                 args={"return": ev.get("episodic_return"),
                       "succ_ratio": ev.get("succ_ratio")})
            push("E", f"eval {ev.get('episode')}", ep_tid, end)
            prev_end = end
        elif kind == "run_end":
            push("i", f"run_end ({ev.get('status')})", ep_tid,
                 max(ts_us, prev_end), s="t")
            prev_end = max(ts_us, prev_end)
        elif kind == "stall":
            push("i", "stall", TRACE_TRACKS["watchdog"], ts_us, s="g",
                 args={"age_s": ev.get("age_s"),
                       "budget_s": ev.get("budget_s"),
                       "last_phase": ev.get("last_phase"),
                       "dispatch_drain_lag": ev.get("dispatch_drain_lag")})
        elif kind == "escalation":
            push("i", "escalation", TRACE_TRACKS["watchdog"], ts_us,
                 s="g", args={"age_s": ev.get("age_s"),
                              "action": ev.get("action")})
        elif kind == "invariant_violation":
            push("i", "invariant_violation", TRACE_TRACKS["watchdog"],
                 ts_us, s="t",
                 args={"episode": ev.get("episode"),
                       "violations": len(ev.get("violations") or [])})
        elif kind == "recovery":
            name = f"{ev.get('site')}/{ev.get('action')}"
            i = rec_index[id(ev)]
            nxt = (_us(float(recoveries[i + 1]["ts"]), t0)
                   if i + 1 < len(recoveries) else ts_us + 1000.0)
            dur = round(max(min(1000.0, nxt - ts_us), 0.0), 1)
            tid = TRACE_TRACKS["recovery"]
            push("B", name, tid, ts_us,
                 args={"episode": ev.get("episode"),
                       "fault": ev.get("fault"),
                       "detail": ev.get("detail")})
            # flow arrows chain the ladder: this action -> the next one
            if i + 1 < len(recoveries):
                flow_id += 1
                push("s", "ladder", tid, ts_us, id=flow_id)
                push("f", "ladder", tid, nxt, id=flow_id, bp="e")
            push("E", name, tid, round(ts_us + dur, 1))
            if ev.get("site") == "prefetcher":
                push("i", ev.get("action") or "restart",
                     TRACE_TRACKS["prefetcher"], ts_us, s="t",
                     args={"episode": ev.get("episode")})
        elif kind == "compile":
            dur = round(float(ev.get("duration_s") or 0.0) * 1e6, 1)
            start = max(compile_end, ts_us - dur)
            end = max(ts_us, start)
            push("B", f"{ev.get('fn')} [{ev.get('stage')}]",
                 TRACE_TRACKS["compile"], start,
                 args={"count": ev.get("count")})
            push("E", f"{ev.get('fn')} [{ev.get('stage')}]",
                 TRACE_TRACKS["compile"], end)
            compile_end = end
        elif kind == "compile_cost":
            push("i", f"cost {ev.get('fn')}", TRACE_TRACKS["compile"],
                 max(ts_us, compile_end), s="t",
                 args={"flops": ev.get("flops"),
                       "bytes_accessed": ev.get("bytes_accessed"),
                       "fusions": ev.get("fusions")})
            compile_end = max(ts_us, compile_end)
        elif kind == "serve_start":
            push("i", "serve_start", TRACE_TRACKS["serve"], ts_us, s="t",
                 args={"tier": ev.get("tier"),
                       "startup_s": ev.get("startup_s")})
        elif kind == "serve_stats":
            push("C", "serve", TRACE_TRACKS["serve"], ts_us,
                 args={"rps": float(ev.get("rps") or 0.0),
                       "p99_ms": float(ev.get("p99_ms") or 0.0),
                       "queue_depth": float(ev.get("queue_depth") or 0)})
        elif kind == "serve_flush":
            # one complete slice per device call ("X": self-contained
            # duration, so overlapping flushes never unbalance a B/E
            # stack); ts is the dispatch wall time the tracer pinned
            dur = round(max(float(ev.get("device_ms") or 0.0), 0.0)
                        * 1e3, 1)
            push("X", f"flush b{ev.get('bucket')}", TRACE_TRACKS["serve"],
                 ts_us, dur=dur,
                 args={"flush_id": ev.get("flush_id"),
                       "n_real": ev.get("n_real"),
                       "pad_fraction": ev.get("pad_fraction")})
        elif kind == "serve_request_span":
            # sampled request: enqueue -> fan-out as one slice, with the
            # queue/batch/device/fan-out split in args; a flow arrow
            # links it to its flush's slice on the serve track
            total_ms = (float(ev.get("latency_ms") or 0.0)
                        + max(float(ev.get("fanout_ms") or 0.0), 0.0))
            push("X", f"req {ev.get('trace_id')}",
                 TRACE_TRACKS["serve_request"], ts_us,
                 dur=round(max(total_ms, 0.0) * 1e3, 1),
                 args={k: ev.get(k) for k in
                       ("trace_id", "flush_id", "bucket", "queue_wait_ms",
                        "batch_wait_ms", "device_ms", "fanout_ms",
                        "latency_ms", "deadline_miss")})
            f_ts = flush_ts.get((seg_of[id(ev)], ev.get("flush_id")))
            if f_ts is not None and f_ts >= ts_us:
                flow_id += 1
                push("s", "serve_req", TRACE_TRACKS["serve_request"],
                     ts_us, id=flow_id)
                push("f", "serve_req", TRACE_TRACKS["serve"], f_ts,
                     id=flow_id, bp="e")
        elif kind == "async_actor_ep":
            # one deferred record per actor-episode; every span below
            # uses the PAYLOAD wall times, not this record's emit ts.
            # All complete slices ("X") — reconstructed spans from three
            # concurrent threads must never share a B/E stack.
            aid = int(ev.get("actor") or 0)
            tid = ACTOR_TRACK_BASE + aid
            s = seg_of[id(ev)]
            ep = ev.get("ep")
            for c0, c1, ver in (ev.get("chunks") or []):
                push("X", f"rollout ep{ep}", tid, _us(float(c0), t0),
                     dur=round(max(float(c1) - float(c0), 0.0) * 1e6, 1),
                     args={"episode": ep, "version": int(ver)})
            for t_enq, wait_s, steps, ver, seq in (ev.get("puts") or []):
                t_enq, wait_s = float(t_enq), max(float(wait_s), 0.0)
                enq_us = _us(t_enq, t0)
                # the backpressure wait the put paid, on the actor track
                push("X", "put", tid, _us(t_enq - wait_s, t0),
                     dur=round(wait_s * 1e6, 1),
                     args={"seq": int(seq), "steps": int(steps),
                           "staleness_wait_s": round(wait_s, 6),
                           "version": int(ver)})
                ing = async_ingest.get((s, int(seq)))
                ing_us = _us(float(ing[0]), t0) if ing else None
                # queued residency on the channel track: put -> pop
                push("X", f"block s{seq}", TRACE_TRACKS["channel"],
                     enq_us,
                     dur=(round(max(ing_us - enq_us, 0.0), 1)
                          if ing_us is not None else 0.0),
                     args={"seq": int(seq), "steps": int(steps),
                           "staleness_wait_s": round(wait_s, 6),
                           "version": int(ver)})
                if ing_us is not None and ing_us >= enq_us:
                    flow_id += 1
                    push("s", "chan", tid, enq_us, id=flow_id,
                         args={"steps": int(steps),
                               "staleness_wait_s": round(wait_s, 6)})
                    push("f", "chan", TRACE_TRACKS["learner"], ing_us,
                         id=flow_id, bp="e")
            for a_ts, ver in (ev.get("adopts") or []):
                a_us = _us(float(a_ts), t0)
                push("i", f"adopt v{int(ver)}", tid, a_us, s="t",
                     args={"version": int(ver)})
                # publish -> adopt: one arrow per adopting actor (the
                # validator balances s/f per flow id, so a version
                # adopted by N actors gets N independent arrows)
                p_ts = async_pub.get((s, int(ver)))
                if p_ts is not None and _us(p_ts, t0) <= a_us:
                    flow_id += 1
                    push("s", f"publish v{int(ver)}",
                         TRACE_TRACKS["learner"], _us(p_ts, t0),
                         id=flow_id)
                    push("f", f"publish v{int(ver)}", tid, a_us,
                         id=flow_id, bp="e")
        elif kind == "async_learner_spans":
            ltid = TRACE_TRACKS["learner"]
            for row in (ev.get("ingests") or []):
                # rows grew a trailing dp-shard id (producer's stable
                # assignment) with the sharded async ring; pre-shard
                # recordings carry 6 elements — unpack tolerantly
                i0, i1, steps, ver, lag, seq = row[:6]
                args = {"seq": int(seq), "steps": int(steps),
                        "version": int(ver), "policy_lag": int(lag)}
                if len(row) > 6:
                    args["replay_shard"] = int(row[6])
                push("X", "replay_ingest", ltid, _us(float(i0), t0),
                     dur=round(max(float(i1) - float(i0), 0.0) * 1e6, 1),
                     args=args)
            for b0, b1, n in (ev.get("bursts") or []):
                push("X", f"learn_burst {int(n)}", ltid,
                     _us(float(b0), t0),
                     dur=round(max(float(b1) - float(b0), 0.0) * 1e6, 1),
                     args={"burst": int(n)})
            for p_ts, ver in (ev.get("publishes") or []):
                push("i", f"publish v{int(ver)}", ltid,
                     _us(float(p_ts), t0), s="t",
                     args={"version": int(ver)})
        # other event kinds (precision, harness_episode, ...) carry no
        # timeline geometry — the report renders them, the trace skips them

    # flows ride INSIDE slices; keep pairs adjacent under the stable sort
    order_key = {"M": 0}
    out.sort(key=lambda e: (e.get("ts", 0.0),
                            order_key.get(e.get("ph"), 1)))
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "metadata": {"run": run, "exporter": "gsc_tpu.obs.trace",
                         "t0_unix_s": t0}}


def validate_trace(trace: Dict) -> List[str]:
    """Strict schema check; returns a list of problems (empty = valid).

    Rules: every event carries ph/name/pid/tid and a numeric ts >= 0;
    events are globally sorted by ts; per (pid, tid) the B/E events form
    a properly nested stack (names match, nothing left open); "X" events
    need dur >= 0; every flow start ("s") has a matching finish ("f")."""
    errors: List[str] = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    stacks: Dict[tuple, List[str]] = {}
    flows_open: Dict[object, int] = {}
    last_ts = None
    for i, ev in enumerate(events):
        for field in ("ph", "name", "pid", "tid"):
            if field not in ev:
                errors.append(f"event {i}: missing {field!r}")
        ph = ev.get("ph")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errors.append(f"event {i}: bad ts {ts!r}")
            continue
        if ph != "M":
            if last_ts is not None and ts < last_ts:
                errors.append(f"event {i}: ts {ts} < previous {last_ts} "
                              "(stream not monotone)")
            last_ts = ts
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks.setdefault(key, []).append(ev.get("name"))
        elif ph == "E":
            stack = stacks.get(key) or []
            if not stack:
                errors.append(f"event {i}: E with empty stack on {key}")
            else:
                top = stack.pop()
                if ev.get("name") and ev["name"] != top:
                    errors.append(f"event {i}: E {ev['name']!r} does not "
                                  f"match open B {top!r} on {key}")
        elif ph == "X":
            if not isinstance(ev.get("dur"), (int, float)) \
                    or ev["dur"] < 0:
                errors.append(f"event {i}: X with bad dur {ev.get('dur')!r}")
        elif ph == "s":
            flows_open[ev.get("id")] = flows_open.get(ev.get("id"), 0) + 1
        elif ph == "f":
            if flows_open.get(ev.get("id"), 0) <= 0:
                errors.append(f"event {i}: flow finish without start "
                              f"(id {ev.get('id')!r})")
            else:
                flows_open[ev["id"]] -= 1
    for key, stack in stacks.items():
        if stack:
            errors.append(f"unclosed B events on {key}: {stack}")
    for fid, n in flows_open.items():
        if n:
            errors.append(f"flow start without finish (id {fid!r})")
    return errors
