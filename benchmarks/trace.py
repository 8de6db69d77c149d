"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone.  What is taken from a trace:

- **device operations**: the events of each device plane's ``XLA Ops``
  line.  Control-flow operations (``while``, ``conditional``, ``call``)
  span the operations they run, so an operation's *self* time is its
  duration less what its children cover, and the device is *busy* during
  the union of its leaf operations only — a ``while`` that waits between
  two small fusions is not busy there.  A trace with no device plane (the
  CPU rehearsal) falls back to host events that carry an ``hlo_op`` stat.
- **top-level loops**: ``while`` operations that no recorded operation
  encloses, in the order they ran.  A trace that starts inside a program
  does not hold the operations that began before it, so what it does hold
  whole is the loops that began after: in a slice over the end of an
  episode, the substep loops of the rollout's last steps and then, last,
  the learn burst's loop.
- **host spans**: the program's own ``TraceAnnotation`` phases, by name,
  on the profiler's clock; each long device-idle gap is attributed to the
  span that covers most of it, or to ``none``.
"""
from __future__ import annotations

import glob
import os
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PHASES = ("dispatch", "drain", "scenario_regen")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def keep(trace_dir: str, dest: str) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
    shutil.copyfile(find_xplane(trace_dir), dest)


def is_device_plane(name: str) -> bool:
    """``/device:TPU:0`` and the like: one plane per chip (a chip's extra
    planes, such as its sparse cores, carry a suffix after the number)."""
    if not name.startswith("/device:"):
        return False
    return name.rsplit(":", 1)[-1].strip().isdigit()


def short_name(name: str, limit: int = 96) -> str:
    """``%fusion.8 = f32[...] fusion(...), kind=kOutput, calls=...`` ->
    ``%fusion.8 fusion kOutput f32[...]``: the instruction, its opcode,
    its kind and its result, without the operands."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    if rest.startswith("("):        # a tuple result: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, tail = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        result, _, tail = rest.partition(" ")
    op = tail.split("(", 1)[0]
    kind = ""
    if "kind=" in tail:
        kind = " " + tail.split("kind=", 1)[1].split(",", 1)[0]
    return f"{head} {op}{kind} {result}"[:limit]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # a stat the binding cannot convert
        return {}


def load(path: str, phases: Sequence[str] = PHASES) -> dict:
    """Planes of interest as plain lists: per device ``ops`` as (name,
    start_ns, duration_ns), and host ``spans``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[Tuple[str, float, float]] = []
    host_ops: List[Tuple[str, float, float]] = []
    wanted = set(phases)
    for plane in data.planes:
        if is_device_plane(plane.name):
            dev = devices.setdefault(plane.name, {"ops": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [(e.name, e.start_ns, e.duration_ns)
                                  for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif not devices and e.duration_ns > 0:
                        st = _stats(e)
                        if "hlo_op" in st:
                            host_ops.append((e.name, e.start_ns,
                                             e.duration_ns))
    if not devices and host_ops:   # CPU rehearsal: no device plane
        devices["/host:CPU (rehearsal)"] = {"ops": host_ops}
    return {"devices": devices, "spans": spans}


def opcode(name: str) -> str:
    """``%while.3 = (...) while(...), condition=...`` -> ``while``."""
    parts = short_name(name, limit=10 ** 6).split(" ")
    return parts[1] if len(parts) > 1 else ""


def self_times(ops: Iterable[Tuple[str, float, float]],
               top_level: Optional[List[Tuple[str, float, float]]] = None
               ) -> Tuple[Dict[str, float], List[Tuple[float, float]]]:
    """Self time per operation name (ns) and the leaf intervals, from
    events that nest: a parent's self time leaves out its children.
    ``top_level`` collects the operations no recorded operation encloses,
    as (name, start, duration)."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    self_ns: Dict[str, float] = {}
    leaves: List[Tuple[float, float]] = []
    stack: List[list] = []       # [name, start, end, child_ns, has_child]

    def close(item):
        name, start, end, child, has_child = item
        self_ns[name] = self_ns.get(name, 0.0) + max(end - start - child, 0.0)
        if not has_child:
            leaves.append((start, end))

    for name, start, dur in ordered:
        end = start + dur
        while stack and stack[-1][2] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][2]) - start
            stack[-1][4] = True
        elif top_level is not None:
            top_level.append((name, start, dur))
        stack.append([name, start, end, 0.0, False])
    while stack:
        close(stack.pop())
    return self_ns, leaves


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def gaps_by_span(busy: List[Tuple[float, float]],
                 spans: List[Tuple[str, float, float]], top: int = 5
                 ) -> List[List]:
    """The longest idle gaps between busy intervals, each under the name
    of the host span that covers most of it (``none`` where none does)."""
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy[:-1], busy[1:]) if b[0] > a[1]),
                  reverse=True)[:top]
    out = []
    for length, g0, g1 in gaps:
        best, cover = "none", 0.0
        for name, start, dur in spans:
            c = min(g1, start + dur) - max(g0, start)
            if c > cover:
                best, cover = name, c
        out.append([best, length * 1e-9])
    return out


def reduce(loaded: dict, window_s: float) -> dict:
    """Busy time and the operations' span (averaged over the chips used),
    the breakdown, and the top-level loops as [start_s, seconds] in the
    order they ran, ``start_s`` counted from the first recorded
    operation."""
    devices = loaded["devices"]
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy_total = 0.0
    op_self: Dict[str, float] = {}
    idle_gaps: List[List] = []
    loops: List[List[float]] = []
    ops_span = 0.0
    n_events = 0
    for dev in devices.values():
        n_events += len(dev["ops"])
        top: List[Tuple[str, float, float]] = []
        self_ns, leaves = self_times(dev["ops"], top)
        merged = union(leaves)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        if merged:
            ops_span += (merged[-1][1] - merged[0][0]) * 1e-9
        first = min((o[1] for o in dev["ops"]), default=0.0)
        loops += [[(t - first) * 1e-9, d * 1e-9] for n, t, d in top
                  if opcode(n) == "while"]
        for name, ns in self_ns.items():
            op_self[name] = op_self.get(name, 0.0) + ns * 1e-9
        idle_gaps += gaps_by_span(merged, loaded["spans"])
    top_ops = sorted(op_self.items(), key=lambda kv: -kv[1])[:10]
    idle_gaps = sorted(idle_gaps, key=lambda g: -g[1])[:5]
    return {"busy_s": busy_total / len(devices), "window_s": window_s,
            "breakdown": {"device_ops": [[short_name(n), s]
                                         for n, s in top_ops],
                          "idle_gaps": idle_gaps},
            "top_level_loops": sorted(loops),
            "ops_span_s": ops_span / len(devices), "n_events": n_events,
            "n_devices": len(devices)}


def reduce_file(path: str, window_s: float,
                phases: Sequence[str] = PHASES) -> dict:
    return reduce(load(path, phases), window_s)


def reduce_dir(trace_dir: str, started: float, stopped: float) -> dict:
    return reduce_file(find_xplane(trace_dir), stopped - started)
