"""Render a run's ``events.jsonl`` into per-episode / per-phase summaries.

Usage:
    python tools/obs_report.py <run_dir | events.jsonl>  [--json]
    python tools/obs_report.py --selftest

Reads the event stream the ``gsc_tpu.obs`` subsystem writes (``cli train``
does by default), prints:

- a per-run header with the dtype policy (the ``precision`` event /
  run_start meta: policy name plus param/gnn/mlp/replay dtypes) and the
  substep scan's unroll factor (run_start meta: ``unroll``) so a
  throughput comparison across runs is attributable to both;
- a per-episode table: SPS, return, success ratio, learner losses, the
  per-episode *delta* of each pipeline phase's host wall (the stream
  carries cumulative ``PhaseTimer`` totals), and device bytes-in-use;
- a final per-phase summary (total wall, mean ms per episode);
- a jit-compile summary from the retrace sentinel's ``compile`` events
  (gsc_tpu.analysis.sentinels.CompileMonitor): traces / XLA compiles and
  compile seconds per jitted entry point, with a retrace-churn flag when
  an entry point traced more than ``--retrace-threshold`` times (a
  steady-state pipelined loop traces each entry point once per static-arg
  variant; more means weak-type scalars or shape drift re-triggering
  tracing);
- every ``stall`` / ``invariant_violation`` record, verbatim fields;
- a recovery timeline from the resilience subsystem's ``recovery`` /
  ``escalation`` events: one line per self-healing action (dispatch retry,
  prefetcher restart, pipeline-off degradation, learner-state rollback,
  checkpoint resave, preemption snapshot) with per-(site, action) totals —
  a run that exits 0 after surviving faults shows HOW it survived;
- a device-memory growth check: bytes_in_use at the first vs last episode
  per device, flagged when growth exceeds ``--mem-growth-threshold``
  (a leaking HBM buffer shows as monotonic growth long before an OOM);
- a learning-dynamics section from the on-device learn ledger's
  ``learn_signal`` events (gsc_tpu.obs.learning): per-topology
  |TD-error| table (mixed batches AND the serial path's stamped
  topology), last-episode Q distribution moments, per-layer grad-norm
  peaks + param norms, replay fill;
- an async-fleet section for ``cli train --async`` runs, from the
  run-level ``async_train`` event plus the deferred flight-recorder
  ledgers (``async_actor_ep`` / ``async_learner_spans``,
  gsc_tpu.parallel.async_rl): a per-actor table (episodes / chunks /
  steps / rollout wall / channel-blocked wall / idle fraction /
  adoptions), the learner's policy-lag percentiles and wall
  decomposition (ingest vs learn-burst vs idle), and the weight
  adoption timeline (publish -> per-actor adopt latency per version);
- a "device time by layer" section for a run made with ``cli train
  --profile``: the newest trace under ``<result_dir>/profile`` read
  through the operation-to-scope map each ``perf.json`` entry keeps
  (``op_map``; ``gsc_tpu.obs.trace.layer_times``): device seconds per
  ``jax.named_scope`` layer, whole executions of each layer and their
  device time, each program's join coverage and own idle share, the
  programs with no map, and the longest device-idle gaps under the host
  span open through them.  Reading the trace needs JAX; everything else
  here does not;
- a serving section for ``cli serve`` runs, from the ``serve_start`` /
  ``serve_stats`` events (gsc_tpu.serve.PolicyServer): tier, requests/s,
  p50/p99 latency overall and per batch bucket, bucket occupancy,
  per-bucket startup (artifact-cache hit + prepare wall), the
  latency-decomposition table (queue-wait / batch-formation wait /
  device wall / fan-out mean per bucket, from the request-path tracer),
  the SLO verdict (attainment, error-budget burn rate, deadline-miss
  ratio, arrival-rate EWMA), and the rejection + pad-waste accounting.

``--json`` emits the same summary as one machine-readable JSON object.
``--selftest`` synthesizes a stream (including a stall and a leak),
renders it, and asserts both are flagged — the CI smoke target.

Stdlib only: this must run on a login node with no JAX installed (the
device-time section alone imports the package, and JAX, to read a
trace; without them it says so).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import Dict, List, Optional

PHASES = ("host_sample", "host_sample_wait", "dispatch", "drain")
# flag growth only past an absolute floor: allocator warmup on a small run
# doubles tiny numbers without meaning anything
MEM_FLOOR_BYTES = 16 * 2 ** 20


def load_events(path: str) -> List[Dict]:
    """Accept a run dir or the events.jsonl itself; walk rotated segments
    (``--obs-rotate-mb`` writes events.jsonl.N .. .1 before the live
    file) oldest-first so the stream reads as one; skip torn tail lines
    (the stream may still be appending).

    Events come back SORTED by ``ts`` within each run_start-delimited
    slice (stable): the hub stamps ``ts`` before taking the sink lock,
    so concurrent threads can interleave out of order in the file — the
    phase-delta logic below assumes one monotone stream.  The sort is
    per-run, never global, so appended runs whose wall clock stepped
    backwards (NTP, VM resume) cannot interleave across run
    boundaries."""
    if os.path.isdir(path):
        path = os.path.join(path, "events.jsonl")
    older = []
    n = 1
    while os.path.exists(f"{path}.{n}"):
        older.append(f"{path}.{n}")
        n += 1
    segments = list(reversed(older)) + (
        [path] if os.path.exists(path) else [])
    if not segments:
        raise SystemExit(f"no events stream at {path}")
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
                    continue   # torn final line of a live run
    def _ts(e):
        ts = e.get("ts") if isinstance(e, dict) else None
        return float(ts) if isinstance(ts, (int, float)) \
            and not isinstance(ts, bool) else float("-inf")

    out, seg = [], []
    for e in events:
        if isinstance(e, dict) and e.get("event") == "run_start" and seg:
            seg.sort(key=_ts)
            out.extend(seg)
            seg = []
        seg.append(e)
    seg.sort(key=_ts)
    out.extend(seg)
    return out


def load_perf(path: str) -> Optional[Dict]:
    """The run's cost ledger (``perf.json``, gsc_tpu.obs.perf) if one was
    written next to the event stream; None otherwise."""
    if not os.path.isdir(path):
        path = os.path.dirname(os.path.abspath(path))
    p = os.path.join(path, "perf.json")
    if not os.path.exists(p):
        return None
    try:
        with open(p) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def layer_summary(path: str, perf: Optional[Dict]) -> Optional[Dict]:
    """Device time by layer of a run made with ``cli train --profile``:
    the newest trace under ``<result_dir>/profile`` joined to the
    ``op_map`` of every ``perf.json`` entry.  None without a trace or
    without a ledger; ``{"error": ...}`` where the trace cannot be read
    (JAX reads it) or holds no chip's operations."""
    run_dir = path if os.path.isdir(path) else \
        os.path.dirname(os.path.abspath(path))
    profile = os.path.join(run_dir, "profile")
    maps = [e.get("op_map") for e in
            ((perf or {}).get("entries") or {}).values() if e]
    if not os.path.isdir(profile) or not any(maps):
        return None
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    try:
        from gsc_tpu.obs.trace import find_profile, layer_times, \
            load_profile
        xplane = find_profile(profile)
        loaded = load_profile(xplane)
    except FileNotFoundError:
        return None
    except ImportError as e:
        return {"error": f"{type(e).__name__}: {e}"}
    if not loaded["devices"]:
        return {"error": "the trace holds no chip plane (a CPU run)"}
    return {"trace": os.path.relpath(xplane, run_dir),
            **layer_times(loaded, maps)}


def phase_deltas(episodes: List[Dict]) -> List[Dict[str, float]]:
    """Per-episode phase seconds from the cumulative totals each episode
    event carries."""
    out, prev = [], {}
    for ev in episodes:
        totals = {name: info.get("total_s", 0.0)
                  for name, info in (ev.get("phases") or {}).items()}
        out.append({name: round(t - prev.get(name, 0.0), 4)
                    for name, t in totals.items()})
        prev = totals
    return out


def device_mem_series(episodes: List[Dict]) -> Dict[str, List[int]]:
    """{device: [bytes_in_use per episode]} over devices that report."""
    series: Dict[str, List[int]] = {}
    for ev in episodes:
        for rec in ev.get("device_memory") or []:
            if "bytes_in_use" in rec:
                series.setdefault(rec["device"], []).append(
                    rec["bytes_in_use"])
    return series


def last_run(events: List[Dict]) -> List[Dict]:
    """The JSONL sink appends, so a reused --obs-dir accumulates several
    runs in one stream; summarize the LAST one (mixing runs would produce
    negative phase deltas and interleaved episode numbers)."""
    starts = [i for i, e in enumerate(events)
              if e.get("event") == "run_start"]
    return events[starts[-1]:] if starts else events


def compile_summary(events: List[Dict],
                    retrace_threshold: int = 3) -> Dict:
    """Per-entry-point jit trace/compile totals from ``compile`` events,
    plus the names whose trace count exceeds the churn threshold."""
    per_fn: Dict[str, Dict] = {}
    for ev in events:
        if ev.get("event") != "compile":
            continue
        fn = ev.get("fn", "?")
        rec = per_fn.setdefault(
            fn, {"traces": 0, "xla_compiles": 0, "compile_s": 0.0})
        # compile_s totals BOTH stages: tracing+transform wall is often
        # the dominant share for large fused programs
        if ev.get("stage") == "trace":
            rec["traces"] += 1
            rec["compile_s"] = round(
                rec["compile_s"] + float(ev.get("duration_s") or 0.0), 4)
        elif ev.get("stage") == "xla":
            rec["xla_compiles"] += 1
            rec["compile_s"] = round(
                rec["compile_s"] + float(ev.get("duration_s") or 0.0), 4)
    flags = sorted(fn for fn, rec in per_fn.items()
                   if rec["traces"] > retrace_threshold)
    return {"per_fn": per_fn, "retrace_flags": flags}


def perf_summary(perf: Optional[Dict]) -> Optional[Dict]:
    """Condense a perf.json cost ledger for the report: one row per
    watched entry point (FLOPs, bytes, fusions, MFU, roofline regime,
    per-dispatch wall) plus the phase split and schema version."""
    if not perf:
        return None
    rows = {}
    for name, e in sorted((perf.get("entries") or {}).items()):
        if not (e or {}).get("available"):
            rows[name] = {"available": False, "error": (e or {}).get("error")}
            continue
        roof = e.get("roofline") or {}
        col = e.get("collectives") or {}
        rows[name] = {
            "flops": e.get("flops"),
            "bytes_accessed": e.get("bytes_accessed"),
            "fusions": e.get("fusions"),
            "dispatches": e.get("dispatches"),
            "wall_ms_mean": (round(1e3 * e["wall_s_mean"], 3)
                             if e.get("wall_s_mean") is not None else None),
            "mfu": e.get("mfu"),
            "regime": roof.get("regime"),
            "roof_multiple": roof.get("roof_multiple"),
            # cross-device movers per call (partitioned executables
            # only; 0 on single-device programs, absent on pre-PR13
            # ledgers) — the tp-vs-sharded interconnect columns
            "collective_count": col.get("count"),
            "collective_bytes": col.get("bytes"),
        }
    phases = perf.get("phases") or {}
    dispatch_s = (phases.get("dispatch") or {}).get("total_s") or 0.0
    host_s = sum((info or {}).get("total_s") or 0.0
                 for name, info in phases.items() if name != "dispatch")
    return {
        "schema_version": perf.get("schema_version"),
        "backend": perf.get("backend"),
        "device_kind": perf.get("device_kind"),
        "peaks": perf.get("peaks"),
        "entries": rows,
        # device-vs-host split: dispatch wall is time handing work to the
        # device (covers device compute on a saturated pipeline), the
        # rest is host-side sampling/draining
        "device_vs_host": {"dispatch_s": round(dispatch_s, 4),
                           "host_s": round(host_s, 4)},
    }


def summarize(events: List[Dict], mem_growth_threshold: float = 0.2,
              retrace_threshold: int = 3,
              perf: Optional[Dict] = None) -> Dict:
    runs_in_stream = max(
        sum(1 for e in events if e.get("event") == "run_start"), 1)
    events = last_run(events)
    episodes = [e for e in events if e.get("event") == "episode"]
    stalls = [e for e in events if e.get("event") == "stall"]
    violations = [e for e in events
                  if e.get("event") == "invariant_violation"]
    recoveries = [e for e in events if e.get("event") == "recovery"]
    escalations = [e for e in events if e.get("event") == "escalation"]
    deltas = phase_deltas(episodes)

    rows = []
    for ev, d in zip(episodes, deltas):
        mem = [r.get("bytes_in_use") for r in (ev.get("device_memory") or [])
               if "bytes_in_use" in r]
        rows.append({
            "episode": ev.get("episode"),
            "sps": ev.get("sps"),
            "return": ev.get("episodic_return"),
            "succ": ev.get("mean_succ_ratio"),
            "critic_loss": ev.get("critic_loss"),
            "actor_loss": ev.get("actor_loss"),
            **{f"{p}_ms": round(1e3 * d.get(p, 0.0), 1) for p in PHASES
               if p in d},
            "trunc": ev.get("truncated_arrivals", 0),
            "drops": sum((ev.get("drop_reasons") or {}).values()),
            "mem_mb": round(sum(mem) / 2 ** 20, 1) if mem else None,
        })

    phase_summary = {}
    if episodes:
        final = episodes[-1].get("phases") or {}
        for name, info in sorted(final.items()):
            phase_summary[name] = {
                "total_s": info.get("total_s"),
                "count": info.get("count"),
                "mean_ms": info.get("mean_ms"),
            }

    # HBM-data availability: distinguish "no allocator stats on this
    # backend" (CPU memory_stats() is None) from "usage was flat" — the
    # device records carry available/backend either way
    mem_unavailable = sorted({
        rec.get("backend", "unknown")
        for ev in episodes for rec in (ev.get("device_memory") or [])
        if rec.get("available") is False})
    mem_flags = []
    for device, series in device_mem_series(episodes).items():
        if len(series) < 2:
            continue
        first, last = series[0], series[-1]
        growth = (last - first) / max(first, 1)
        if last - first > MEM_FLOOR_BYTES and growth > mem_growth_threshold:
            mem_flags.append({
                "device": device,
                "first_bytes": first, "last_bytes": last,
                "growth_pct": round(100 * growth, 1),
            })

    last_run_end = next((e for e in reversed(events)
                         if e.get("event") == "run_end"), None)
    # dtype-policy header fields: the trainer emits one `precision` event
    # per run (RunObserver.record_precision); run_start meta carries the
    # policy name too — either suffices for the header
    precision_ev = next((e for e in events
                         if e.get("event") == "precision"), None)
    run_start = next((e for e in events
                      if e.get("event") == "run_start"), None)
    precision = None
    if precision_ev is not None:
        precision = {k: precision_ev.get(k)
                     for k in ("name", "param_dtype", "gnn_compute",
                               "mlp_compute", "replay_dtype")}
    elif run_start is not None and run_start.get("precision"):
        precision = {"name": run_start["precision"]}
    # engine-knob header field (run_start meta, cli train): the
    # scan-unroll factor the run was built with, so a throughput
    # comparison across runs attributes the engine share
    engine = None
    if run_start is not None and run_start.get("unroll") is not None:
        engine = {"unroll": run_start["unroll"]}
    # mesh header fields (run_start meta, cli train --mesh): the DPxMP
    # carving, the partition rulebook and the compact per-leaf spec
    # counts, so a multi-chip run's layout is readable off the report
    mesh = None
    if run_start is not None and run_start.get("mesh"):
        mesh = {"mesh": run_start["mesh"],
                "partition_rules": run_start.get("partition_rules"),
                "partition_specs": run_start.get("partition_specs") or {}}
    # mixed-topology section (cli train --topo-mix): harness_episode
    # events carry per-topology mean returns when the batch is a mixture
    # — aggregated here per network name so a collapsing mixture member
    # is readable off the report, not buried in replica vectors.
    # Single-replica runs stamp a `topology` field on their episode
    # events instead (the serial trainer path) — merged into the SAME
    # table, so homogeneous and mixed runs report through one surface.
    topo_mix = (run_start or {}).get("topo_mix")
    per_topology = {}

    def _topo_rec(name):
        return per_topology.setdefault(
            name, {"episodes": 0, "sum": 0.0, "last": None})

    for ev in events:
        if ev.get("event") == "harness_episode":
            for name, v in (ev.get("per_topology_return") or {}).items():
                rec = _topo_rec(name)
                rec["episodes"] += 1
                rec["sum"] += float(v)
                rec["last"] = float(v)
        elif ev.get("event") == "episode" and ev.get("topology") \
                and isinstance(ev.get("episodic_return"), (int, float)):
            rec = _topo_rec(str(ev["topology"]))
            rec["episodes"] += 1
            rec["sum"] += float(ev["episodic_return"])
            rec["last"] = float(ev["episodic_return"])
    per_topology = {
        name: {"episodes": r["episodes"],
               "mean_return": round(r["sum"] / max(r["episodes"], 1), 3),
               "last_return": round(r["last"], 3)}
        for name, r in per_topology.items()}
    # learning-dynamics section (the on-device learn ledger,
    # gsc_tpu.obs.learning): per-topology |TD-error|, Q distribution
    # moments, per-layer grad/param norm health, replay fill — one
    # learn_signal event per drained episode
    learning = _learning_summary(
        [e for e in events if e.get("event") == "learn_signal"])
    # async-fleet section (cli train --async): the run-level async_train
    # info event plus the deferred flight-recorder ledgers
    async_fleet = _async_summary(events)
    # serving section (cli serve runs): the final serve_stats event holds
    # the cumulative numbers; serve_start carries startup + cache hits
    serve_start = next((e for e in events
                        if e.get("event") == "serve_start"), None)
    serve_stats = [e for e in events if e.get("event") == "serve_stats"]
    serving = None
    if serve_start is not None or serve_stats:
        # headline numbers come from the last NON-worker stats record
        # when one exists (single-server runs); in a fleet every
        # serve_stats is worker-tagged, so the shared-histogram numbers
        # (p50/p99/rps) are fleet-wide on any of them while the request
        # total comes from fleet_stats below
        untagged = [e for e in serve_stats if not e.get("worker")]
        if untagged:
            last = untagged[-1]
        elif serve_stats:
            # fleet run: prefer a real worker's record over the spr
            # brownout tier's (it closes last, and its tier/SLO would
            # mislabel a learned fleet's headline)
            non_spr = [e for e in serve_stats if e.get("worker") != "spr"]
            last = (non_spr or serve_stats)[-1]
        else:
            last = {}
        # fleet view (cli serve --workers N): per-worker final stats
        # (each worker's serve_stats carry worker= + worker-local
        # requests/occupancy), the fleet_stats total record, and the
        # hot-swap timeline from weight_swap events
        per_worker: Dict[str, Dict] = {}
        for ev in serve_stats:
            if ev.get("worker"):
                per_worker[ev["worker"]] = {
                    "requests": ev.get("worker_requests",
                                       ev.get("requests")),
                    "occupancy": ev.get("occupancy") or {},
                    "queue_depth": ev.get("queue_depth"),
                    "policy_version": ev.get("policy_version", 0),
                    "swaps": ev.get("swaps", 0),
                }
        fleet_stats = next((e for e in reversed(events)
                            if e.get("event") == "fleet_stats"), None)
        swap_timeline = [
            {"worker": ev.get("worker"), "version": ev.get("version"),
             "ts": ev.get("ts"), "swap_ms": ev.get("swap_ms"),
             "requests_in_flight": ev.get("requests_in_flight"),
             "weights_applied": ev.get("weights_applied")}
            for ev in events if ev.get("event") == "weight_swap"]
        serving = {
            "tier": last.get("tier") or (serve_start or {}).get("tier"),
            "requests": last.get("requests"),
            "rps": last.get("rps"),
            "p50_ms": last.get("p50_ms"),
            "p99_ms": last.get("p99_ms"),
            "queue_depth": last.get("queue_depth"),
            "occupancy": last.get("occupancy") or {},
            "buckets": last.get("buckets") or {},
            "startup_s": (serve_start or {}).get("startup_s"),
            "bucket_prepare": (serve_start or {}).get("bucket_prepare")
            or {},
            # request-path tracing + SLO engine (gsc_tpu.obs.slo): the
            # final serve_stats carries the per-bucket latency split,
            # the SLO snapshot and the rejection totals when tracing ran
            "decomposition": last.get("decomposition") or {},
            "slo": last.get("slo"),
            "rejected": last.get("rejected") or {},
            "workers": per_worker,
            "fleet": fleet_stats,
            "swap_timeline": swap_timeline,
        }
        if fleet_stats is not None and not untagged:
            # fleet run: the request total, merged SLO verdict and
            # merged occupancy are the fleet's, not the last-reporting
            # worker's
            serving["requests"] = fleet_stats.get("requests",
                                                  serving["requests"])
            if fleet_stats.get("slo"):
                serving["slo"] = fleet_stats["slo"]
            merged_occ: Dict[str, int] = {}
            for rec in per_worker.values():
                for b, n in (rec.get("occupancy") or {}).items():
                    merged_occ[b] = merged_occ.get(b, 0) + int(n)
            if merged_occ:
                serving["occupancy"] = merged_occ
    return {
        "episodes": len(episodes),
        "run": (episodes[0].get("run") if episodes
                else (serve_start or {}).get("run")),
        "serving": serving,
        "runs_in_stream": runs_in_stream,
        "status": (last_run_end or {}).get("status"),
        "precision": precision,
        "engine": engine,
        "mesh": mesh,
        "topo_mix": topo_mix,
        "per_topology": per_topology,
        "learning": learning,
        "async_fleet": async_fleet,
        "rows": rows,
        "phase_summary": phase_summary,
        "stalls": stalls,
        "invariant_violations": violations,
        "recoveries": recoveries,
        "escalations": escalations,
        "recovery_totals": _recovery_totals(recoveries),
        "memory_growth_flags": mem_flags,
        "memory_unavailable_backends": mem_unavailable,
        "drop_totals": _drop_totals(episodes),
        "compiles": compile_summary(events, retrace_threshold),
        "perf": perf_summary(perf),
    }


def _learning_summary(learn_events: List[Dict]) -> Optional[Dict]:
    """Condense the per-episode ``learn_signal`` stream: per-topology
    |TD| means, first->last overall |TD|, the last episode's Q moments,
    per-layer grad-norm peaks (exploding gradients show as a peak far
    above the last value) + last param norms, and replay fill."""
    if not learn_events:
        return None
    per_topo: Dict[str, Dict] = {}
    grad_peak: Dict[str, float] = {}
    td_series = []
    for ev in learn_events:
        for name, v in (ev.get("per_topology_td") or {}).items():
            rec = per_topo.setdefault(
                name, {"episodes": 0, "sum": 0.0, "last": None})
            rec["episodes"] += 1
            rec["sum"] += float(v)
            rec["last"] = float(v)
        for layer, v in (ev.get("grad_norms") or {}).items():
            if isinstance(v, (int, float)):
                grad_peak[layer] = max(grad_peak.get(layer, 0.0), float(v))
        if isinstance(ev.get("td_abs_mean"), (int, float)):
            td_series.append(float(ev["td_abs_mean"]))
    last = learn_events[-1]
    return {
        "episodes": len(learn_events),
        "per_topology_td": {
            name: {"episodes": r["episodes"],
                   "mean_td_abs": round(r["sum"] / max(r["episodes"], 1), 6),
                   "last_td_abs": round(r["last"], 6)}
            for name, r in per_topo.items()},
        "td_abs_first": td_series[0] if td_series else None,
        "td_abs_last": td_series[-1] if td_series else None,
        "q_last": {k: last.get(k)
                   for k in ("q_mean", "q_std", "q_min", "q_max")},
        "grad_norm_peak": {k: round(v, 6)
                           for k, v in sorted(grad_peak.items())},
        "grad_norms_last": last.get("grad_norms") or {},
        "param_norms_last": last.get("param_norms") or {},
        "replay_fill_last": (last.get("replay") or {}).get("fill"),
    }


def _async_summary(events: List[Dict]) -> Optional[Dict]:
    """Condense the async-fleet flight-recorder records: the run-level
    ``async_train`` info event plus the deferred ``async_actor_ep`` /
    ``async_learner_spans`` ledgers (gsc_tpu.parallel.async_rl).  Three
    views: a per-actor table (episodes / chunks / steps / rollout wall /
    channel-blocked wall / idle fraction / adoptions), the learner's
    lag + wall decomposition (ingest vs learn-burst vs idle), and the
    weight adoption timeline (publish -> per-actor adopt latency per
    version)."""
    info = next((e for e in reversed(events)
                 if e.get("event") == "async_train"), None)
    actor_eps = [e for e in events if e.get("event") == "async_actor_ep"]
    spans = [e for e in events
             if e.get("event") == "async_learner_spans"]
    if info is None and not actor_eps and not spans:
        return None
    fracs = (info or {}).get("actor_idle_fracs") or []
    per_actor: Dict[int, Dict] = {}
    adopts_by_ver: Dict[int, Dict[int, float]] = {}
    for ev in actor_eps:
        aid = int(ev.get("actor", 0))
        rec = per_actor.setdefault(aid, {
            "episodes": 0, "chunks": 0, "steps": 0, "rollout_s": 0.0,
            "blocked_s": 0.0, "adopts": 0, "last_version": 0})
        rec["episodes"] += 1
        for c in ev.get("chunks") or []:
            rec["chunks"] += 1
            rec["rollout_s"] += float(c[1]) - float(c[0])
        for p in ev.get("puts") or []:
            rec["blocked_s"] += float(p[1])
            rec["steps"] += int(p[2])
        for a in ev.get("adopts") or []:
            rec["adopts"] += 1
            ver = int(a[1])
            rec["last_version"] = max(rec["last_version"], ver)
            prev = adopts_by_ver.setdefault(ver, {}).get(aid)
            ts = float(a[0])
            if prev is None or ts < prev:
                adopts_by_ver[ver][aid] = ts
    for aid, rec in per_actor.items():
        rec["rollout_s"] = round(rec["rollout_s"], 4)
        rec["blocked_s"] = round(rec["blocked_s"], 4)
        if aid < len(fracs):
            rec["idle_frac"] = fracs[aid]
    ingest_s = burst_s = 0.0
    n_ingests = n_bursts = 0
    lags: List[int] = []
    publishes: Dict[int, float] = {}
    for ev in spans:
        for r in ev.get("ingests") or []:
            n_ingests += 1
            ingest_s += float(r[1]) - float(r[0])
            lags.append(int(r[4]))
        for r in ev.get("bursts") or []:
            n_bursts += 1
            burst_s += float(r[1]) - float(r[0])
        for r in ev.get("publishes") or []:
            ver, ts = int(r[1]), float(r[0])
            if ver not in publishes or ts < publishes[ver]:
                publishes[ver] = ts
    timeline = []
    for ver in sorted(publishes):
        timeline.append({
            "version": ver, "publish_ts": publishes[ver],
            "adopt_lag_s": {
                aid: round(ts - publishes[ver], 4)
                for aid, ts in sorted(
                    (adopts_by_ver.get(ver) or {}).items())}})
    orphan_adopts = sorted(v for v in adopts_by_ver if v not in publishes)
    wall = (info or {}).get("wall_s")
    idle_s = (info or {}).get("learner_idle_s")
    decomposition = {
        "ingest_s": round(ingest_s, 4), "n_ingests": n_ingests,
        "burst_s": round(burst_s, 4), "n_bursts": n_bursts,
        "idle_s": idle_s,
        # the remainder is scheduling + publish + drain overhead — a
        # learner whose wall is neither ingesting, learning nor idling
        # is losing time to the loop itself
        "other_s": (round(wall - ingest_s - burst_s - idle_s, 4)
                    if isinstance(wall, (int, float))
                    and isinstance(idle_s, (int, float)) else None),
    }
    lag = {
        "samples": len(lags),
        "max": max(lags) if lags else 0,
        "mean": (round(sum(lags) / len(lags), 4) if lags else 0.0),
    }
    if info:
        for k in ("policy_lag_p50", "policy_lag_p99", "policy_lag_max",
                  "policy_lag_mean"):
            if isinstance(info.get(k), (int, float)):
                lag[k.replace("policy_lag_", "")] = info[k]
    return {
        "info": {k: info.get(k) for k in (
            "actors", "episodes_drained", "produced_steps",
            "ingested_steps", "transitions_lost", "bursts", "publishes",
            "published_version", "wall_s", "learner_idle_frac",
            "actor_idle_frac")} if info else None,
        "per_actor": per_actor,
        "lag": lag,
        "decomposition": decomposition,
        "adoption_timeline": timeline,
        "orphan_adopt_versions": orphan_adopts,
    }


def _recovery_totals(recoveries: List[Dict]) -> Dict[str, int]:
    """``{"site/action": count}`` over the recovery timeline."""
    totals: Dict[str, int] = {}
    for ev in recoveries:
        key = f"{ev.get('site', '?')}/{ev.get('action', '?')}"
        totals[key] = totals.get(key, 0) + 1
    return totals


def _drop_totals(episodes: List[Dict]) -> Dict[str, int]:
    totals: Dict[str, int] = {}
    for ev in episodes:
        for reason, n in (ev.get("drop_reasons") or {}).items():
            totals[reason] = totals.get(reason, 0) + int(n)
    return totals


def _fmt(v, width) -> str:
    if v is None:
        s = "-"
    elif isinstance(v, float):
        s = f"{v:.3f}" if abs(v) < 1000 else f"{v:.0f}"
    else:
        s = str(v)
    return s.rjust(width)


def _render_layers(layers: Dict, w) -> None:
    """The "device time by layer" section (:func:`layer_summary`)."""
    w("\ndevice time by layer")
    if layers.get("error"):
        w(f": trace not read ({layers['error']})\n")
        return
    w(f" ({layers.get('trace')}):\n")
    scopes = layers.get("scopes") or {}
    inner = scopes.get("innermost") or {}
    incl = scopes.get("inclusive") or {}
    w(f"  {'scope':<18} {'self_ms':>10} {'with_nested_ms':>15}\n")
    for name in sorted(incl, key=lambda n: -incl[n]):
        w(f"  {name:<18} {1e3 * inner.get(name, 0.0):>10.3f} "
          f"{1e3 * incl[name]:>15.3f}\n")
    unscoped = 1e3 * (scopes.get("unscoped") or 0.0)
    w(f"  {'unscoped':<18} {unscoped:>10.3f}\n")
    for kind in ("unmatched", "unmapped"):
        for module, sec in sorted((scopes.get(kind) or {}).items()):
            w(f"  {kind} {module}: {1e3 * sec:.3f} ms\n")
    execs = layers.get("executions") or {}
    if execs:
        w(f"  {'whole executions':<40} {'count':>7} {'ms_each':>10}\n")
        for path, rec in sorted(execs.items()):
            w(f"  {path:<40} {rec['executions']:>7} "
              f"{1e3 * rec['per_execution_s']:>10.4f}\n")
    for module, share in sorted((layers.get("coverage") or {}).items()):
        prog = (layers.get("programs") or {}).get(module) or {}
        idle = (100 * (1 - prog["busy_s"] / prog["span_s"])
                if prog.get("span_s") else None)
        w(f"  {module}: join coverage {100 * share:.2f}%, "
          f"{prog.get('executions', 0)} executions, idle inside "
          f"{'-' if idle is None else f'{idle:.2f}%'}\n")
    gaps = layers.get("idle_spans") or []
    if gaps:
        w("  longest device-idle gaps (host span open): "
          + ", ".join(f"{name} {1e3 * sec:.3f} ms" for name, sec in gaps)
          + "\n")


def render_text(summary: Dict, out=sys.stdout):
    w = out.write
    w(f"run: {summary['run']}  episodes: {summary['episodes']}  "
      f"status: {summary['status']}\n")
    perf = summary.get("perf")
    if perf:
        w(f"perf ledger: schema v{perf.get('schema_version')}  "
          f"backend {perf.get('backend')}\n")
    prec = summary.get("precision")
    if prec:
        detail = ""
        if prec.get("param_dtype"):
            detail = (f"  (param {prec['param_dtype']} / gnn "
                      f"{prec.get('gnn_compute')} / mlp "
                      f"{prec.get('mlp_compute')} / replay "
                      f"{prec.get('replay_dtype')})")
        w(f"precision: {prec.get('name')}{detail}\n")
    eng = summary.get("engine")
    if eng:
        w(f"substep unroll: {eng.get('unroll')}\n")
    mesh = summary.get("mesh")
    if mesh:
        specs = mesh.get("partition_specs") or {}
        spec_txt = ", ".join(f"{k} x{v}" for k, v in specs.items())
        w(f"mesh: {mesh.get('mesh')}  rules: "
          f"{mesh.get('partition_rules')}"
          + (f"  ({spec_txt})" if spec_txt else "") + "\n")
    if summary.get("topo_mix"):
        w(f"topo mix: {summary['topo_mix']}\n")
    if summary.get("runs_in_stream", 1) > 1:
        w(f"(stream holds {summary['runs_in_stream']} appended runs — "
          "showing the last)\n")
    sv = summary.get("serving")
    if sv:
        w(f"\nserving ({sv.get('tier')} tier): "
          f"{sv.get('requests')} requests  {sv.get('rps')} req/s  "
          f"p50 {sv.get('p50_ms')} ms  p99 {sv.get('p99_ms')} ms  "
          f"startup {sv.get('startup_s')}s\n")
        buckets = set(sv.get("buckets", {})) | set(sv.get("occupancy", {})) \
            | set(sv.get("bucket_prepare", {}))
        for b in sorted(buckets, key=int):
            lat = sv.get("buckets", {}).get(b, {})
            prep = sv.get("bucket_prepare", {}).get(b, {})
            w(f"  bucket {b:>4}: occupancy "
              f"{sv.get('occupancy', {}).get(b, 0):>6}   "
              f"p50 {lat.get('p50_ms', '-'):>8} ms   "
              f"p99 {lat.get('p99_ms', '-'):>8} ms   "
              f"cache_hit {str(prep.get('cache_hit', '-')):<5} "
              f"prepare {prep.get('prepare_s', '-')}s\n")
        slo = sv.get("slo")
        if slo:
            w(f"  SLO: p99 target {_fmt(slo.get('p99_target_ms'), 1)} ms  "
              f"attainment {_fmt(slo.get('attainment'), 1)}  "
              f"budget burn {_fmt(slo.get('burn_rate'), 1)}x  "
              f"deadline-miss {_fmt(slo.get('deadline_miss_ratio'), 1)}  "
              f"arrival {_fmt(slo.get('arrival_rate_rps'), 1)} rps\n")
            w(f"  pad waste {_fmt(slo.get('pad_waste'), 1)}  "
              f"queue-wait fraction "
              f"{_fmt(slo.get('queue_wait_frac'), 1)}\n")
        if sv.get("rejected"):
            rej = sv["rejected"]
            w("  rejected: " + "  ".join(
                f"{reason} {n}" for reason, n in sorted(rej.items()))
              + "\n")
        if sv.get("decomposition"):
            w("  latency decomposition (ms mean per bucket: queue-wait /"
              " batch-formation / device / fan-out):\n")
            w(f"  {'bucket':>8} {'queue_ms':>10} {'batch_ms':>10} "
              f"{'device_ms':>10} {'fanout_ms':>10}\n")
            for b in sorted(sv["decomposition"], key=int):
                row = sv["decomposition"][b]
                w(f"  {b:>8} {_fmt(row.get('queue_ms'), 10)} "
                  f"{_fmt(row.get('batch_ms'), 10)} "
                  f"{_fmt(row.get('device_ms'), 10)} "
                  f"{_fmt(row.get('fanout_ms'), 10)}\n")
        if sv.get("workers"):
            fl = sv.get("fleet") or {}
            head = f"\n  fleet: {len(sv['workers'])} worker(s)"
            if fl:
                head += (f"  {fl.get('requests')} requests total  "
                         f"{fl.get('swaps')} hot-swap(s)")
                brown = fl.get("brownout") or {}
                if any(brown.values()):
                    head += "  brownout: " + "  ".join(
                        f"{reason} {n}"
                        for reason, n in sorted(brown.items()) if n)
            w(head + "\n")
            w(f"  {'worker':>8} {'requests':>9} {'queue':>6} "
              f"{'version':>8} {'swaps':>6} {'occupancy':<24}\n")
            for name in sorted(sv["workers"]):
                rec = sv["workers"][name]
                occ = " ".join(f"b{b}:{n}" for b, n in
                               sorted((rec.get("occupancy") or {}).items(),
                                      key=lambda kv: int(kv[0])))
                w(f"  {name:>8} {_fmt(rec.get('requests'), 9)} "
                  f"{_fmt(rec.get('queue_depth'), 6)} "
                  f"{_fmt(rec.get('policy_version'), 8)} "
                  f"{_fmt(rec.get('swaps'), 6)} {occ:<24}\n")
        if sv.get("swap_timeline"):
            w("  hot-swap timeline (version @ wall, requests in flight "
              "at the swap):\n")
            t00 = sv["swap_timeline"][0].get("ts") or 0.0
            for s in sv["swap_timeline"]:
                dt = (s.get("ts") or 0.0) - t00
                w(f"    +{dt:7.3f}s  v{s.get('version')}"
                  f"  worker {s.get('worker') or '-':<5}"
                  f"  in-flight {_fmt(s.get('requests_in_flight'), 3)}"
                  f"  swap {_fmt(s.get('swap_ms'), 1)} ms"
                  + ("" if s.get("weights_applied", True)
                     else "  (version stamp only)") + "\n")
    rows = summary["rows"]
    if rows:
        w("(*_ms columns are phase-wall deltas between consecutive "
          "episode events; on pipelined runs the deferred drain shifts "
          "attribution one row — totals below are exact)\n")
        cols = list(rows[0].keys())
        widths = {c: max(len(c), 9) for c in cols}
        w("  ".join(c.rjust(widths[c]) for c in cols) + "\n")
        for r in rows:
            w("  ".join(_fmt(r.get(c), widths[c]) for c in cols) + "\n")
    if summary.get("per_topology"):
        w("\nper-topology returns (mixed batch, mean over the topology's "
          "replicas):\n")
        w(f"  {'topology':<28} {'episodes':>8} {'mean_return':>12} "
          f"{'last_return':>12}\n")
        for name, rec in sorted(summary["per_topology"].items()):
            w(f"  {name:<28} {rec['episodes']:>8} "
              f"{rec['mean_return']:>12} {rec['last_return']:>12}\n")
    ln = summary.get("learning")
    if ln:
        w(f"\nlearning dynamics (on-device learn ledger, "
          f"{ln['episodes']} episode(s)):\n")
        w(f"  |TD| mean: {ln.get('td_abs_first')} -> "
          f"{ln.get('td_abs_last')}   Q last: "
          f"mean {ln['q_last'].get('q_mean')}  std "
          f"{ln['q_last'].get('q_std')}  min {ln['q_last'].get('q_min')}  "
          f"max {ln['q_last'].get('q_max')}   replay fill "
          f"{ln.get('replay_fill_last')}\n")
        if ln.get("per_topology_td"):
            w(f"  {'topology':<28} {'episodes':>8} {'mean_|TD|':>12} "
              f"{'last_|TD|':>12}\n")
            for name, rec in sorted(ln["per_topology_td"].items()):
                w(f"  {name:<28} {rec['episodes']:>8} "
                  f"{rec['mean_td_abs']:>12} {rec['last_td_abs']:>12}\n")
        if ln.get("grad_norm_peak"):
            w("  grad/param health (peak grad norm | last grad | "
              "last param, per layer):\n")
            for layer in sorted(ln["grad_norm_peak"]):
                w(f"    {layer:<28} peak {ln['grad_norm_peak'][layer]:>12} "
                  f" last {_fmt(ln['grad_norms_last'].get(layer), 12)} "
                  f" param {_fmt(ln['param_norms_last'].get(layer), 12)}\n")
    af = summary.get("async_fleet")
    if af:
        inf = af.get("info") or {}
        w(f"\nasync fleet ({inf.get('actors', '?')} actor(s), wall "
          f"{inf.get('wall_s', '?')}s): produced "
          f"{inf.get('produced_steps', '?')} steps, ingested "
          f"{inf.get('ingested_steps', '?')}, lost "
          f"{inf.get('transitions_lost', '?')}; "
          f"{inf.get('bursts', '?')} burst(s), "
          f"{inf.get('publishes', '?')} publish(es) "
          f"(last v{inf.get('published_version', '?')})\n")
        lag = af.get("lag") or {}
        w(f"  policy lag (versions): mean {_fmt(lag.get('mean'), 1)}  "
          f"p50 {_fmt(lag.get('p50'), 1)}  p99 {_fmt(lag.get('p99'), 1)}  "
          f"max {_fmt(lag.get('max'), 1)}  "
          f"({lag.get('samples', 0)} ingest(s))\n")
        dec = af.get("decomposition") or {}
        w(f"  learner wall: ingest {_fmt(dec.get('ingest_s'), 1)}s "
          f"({dec.get('n_ingests')}x)  learn-burst "
          f"{_fmt(dec.get('burst_s'), 1)}s ({dec.get('n_bursts')}x)  "
          f"idle {_fmt(dec.get('idle_s'), 1)}s "
          f"(frac {_fmt(inf.get('learner_idle_frac'), 1)})  "
          f"other {_fmt(dec.get('other_s'), 1)}s\n")
        if af.get("per_actor"):
            w(f"  {'actor':>6} {'episodes':>8} {'chunks':>7} {'steps':>8} "
              f"{'rollout_s':>10} {'blocked_s':>10} {'idle_frac':>10} "
              f"{'adopts':>7} {'last_v':>7}\n")
            for aid in sorted(af["per_actor"]):
                rec = af["per_actor"][aid]
                w(f"  {aid:>6} {_fmt(rec.get('episodes'), 8)} "
                  f"{_fmt(rec.get('chunks'), 7)} "
                  f"{_fmt(rec.get('steps'), 8)} "
                  f"{_fmt(rec.get('rollout_s'), 10)} "
                  f"{_fmt(rec.get('blocked_s'), 10)} "
                  f"{_fmt(rec.get('idle_frac'), 10)} "
                  f"{_fmt(rec.get('adopts'), 7)} "
                  f"{_fmt(rec.get('last_version'), 7)}\n")
        if af.get("adoption_timeline"):
            w("  adoption timeline (publish wall offset; per-actor "
              "adopt lag after the publish):\n")
            t00 = af["adoption_timeline"][0].get("publish_ts") or 0.0
            for rec in af["adoption_timeline"]:
                dt = (rec.get("publish_ts") or 0.0) - t00
                adopters = rec.get("adopt_lag_s") or {}
                tail = "  ".join(
                    f"actor{aid} +{adopters[aid]:.3f}s"
                    for aid in sorted(adopters)) or "(not adopted)"
                w(f"    +{dt:7.3f}s  v{rec.get('version')}  -> {tail}\n")
        if af.get("orphan_adopt_versions"):
            w("  (adopted version(s) with no recorded publish: "
              + ", ".join(f"v{v}" for v in af["orphan_adopt_versions"])
              + " — initial weights or a truncated ledger)\n")
    if perf and perf.get("entries"):
        w("\nperf (device-cost ledger, per watched entry point):\n")
        w(f"  {'entry':<20} {'flops':>12} {'bytes':>12} {'fusions':>8} "
          f"{'coll':>6} {'coll_B':>10} "
          f"{'disp':>6} {'wall_ms':>9} {'mfu':>10} {'regime':<14} "
          f"{'roof_x':>8}\n")
        for name, r in perf["entries"].items():
            if not r.get("available", True):
                w(f"  {name:<20} (cost model unavailable: "
                  f"{r.get('error')})\n")
                continue
            w(f"  {name:<20} {_fmt(r.get('flops'), 12)} "
              f"{_fmt(r.get('bytes_accessed'), 12)} "
              f"{_fmt(r.get('fusions'), 8)} "
              f"{_fmt(r.get('collective_count'), 6)} "
              f"{_fmt(r.get('collective_bytes'), 10)} "
              f"{_fmt(r.get('dispatches'), 6)} "
              f"{_fmt(r.get('wall_ms_mean'), 9)} "
              f"{r.get('mfu') if r.get('mfu') is not None else '-':>10} "
              f"{(r.get('regime') or '-'):<14} "
              f"{_fmt(r.get('roof_multiple'), 8)}\n")
        dvh = perf.get("device_vs_host") or {}
        w(f"  device-vs-host wall: dispatch {dvh.get('dispatch_s')}s / "
          f"host {dvh.get('host_s')}s\n")
    layers = summary.get("layers")
    if layers:
        _render_layers(layers, w)
    w("\nper-phase host wall (cumulative):\n")
    for name, info in summary["phase_summary"].items():
        w(f"  {name:<18} total {info['total_s']:>9}s   "
          f"count {info['count']:>5}   mean {info['mean_ms']:>8} ms\n")
    if summary["drop_totals"]:
        w("\nsim drop totals: "
          + json.dumps(summary["drop_totals"]) + "\n")
    compiles = summary.get("compiles") or {}
    if compiles.get("per_fn"):
        w("\njit compiles (retrace sentinel):\n")
        for fn, rec in sorted(compiles["per_fn"].items()):
            w(f"  {fn:<20} traces {rec['traces']:>3}   xla "
              f"{rec['xla_compiles']:>3}   compile {rec['compile_s']:>8}s\n")
    if compiles.get("retrace_flags"):
        w(f"\n!! RETRACE CHURN: {', '.join(compiles['retrace_flags'])} "
          "traced more than the steady-state budget — look for weak-type "
          "scalars or shape drift in the episode loop\n")
    if summary.get("recoveries"):
        recs = summary["recoveries"]
        w(f"\nrecovery timeline ({len(recs)} action(s); totals "
          + json.dumps(summary.get("recovery_totals", {})) + "):\n")
        for r in recs:
            line = (f"  ep {r.get('episode', '-'):>4}  "
                    f"{r.get('site', '?')}/{r.get('action', '?')}")
            if r.get("fault"):
                line += f"  fault={r['fault']}"
            if r.get("attempt") is not None:
                line += f"  attempt={r['attempt']}"
            w(line + "\n")
            if r.get("detail"):
                w(f"        {r['detail']}\n")
    for esc in summary.get("escalations") or []:
        w(f"\n!! WATCHDOG ESCALATION: quiet {esc.get('age_s')}s "
          f"(budget {esc.get('budget_s')}s x "
          f"{esc.get('quiet_periods')} periods) -> {esc.get('action')}\n")
    if summary["stalls"]:
        w(f"\n!! {len(summary['stalls'])} STALL(s):\n")
        for s in summary["stalls"]:
            w(f"  age {s.get('age_s')}s / budget {s.get('budget_s')}s — "
              f"stuck in phase {s.get('last_phase')!r} "
              f"({s.get('last_phase_state')}), dispatch-drain lag "
              f"{s.get('dispatch_drain_lag')}, "
              f"prefetch queue {s.get('prefetch_queue_depth', '-')}, "
              f"prefetcher alive {s.get('prefetcher_alive', '-')}\n")
    if summary["invariant_violations"]:
        w(f"\n!! {len(summary['invariant_violations'])} INVARIANT "
          "VIOLATION(s):\n")
        for v in summary["invariant_violations"]:
            w(f"  episode {v.get('episode')}: "
              + "; ".join(v.get("violations", [])) + "\n")
    if summary["memory_growth_flags"]:
        w("\n!! DEVICE MEMORY GROWTH:\n")
        for m in summary["memory_growth_flags"]:
            w(f"  {m['device']}: {m['first_bytes']} -> {m['last_bytes']} "
              f"bytes (+{m['growth_pct']}%)\n")
    if summary.get("memory_unavailable_backends"):
        w("\ndevice memory: no HBM data — backend(s) "
          f"{', '.join(summary['memory_unavailable_backends'])} report "
          "no allocator stats (memory_stats() is None on CPU); flat "
          "usage and missing data are NOT the same thing\n")
    if not (summary["stalls"] or summary["invariant_violations"]
            or summary["memory_growth_flags"]
            or summary.get("recoveries")
            or (summary.get("compiles") or {}).get("retrace_flags")):
        w("\nhealthy: no stalls, no invariant violations, no device "
          "memory growth, no retrace churn, no recovery actions\n")


# ------------------------------------------------------------------ selftest
def _synthetic_events(path: str, episodes: int = 5):
    """A stream with the real schema: growing cumulative phases, one stall,
    leaking device memory."""
    base = 1_000_000_000.0
    with open(path, "w") as f:
        def emit(rec):
            f.write(json.dumps(rec) + "\n")

        emit({"event": "run_start", "ts": base, "run": "selftest",
              "episodes": episodes, "precision": "bf16",
              "unroll": 2,
              "mesh": "4x2", "partition_rules": "sharded",
              "topo_mix": "schedule,abilene+bursty",
              "partition_specs": {"PartitionSpec()": 87,
                                  "PartitionSpec(None, 'mp')": 44}})
        # mixed-topology harness events: per-replica topology names +
        # per-topology mean returns ride each episode's harness record
        for ep in range(2):
            emit({"event": "harness_episode", "ts": base + ep,
                  "run": "selftest", "episode": ep,
                  "episodic_return": 1.0 + ep, "mean_succ_ratio": 0.5,
                  "final_succ_ratio": 0.5,
                  "per_replica_return": [2.0 + ep, 0.0 + ep],
                  "topology": ["abilene.graphml", "abilene+bursty"],
                  "per_topology_return": {"abilene.graphml": 2.0 + ep,
                                          "abilene+bursty": 0.0 + ep},
                  "state_finite": True})
        # the dtype-gauge event the trainer emits via record_precision
        emit({"event": "precision", "ts": base, "run": "selftest",
              "name": "bf16", "param_dtype": "float32",
              "gnn_compute": "bfloat16", "mlp_compute": "bfloat16",
              "replay_dtype": "bfloat16"})
        # retrace-sentinel events: one healthy entry point (single trace
        # + compile) and one churning (retraces every episode)
        emit({"event": "compile", "ts": base, "run": "selftest",
              "fn": "episode_step", "stage": "trace",
              "duration_s": 0.8, "count": 1})
        emit({"event": "compile", "ts": base, "run": "selftest",
              "fn": "episode_step", "stage": "xla",
              "duration_s": 2.5, "count": 1})
        for k in range(5):
            emit({"event": "compile", "ts": base + k, "run": "selftest",
                  "fn": "leaky_fn", "stage": "trace",
                  "duration_s": 0.1, "count": k + 1})
        # learn_signal events (the on-device learn ledger): per-topology
        # |TD| segments, Q moments, layer norms, replay fill — the
        # learning-dynamics section must surface the TD trend, the
        # per-layer grad-norm peak and the replay fill
        for ep in range(2):
            emit({"event": "learn_signal", "ts": base + ep + 0.5,
                  "run": "selftest", "episode": ep,
                  "td_abs_mean": 0.5 - 0.1 * ep,
                  "per_topology_td": {"abilene.graphml": 0.4,
                                      "abilene+bursty": 0.6 - 0.1 * ep},
                  "q_mean": 0.3, "q_std": 0.1, "q_min": -0.2, "q_max": 0.9,
                  "grad_norms": {"actor/Dense_0": 1.5 + ep,
                                 "critic/Dense_0": 2.0},
                  "param_norms": {"actor/Dense_0": 10.0,
                                  "critic/Dense_0": 12.0},
                  "replay": {"size": [16], "fill": 0.5,
                             "age_mean_steps": 7.5}})
        disp = drain = 0.0
        for ep in range(episodes):
            disp += 0.010
            drain += 0.002
            emit({"event": "episode", "ts": base + ep, "run": "selftest",
                  "episode": ep, "global_step": 4 * ep + 3,
                  "sps": 100.0 + ep, "episodic_return": -1.0 + 0.1 * ep,
                  # serial-path topology identity: single-replica runs
                  # stamp the scheduled network on their episode events
                  "topology": "line3.graphml",
                  "mean_succ_ratio": 0.5, "critic_loss": 0.2,
                  "actor_loss": -0.1, "q_values": 0.3,
                  "drop_reasons": {"TTL": ep, "DECISION": 0,
                                   "LINK_CAP": 0, "NODE_CAP": 1},
                  "truncated_arrivals": 0, "replay_bytes": 4096,
                  "phases": {
                      "dispatch": {"total_s": round(disp, 4),
                                   "count": ep + 1, "mean_ms": 10.0},
                      "drain": {"total_s": round(drain, 4),
                                "count": ep + 1, "mean_ms": 2.0},
                      # per-episode scenario production (the cost the
                      # on-device factory deletes) rides the generic
                      # phase columns — locked in here so the rendering
                      # never silently drops it
                      "scenario_regen": {"total_s": round(0.01 * (ep + 1),
                                                          4),
                                         "count": ep + 1,
                                         "mean_ms": 10.0}},
                  # 64 MiB -> 64+96*ep MiB: well past floor + threshold;
                  # the second device has NO allocator stats (the CPU
                  # memory_stats()=None shape) — the report must call
                  # that out instead of reading it as flat usage
                  "device_memory": [{
                      "device": "FAKE_TPU_0", "available": True,
                      "backend": "tpu",
                      "bytes_in_use": (64 + 96 * ep) * 2 ** 20,
                      "peak_bytes_in_use": 256 * 2 ** 20,
                      "bytes_limit": 16 * 2 ** 30},
                      {"device": "FAKE_CPU_0", "available": False,
                       "backend": "cpu"}]})
        emit({"event": "stall", "ts": base + episodes, "run": "selftest",
              "age_s": 12.5, "budget_s": 10.0, "last_phase": "dispatch",
              "last_phase_state": "running", "episodes_dispatched": 5,
              "episodes_drained": 4, "dispatch_drain_lag": 1,
              "heartbeats": {"episode": 12.5, "prefetcher": 0.2},
              "prefetch_queue_depth": 2, "prefetcher_alive": True})
        emit({"event": "invariant_violation", "ts": base + episodes,
              "run": "selftest", "episode": 3,
              "violations": ["negative node_load"]})
        # resilience recovery timeline: a dispatch retry and a rollback,
        # plus one watchdog escalation — the report must surface all three
        emit({"event": "recovery", "ts": base + 2, "run": "selftest",
              "episode": 1, "site": "dispatch", "action": "retry",
              "fault": "TransientDispatchError('injected')", "attempt": 1,
              "detail": "backing off 0.05s before re-dispatch"})
        emit({"event": "recovery", "ts": base + 3, "run": "selftest",
              "episode": 2, "site": "learner_state", "action": "rollback",
              "fault": "non_finite_state",
              "detail": "restored snapshot of episode 1"})
        emit({"event": "escalation", "ts": base + 4, "run": "selftest",
              "age_s": 0.8, "budget_s": 0.2, "quiet_periods": 2,
              "action": "callback"})
        # serving events (cli serve / PolicyServer): startup with one
        # cache hit + one cold bucket, then a final cumulative stats
        # record — the report must surface rps/p50/p99 and the per-bucket
        # occupancy + cache-hit pattern
        emit({"event": "serve_start", "ts": base + 5, "run": "selftest",
              "tier": "learned", "buckets": [1, 4], "deadline_ms": 5.0,
              "startup_s": 1.25,
              "bucket_prepare": {"1": {"cache_hit": True,
                                       "prepare_s": 0.2},
                                 "4": {"cache_hit": False,
                                       "prepare_s": 0.9}},
              "cache_dir": "/tmp/cache", "fingerprint": "abc"})
        # request-path tracer spans: flush slices are always recorded,
        # request spans head-sampled — the trace exporter (not this
        # report) renders them; they must round-trip the reader unharmed
        emit({"event": "serve_flush", "ts": base + 5.2, "run": "selftest",
              "flush_id": 0, "bucket": 4, "n_real": 3,
              "pad_fraction": 0.25, "device_ms": 1.5, "queue_depth": 2})
        emit({"event": "serve_request_span", "ts": base + 5.1,
              "run": "selftest", "trace_id": 0, "flush_id": 0,
              "bucket": 4, "queue_wait_ms": 0.4, "batch_wait_ms": 3.1,
              "device_ms": 1.5, "fanout_ms": 0.1, "latency_ms": 5.0,
              "deadline_miss": False})
        emit({"event": "serve_stats", "ts": base + 6, "run": "selftest",
              "tier": "learned", "final": True, "requests": 200,
              "rps": 512.5, "p50_ms": 1.2, "p99_ms": 7.9, "mean_ms": 1.9,
              "max_ms": 9.0, "queue_depth": 0,
              "occupancy": {"1": 40, "4": 160},
              "buckets": {"1": {"p50_ms": 0.9, "p99_ms": 2.0,
                                "requests": 40},
                          "4": {"p50_ms": 1.3, "p99_ms": 7.9,
                                "requests": 160}},
              # SLO engine + tracer extras (gsc_tpu.obs.slo): the final
              # stats event folds in the decomposition, SLO snapshot
              # and rejection totals — the report must surface all three
              "decomposition": {"1": {"queue_ms": 0.2, "batch_ms": 5.0,
                                      "device_ms": 0.8,
                                      "fanout_ms": 0.05},
                                "4": {"queue_ms": 0.9, "batch_ms": 2.1,
                                      "device_ms": 1.5,
                                      "fanout_ms": 0.12}},
              "slo": {"p99_target_ms": 10.0, "attainment": 0.97,
                      "burn_rate": 3.0, "deadline_miss_ratio": 0.12,
                      "deadline_misses": 24, "arrival_rate_rps": 812.0,
                      "pad_waste": 0.31, "queue_wait_frac": 0.22},
              "rejected": {"queue_full": 3, "stopping": 0}})
        # fleet view (cli serve --workers N + --hot-swap-dir): per-worker
        # final serve_stats, the hot-swap timeline, and the fleet total
        # record — the report renders the worker table + swap timeline
        emit({"event": "weight_swap", "ts": base + 5.4, "run": "selftest",
              "worker": "w0", "version": 2, "fingerprint": "def",
              "tier": "learned", "swap_ms": 0.8, "weights_applied": True,
              "requests_in_flight": 3})
        emit({"event": "weight_swap", "ts": base + 5.6, "run": "selftest",
              "worker": "w1", "version": 2, "fingerprint": "def",
              "tier": "learned", "swap_ms": 0.5, "weights_applied": True,
              "requests_in_flight": 1})
        emit({"event": "serve_stats", "ts": base + 6.1, "run": "selftest",
              "tier": "learned", "final": True, "requests": 120,
              "worker": "w0", "worker_requests": 120,
              "policy_version": 2, "swaps": 1,
              "rps": 512.5, "p50_ms": 1.2, "p99_ms": 7.9, "mean_ms": 1.9,
              "max_ms": 9.0, "queue_depth": 1,
              "occupancy": {"1": 20, "4": 100}, "buckets": {}})
        emit({"event": "serve_stats", "ts": base + 6.2, "run": "selftest",
              "tier": "learned", "final": True, "requests": 80,
              "worker": "w1", "worker_requests": 80,
              "policy_version": 2, "swaps": 1,
              "rps": 512.5, "p50_ms": 1.2, "p99_ms": 7.9, "mean_ms": 1.9,
              "max_ms": 9.0, "queue_depth": 0,
              "occupancy": {"1": 20, "4": 60}, "buckets": {}})
        emit({"event": "fleet_stats", "ts": base + 6.3, "run": "selftest",
              "final": True, "workers": ["w0", "w1"], "requests": 200,
              "swaps": 2, "brownout": {"slo_burn": 0, "overflow": 5},
              "per_worker": {}, "slo": None})
        # async-fleet flight recorder (cli train --async): the deferred
        # per-actor episode ledgers + learner spans + the run-level
        # async_train info event — the report renders the per-actor
        # table, the lag/idle decomposition and the adoption timeline
        t = base + 4
        emit({"event": "async_actor_ep", "ts": t + 1.0, "run": "selftest",
              "ep": 0, "actor": 0,
              "chunks": [[t, t + 0.1, 0], [t + 0.2, t + 0.3, 1]],
              "puts": [[t + 0.1, 0.02, 64, 0, 1],
                       [t + 0.3, 0.0, 64, 1, 3]],
              "adopts": [[t + 0.15, 1]]})
        emit({"event": "async_actor_ep", "ts": t + 1.0, "run": "selftest",
              "ep": 1, "actor": 1,
              "chunks": [[t + 0.05, 0.15 + t, 0]],
              "puts": [[t + 0.15, 0.5, 64, 0, 2]],
              "adopts": [[t + 0.4, 1]]})
        emit({"event": "async_learner_spans", "ts": t + 1.0,
              "run": "selftest", "part": 0, "parts": 1,
              "ingests": [[t + 0.11, t + 0.12, 64, 0, 0, 1],
                          [t + 0.16, t + 0.17, 64, 0, 0, 2],
                          [t + 0.31, t + 0.32, 64, 1, 1, 3]],
              "bursts": [[t + 0.12, t + 0.14, 2]],
              "publishes": [[t + 0.14, 1]]})
        emit({"event": "async_train", "ts": t + 1.1, "run": "selftest",
              "actors": 2, "episodes_drained": 2, "produced_steps": 192,
              "ingested_steps": 192, "transitions_lost": 0, "bursts": 1,
              "publishes": 1, "published_version": 1, "max_staleness": 1,
              "max_replay_lag": 64, "policy_lag_max": 1,
              "policy_lag_mean": 0.33, "policy_lag_p50": 0,
              "policy_lag_p99": 1, "wall_s": 1.0, "learner_idle_s": 0.2,
              "learner_idle_frac": 0.2,
              "actor_idle_fracs": [0.02, 0.5], "actor_idle_frac": 0.5})
        emit({"event": "run_end", "ts": base + episodes + 1,
              "run": "selftest", "status": "ok", "episodes": episodes})


def _synthetic_perf(path: str):
    """A cost-ledger document with the gsc_tpu.obs.perf schema."""
    with open(path, "w") as f:
        json.dump({
            "schema_version": 1, "ts": 1_000_000_000.0, "backend": "tpu",
            "device_kind": "TPU v5 lite", "device_count": 1,
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
            "run": "selftest",
            "entries": {
                "episode_step": {
                    "available": True, "flops": 6668188.0,
                    "bytes_accessed": 6770940.0, "fusions": 718,
                    "ops": {"while": 21, "dot": 167},
                    "collectives": {"ops": {}, "count": 0, "bytes": 0},
                    "arithmetic_intensity": 0.9848,
                    "dispatches": 5, "wall_s_total": 0.05,
                    "wall_s_mean": 0.01, "mfu": 0.0133,
                    "roofline": {"intensity": 0.9848, "ridge": 240.5,
                                 "regime": "memory_bound",
                                 "roof_multiple": 29.5}},
                "chunk_step_sharded": {
                    "available": True, "flops": 6668188.0,
                    "bytes_accessed": 6770940.0, "fusions": 731,
                    "ops": {"while": 21, "dot": 167},
                    # a partitioned executable: the tp interconnect
                    # columns the report must surface
                    "collectives": {
                        "ops": {"all-reduce": {"count": 6,
                                               "bytes": 73728}},
                        "count": 6, "bytes": 73728}},
                "serve_policy_b8": {"available": False,
                                    "error": "RuntimeError: no backend"},
            },
            "phases": {"dispatch": {"total_s": 0.05, "count": 5,
                                    "mean_ms": 10.0},
                       "drain": {"total_s": 0.01, "count": 5,
                                 "mean_ms": 2.0}},
        }, f)


def selftest() -> int:
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "events.jsonl")
        _synthetic_events(path)
        _synthetic_perf(os.path.join(tmp, "perf.json"))
        summary = summarize(load_events(path), perf=load_perf(tmp))
        assert summary["episodes"] == 5, summary
        # perf section: ledger rows condensed, schema version surfaced,
        # the unavailable serve entry kept visible rather than dropped
        pf = summary["perf"]
        assert pf["schema_version"] == 1 and pf["backend"] == "tpu" \
            and pf["device_kind"] == "TPU v5 lite", pf
        row = pf["entries"]["episode_step"]
        assert row["fusions"] == 718 and row["mfu"] == 0.0133 \
            and row["regime"] == "memory_bound" \
            and row["wall_ms_mean"] == 10.0, row
        # the interconnect columns: 0 on the single-device entry, the
        # partitioned executable's all-reduce payload on the sharded one
        assert row["collective_count"] == 0, row
        sh = pf["entries"]["chunk_step_sharded"]
        assert sh["collective_count"] == 6 \
            and sh["collective_bytes"] == 73728, sh
        assert pf["entries"]["serve_policy_b8"]["available"] is False
        assert pf["device_vs_host"] == {"dispatch_s": 0.05,
                                        "host_s": 0.01}, pf
        # no-HBM-data flag: the CPU device reported available=False
        assert summary["memory_unavailable_backends"] == ["cpu"], summary
        assert summary["precision"] == {
            "name": "bf16", "param_dtype": "float32",
            "gnn_compute": "bfloat16", "mlp_compute": "bfloat16",
            "replay_dtype": "bfloat16"}, "precision header not surfaced"
        assert summary["engine"] == {"unroll": 2}, \
            "engine-knob header not surfaced"
        assert summary["mesh"] == {
            "mesh": "4x2", "partition_rules": "sharded",
            "partition_specs": {"PartitionSpec()": 87,
                                "PartitionSpec(None, 'mp')": 44}}, \
            "mesh header not surfaced"
        assert summary["topo_mix"] == "schedule,abilene+bursty", \
            "topo_mix header not surfaced"
        assert summary["per_topology"] == {
            "abilene.graphml": {"episodes": 2, "mean_return": 2.5,
                                "last_return": 3.0},
            "abilene+bursty": {"episodes": 2, "mean_return": 0.5,
                               "last_return": 1.0},
            # the serial path's stamped episode events land in the SAME
            # table as the harness's mixed-batch attribution
            "line3.graphml": {"episodes": 5, "mean_return": -0.8,
                              "last_return": -0.6}}, \
            "per-topology returns not aggregated"
        ln = summary["learning"]
        assert ln and ln["episodes"] == 2, ln
        assert ln["per_topology_td"]["abilene+bursty"] == {
            "episodes": 2, "mean_td_abs": 0.55, "last_td_abs": 0.5}, ln
        assert ln["td_abs_first"] == 0.5 and ln["td_abs_last"] == 0.4, ln
        assert ln["q_last"] == {"q_mean": 0.3, "q_std": 0.1,
                                "q_min": -0.2, "q_max": 0.9}, ln
        assert ln["grad_norm_peak"]["actor/Dense_0"] == 2.5, \
            "per-layer grad-norm peak not tracked"
        assert ln["replay_fill_last"] == 0.5, ln
        import io
        txt = io.StringIO()
        render_text(summary, out=txt)
        assert "SLO: p99 target" in txt.getvalue() \
            and "latency decomposition" in txt.getvalue() \
            and "rejected: queue_full 3" in txt.getvalue(), \
            "serving SLO/decomposition/rejection lines not rendered"
        assert "perf ledger: schema v1" in txt.getvalue(), \
            "perf schema-version header not rendered"
        assert "perf (device-cost ledger" in txt.getvalue() \
            and "memory_bound" in txt.getvalue(), \
            "perf section not rendered"
        assert "coll_B" in txt.getvalue() \
            and "73728" in txt.getvalue(), \
            "collective count/bytes columns not rendered"
        assert "no HBM data" in txt.getvalue(), \
            "memory-unavailable note not rendered"
        assert "mesh: 4x2  rules: sharded" in txt.getvalue(), \
            "mesh header line not rendered"
        assert "topo mix: schedule,abilene+bursty" in txt.getvalue(), \
            "topo-mix header line not rendered"
        assert "per-topology returns" in txt.getvalue() \
            and "abilene+bursty" in txt.getvalue(), \
            "per-topology table not rendered"
        assert "learning dynamics" in txt.getvalue() \
            and "grad/param health" in txt.getvalue(), \
            "learning-dynamics section not rendered"
        assert len(summary["stalls"]) == 1, "stall not surfaced"
        assert summary["stalls"][0]["last_phase"] == "dispatch"
        assert len(summary["invariant_violations"]) == 1
        assert summary["memory_growth_flags"], "memory growth not flagged"
        comp = summary["compiles"]["per_fn"]
        # 0.8 s trace + 2.5 s xla: both stages count as compile wall
        assert comp["episode_step"] == {
            "traces": 1, "xla_compiles": 1, "compile_s": 3.3}, comp
        assert summary["compiles"]["retrace_flags"] == ["leaky_fn"], \
            "retrace churn not flagged"
        assert len(summary["recoveries"]) == 2, "recovery timeline lost"
        assert summary["recovery_totals"] == {
            "dispatch/retry": 1, "learner_state/rollback": 1}, summary
        assert len(summary["escalations"]) == 1, "escalation not surfaced"
        sv = summary["serving"]
        assert sv and sv["tier"] == "learned" and sv["requests"] == 200, sv
        assert sv["rps"] == 512.5 and sv["p99_ms"] == 7.9, \
            "serving throughput/latency not surfaced"
        assert sv["occupancy"] == {"1": 40, "4": 160}, sv
        assert sv["bucket_prepare"]["1"]["cache_hit"] is True \
            and sv["bucket_prepare"]["4"]["cache_hit"] is False, \
            "per-bucket cache-hit pattern lost"
        assert sv["buckets"]["4"]["p99_ms"] == 7.9, sv
        # SLO engine + tracer section: attainment/burn, rejection and
        # pad-waste accounting, and the per-bucket latency split
        assert sv["slo"]["attainment"] == 0.97 \
            and sv["slo"]["burn_rate"] == 3.0 \
            and sv["slo"]["deadline_miss_ratio"] == 0.12, \
            "SLO snapshot not surfaced"
        assert sv["rejected"] == {"queue_full": 3, "stopping": 0}, \
            "rejection totals lost"
        assert sv["slo"]["pad_waste"] == 0.31, sv["slo"]
        assert sv["decomposition"]["4"]["batch_ms"] == 2.1 \
            and sv["decomposition"]["1"]["device_ms"] == 0.8, \
            "latency decomposition lost"
        # fleet view: per-worker table rows + the hot-swap timeline
        assert set(sv["workers"]) == {"w0", "w1"}, sv["workers"]
        assert sv["workers"]["w0"] == {
            "requests": 120, "occupancy": {"1": 20, "4": 100},
            "queue_depth": 1, "policy_version": 2, "swaps": 1}, \
            sv["workers"]
        assert sv["fleet"]["requests"] == 200 \
            and sv["fleet"]["swaps"] == 2, sv["fleet"]
        assert [s["version"] for s in sv["swap_timeline"]] == [2, 2] \
            and sv["swap_timeline"][0]["requests_in_flight"] == 3, \
            "hot-swap timeline lost"
        # async-fleet section: per-actor table, lag/idle decomposition,
        # adoption timeline — all three views reconstructed from the
        # deferred flight-recorder ledgers + the async_train info event
        af = summary["async_fleet"]
        assert af and af["info"]["actors"] == 2 \
            and af["info"]["transitions_lost"] == 0, af
        assert set(af["per_actor"]) == {0, 1}, af["per_actor"]
        a0 = af["per_actor"][0]
        assert a0["episodes"] == 1 and a0["chunks"] == 2 \
            and a0["steps"] == 128 and a0["adopts"] == 1 \
            and a0["last_version"] == 1, a0
        assert abs(a0["rollout_s"] - 0.2) < 1e-6 \
            and abs(a0["blocked_s"] - 0.02) < 1e-6, a0
        assert a0["idle_frac"] == 0.02 \
            and af["per_actor"][1]["idle_frac"] == 0.5, af["per_actor"]
        assert af["lag"]["samples"] == 3 and af["lag"]["max"] == 1 \
            and af["lag"]["p99"] == 1, af["lag"]
        dec = af["decomposition"]
        assert dec["n_ingests"] == 3 and dec["n_bursts"] == 1 \
            and abs(dec["ingest_s"] - 0.03) < 1e-6 \
            and abs(dec["burst_s"] - 0.02) < 1e-6, dec
        assert dec["idle_s"] == 0.2 \
            and abs(dec["other_s"] - (1.0 - 0.03 - 0.02 - 0.2)) < 1e-6, \
            dec
        tl = af["adoption_timeline"]
        assert len(tl) == 1 and tl[0]["version"] == 1, tl
        # actor0 adopted 0.01s after the publish, actor1 0.26s after
        assert abs(tl[0]["adopt_lag_s"][0] - 0.01) < 1e-6 \
            and abs(tl[0]["adopt_lag_s"][1] - 0.26) < 1e-6, tl
        assert af["orphan_adopt_versions"] == [], af
        async_txt = io.StringIO()
        render_text(summary, out=async_txt)
        assert "async fleet (2 actor(s)" in async_txt.getvalue() \
            and "adoption timeline" in async_txt.getvalue() \
            and "learner wall: ingest" in async_txt.getvalue(), \
            "async-fleet section not rendered"
        fleet_txt = io.StringIO()
        render_text(summary, out=fleet_txt)
        assert "fleet: 2 worker(s)" in fleet_txt.getvalue() \
            and "hot-swap timeline" in fleet_txt.getvalue() \
            and "brownout: overflow 5" in fleet_txt.getvalue(), \
            "fleet table / swap timeline not rendered"
        assert summary["drop_totals"]["TTL"] == 0 + 1 + 2 + 3 + 4
        deltas = phase_deltas([e for e in last_run(load_events(path))
                               if e.get("event") == "episode"])
        assert abs(deltas[2]["dispatch"] - 0.010) < 1e-6, deltas[2]
        render_text(summary)   # must not raise on a flagged stream
        # append-mode reuse: a second run landing in the same stream must
        # not corrupt the summary — the report partitions on run_start.
        # The appended run's timestamps are SHIFTED (a real second run
        # starts later; the reader now ts-sorts, so an identical-ts copy
        # would interleave with the first run's records)
        lines0 = [json.loads(line) for line in open(path)
                  if line.strip()]
        with open(path, "a") as f:
            for rec in lines0:
                f.write(json.dumps({**rec, "ts": rec["ts"] + 1000.0})
                        + "\n")
        s2 = summarize(load_events(path))
        assert s2["runs_in_stream"] == 2 and s2["episodes"] == 5, s2
        render_text(s2, out=open(os.devnull, "w"))
        # rotation roundtrip (--obs-rotate-mb layout): split the stream
        # into a .1 segment + live tail — the reader must walk the
        # segments and reassemble the identical stream
        lines = open(path).read().splitlines(keepends=True)
        cut = len(lines) // 2
        with open(path + ".1", "w") as f:
            f.writelines(lines[:cut])
        with open(path, "w") as f:
            f.writelines(lines[cut:])
        reassembled = sorted(
            (json.loads(line) for line in lines if line.strip()),
            key=lambda e: e["ts"])   # the reader's ts-sorted view
        assert load_events(path) == reassembled, \
            "rotated segments did not reassemble the stream"
        s3 = summarize(load_events(path))
        assert s3["runs_in_stream"] == 2 and s3["episodes"] == 5, s3
    print("obs_report selftest: OK")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?",
                    help="run directory or events.jsonl")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object")
    ap.add_argument("--mem-growth-threshold", type=float, default=0.2,
                    help="fractional bytes_in_use growth (first->last "
                         "episode) flagged as a leak [default 0.2]")
    ap.add_argument("--retrace-threshold", type=int, default=3,
                    help="traces per jitted entry point above which "
                         "retrace churn is flagged [default 3]")
    ap.add_argument("--selftest", action="store_true",
                    help="synthesize a stream and verify the report "
                         "flags its stall/leak (CI smoke target)")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.path:
        ap.error("path required (or --selftest)")
    perf = load_perf(args.path)
    summary = summarize(load_events(args.path),
                        mem_growth_threshold=args.mem_growth_threshold,
                        retrace_threshold=args.retrace_threshold,
                        perf=perf)
    summary["layers"] = layer_summary(args.path, perf)
    if args.json:
        json.dump(summary, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        render_text(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
