"""Cross-run perf regression tracker over the banked bench artifacts.

The repo accumulates a perf trajectory nobody reads mechanically:
``BENCH_r0*.json`` (env-steps/s ladder rounds), ``MULTICHIP_r0*.json``
(mesh-carving bit-equality matrices), ``SERVE_r0*.json`` (latency-SLA
legs), the per-run ``perf.json`` cost ledgers (gsc_tpu.obs.perf) and the
per-run ``curves.json`` learning-curve envelopes (gsc_tpu.obs.curves:
final-window return, AUC, episodes-to-threshold — the banded quality
envelope ROADMAP item 2 trades bit-exactness against).
This tool makes that trajectory a guarded artifact:

- **ingest**: normalize any mix of those files into rows of one
  cumulative ``BENCH_TRAJECTORY.json`` (schema-versioned, keyed by
  artifact name; re-ingesting updates in place);
- **diff**: compare a current row (by name, or straight from a file)
  against a NAMED BASELINE row with per-metric tolerance bands, exit
  nonzero on any regression — the fusion-budget discipline,
  generalized to every perf-relevant number.

Verdicts per metric: ``ok`` (within band), ``improved``, ``regression``
(beyond band in the bad direction), ``missing`` (only one side has it —
informational, never fatal).  Overall verdict is ``regression`` iff any
metric regressed; a baseline name that is not in the trajectory is the
distinct ``missing-baseline`` verdict (exit 3), so CI can tell "got
slower" from "never measured".

Exit codes: 0 ok/improved, 1 regression, 2 usage/parse error,
3 missing baseline.

Usage:
    python tools/bench_diff.py ingest --scan . --out BENCH_TRAJECTORY.json
    python tools/bench_diff.py ingest results/run1/perf.json
    python tools/bench_diff.py diff BENCH_r04 --baseline BENCH_r03
    python tools/bench_diff.py diff results/run2/perf.json \
        --baseline perf_run1 --tolerance mfu=0.3
    python tools/bench_diff.py --selftest

Stdlib only: this must run on a login node with no JAX installed.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

TRAJECTORY_SCHEMA_VERSION = 1

# metric gating rules, matched by key SUFFIX (first match wins):
# (suffix, higher_is_better, relative tolerance band[, absolute band
# floor]).  A metric with no matching rule is carried in the rows but
# never gated — flops/bytes legitimately move when the model changes;
# rates/latencies/fusion counts are the contract.  The absolute floor
# exists for metrics that legitimately sit at or cross ZERO (episode
# returns): band = max(tol * |baseline|, floor), so a baseline of ~0
# never shrinks the band to nothing and flags pure noise as regression
# (the strictly-positive perf metrics keep the historic relative-only
# band — an explicit floor of 0.0).
METRIC_RULES: List[Tuple] = [
    ("env_steps_per_sec", True, 0.10),
    ("vs_baseline", True, 0.10),
    ("rps", True, 0.15),
    ("p99_ms", False, 0.25),
    ("p50_ms", False, 0.25),
    ("mfu", True, 0.15),
    ("sps", True, 0.15),             # mixtopo mixed/homogeneous rates
    # ASYNC mesh rounds (r02+): scaling-efficiency axis — per-grid rate
    # divided by device count (suffix does NOT end in `sps`, so it needs
    # its own band), and the HLO-mined collective count on the compiled
    # dp-sharded replay ingest (the zero-collective contract: ANY growth
    # means blocks started paying a gather/reshard per ingest)
    ("sps_per_device", True, 0.15),
    ("ingest_collectives", False, 0.0),
    ("fusions", False, 0.05),
    ("jit_traces", False, 0.0),      # any retrace growth is churn
    ("legs_ok", True, 0.0),
    ("bit_equal", True, 0.0),
    ("cold_start_s", False, 0.25),
    ("cache_hit_start_s", False, 0.25),
    # learning-curve envelope metrics (per-run curves.json summaries,
    # gsc_tpu.obs.curves) — the quality_anchor trade currency ROADMAP
    # item 2 names: a tensor-parallel rulebook is acceptable when these
    # stay inside the bands, not only when results are bit-identical.
    # Returns legitimately cross zero, so they carry absolute floors
    # (episode-return units / episodes / |TD| units respectively).
    ("final_window_return", True, 0.20, 1.0),
    ("auc_return", True, 0.25, 1.0),
    ("episodes_to_threshold", False, 0.25, 1.0),
    ("final_window_td_abs", False, 0.30, 0.05),
    # serving SLO metrics (cli serve / PolicyServer slo summaries, banked
    # per serve_bench leg and as per-run slo.json documents) — SERVE rows
    # gate on serving QUALITY, not just rps/p99.  Ratios legitimately sit
    # at/near zero (a healthy run has no deadline misses), so every band
    # carries an absolute floor in ratio units.
    ("slo_deadline_miss_ratio", False, 0.25, 0.02),
    ("slo_pad_waste", False, 0.25, 0.05),
    ("slo_queue_wait_frac", False, 0.30, 0.05),
    ("slo_burn_rate", False, 0.25, 0.25),
    ("slo_attainment", True, 0.05, 0.02),
    # async actor/learner rows (ASYNC_r*, tools/async_bench.py): the
    # learner-idle fraction is the decoupling claim itself — the learner
    # must not creep back toward blocking on acting.  Lower is better; a
    # healthy run sits near zero, so the band carries an absolute floor
    # in ratio units (the per-leg *_sps rates gate under the shared 15%
    # `sps` band above, and per-leg trace counts under `jit_traces`).
    ("learner_idle_frac", False, 0.25, 0.05),
    # flight-recorder lag/idle axes on ASYNC rows: the p99 policy lag is
    # the staleness contract (a learner suddenly training on much older
    # acting policies regresses generalization claims even when raw sps
    # holds), the max per-actor idle fraction is the dispatch-side twin
    # of learner_idle_frac — an actor spending its wall blocked on the
    # channel means the learn side became the bottleneck.  Both sit near
    # small integers / zero on healthy runs, so both carry absolute
    # floors (versions / ratio units).
    ("policy_lag_p99", False, 0.50, 1.0),
    ("actor_idle_frac", False, 0.25, 0.10),
]

# filename patterns `ingest --scan` picks up.  perf.json ledgers and
# curves.json learning curves are searched RECURSIVELY: runs write them
# at results/<id>/<timestamp>/ (utils.experiment.setup_result_dir
# layout), arbitrarily deep below the scan root.
SCAN_PATTERNS = ("BENCH_r*.json", "MULTICHIP_r*.json", "SERVE_r*.json",
                 "MIXTOPO_r*.json", "SCEN_r*.json", "ASYNC_r*.json",
                 "CHAOS_r*.json",
                 "**/perf.json", "**/curves.json", "**/slo.json")


def metric_rule(name: str) -> Optional[Tuple[bool, float, float]]:
    """(higher_is_better, relative tolerance, absolute band floor) for a
    gated metric; None = informational."""
    for rule in METRIC_RULES:
        suffix, higher, tol = rule[:3]
        if name.endswith(suffix):
            return higher, tol, (rule[3] if len(rule) > 3 else 0.0)
    return None


# ------------------------------------------------------------- extraction
def _num(v) -> Optional[float]:
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else None


def _bench_row(d: Dict) -> Dict:
    """An ``env_steps_per_sec_per_chip`` artifact line (the root
    ``*_rNN.json`` records; possibly a driver wrapper's `parsed`)."""
    status = d.get("status") or ("failed" if d.get("error") else "ok")
    metrics: Dict[str, float] = {}
    if status == "ok":
        if _num(d.get("value")) is not None:
            metrics["env_steps_per_sec"] = float(d["value"])
        if _num(d.get("vs_baseline")) is not None:
            metrics["vs_baseline"] = float(d["vs_baseline"])
        # MIXTOPO/SCEN rounds share the metric name but report paired
        # rates: the `_sps` suffix gates them under the 15% rate band;
        # the ratios and the scenario_regen walls are context
        for k in ("mixed_sps", "homogeneous_sps", "mixed_vs_homogeneous",
                  "factory_sps", "host_regen_sps", "factory_vs_host",
                  "factory_scenario_regen_s", "host_scenario_regen_s",
                  # ASYNC rounds: sync control + per-actor-count async
                  # rates (`_sps` band), the learner-idle fraction (its
                  # own lower-is-better band), speedups + curve metrics
                  "sync_sps", "async1_sps", "async2_sps", "async4_sps",
                  "learner_idle_frac", "async2_vs_sync", "async4_vs_sync",
                  # ASYNC mesh rounds (r02): dp-leg rates (`_sps` band),
                  # the per-device scaling axis (`_sps_per_device`
                  # band), the zero-collective ingest count (0%
                  # tolerance), speedup ratios as context
                  "async_dp2_sps", "async_dp4_sps",
                  "async2_sps_per_device", "async_dp2_sps_per_device",
                  "async_dp4_sps_per_device", "ingest_collectives",
                  "async_dp2_vs_async2", "async_dp4_vs_async2",
                  # flight-recorder lag/idle axes on ASYNC rows: p99
                  # staleness + worst per-actor idle gate under their
                  # own lower-is-better bands
                  "policy_lag_p99", "actor_idle_frac",
                  # CHAOS rounds (tools/chaos_smoke.py --round): the
                  # fault-injected vs fault-free rates gate under the
                  # shared 15% `_sps` band — self-healing must cost
                  # recovery DETOURS, not steady-state throughput.  The
                  # recovery tallies land as informational keys (no
                  # band: how many faults a plan fires is the plan's
                  # business, drift is context not regression)
                  "chaos_sps", "control_sps", "chaos_vs_control",
                  "recoveries_total", "actor_restarts",
                  "blocks_quarantined",
                  "sync_final_window_return", "async_final_window_return",
                  "sync_auc_return", "async_auc_return"):
            if _num(d.get(k)) is not None:
                metrics[k] = float(d[k])
        for fn, n in (d.get("jit_traces") or {}).items():
            if _num(n) is not None:
                metrics[f"{fn}_jit_traces"] = float(n)
        # MIXTOPO/SCEN rounds record per-leg trace counts; keys end in
        # `_jit_traces` so the 0%-tolerance retrace band gates them too
        for leg in ("homogeneous", "mixed", "factory", "host_regen",
                    "sync", "async1", "async2", "async4",
                    "async_dp2", "async_dp4"):
            for fn, n in (d.get(f"jit_traces_{leg}") or {}).items():
                if _num(n) is not None:
                    metrics[f"{leg}_{fn}_jit_traces"] = float(n)
        for fn, cost in (d.get("cost") or {}).items():
            for k in ("fusions", "mfu", "flops", "bytes_accessed"):
                if _num((cost or {}).get(k)) is not None:
                    metrics[f"{fn}_{k}"] = float(cost[k])
    return {"kind": "bench", "status": status, "metrics": metrics,
            "context": {k: d.get(k) for k in
                        ("pipeline", "precision", "unroll", "mesh",
                         "topo_mix", "async_actors",
                         "policy_lag_max", "produced_steps",
                         "ingested_steps", "ring_shards") if k in d}}


def _multichip_row(d: Dict) -> Dict:
    metrics: Dict[str, float] = {}
    for k in ("legs_ok", "legs_total", "devices"):
        if _num(d.get(k)) is not None:
            metrics[k] = float(d[k])
    if "bit_equal_across_carvings" in d:
        metrics["bit_equal"] = 1.0 if d["bit_equal_across_carvings"] else 0.0
    walls = [leg.get("wall_s") for leg in d.get("legs") or []
             if _num(leg.get("wall_s")) is not None]
    if walls:
        metrics["mean_leg_wall_s"] = round(sum(walls) / len(walls), 3)
    return {"kind": "multichip", "status": d.get("status", "ok"),
            "metrics": metrics, "context": {"mode": d.get("mode")}}


# the SLO-summary keys that become gated `slo_*` metrics on serve rows
# (arrival rate / p99 target are context, not gates — and an `_rps`
# suffix would wrongly match the throughput band)
_SLO_GATED_KEYS = ("deadline_miss_ratio", "pad_waste", "queue_wait_frac",
                   "burn_rate", "attainment")


def _slo_metrics(slo: Dict, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k in _SLO_GATED_KEYS:
        if _num((slo or {}).get(k)) is not None:
            out[f"{prefix}slo_{k}"] = float(slo[k])
    return out


def _serve_row(d: Dict) -> Dict:
    metrics: Dict[str, float] = {}
    for k in ("cold_start_s", "cache_hit_start_s"):
        if _num(d.get(k)) is not None:
            metrics[k] = float(d[k])
    legs = d.get("legs") or {}
    for leg_name, leg in legs.items():
        for k in ("rps", "p50_ms", "p99_ms"):
            if _num((leg or {}).get(k)) is not None:
                metrics[f"{leg_name}_{k}"] = float(leg[k])
        # per-leg SLO summary (serve_bench banks the cli serve `slo`
        # block): deadline-miss ratio, pad waste, queue-wait fraction,
        # burn rate, attainment gate under the slo_* bands
        metrics.update(_slo_metrics((leg or {}).get("slo"),
                                    prefix=f"{leg_name}_"))
    # flat single-run serve JSON (cli serve output) has rps/p99 top-level
    for k in ("rps", "p50_ms", "p99_ms"):
        if _num(d.get(k)) is not None:
            metrics[k] = float(d[k])
    metrics.update(_slo_metrics(d.get("slo")))
    return {"kind": "serve", "status": d.get("status", "ok"),
            "metrics": metrics,
            "context": {k: d.get(k) for k in ("tier", "buckets", "platform")
                        if k in d}}


def _slo_row(d: Dict) -> Dict:
    """A per-run slo.json document (gsc_tpu.obs.slo, written by
    PolicyServer.close): the same gated slo_* axes as a serve row, plus
    the run's latency percentiles."""
    metrics = _slo_metrics(d)
    for k in ("p50_latency_ms", "p99_latency_ms"):
        if _num(d.get(k)) is not None:
            # suffix-normalize so the p50/p99 latency bands gate them
            metrics[k.replace("_latency", "")] = float(d[k])
    if _num(d.get("requests")) is not None:
        metrics["requests"] = float(d["requests"])   # informational
    return {"kind": "slo", "status": "ok", "metrics": metrics,
            "context": {"run": d.get("run"), "tier": d.get("tier"),
                        "slo_schema": d.get("schema_version"),
                        "deadline_ms": d.get("deadline_ms")}}


def _perf_row(d: Dict) -> Dict:
    """A gsc_tpu.obs.perf ledger (perf.json)."""
    metrics: Dict[str, float] = {}
    for name, e in (d.get("entries") or {}).items():
        if not (e or {}).get("available"):
            continue
        for k in ("fusions", "mfu", "flops", "bytes_accessed",
                  "arithmetic_intensity", "wall_s_mean"):
            if _num(e.get(k)) is not None:
                metrics[f"{name}_{k}"] = float(e[k])
        # collective count/bytes per entry (the tp-vs-sharded
        # interconnect axis).  Informational, never gated: collective
        # payload legitimately moves with the model and the rulebook —
        # the point is that the comparison is machine-READ, the verdict
        # stays with the learning-curve/throughput bands
        col = e.get("collectives") or {}
        for k in ("count", "bytes"):
            if _num(col.get(k)) is not None:
                metrics[f"{name}_collective_{k}"] = float(col[k])
    return {"kind": "perf_ledger", "status": "ok", "metrics": metrics,
            "context": {"backend": d.get("backend"),
                        "device_kind": d.get("device_kind"),
                        "run": d.get("run"),
                        "ledger_schema": d.get("schema_version")}}


def _curves_row(d: Dict) -> Dict:
    """A gsc_tpu.obs.curves learning-curve document (curves.json).  The
    summary's envelope metrics gate; ``episodes_to_threshold`` is often
    null (a run that never rose has no time-to-learn) and is then simply
    absent — the diff reports it as ``missing``, never a regression."""
    summary = d.get("summary") or {}
    metrics: Dict[str, float] = {}
    for k in ("final_window_return", "auc_return", "episodes_to_threshold",
              "final_window_td_abs", "first_window_return"):
        if _num(summary.get(k)) is not None:
            metrics[k] = float(summary[k])
    if _num(d.get("episodes")) is not None:
        metrics["episodes"] = float(d["episodes"])
    return {"kind": "curves", "status": "ok", "metrics": metrics,
            "context": {"run": d.get("run"),
                        "curves_schema": d.get("schema_version"),
                        "window": summary.get("window")}}


def extract_row(path: str) -> Optional[Dict]:
    """Classify + normalize one artifact file; None if unrecognized."""
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"[bench_diff] skipping {path}: {e}", file=sys.stderr)
        return None
    if not isinstance(d, dict):
        return None
    # driver wrapper rounds bank the artifact line under "parsed"; a
    # wrapper whose run produced no parseable line at all is still a
    # FAILED bench row (round never ran != round was slow)
    if "parsed" in d:
        if not isinstance(d["parsed"], dict):
            d = {"metric": "env_steps_per_sec_per_chip",
                 "status": "failed"}
        else:
            d = d["parsed"]
    if d.get("metric") == "env_steps_per_sec_per_chip":
        row = _bench_row(d)
    elif d.get("metric") == "serve_requests_per_sec" or (
            "legs" in d and "cache_hit_start_s" in d):
        row = _serve_row(d)
    elif d.get("mode") == "mesh_matrix" or "bit_equal_across_carvings" in d:
        row = _multichip_row(d)
    elif "schema_version" in d and "entries" in d:
        row = _perf_row(d)
    elif "schema_version" in d and "series" in d and "summary" in d:
        row = _curves_row(d)
    elif "schema_version" in d and "deadline_miss_ratio" in d:
        row = _slo_row(d)
    else:
        return None
    base = os.path.basename(path)
    name = os.path.splitext(base)[0]
    if name in ("perf", "curves", "slo"):
        # per-run artifacts share their filename; key by run dir (or the
        # document's recorded run id) so two runs never collide
        run = (row.get("context") or {}).get("run")
        name = f"{name}_{run or os.path.basename(os.path.dirname(os.path.abspath(path)))}"
    row.update(name=name, source=path)
    return row


# -------------------------------------------------------------- trajectory
def load_trajectory(path: str) -> Dict:
    if path and os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema_version") != TRAJECTORY_SCHEMA_VERSION:
            print(f"[bench_diff] {path} has schema "
                  f"{doc.get('schema_version')!r}; rewriting as "
                  f"v{TRAJECTORY_SCHEMA_VERSION}", file=sys.stderr)
            doc = {"schema_version": TRAJECTORY_SCHEMA_VERSION,
                   "rows": doc.get("rows", {})}
        return doc
    return {"schema_version": TRAJECTORY_SCHEMA_VERSION, "rows": {}}


def write_trajectory(path: str, doc: Dict) -> str:
    """Atomic rewrite (temp + os.replace) — same contract as the obs
    snapshot writer, reimplemented here to stay stdlib-only."""
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path))
                               or ".", prefix=".bench_traj.")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return path


def ingest(paths: List[str], out: str, scan: Optional[str] = None) -> Dict:
    doc = load_trajectory(out)
    candidates = list(paths)
    if scan:
        for pattern in SCAN_PATTERNS:
            candidates.extend(sorted(glob.glob(os.path.join(scan, pattern),
                                               recursive=True)))
    seen = set()
    ingested = []
    for p in candidates:
        p = os.path.normpath(p)
        if p in seen:
            continue
        seen.add(p)
        row = extract_row(p)
        if row is None:
            continue
        doc["rows"][row["name"]] = {k: v for k, v in row.items()
                                    if k != "name"}
        ingested.append(row["name"])
    write_trajectory(out, doc)
    print(f"[bench_diff] {out}: {len(doc['rows'])} row(s) "
          f"({len(ingested)} ingested: {', '.join(ingested) or '-'})")
    return doc


# -------------------------------------------------------------------- diff
def diff_rows(current: Dict, baseline: Dict,
              tolerances: Optional[Dict[str, float]] = None) -> Dict:
    """Per-metric verdicts for a (current, baseline) row pair.

    A non-ok CURRENT row is its own overall verdict (``failed-current``,
    gated like a regression): a crashed round has no measurements, and
    diffing its empty metric set would otherwise come out clean — the
    exact "never ran reads as fine" failure the status field exists to
    prevent.  A non-ok BASELINE is ``failed-baseline`` (gated like
    missing-baseline: there is nothing to regress against)."""
    tolerances = tolerances or {}
    if current.get("status", "ok") != "ok":
        return {"verdict": "failed-current", "regressions": [],
                "gated_metrics": 0, "current": current.get("name"),
                "baseline": baseline.get("name"), "metrics": {}}
    if baseline.get("status", "ok") != "ok":
        return {"verdict": "failed-baseline", "regressions": [],
                "gated_metrics": 0, "current": current.get("name"),
                "baseline": baseline.get("name"), "metrics": {}}
    cm, bm = current.get("metrics", {}), baseline.get("metrics", {})
    per_metric = {}
    regressions = []
    for name in sorted(set(cm) | set(bm)):
        rule = metric_rule(name)
        if name not in cm or name not in bm:
            per_metric[name] = {"verdict": "missing",
                                "current": cm.get(name),
                                "baseline": bm.get(name)}
            continue
        cur, base = cm[name], bm[name]
        rec = {"current": cur, "baseline": base}
        if base != 0:
            rec["change_pct"] = round(100.0 * (cur - base) / abs(base), 2)
        if rule is None:
            rec["verdict"] = "informational"
        else:
            higher, tol, floor = rule
            tol = tolerances.get(name, tol)
            delta = (cur - base) if higher else (base - cur)   # + is good
            # the floor keeps a near-zero baseline (returns oscillating
            # around 0) from shrinking the band to nothing and gating
            # on noise; 0.0 for the strictly-positive perf metrics
            band = max(tol * abs(base), floor)
            if delta < -band - 1e-12:
                rec["verdict"] = "regression"
                rec["tolerance"] = tol
                regressions.append(name)
            elif delta > band + 1e-12:
                rec["verdict"] = "improved"
            else:
                rec["verdict"] = "ok"
        per_metric[name] = rec
    gated = [m for m in per_metric
             if per_metric[m]["verdict"] in ("ok", "improved", "regression")]
    return {
        "verdict": "regression" if regressions else "ok",
        "regressions": regressions,
        "gated_metrics": len(gated),
        "current": current.get("name"),
        "baseline": baseline.get("name"),
        "metrics": per_metric,
    }


def resolve_row(spec: str, doc: Dict) -> Optional[Dict]:
    """A row by trajectory name, or extracted fresh from a file path."""
    row = doc.get("rows", {}).get(spec)
    if row is not None:
        return {**row, "name": spec}
    if os.path.exists(spec):
        return extract_row(spec)
    # a path-like spec (results/run1/perf.json) that doesn't exist is a
    # usage error, not a missing baseline
    return None


# ---------------------------------------------------------------- selftest
def selftest() -> int:
    import io
    with tempfile.TemporaryDirectory() as tmp:
        def dump(name, obj):
            p = os.path.join(tmp, name)
            with open(p, "w") as f:
                json.dump(obj, f)
            return p

        good = dump("BENCH_r98.json", {
            "metric": "env_steps_per_sec_per_chip", "status": "ok",
            "value": 2000.0, "vs_baseline": 15.3, "unit": "env-steps/s",
            "jit_traces": {"chunk_step": 1},
            "cost": {"chunk_step": {"available": True, "fusions": 280,
                                    "mfu": 0.02, "flops": 1e9}}})
        slow = dump("BENCH_r99.json", {
            "metric": "env_steps_per_sec_per_chip", "status": "ok",
            "value": 1500.0, "vs_baseline": 11.5, "unit": "env-steps/s",
            "jit_traces": {"chunk_step": 2},
            "cost": {"chunk_step": {"available": True, "fusions": 310,
                                    "mfu": 0.014, "flops": 1e9}}})
        wrapper = dump("BENCH_r97.json", {   # driver wrapper + failed row
            "n": 5, "rc": 1,
            "parsed": {"metric": "env_steps_per_sec_per_chip",
                       "value": 0.0, "error": "backend unreachable"}})
        perf = dump("perf.json", {
            "schema_version": 1, "backend": "cpu", "run": "selftest",
            "entries": {"episode_step": {
                "available": True, "flops": 6.6e6, "bytes_accessed": 6.7e6,
                "fusions": 718, "mfu": 1e-4, "wall_s_mean": 1.3}}})
        slo = dump("slo.json", {
            "schema_version": 1, "run": "sloself", "tier": "learned",
            "deadline_ms": 5.0, "requests": 200,
            "deadline_miss_ratio": 0.05, "pad_waste": 0.2,
            "queue_wait_frac": 0.3, "burn_rate": 1.0,
            "attainment": 0.99, "arrival_rate_rps": 900.0,
            "p50_latency_ms": 1.2, "p99_latency_ms": 6.0})
        curves = dump("curves.json", {
            "schema_version": 1, "run": "curveself", "episodes": 12,
            "series": {"episode": list(range(12))}, "per_topology": {},
            "summary": {"window": 10, "final_window_return": 20.0,
                        "first_window_return": -10.0, "auc_return": 5.0,
                        "episodes_to_threshold": 8,
                        "final_window_td_abs": 0.4}})
        traj = os.path.join(tmp, "BENCH_TRAJECTORY.json")
        doc = ingest([good, slow, wrapper, perf, curves, slo], traj)
        assert set(doc["rows"]) == {"BENCH_r98", "BENCH_r99", "BENCH_r97",
                                    "perf_selftest", "curves_curveself",
                                    "slo_sloself"}, \
            doc["rows"].keys()
        assert doc["rows"]["BENCH_r97"]["status"] == "failed"
        assert doc["rows"]["perf_selftest"]["metrics"][
            "episode_step_fusions"] == 718.0
        assert doc["rows"]["curves_curveself"]["metrics"][
            "final_window_return"] == 20.0
        assert doc["rows"]["slo_sloself"]["metrics"][
            "slo_deadline_miss_ratio"] == 0.05

        # per-run ledgers live at results/<id>/<timestamp>/perf.json —
        # `--scan` must find them recursively
        nested = os.path.join(tmp, "results", "exp1", "ts1", "perf.json")
        os.makedirs(os.path.dirname(nested))
        with open(nested, "w") as f:
            json.dump({"schema_version": 1, "backend": "cpu",
                       "run": "nested",
                       "entries": {"episode_step": {
                           "available": True, "flops": 1.0,
                           "fusions": 2}}}, f)
        doc2 = ingest([], os.path.join(tmp, "t2.json"), scan=tmp)
        assert "perf_nested" in doc2["rows"], doc2["rows"].keys()

        # self-compare: identical rows must be clean
        d = diff_rows({**doc["rows"]["BENCH_r98"], "name": "BENCH_r98"},
                      {**doc["rows"]["BENCH_r98"], "name": "BENCH_r98"})
        assert d["verdict"] == "ok" and not d["regressions"], d

        # slower + more fusions + retrace growth vs the good round: every
        # gated axis must flag
        d = diff_rows({**doc["rows"]["BENCH_r99"], "name": "BENCH_r99"},
                      {**doc["rows"]["BENCH_r98"], "name": "BENCH_r98"})
        assert d["verdict"] == "regression", d
        for m in ("env_steps_per_sec", "chunk_step_fusions",
                  "chunk_step_mfu", "chunk_step_jit_traces"):
            assert m in d["regressions"], (m, d["regressions"])
        # flops unchanged and ungated
        assert d["metrics"]["chunk_step_flops"]["verdict"] \
            == "informational", d["metrics"]["chunk_step_flops"]

        # the reverse direction is an improvement, not a regression
        d = diff_rows({**doc["rows"]["BENCH_r98"], "name": "BENCH_r98"},
                      {**doc["rows"]["BENCH_r99"], "name": "BENCH_r99"})
        assert d["verdict"] == "ok" \
            and d["metrics"]["env_steps_per_sec"]["verdict"] == "improved"

        # learning-curve envelope: a run that learns less (lower final-
        # window return / AUC, slower to threshold, more residual TD)
        # regresses on every curve axis; self-compare stays clean
        crow = {**doc["rows"]["curves_curveself"], "name": "cur"}
        d = diff_rows(crow, {**doc["rows"]["curves_curveself"],
                             "name": "base"})
        assert d["verdict"] == "ok" and not d["regressions"], d
        worse = {"name": "worse", "status": "ok", "kind": "curves",
                 "metrics": {"final_window_return": 10.0, "auc_return": 3.0,
                             "episodes_to_threshold": 11.0,
                             "final_window_td_abs": 0.6, "episodes": 12.0}}
        d = diff_rows(worse, crow)
        assert d["verdict"] == "regression", d
        for m in ("final_window_return", "auc_return",
                  "episodes_to_threshold", "final_window_td_abs"):
            assert m in d["regressions"], (m, d["regressions"])
        # `episodes` carries no rule — run length is context, not a gate
        assert d["metrics"]["episodes"]["verdict"] == "informational", d
        # absolute band floor: returns oscillating around zero must not
        # gate on noise (relative band alone would be ~0.002 here)
        d = diff_rows({"name": "n1",
                       "metrics": {"final_window_return": -0.01}},
                      {"name": "n0",
                       "metrics": {"final_window_return": 0.01}})
        assert d["verdict"] == "ok", d
        # ...while a real collapse past the floor still flags
        d = diff_rows({"name": "n2",
                       "metrics": {"final_window_return": -2.5}},
                      {"name": "n0",
                       "metrics": {"final_window_return": 0.01}})
        assert d["verdict"] == "regression", d

        # serving SLO bands: a run that misses more deadlines, wastes
        # more padding, queues longer and burns budget faster regresses
        # on every slo axis; attainment collapse flags too
        srow = {**doc["rows"]["slo_sloself"], "name": "slo_base"}
        d = diff_rows(srow, srow)
        assert d["verdict"] == "ok" and not d["regressions"], d
        worse_slo = {"name": "slo_bad", "status": "ok", "kind": "slo",
                     "metrics": {"slo_deadline_miss_ratio": 0.4,
                                 "slo_pad_waste": 0.6,
                                 "slo_queue_wait_frac": 0.7,
                                 "slo_burn_rate": 4.0,
                                 "slo_attainment": 0.6}}
        d = diff_rows(worse_slo, srow)
        assert d["verdict"] == "regression", d
        for m in ("slo_deadline_miss_ratio", "slo_pad_waste",
                  "slo_queue_wait_frac", "slo_burn_rate",
                  "slo_attainment"):
            assert m in d["regressions"], (m, d["regressions"])
        # the reverse direction improves, never flags
        d = diff_rows(srow, worse_slo)
        assert d["verdict"] == "ok" and not d["regressions"], d
        # absolute floors: near-zero miss-ratio jitter is noise, not a
        # regression (relative band alone would be ~0)
        d = diff_rows({"name": "j1",
                       "metrics": {"slo_deadline_miss_ratio": 0.015}},
                      {"name": "j0",
                       "metrics": {"slo_deadline_miss_ratio": 0.0}})
        assert d["verdict"] == "ok", d
        # serve artifacts with per-leg slo blocks gate by leg
        serve_art = dump("SERVE_r96.json", {
            "metric": "serve_requests_per_sec",
            "cold_start_s": 0.5, "cache_hit_start_s": 0.2,
            "legs": {"warm": {"rps": 5000.0, "p50_ms": 1.0,
                              "p99_ms": 4.0,
                              "slo": {"deadline_miss_ratio": 0.1,
                                      "pad_waste": 0.25,
                                      "queue_wait_frac": 0.4,
                                      "burn_rate": 2.0,
                                      "attainment": 0.95,
                                      "arrival_rate_rps": 5100.0}}}})
        srow2 = extract_row(serve_art)
        assert srow2["metrics"]["warm_slo_deadline_miss_ratio"] == 0.1, \
            srow2["metrics"]
        # arrival rate stays ungated context (an `_rps` suffix would
        # wrongly ride the throughput band)
        assert not any("arrival" in m for m in srow2["metrics"]), \
            srow2["metrics"]
        worse_leg = dict(srow2, name="serve_bad",
                         metrics={**srow2["metrics"],
                                  "warm_slo_deadline_miss_ratio": 0.5})
        d = diff_rows(worse_leg, {**srow2, "name": "serve_base"})
        assert d["verdict"] == "regression" \
            and "warm_slo_deadline_miss_ratio" in d["regressions"], d

        # SCEN rounds (on-device scenario factory vs host regen): the
        # paired `_sps` rates gate under the throughput band, per-leg
        # trace counts under the 0% retrace band, the ratio + deleted
        # scenario_regen walls stay informational context
        scen = dump("SCEN_r95.json", {
            "metric": "env_steps_per_sec_per_chip", "status": "ok",
            "factory_sps": 30.0, "host_regen_sps": 24.0,
            "factory_vs_host": 1.25, "factory_scenario_regen_s": 0.02,
            "host_scenario_regen_s": 1.9,
            "jit_traces_factory": {"chunk_step": 1, "factory_sample": 1},
            "jit_traces_host_regen": {"chunk_step": 1}})
        scrow = extract_row(scen)
        assert scrow["metrics"]["factory_sps"] == 30.0 \
            and scrow["metrics"]["host_regen_sps"] == 24.0, \
            scrow["metrics"]
        assert scrow["metrics"]["factory_factory_sample_jit_traces"] \
            == 1.0, scrow["metrics"]
        d = diff_rows({**scrow, "name": "scen_self"},
                      {**scrow, "name": "scen_base"})
        assert d["verdict"] == "ok" and not d["regressions"], d
        assert d["metrics"]["factory_vs_host"]["verdict"] \
            == "informational", d["metrics"]["factory_vs_host"]
        slower_scen = dict(scrow, name="scen_slow",
                           metrics={**scrow["metrics"],
                                    "factory_sps": 20.0})
        d = diff_rows(slower_scen, {**scrow, "name": "scen_base"})
        assert d["verdict"] == "regression" \
            and "factory_sps" in d["regressions"], d

        # ASYNC flight-recorder axes: lag blow-up / actors starving on
        # the channel regress under their own bands; the absolute
        # floors absorb healthy-run jitter (lag oscillating by a
        # version, idle a few points above zero)
        arow = dump("ASYNC_r90.json", {
            "metric": "env_steps_per_sec_per_chip", "status": "ok",
            "sync_sps": 100.0, "async2_sps": 130.0,
            "learner_idle_frac": 0.02, "policy_lag_p99": 2.0,
            "actor_idle_frac": 0.05})
        abase = extract_row(arow)
        assert abase["metrics"]["policy_lag_p99"] == 2.0 \
            and abase["metrics"]["actor_idle_frac"] == 0.05, \
            abase["metrics"]
        d = diff_rows({**abase, "name": "async_self"},
                      {**abase, "name": "async_base"})
        assert d["verdict"] == "ok" and not d["regressions"], d
        jittery = dict(abase, name="async_jitter",
                       metrics={**abase["metrics"],
                                "policy_lag_p99": 3.0,
                                "actor_idle_frac": 0.11})
        d = diff_rows(jittery, {**abase, "name": "async_base"})
        assert d["verdict"] == "ok", d   # within floor-widened bands
        stale = dict(abase, name="async_stale",
                     metrics={**abase["metrics"],
                              "policy_lag_p99": 9.0,
                              "actor_idle_frac": 0.40})
        d = diff_rows(stale, {**abase, "name": "async_base"})
        assert d["verdict"] == "regression", d
        for m in ("policy_lag_p99", "actor_idle_frac"):
            assert m in d["regressions"], (m, d["regressions"])

        # ASYNC mesh rounds (r02): the per-device scaling axis gates
        # under its own 15% band, the zero-collective ingest contract
        # under 0% tolerance — ONE collective appearing on the compiled
        # dp ingest is a regression, not jitter; dp-leg trace counts
        # ride the `_jit_traces` retrace band
        mrow = dump("ASYNC_r91.json", {
            "metric": "env_steps_per_sec_per_chip", "status": "ok",
            "async2_sps": 130.0, "async_dp2_sps": 120.0,
            "async_dp2_sps_per_device": 60.0,
            "ingest_collectives": 0, "ring_shards": {"async_dp2": 2},
            "jit_traces_async_dp2": {"replay_ingest": 1}})
        mbase = extract_row(mrow)
        assert mbase["metrics"]["async_dp2_sps_per_device"] == 60.0 \
            and mbase["metrics"]["ingest_collectives"] == 0.0 \
            and mbase["metrics"]["async_dp2_replay_ingest_jit_traces"] \
            == 1.0, mbase["metrics"]
        assert mbase["context"]["ring_shards"] == {"async_dp2": 2}, \
            mbase["context"]
        d = diff_rows({**mbase, "name": "mesh_self"},
                      {**mbase, "name": "mesh_base"})
        assert d["verdict"] == "ok" and not d["regressions"], d
        leaky = dict(mbase, name="mesh_leaky",
                     metrics={**mbase["metrics"],
                              "async_dp2_sps_per_device": 40.0,
                              "ingest_collectives": 1.0})
        d = diff_rows(leaky, {**mbase, "name": "mesh_base"})
        assert d["verdict"] == "regression", d
        for m in ("async_dp2_sps_per_device", "ingest_collectives"):
            assert m in d["regressions"], (m, d["regressions"])

        # a widened tolerance declassifies a small regression
        d = diff_rows({"name": "a", "metrics": {"x_mfu": 0.9}},
                      {"name": "b", "metrics": {"x_mfu": 1.0}},
                      tolerances={"x_mfu": 0.5})
        assert d["verdict"] == "ok", d

        # failed rows never diff clean: a crashed current gates like a
        # regression, a crashed baseline like a missing one
        d = diff_rows({**doc["rows"]["BENCH_r97"], "name": "BENCH_r97"},
                      {**doc["rows"]["BENCH_r98"], "name": "BENCH_r98"})
        assert d["verdict"] == "failed-current", d
        rc = main(["diff", "BENCH_r97", "--baseline", "BENCH_r98",
                   "--trajectory", traj])
        assert rc == 1, rc
        rc = main(["diff", "BENCH_r98", "--baseline", "BENCH_r97",
                   "--trajectory", traj])
        assert rc == 3, rc

        # CLI: missing baseline is its own verdict + exit code
        rc = main(["diff", "BENCH_r98", "--baseline", "BENCH_r77",
                   "--trajectory", traj])
        assert rc == 3, rc
        rc = main(["diff", "BENCH_r99", "--baseline", "BENCH_r98",
                   "--trajectory", traj])
        assert rc == 1, rc
        rc = main(["diff", "BENCH_r98", "--baseline", "BENCH_r98",
                   "--trajectory", traj])
        assert rc == 0, rc
    print("bench_diff selftest: OK")
    return 0


# --------------------------------------------------------------------- cli
def _parse_tolerances(specs: List[str]) -> Dict[str, float]:
    out = {}
    for s in specs:
        if "=" not in s:
            raise SystemExit(f"--tolerance expects metric=frac, got {s!r}")
        k, v = s.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            raise SystemExit(f"--tolerance {s!r}: {v!r} is not a number")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true",
                    help="synthetic-artifact verdict check (CI smoke)")
    sub = ap.add_subparsers(dest="cmd")
    ing = sub.add_parser("ingest", help="normalize artifacts into the "
                                        "cumulative trajectory")
    ing.add_argument("paths", nargs="*", help="artifact files")
    ing.add_argument("--scan", default=None,
                     help="also glob BENCH_r*/MULTICHIP_r*/SERVE_r*/SCEN_r*/"
                          "perf.json/curves.json/slo.json under this "
                          "directory")
    ing.add_argument("--out", default="BENCH_TRAJECTORY.json")
    dif = sub.add_parser("diff", help="current vs named baseline, exit "
                                      "nonzero on regression")
    dif.add_argument("current", help="trajectory row name or artifact path")
    dif.add_argument("--baseline", required=True,
                     help="trajectory row name (or artifact path)")
    dif.add_argument("--trajectory", default="BENCH_TRAJECTORY.json")
    dif.add_argument("--tolerance", action="append", default=[],
                     metavar="METRIC=FRAC",
                     help="override a metric's relative band "
                          "(repeatable), e.g. --tolerance mfu=0.3")
    dif.add_argument("--json", action="store_true",
                     help="emit the full diff as JSON")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.cmd == "ingest":
        if not args.paths and not args.scan:
            ing.error("give artifact paths and/or --scan DIR")
        ingest(args.paths, args.out, scan=args.scan)
        return 0
    if args.cmd == "diff":
        doc = load_trajectory(args.trajectory)
        current = resolve_row(args.current, doc)
        if current is None:
            print(f"bench_diff: current {args.current!r} is neither a "
                  f"trajectory row nor a readable artifact",
                  file=sys.stderr)
            return 2
        baseline = resolve_row(args.baseline, doc)
        if baseline is None:
            print(json.dumps({"verdict": "missing-baseline",
                              "baseline": args.baseline,
                              "known_rows": sorted(doc.get("rows", {}))}))
            return 3
        d = diff_rows(current, baseline,
                      _parse_tolerances(args.tolerance))
        if args.json:
            print(json.dumps(d, indent=1))
        else:
            print(f"bench_diff: {d['current']} vs baseline "
                  f"{d['baseline']}: {d['verdict'].upper()} "
                  f"({d['gated_metrics']} gated metric(s))")
            for name, rec in d["metrics"].items():
                if rec["verdict"] in ("regression", "improved"):
                    print(f"  {rec['verdict']:>11}  {name}: "
                          f"{rec['baseline']} -> {rec['current']} "
                          f"({rec.get('change_pct', '?')}%)")
        # failed-current gates like a regression (a crashed round must
        # not pass); failed-baseline like missing-baseline (nothing to
        # regress against)
        return {"regression": 1, "failed-current": 1,
                "failed-baseline": 3}.get(d["verdict"], 0)
    ap.error("give a subcommand (ingest | diff) or --selftest")
    return 2


if __name__ == "__main__":
    sys.exit(main())
