"""Flight-recorder smoke: the async fleet's black-box layer end to end
through the real CLI.

The CI-stage proof that the PR-17 observability actually lands on a real
``cli train --async`` run plus a deliberately wedged fleet:

- a tiny 3-episode, 2-replica, 2-actor CPU train run with the series
  recorder on must exit 0 and leave a schema-versioned ``series.json``
  whose rings are non-trivial (>= 3 metrics, including the async verdict
  series) and whose LAST points agree with the final ``metrics.json``
  snapshot — history never drifts from the gauges,
- the same run's event stream must reconstruct a STRICT-validator-clean
  Chrome trace with one track per actor (rollout/put spans), the
  channel's put→pop residency slices, learner ingest/learn-burst spans
  and BALANCED publish→adopt flow arrows,
- an injected wedge (one fleet thread registered with the watchdog and
  never beating again, stuck in ``blocked_put``) must produce a stall
  event NAMING that thread and phase, then escalate into a
  ``blackbox.json`` post-mortem carrying the series tail and the
  thread-phase picture,
- gate through ``bench_diff``: an ASYNC-shaped row with the new
  ``policy_lag_p99`` / ``actor_idle_frac`` fields self-compares clean
  (rc 0) while an injected staleness blow-up is caught (rc 1).

Run by ``tools/ci_check.sh`` after the async stage; standalone:

    JAX_PLATFORMS=cpu python tools/flight_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EPISODES = 3
ACTORS = 2
SERIES_WINDOW = 256
# the wedge stage's per-thread heartbeat budget (escalation fires at
# budget * (1 + escalate_after) of silence; the poll floor is 0.25s)
WEDGE_BUDGET_S = 0.05


def _configure_jax():
    import jax

    from gsc_tpu.runtime import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")   # a CPU smoke, explicitly
    enable_compile_cache()


def fail(msg: str) -> int:
    print(f"flight smoke: FAIL — {msg}")
    return 1


def _check_series(rdir: str):
    """series.json: schema-versioned, non-trivial, last points == the
    final metrics.json gauges.  Returns (error, n_series, n_matched)."""
    from gsc_tpu.obs import SERIES_SCHEMA_VERSION
    spath = os.path.join(rdir, "series.json")
    if not os.path.exists(spath):
        return "series.json missing from the run dir", 0, 0
    doc = json.load(open(spath))
    if doc.get("schema_version") != SERIES_SCHEMA_VERSION:
        return f"series.json schema_version {doc.get('schema_version')}", 0, 0
    series = doc.get("series") or {}
    if len(series) < 3:
        return f"series.json holds {len(series)} rings (want >= 3)", 0, 0
    for want in ("gsc_sps{", "gsc_learner_idle_frac{",
                 "gsc_actor_idle_frac{"):
        if not any(k.startswith(want) for k in series):
            return f"series.json missing the {want}... ring", 0, 0
    snap = json.load(open(os.path.join(rdir, "metrics.json")))["metrics"]
    matched = 0
    for name, pts in series.items():
        if any(a[0] > b[0] for a, b in zip(pts, pts[1:])):
            return f"ring {name} timestamps not monotone", 0, 0
        if name in snap:
            if abs(float(snap[name]) - float(pts[-1][1])) > 1e-9:
                return (f"ring {name} last point {pts[-1][1]} != "
                        f"snapshot {snap[name]}"), 0, 0
            matched += 1
    if matched < 3:
        return (f"only {matched} rings intersect metrics.json "
                "(want >= 3)"), 0, 0
    return None, len(series), matched


def _check_trace(rdir: str):
    """Strict-validator-clean async trace with per-actor tracks and
    balanced flow arrows.  Returns (error, n_trace_events)."""
    from gsc_tpu.obs.trace import (ACTOR_TRACK_BASE, TRACE_TRACKS,
                                   build_trace, read_events,
                                   validate_trace)
    events = read_events(os.path.join(rdir, "events.jsonl"))
    kinds = {e.get("event") for e in events}
    if not {"async_actor_ep", "async_learner_spans"} <= kinds:
        return f"flight-ledger events missing from the stream: {kinds}", 0
    trace = build_trace(events)
    errors = validate_trace(trace)
    if errors:
        return f"trace validator: {errors[:3]} (+{len(errors) - 3})" \
            if len(errors) > 3 else f"trace validator: {errors}", 0
    tev = trace["traceEvents"]
    names = {e["args"]["name"] for e in tev
             if e["ph"] == "M" and e["name"] == "thread_name"}
    want_tracks = {f"actor{a}" for a in range(ACTORS)}
    if not want_tracks <= names:
        return f"actor tracks {want_tracks} not announced (got {names})", 0
    rollout_tids = {e["tid"] for e in tev if e["ph"] == "X"
                    and e["name"].startswith("rollout ep")}
    if rollout_tids != {ACTOR_TRACK_BASE + a for a in range(ACTORS)}:
        return f"rollout spans on tracks {rollout_tids}", 0
    if not any(e["ph"] == "X" and e["name"].startswith("block s")
               and e["tid"] == TRACE_TRACKS["channel"] for e in tev):
        return "no channel residency slices", 0
    ltid = TRACE_TRACKS["learner"]
    for name in ("replay_ingest", "learn_burst"):
        if not any(e["ph"] == "X" and e["name"].startswith(name)
                   and e["tid"] == ltid for e in tev):
            return f"no {name} spans on the learner track", 0
    for flow in ("chan", "publish v"):
        n_s = sum(1 for e in tev
                  if e["ph"] == "s" and e["name"].startswith(flow))
        n_f = sum(1 for e in tev
                  if e["ph"] == "f" and e["name"].startswith(flow))
        if n_s != n_f:
            return f"{flow!r} flows unbalanced: {n_s} starts/{n_f} ends", 0
    return None, len(tev)


def _check_wedge(tmp: str):
    """Injected wedge: a watched fleet thread that never beats again must
    stall BY NAME and escalate into the black-box dump."""
    from gsc_tpu.obs import BLACKBOX_SCHEMA_VERSION, RunObserver
    obs = RunObserver(os.path.join(tmp, "wedge"), run_id="wedge",
                      series_window=32, watchdog_budget_s=WEDGE_BUDGET_S,
                      watchdog_escalate=1, compile_events=False)
    obs.start(meta={"stage": "flight_smoke_wedge"})
    obs.hub.series("policy_lag", 2.0)
    obs.resume_watchdog()
    obs.watch_fleet(["actor0", "actor1", "learner"],
                    budget_s=WEDGE_BUDGET_S)
    obs.hub.note_thread_phase("actor0", "dispatch")
    obs.hub.note_thread_phase("actor1", "blocked_put")
    deadline = time.time() + 10.0
    while time.time() < deadline \
            and not os.path.exists(obs.blackbox_path):
        # healthy threads (and the main loop) keep beating; actor1 never
        # beats again — the wedge under test
        obs.hub.beat("episode")
        obs.hub.beat("actor0")
        obs.hub.beat("learner")
        time.sleep(0.02)
    obs.close()
    if not os.path.exists(obs.blackbox_path):
        return "wedged actor never escalated into blackbox.json"
    doc = json.load(open(obs.blackbox_path))
    if doc.get("schema_version") != BLACKBOX_SCHEMA_VERSION:
        return f"blackbox schema_version {doc.get('schema_version')}"
    if doc.get("reason") != "watchdog_escalation:actor1":
        return f"blackbox reason {doc.get('reason')!r}"
    if doc.get("thread_phases", {}).get("actor1") != "blocked_put":
        return f"blackbox thread_phases {doc.get('thread_phases')}"
    if not any(k.startswith("gsc_policy_lag") for k in doc.get("series", {})):
        return "blackbox series tail missing the policy_lag ring"
    events = [json.loads(line) for line in open(obs.events_path)]
    stalls = [e for e in events if e.get("event") == "stall"
              and e.get("thread") == "actor1"]
    if not stalls:
        return "no stall event naming the wedged actor"
    if stalls[0].get("last_phase") != "blocked_put":
        return f"stall last_phase {stalls[0].get('last_phase')!r}"
    return None


def main() -> int:
    _configure_jax()
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    tmp = tempfile.mkdtemp(prefix="gsc_flight_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", str(EPISODES), "--replicas", "2",
        "--chunk", "3", "--async", "--async-actors", str(ACTORS),
        "--obs-series-window", str(SERIES_WINDOW), "--no-perf",
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code} under --async")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]

    err, n_series, n_matched = _check_series(rdir)
    if err:
        return fail(err)
    err, n_trace = _check_trace(rdir)
    if err:
        return fail(err)
    err = _check_wedge(tmp)
    if err:
        return fail(err)

    # bench_diff gate over the ASYNC row's new staleness/idle fields:
    # self-compare clean, injected policy-lag blow-up caught
    import bench_diff
    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]
    info = [e for e in events if e.get("event") == "async_train"][-1]
    row = {"metric": "env_steps_per_sec_per_chip", "status": "ok",
           "async_actors": ACTORS, "sync_sps": 100.0, "async2_sps": 100.0,
           "learner_idle_frac": round(float(info["learner_idle_frac"]), 4),
           "policy_lag_p99": float(info["policy_lag_p99"]),
           "actor_idle_frac": round(float(info["actor_idle_frac"]), 4)}
    row_path = os.path.join(tmp, "ASYNC_r98.json")
    with open(row_path, "w") as f:
        json.dump(row, f)
    traj = os.path.join(tmp, "traj.json")
    doc = bench_diff.ingest([row_path], traj)
    got = doc["rows"]["ASYNC_r98"]["metrics"]
    if "policy_lag_p99" not in got or "actor_idle_frac" not in got:
        return fail(f"ASYNC row missing flight metrics: {sorted(got)}")
    rc = bench_diff.main(["diff", "ASYNC_r98", "--baseline", "ASYNC_r98",
                          "--trajectory", traj])
    if rc != 0:
        return fail(f"ASYNC self-compare rc={rc} (want 0)")
    bad = dict(row, policy_lag_p99=float(info["policy_lag_p99"]) + 50.0)
    bad_path = os.path.join(tmp, "ASYNC_bad.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    rc = bench_diff.main(["diff", bad_path, "--baseline", "ASYNC_r98",
                          "--trajectory", traj])
    if rc != 1:
        return fail(f"injected policy-lag blow-up rc={rc} (want 1)")

    print(f"flight smoke: OK — {n_series} series rings ({n_matched} "
          f"snapshot-matched), validator-clean async trace "
          f"({n_trace} events), wedged actor1 escalated into "
          "blackbox.json, ASYNC flight fields gated both directions")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
