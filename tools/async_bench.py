"""ASYNC bench: sync control vs decoupled actor/learner at matched budgets.

CPU-only: this tool starts JAX child processes (and, for the smokes,
runs JAX in the parent first), and a chip belongs to one process at a
time — it refuses to start unless ``JAX_PLATFORMS=cpu``
(``gsc_tpu.runtime.require_cpu_env``).  Nothing it prints is a device
number.

The Sebulba-split's throughput claim, measured instead of asserted: four
fresh-subprocess legs run the SAME tiny flagship stack with the SAME
entry points (``reset_all`` / ``rollout_episodes`` / ``learn_burst``),
the same episode count and the same one-burst-per-episode gradient
budget (``learn_ratio=1.0``), and differ ONLY in how acting and
learning interleave:

- ``sync``: the control — one thread alternates rollout chunks and the
  episode's learn burst, the seed's strictly-coupled cadence (donating
  dispatch, the sync path's contract);
- ``async1`` / ``async2`` / ``async4``: ``run_async`` with 1 / 2 / 4
  actor threads feeding the device-resident ring through
  ``replay_ingest`` while the learner bursts back-to-back
  (``donate=False`` actor blocks, the one donated call is the ingest).

Banked as ``ASYNC_r01.json`` (``--bank``): per-leg env-steps/s (gated by
tools/bench_diff.py under the 15% ``_sps`` band once ingested), the
decoupling claim ``async >= sync at >= 2 actors``, the learner-idle
bound (``learner_idle_frac`` < 0.10 at steady state — the phase-ledger
proof the learner never waits on acting), the staleness ledger
(``policy_lag_max``, produced == ingested), and the banded learning-
curve equivalence (``final_window_return`` 20%/floor 1.0,
``auc_return`` 25%/floor 1.0 — actors act on K-burst-old weights by
design, so the bank refuses a green row only when the async curve
leaves the band, not when it is merely not bit-equal).  A round that
fails any gate parks as ``ASYNC_r01.failed.json`` — never overwriting a
previously banked green artifact — and still ingests as a failed row.

Round r02 (``--round r02``, banked as ``ASYNC_r02.json``) sweeps the
MESH axis instead of the actor-count axis: ``async2`` re-runs as the
single-device baseline, and ``async_dp2`` / ``async_dp4`` run the SAME
stack on 2 / 4 forced host devices
(``--xla_force_host_platform_device_count``) under a pure-dp
``ShardingPlan`` (``2x1`` / ``4x1``) — the dp-sharded replay ring with
the shard_map per-shard donated ingest.  Gates: drain accounting per
leg, ``ingest_collectives == 0`` on every dp leg (the HLO-mined
zero-collective ingest contract), learner-idle bound, and per-grid
throughput above the baseline's per-device share (``DP_SHARE_FLOOR``
— the forced devices slice ONE physical core, so dp legs pay real
overhead and can never win; the floor catches collective storms,
bench_diff's bands catch cross-round drift).

Usage:
    JAX_PLATFORMS=cpu python tools/async_bench.py --bank
    JAX_PLATFORMS=cpu python tools/async_bench.py --round r02 --bank
    JAX_PLATFORMS=cpu python tools/async_bench.py --worker async_dp2
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

B = 8
EPISODE_STEPS = 10
CHUNK = 5
MEASURE_EPISODES = 6
FINAL_WINDOW = 3
MAX_NODES, MAX_EDGES = 12, 16
LEG_TIMEOUT_S = 900
IDLE_FRAC_MAX = 0.10
CURVE_BANDS = {"final_window_return": (0.20, 1.0),
               "auc_return": (0.25, 1.0)}
LEGS = ("sync", "async1", "async2", "async4")
# round r02: the mesh sweep — single-device async2 baseline vs the SAME
# stack dp-sharded over 2 / 4 forced host devices (pure-dp plans)
LEGS_R02 = ("async2", "async_dp2", "async_dp4")
# per-grid throughput floor for the dp legs, as a fraction of
# async2_sps / devices: forced host devices slice ONE physical core N
# ways, so a dp leg pays real partition/sync overhead per device
# (measured ~33% at 2, ~45% at 4 on this box) and can never win.  The
# honest in-round gate is a FLOOR at the baseline's per-device share —
# dp-sharding must beat running the whole grid's work on 1/N of the
# core, which a collective-regressed ingest (the GSPMD row-scatter
# emitted 28 all-gathers before the shard_map rewrite) crashes
# through.  Cross-round drift of the banked absolute rates is
# bench_diff's 15% `_sps`/`_sps_per_device` bands' job, not this
# gate's; per-device SCALING is the chip window's to measure.
DP_SHARE_FLOOR = 1.0


def _leg_devices(leg: str) -> int:
    return int(leg[len("async_dp"):]) if leg.startswith("async_dp") else 1


def _configure_jax():
    """main() has already refused anything but JAX_PLATFORMS=cpu."""
    from gsc_tpu.runtime import enable_compile_cache

    enable_compile_cache()


def _curve_metrics(returns):
    w = returns[-FINAL_WINDOW:]
    return (round(sum(w) / len(w), 4),
            round(sum(returns) / len(returns), 4))


def worker(leg: str) -> int:
    """One leg, printed as a JSON line (the bank parses the last line)."""
    if leg not in LEGS and leg not in LEGS_R02:
        raise SystemExit(f"unknown leg {leg!r} "
                         f"(want one of {LEGS + LEGS_R02[1:]})")
    _configure_jax()
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as ge
    from gsc_tpu.analysis.sentinels import CompileMonitor
    from gsc_tpu.parallel import ParallelDDPG, ShardingPlan
    from gsc_tpu.utils.telemetry import PhaseTimer

    devices = _leg_devices(leg)
    if leg.startswith("async_dp"):
        actors = 2   # matched to the async2 baseline leg
        if len(jax.devices()) != devices:
            raise SystemExit(
                f"{leg} needs {devices} forced host devices, found "
                f"{len(jax.devices())} — run via the bank (it sets "
                "--xla_force_host_platform_device_count)")
    else:
        actors = 0 if leg == "sync" else int(leg[len("async"):])
    plan = ShardingPlan.from_spec(f"{devices}x1") if devices > 1 else None
    env, agent, topo, traffic0 = ge._flagship(
        max_nodes=MAX_NODES, max_edges=MAX_EDGES,
        episode_steps=EPISODE_STEPS, max_flows=64)
    traffic = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * B), traffic0)
    monitor = CompileMonitor().start()
    base = jax.random.PRNGKey(0)
    chunks = EPISODE_STEPS // CHUNK
    # donate on the sync control (its historic dispatch contract); the
    # async legs hand actor blocks across threads by reference — their
    # one donated call is run_async's learner-owned replay_ingest
    pddpg = ParallelDDPG(env, agent, num_replicas=B,
                         donate=(actors == 0), plan=plan)
    env_states, obs = pddpg.reset_all(base, topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)

    row = {"leg": leg, "status": "ok", "replicas": B, "chunk": CHUNK,
           "episode_steps": EPISODE_STEPS,
           "episodes_measured": MEASURE_EPISODES, "async_actors": actors,
           "devices": devices,
           "mesh": plan.describe() if plan is not None else None}

    def traces():
        return {fn: t for fn, (t, _c) in monitor.snapshot().items()
                if t and fn in ("rollout_episodes", "learn_burst",
                                "reset_all", "replay_ingest")}

    if actors == 0:
        # the control: strictly alternating act/learn on one thread,
        # same entry points, one burst per episode
        def sync_episode(ep, state, buffers):
            env_states, obs = pddpg.reset_all(
                jax.random.fold_in(base, ep), topo, traffic)
            ret = 0.0
            for c in range(chunks):
                start = jnp.int32(ep * EPISODE_STEPS + c * CHUNK)
                state, buffers, env_states, obs, stats = \
                    pddpg.rollout_episodes(state, buffers, env_states,
                                           obs, topo, traffic, start,
                                           CHUNK)
                ret += float(stats["episodic_return"])
            state, _metrics = pddpg.learn_burst(state, buffers)
            return state, buffers, ret

        t_warm = time.time()
        state, buffers, _ = sync_episode(0, state, buffers)
        jax.block_until_ready(state.actor_params)
        warm_s = time.time() - t_warm
        returns = []
        t0 = time.time()
        for ep in range(1, MEASURE_EPISODES + 1):
            state, buffers, ret = sync_episode(ep, state, buffers)
            returns.append(ret)
        jax.block_until_ready(state.actor_params)
        wall = time.time() - t0
        final_w, auc = _curve_metrics(returns)
        row.update({
            "sps": round(MEASURE_EPISODES * EPISODE_STEPS * B / wall, 2),
            "measure_wall_s": round(wall, 2), "warmup_s": round(warm_s, 2),
            "final_window_return": final_w, "auc_return": auc,
            "returns": [round(r, 4) for r in returns],
            "jit_traces": traces(),
        })
    else:
        from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

        scenario_fn = lambda ep: (topo, traffic)   # noqa: E731
        cfg = AsyncConfig(actor_threads=actors)
        # warmup: one episode per actor compiles every entry point on
        # both sides of the split (reset_all/rollout_episodes actor-side,
        # replay_ingest/learn_burst learner-side)
        t_warm = time.time()
        res = run_async(pddpg, scenario_fn, state, buffers,
                        episodes=actors, episode_steps=EPISODE_STEPS,
                        chunk=CHUNK, seed=0, cfg=cfg)
        state, buffers = res.state, res.buffers
        warm_s = time.time() - t_warm
        timer = PhaseTimer()   # fresh ledger: warmup wall excluded
        t0 = time.time()
        res = run_async(pddpg, scenario_fn, state, buffers,
                        episodes=actors + MEASURE_EPISODES,
                        episode_steps=EPISODE_STEPS, chunk=CHUNK, seed=0,
                        cfg=cfg, timer=timer, start_episode=actors)
        wall = time.time() - t0
        # curve in EPISODE-INDEX order (completion order is a thread
        # race; the index rides on every drained record)
        eps = sorted(res.episodes, key=lambda r: r["episode"])
        returns = [r["episodic_return"] for r in eps]
        final_w, auc = _curve_metrics(returns)
        info = res.info
        sps = round(MEASURE_EPISODES * EPISODE_STEPS * B / wall, 2)
        row.update({
            "sps": sps,
            # per-grid vs per-device: on a real pod sps_per_device is the
            # scaling-efficiency axis; on the forced-device CPU box it
            # documents how thin the shared core is sliced
            "sps_per_device": round(sps / devices, 2),
            "ring_shards": info.get("ring_shards", 1),
            "ingest_collectives": info.get("ingest_collectives"),
            "measure_wall_s": round(wall, 2), "warmup_s": round(warm_s, 2),
            "final_window_return": final_w, "auc_return": auc,
            "returns": [round(r, 4) for r in returns],
            "learner_idle_frac": info["learner_idle_frac"],
            "learner_idle_s": info["learner_idle_s"],
            "bursts": info["bursts"],
            "produced_steps": info["produced_steps"],
            "ingested_steps": info["ingested_steps"],
            "transitions_lost": info["transitions_lost"],
            "policy_lag_max": info["policy_lag_max"],
            "policy_lag_mean": info["policy_lag_mean"],
            # flight-recorder lag/idle axes (PR 17): staleness
            # percentiles + the dispatch-side idle twin of the
            # learner-idle gate (max over actors; per-actor vector kept
            # for the leg record)
            "policy_lag_p50": info.get("policy_lag_p50", 0),
            "policy_lag_p99": info.get("policy_lag_p99", 0),
            "actor_idle_frac": info.get("actor_idle_frac", 0.0),
            "actor_idle_fracs": info.get("actor_idle_fracs", []),
            "phases": timer.summary(),
            "jit_traces": traces(),
        })
    print(json.dumps(row), flush=True)
    return 0


def _run_leg(leg: str) -> dict:
    """Fresh subprocess per leg (the 1-core box must never run two jax
    programs concurrently; a fresh process also keeps the legs'
    trace-count accounting independent)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", leg]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # mesh legs: carve N virtual host devices out of the one CPU before
    # jax initialises; non-mesh legs must NOT inherit a forced count
    # from the caller's environment
    devices = _leg_devices(leg)
    if devices > 1:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    else:
        env.pop("XLA_FLAGS", None)
    t0 = time.time()
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=LEG_TIMEOUT_S, env=env)
    except subprocess.TimeoutExpired:
        return {"leg": leg, "status": "failed",
                "reason": f"timeout after {LEG_TIMEOUT_S}s"}
    lines = [l for l in out.stdout.strip().splitlines() if l.strip()]
    for line in reversed(lines):
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict) and row.get("leg") == leg:
            row["leg_wall_s"] = round(time.time() - t0, 1)
            return row
    return {"leg": leg, "status": "failed",
            "reason": f"rc={out.returncode}, no parseable row",
            "tail": (out.stdout + out.stderr)[-2000:]}


def _within(name: str, a: float, b: float) -> bool:
    rel, floor = CURVE_BANDS[name]
    return abs(a - b) <= max(rel * abs(b), floor)


def main(argv=None) -> int:
    from gsc_tpu.runtime import require_cpu_env
    require_cpu_env("tools/async_bench.py")
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", default=None,
                    help="run one leg in-process "
                         f"({'|'.join(LEGS + LEGS_R02[1:])})")
    ap.add_argument("--round", default="r01", choices=("r01", "r02"),
                    dest="round_", metavar="ROUND",
                    help="r01: actor-count sweep (sync control); "
                         "r02: mesh sweep (dp-sharded ring on forced "
                         "host devices)")
    ap.add_argument("--bank", action="store_true",
                    help="write ASYNC_<round>.json next to the repo root")
    ap.add_argument("--out", default=None,
                    help="bank path (default <repo>/ASYNC_<round>.json)")
    ap.add_argument("--trajectory", default=None,
                    help="also ingest the banked row into this "
                         "BENCH_TRAJECTORY.json")
    args = ap.parse_args(argv)
    if args.worker:
        return worker(args.worker)
    if args.round_ == "r02":
        return _main_r02(args)

    legs = {leg: _run_leg(leg) for leg in LEGS}
    ok = all(l.get("status") == "ok" for l in legs.values())
    doc = {
        "metric": "env_steps_per_sec_per_chip",
        "unit": "env-steps/s", "round": 1, "platform": "cpu",
        "status": "ok" if ok else "failed",
        "replicas": B, "chunk": CHUNK, "episode_steps": EPISODE_STEPS,
        "episodes_measured": MEASURE_EPISODES,
        "legs": [legs[leg] for leg in LEGS],
    }
    reasons = []
    if ok:
        s, a1, a2, a4 = (legs[leg] for leg in LEGS)
        idle = max(a2["learner_idle_frac"], a4["learner_idle_frac"])
        doc.update({
            "sync_sps": s["sps"], "async1_sps": a1["sps"],
            "async2_sps": a2["sps"], "async4_sps": a4["sps"],
            "async2_vs_sync": round(a2["sps"] / s["sps"], 3),
            "async4_vs_sync": round(a4["sps"] / s["sps"], 3),
            "async_actors": 2,   # the headline gated leg
            "learner_idle_frac": idle,
            "policy_lag_max": max(a2["policy_lag_max"],
                                  a4["policy_lag_max"]),
            # worst-case staleness p99 / actor-idle across the async
            # legs: the bench_diff `policy_lag_p99` and
            # `actor_idle_frac` bands gate these (BENCH_NOTES
            # conventions for ASYNC rows)
            "policy_lag_p99": max(a2.get("policy_lag_p99", 0),
                                  a4.get("policy_lag_p99", 0)),
            "actor_idle_frac": max(a2.get("actor_idle_frac", 0.0),
                                   a4.get("actor_idle_frac", 0.0)),
            "produced_steps": a2["produced_steps"],
            "ingested_steps": a2["ingested_steps"],
            "sync_final_window_return": s["final_window_return"],
            "async_final_window_return": a2["final_window_return"],
            "sync_auc_return": s["auc_return"],
            "async_auc_return": a2["auc_return"],
            "jit_traces_sync": s["jit_traces"],
            "jit_traces_async1": a1["jit_traces"],
            "jit_traces_async2": a2["jit_traces"],
            "jit_traces_async4": a4["jit_traces"],
        })
        # gate 1: the decoupling claim — async >= sync at >= 2 actors
        for leg in (a2, a4):
            if leg["sps"] < s["sps"]:
                reasons.append(
                    f"{leg['leg']}_sps {leg['sps']} < sync_sps {s['sps']} "
                    "— the round does not support the decoupling claim")
        # gate 2: the learner never waits on acting at steady state
        for leg in (a2, a4):
            if leg["learner_idle_frac"] >= IDLE_FRAC_MAX:
                reasons.append(
                    f"{leg['leg']} learner_idle_frac "
                    f"{leg['learner_idle_frac']} >= {IDLE_FRAC_MAX} — "
                    "the learner waited on acting")
        # gate 3: drain-proved accounting on every async leg
        for leg in (a1, a2, a4):
            if leg["transitions_lost"] != 0 \
                    or leg["produced_steps"] != leg["ingested_steps"]:
                reasons.append(f"{leg['leg']} lost transitions: "
                               f"produced {leg['produced_steps']} vs "
                               f"ingested {leg['ingested_steps']}")
        # gate 4: banded curve equivalence at the matched budget
        for name, s_key, a_key in (
                ("final_window_return", "sync_final_window_return",
                 "async_final_window_return"),
                ("auc_return", "sync_auc_return", "async_auc_return")):
            if not _within(name, doc[a_key], doc[s_key]):
                rel, floor = CURVE_BANDS[name]
                reasons.append(
                    f"async {name} {doc[a_key]} outside the "
                    f"{int(rel * 100)}%/floor-{floor} band around sync "
                    f"{doc[s_key]}")
        doc["async_ge_sync"] = not any("decoupling" in r for r in reasons)
        doc["note"] = (
            "Matched-budget comparison on the 1-core CPU box (fresh "
            "subprocess per leg, warm persistent compile cache, warmup "
            "episodes excluded): same entry points, same "
            f"{MEASURE_EPISODES}x{EPISODE_STEPS}x{B} env-step and "
            "one-burst-per-episode gradient budgets; the sync control "
            "alternates act/learn on one thread, the async legs feed "
            "the device-resident ring from 1/2/4 actor threads while "
            f"the learner bursts back-to-back.  sync {s['sps']} vs "
            f"async2 {a2['sps']} / async4 {a4['sps']} env-steps/s, "
            f"learner_idle_frac {idle}, policy_lag_max "
            f"{doc['policy_lag_max']}.  Curves are banded, not "
            "bit-equal: actors act on K-burst-old weights by design.")
        try:
            import jax
            doc["jax"] = jax.__version__
        except Exception:
            pass
    return _finish(doc, ok, reasons, args, "ASYNC_r01.json")


def _finish(doc, ok, reasons, args, default_name) -> int:
    claim_holds = ok and not reasons
    if ok and reasons:
        doc["status"] = "failed"
        doc["reason"] = "; ".join(reasons)
    print(json.dumps(doc, indent=1))
    if args.bank or args.out:
        out = args.out or os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), default_name)
        if not claim_holds:
            # never overwrite a previously banked GREEN artifact with a
            # losing/failed round — park the evidence next to it (the
            # ASYNC_r*.json scan still ingests it as a failed row)
            out = os.path.splitext(out)[0] + ".failed.json"
        with open(out, "w") as fobj:
            json.dump(doc, fobj, indent=1)
            fobj.write("\n")
        print(f"[async_bench] banked {out}")
        if args.trajectory:
            import bench_diff
            bench_diff.ingest([out], args.trajectory)
        if not claim_holds:
            print("[async_bench] FAIL: "
                  f"{doc.get('reason', 'leg failure')}")
            return 1
    return 0 if claim_holds else 1


def _main_r02(args) -> int:
    """The mesh round: dp-sharded ring on forced host devices vs the
    single-device async2 baseline, same actor count everywhere."""
    legs = {leg: _run_leg(leg) for leg in LEGS_R02}
    ok = all(l.get("status") == "ok" for l in legs.values())
    doc = {
        "metric": "env_steps_per_sec_per_chip",
        "unit": "env-steps/s", "round": 2, "platform": "cpu",
        "status": "ok" if ok else "failed",
        "replicas": B, "chunk": CHUNK, "episode_steps": EPISODE_STEPS,
        "episodes_measured": MEASURE_EPISODES, "async_actors": 2,
        "legs": [legs[leg] for leg in LEGS_R02],
    }
    reasons = []
    if ok:
        a2, d2, d4 = (legs[leg] for leg in LEGS_R02)
        dp_legs = (d2, d4)
        idle = max(l["learner_idle_frac"] for l in legs.values())
        doc.update({
            "async2_sps": a2["sps"],
            "async_dp2_sps": d2["sps"], "async_dp4_sps": d4["sps"],
            "async2_sps_per_device": a2["sps_per_device"],
            "async_dp2_sps_per_device": d2["sps_per_device"],
            "async_dp4_sps_per_device": d4["sps_per_device"],
            "async_dp2_vs_async2": round(d2["sps"] / a2["sps"], 3),
            "async_dp4_vs_async2": round(d4["sps"] / a2["sps"], 3),
            "mesh": {l["leg"]: l["mesh"] for l in dp_legs},
            "ring_shards": {l["leg"]: l["ring_shards"]
                            for l in legs.values()},
            # HLO-mined collective count on the compiled ingest, worst
            # dp leg — 0 or the round is dead (bench_diff gates growth
            # at 0% tolerance once banked)
            "ingest_collectives": max(int(l["ingest_collectives"] or 0)
                                      for l in dp_legs),
            "learner_idle_frac": idle,
            "policy_lag_max": max(l["policy_lag_max"]
                                  for l in legs.values()),
            "policy_lag_p99": max(l.get("policy_lag_p99", 0)
                                  for l in legs.values()),
            "actor_idle_frac": max(l.get("actor_idle_frac", 0.0)
                                   for l in legs.values()),
            "produced_steps": d4["produced_steps"],
            "ingested_steps": d4["ingested_steps"],
            "jit_traces_async2": a2["jit_traces"],
            "jit_traces_async_dp2": d2["jit_traces"],
            "jit_traces_async_dp4": d4["jit_traces"],
        })
        # gate 1: drain-proved accounting on every leg
        for l in legs.values():
            if l["transitions_lost"] != 0 \
                    or l["produced_steps"] != l["ingested_steps"]:
                reasons.append(f"{l['leg']} lost transitions: "
                               f"produced {l['produced_steps']} vs "
                               f"ingested {l['ingested_steps']}")
        # gate 2: the zero-collective ingest contract — blocks land on
        # the learner mesh exactly once and never move again
        for l in dp_legs:
            if int(l["ingest_collectives"] or 0) != 0:
                reasons.append(
                    f"{l['leg']} compiled replay_ingest with "
                    f"{l['ingest_collectives']} collective op(s) — the "
                    "dp-sharded ring is paying a gather/reshard per "
                    "block")
        # gate 3: the learner never waits on acting at steady state
        for l in legs.values():
            if l["learner_idle_frac"] >= IDLE_FRAC_MAX:
                reasons.append(
                    f"{l['leg']} learner_idle_frac "
                    f"{l['learner_idle_frac']} >= {IDLE_FRAC_MAX} — "
                    "the learner waited on acting")
        # gate 4: per-grid throughput above the baseline's per-device
        # share — see DP_SHARE_FLOOR for why this is a floor, not a band
        for l in dp_legs:
            floor = round(DP_SHARE_FLOOR * a2["sps"] / l["devices"], 2)
            if l["sps"] < floor:
                reasons.append(
                    f"{l['leg']}_sps {l['sps']} < {floor} "
                    f"(async2_sps {a2['sps']} / {l['devices']} devices) "
                    "— sharding overhead ate the whole parallelism "
                    "budget (collective storm on the hot path?)")
        doc["note"] = (
            "Mesh sweep on the 1-core CPU box (fresh subprocess per "
            "leg; dp legs carve the core into forced host devices with "
            "--xla_force_host_platform_device_count, so per-grid "
            "throughput can only LOSE to sharding overhead — the gate "
            "is a FLOOR at async2_sps/devices, the baseline's "
            "per-device share, not a speedup claim; cross-round drift "
            "gates under bench_diff's 15% rate bands).  All "
            f"legs: {MEASURE_EPISODES}x{EPISODE_STEPS}x{B} env-steps, "
            "2 actor threads, one burst per episode.  dp legs run the "
            "replay ring resident-sharded over the plan's dp axis with "
            "the shard_map per-shard donated ingest; "
            f"ingest_collectives {doc['ingest_collectives']} (HLO-mined "
            "on the AOT-compiled ingest executable).  async2 "
            f"{a2['sps']} vs async_dp2 {d2['sps']} / async_dp4 "
            f"{d4['sps']} env-steps/s, learner_idle_frac {idle}, "
            f"policy_lag_p99 {doc['policy_lag_p99']}.")
        try:
            import jax
            doc["jax"] = jax.__version__
        except Exception:
            pass
    return _finish(doc, ok, reasons, args, "ASYNC_r02.json")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
