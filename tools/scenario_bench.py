"""SCEN bench: host-regen vs on-device scenario factory at equal B.

CPU-only: this tool starts JAX child processes (and, for the smokes,
runs JAX in the parent first), and a chip belongs to one process at a
time — it refuses to start unless ``JAX_PLATFORMS=cpu``
(``gsc_tpu.runtime.require_cpu_env``).  Nothing it prints is a device
number.

The factory's throughput claim, measured instead of asserted: two
fresh-subprocess legs run the SAME replica-parallel training shape
(equal B, equal episode_steps/chunk, per-episode scenario regeneration)
and differ ONLY in where the scenario pipeline runs:

- ``host_regen``: the PR 9 registry path with HOST traffic production —
  a K=4 ``--topo-mix``-style mixture whose per-replica
  ``TrafficSchedule`` is rebuilt in Python and shipped host->device
  every episode (``mix_traffic_host``), the cost the ``scenario_regen``
  phase makes visible;
- ``factory``: the on-device factory — one jitted ``factory_sample``
  call per episode draws fresh per-replica (topology, traffic, fault
  plan) tensors; the ``scenario_regen`` phase collapses to
  dispatch-enqueue time.

Banked as ``SCEN_r01.json`` (``--bank``): paired ``factory_sps`` /
``host_regen_sps`` rates (gated by tools/bench_diff.py under the 15%
``_sps`` band once ingested), per-leg ``scenario_regen`` walls, per-leg
dispatch trace counts (0%-band ``_jit_traces`` keys), and the
``factory_ge_host`` verdict the bank refuses to write green when the
claim fails.  The scenario DISTRIBUTIONS necessarily differ (a fixed
4-member mixture vs the sampled families) — the comparison is the
scenario-production pipeline at equal dispatch shape, not sim physics.

Usage:
    JAX_PLATFORMS=cpu python tools/scenario_bench.py --bank
    JAX_PLATFORMS=cpu python tools/scenario_bench.py --worker factory
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
MEASURE_EPISODES = 3
MAX_NODES, MAX_EDGES = 12, 16
HOST_MIX = "star6,ring6,line6,random8:3"
FACTORY_MIX = "factory:star-ring-line-random+shapes~faults"
LEG_TIMEOUT_S = 900


def _configure_jax():
    """main() has already refused anything but JAX_PLATFORMS=cpu."""
    from gsc_tpu.runtime import enable_compile_cache

    enable_compile_cache()


def worker(leg: str) -> int:
    """One leg, printed as a JSON line (the bank parses the last line)."""
    _configure_jax()
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as ge
    from gsc_tpu.analysis.sentinels import CompileMonitor
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.utils.telemetry import PhaseTimer

    env, agent, _, _ = ge._flagship(
        max_nodes=MAX_NODES, max_edges=MAX_EDGES,
        episode_steps=EPISODE_STEPS, max_flows=64, gen_traffic=False)
    monitor = CompileMonitor().start()
    timer = PhaseTimer()
    base = jax.random.PRNGKey(0)

    if leg == "factory":
        from gsc_tpu.topology.factory import ScenarioFactory, parse_factory
        factory = ScenarioFactory(
            parse_factory(FACTORY_MIX), env.sim_cfg, env.service,
            EPISODE_STEPS, max_nodes=MAX_NODES, max_edges=MAX_EDGES)
        probs = jnp.full((factory.spec.num_families,),
                         1.0 / factory.spec.num_families)

        def episode_scenario(ep):
            return factory.sample_batch(
                jax.random.fold_in(base, 2000 + ep), probs, B)
    elif leg == "host_regen":
        from gsc_tpu.topology import DEFAULT_REGISTRY, TopologyBucket
        from gsc_tpu.topology.scenarios import (build_mix_entries,
                                                mix_traffic_host, plan_mix)
        bucket = TopologyBucket(MAX_NODES, MAX_EDGES)
        entries = build_mix_entries(HOST_MIX, DEFAULT_REGISTRY, bucket,
                                    dt=env.sim_cfg.dt)
        plan = plan_mix(entries, B, bucket, env.sim_cfg, EPISODE_STEPS)

        def episode_scenario(ep):
            # the PR 9 host production path: per-replica Python traffic
            # generation + the host->device ship, every episode
            traffic = mix_traffic_host(
                plan, env.sim_cfg, env.service, EPISODE_STEPS,
                seed_for=lambda r: 1000 * ep + r)
            return plan.topo, jax.device_put(traffic)
    else:
        raise SystemExit(f"unknown leg {leg!r}")

    pddpg = ParallelDDPG(env, agent, num_replicas=B, donate=True,
                         per_replica_topology=True)
    chunks = EPISODE_STEPS // CHUNK

    def run_episode(ep, state, buffers):
        with timer.phase("scenario_regen"):
            topo, traffic = episode_scenario(ep)
        env_states, obs = pddpg.reset_all(
            jax.random.fold_in(base, ep), topo, traffic)
        with timer.phase("dispatch"):
            for c in range(chunks):
                start = jnp.int32(ep * EPISODE_STEPS + c * CHUNK)
                state, buffers, env_states, obs, stats, _ = \
                    pddpg.chunk_step(state, buffers, env_states, obs,
                                     topo, traffic, start, CHUNK,
                                     learn=(c == chunks - 1))
        return state, buffers, stats

    # warmup episode 0: compiles + the agent's random-action start
    topo0, traffic0 = episode_scenario(0)
    env_states, obs = pddpg.reset_all(base, topo0, traffic0)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    t_warm = time.time()
    state, buffers, stats = run_episode(0, state, buffers)
    jax.block_until_ready(stats)
    warm_s = time.time() - t_warm
    # measured window: fresh timer so warmup compiles/regen don't ride
    timer = PhaseTimer()
    t0 = time.time()
    for ep in range(1, MEASURE_EPISODES + 1):
        state, buffers, stats = run_episode(ep, state, buffers)
    jax.block_until_ready(stats)
    wall = time.time() - t0
    sps = MEASURE_EPISODES * EPISODE_STEPS * B / wall
    phases = timer.summary()
    print(json.dumps({
        "leg": leg, "status": "ok", "sps": round(sps, 2),
        "episodes_measured": MEASURE_EPISODES, "replicas": B,
        "chunk": CHUNK, "episode_steps": EPISODE_STEPS,
        "measure_wall_s": round(wall, 2),
        "warmup_s": round(warm_s, 2),
        "scenario_regen_s": (phases.get("scenario_regen")
                             or {}).get("total_s", 0.0),
        "phases": phases,
        "jit_traces": {fn: t for fn, (t, _c)
                       in monitor.snapshot().items() if t and fn in
                       ("chunk_step", "reset_all", "factory_sample")},
        "final_return": round(float(stats["episodic_return"]), 4),
    }), flush=True)
    return 0


def _run_leg(leg: str) -> dict:
    """Fresh subprocess per leg (the 1-core box must never run two jax
    programs concurrently; a fresh process also keeps the legs'
    trace-count accounting independent)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", leg]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
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


def main(argv=None) -> int:
    from gsc_tpu.runtime import require_cpu_env
    require_cpu_env("tools/scenario_bench.py")
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", default=None,
                    help="run one leg in-process (factory|host_regen)")
    ap.add_argument("--bank", action="store_true",
                    help="write SCEN_r01.json next to the repo root")
    ap.add_argument("--out", default=None,
                    help="bank path (default <repo>/SCEN_r01.json)")
    args = ap.parse_args(argv)
    if args.worker:
        return worker(args.worker)

    legs = {leg: _run_leg(leg) for leg in ("host_regen", "factory")}
    ok = all(l.get("status") == "ok" for l in legs.values())
    doc = {
        "metric": "env_steps_per_sec_per_chip",
        "unit": "env-steps/s", "round": 1, "platform": "cpu",
        "status": "ok" if ok else "failed",
        "replicas": B, "chunk": CHUNK, "episode_steps": EPISODE_STEPS,
        "episodes_measured": MEASURE_EPISODES,
        "host_mix": HOST_MIX, "factory_mix": FACTORY_MIX,
        "legs": [legs["host_regen"], legs["factory"]],
    }
    if ok:
        f, h = legs["factory"], legs["host_regen"]
        doc.update({
            "factory_sps": f["sps"], "host_regen_sps": h["sps"],
            "factory_vs_host": round(f["sps"] / h["sps"], 3),
            "factory_scenario_regen_s": f["scenario_regen_s"],
            "host_scenario_regen_s": h["scenario_regen_s"],
            "jit_traces_factory": f["jit_traces"],
            "jit_traces_host_regen": h["jit_traces"],
            "factory_ge_host": f["sps"] >= h["sps"],
            "note": (
                "Equal-B comparison on the 1-core CPU box (fresh "
                "subprocess per leg, warm persistent compile cache, "
                f"warmup episode excluded): replacing per-episode HOST "
                f"scenario production (K=4 registry mixture, per-replica "
                f"Python traffic + host->device ship) with the jitted "
                f"on-device factory draw moves the scenario_regen wall "
                f"from {h['scenario_regen_s']}s to "
                f"{f['scenario_regen_s']}s over "
                f"{MEASURE_EPISODES} episodes and the env-steps/s from "
                f"{h['sps']} to {f['sps']}.  Distributions necessarily "
                "differ (fixed mixture vs sampled families) — the "
                "comparison is the scenario pipeline at equal dispatch "
                "shape."),
        })
        try:
            import jax
            doc["jax"] = jax.__version__
        except Exception:
            pass
    claim_holds = ok and doc.get("factory_ge_host", False)
    if ok and not claim_holds:
        # a round whose factory leg LOSES must never read as a healthy
        # row: mark it failed (bench_diff's failed-current discipline)
        doc["status"] = "failed"
        doc["reason"] = ("factory_sps < host_regen_sps — the round does "
                         "not support the throughput claim")
    print(json.dumps(doc, indent=1))
    if args.bank or args.out:
        out = args.out or os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "SCEN_r01.json")
        if not claim_holds:
            # never overwrite a previously banked GREEN artifact with a
            # losing/failed round — park the evidence next to it (the
            # SCEN_r*.json scan still ingests it as a failed row)
            out = os.path.splitext(out)[0] + ".failed.json"
        with open(out, "w") as fobj:
            json.dump(doc, fobj, indent=1)
            fobj.write("\n")
        print(f"[scenario_bench] banked {out}")
        if not claim_holds:
            print("[scenario_bench] FAIL: "
                  f"{doc.get('reason', 'leg failure')}")
            return 1
    return 0 if claim_holds else 1


if __name__ == "__main__":
    sys.exit(main())
