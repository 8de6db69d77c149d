"""Reward-curve comparison vs the UNMODIFIED reference simulator — the
BASELINE-protocol "reproduce the reference's reward curve on config 1"
anchor, done without the reference's (uninstallable) torch agent stack.

Both sides run the flagship config-1 scenario (Abilene in4-rand-cap1-2,
abc chain, sample_config, matched seed) under the SAME uniform
place-everywhere action, and both reward streams are computed by ONE
implementation — ``gsc_tpu.env.rewards.compute_reward`` (itself a
line-cited port of gym_env.py:223-380) — from each simulator's
per-interval flow metrics.  What this isolates is the SIMULATOR'S
contribution to the reward signal: if the engine's physics diverged, the
curves would split; matched curves mean an agent training on gsc_tpu sees
the same reward landscape the reference agent saw.

Per-interval metrics come from DELTAS of cumulative counters
(processed/dropped/total_end2end_delay) on both sides — deliberately NOT
from the reference's run_* metrics, whose reset timing belongs to its
result-writer SimPy process (writer.py:222) and would entangle the
comparison with writer scheduling.

    python tools/reward_curve.py                  # both sides + compare
    python tools/reward_curve.py --side reference # (no jax import)
    python tools/reward_curve.py --side engine
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
_TOOLS = os.path.join(REPO, "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

REFERENCE = os.environ.get("GSC_REFERENCE_DIR", "/root/reference")
NETWORK = "configs/networks/abilene/abilene-in4-rand-cap1-2.graphml"
SERVICE = "configs/service_functions/abc.yaml"
CONFIG = "configs/config/simulator/sample_config.yaml"
SEED = 1234


def reference_curve(steps):
    """Per-step cumulative (processed, dropped, e2e_sum) from the real
    reference coordsim under the minisimpy shim.  No jax anywhere."""
    import run_reference
    run_reference._install_shim()
    from siminterface import Simulator

    sim = Simulator(os.path.join(REFERENCE, NETWORK),
                    os.path.join(REFERENCE, SERVICE),
                    os.path.join(REFERENCE, CONFIG), test_mode=False)
    sim.init(SEED)
    action = run_reference.uniform_action(sim.network, sim.sfc_list,
                                          sim.sf_list)
    rows = []
    for _ in range(steps):
        sim.apply(action)
        m = sim.params.metrics.metrics
        rows.append({"processed": int(m["processed_flows"]),
                     "dropped": int(m["dropped_flows"]),
                     "e2e_sum": float(m["total_end2end_delay"])})
    return {"side": "reference", "n_nodes": len(sim.network.nodes),
            "rows": rows}


def uniform_engine_run(network, steps, seed, config=None, overrides=None,
                       max_nodes=24, max_edges=37, per_step=False):
    """THE canonical uniform-action engine harness (cli-simulate
    semantics): uniform schedule over real nodes, everything placed
    everywhere.  Shared by tests/test_reference_parity.py (final-metrics
    parity) and the reward-curve anchor (``per_step=True`` captures the
    cumulative counter series) so the two can't desynchronize.  Returns
    the final SimMetrics, plus the per-step row list when asked.

    Backend selection is the CALLER's job (conftest pins CPU for tests;
    this tool's main() pins CPU before dispatch) — a config update here
    would be a silent no-op in any process whose backend already
    initialized."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gsc_tpu.config.loader import load_service, load_sim
    from gsc_tpu.config.schema import EnvLimits
    from gsc_tpu.sim.engine import SimEngine
    from gsc_tpu.sim.traffic import generate_traffic
    from gsc_tpu.topology.compiler import load_topology

    svc = load_service(os.path.join(REFERENCE, SERVICE))
    sim_cfg = load_sim(config or os.path.join(REFERENCE, CONFIG),
                       **(overrides or {}))
    limits = EnvLimits.for_service(svc, max_nodes=max_nodes,
                                   max_edges=max_edges)
    topo = load_topology(network, max_nodes=max_nodes, max_edges=max_edges,
                         seed=seed)
    traffic = generate_traffic(sim_cfg, svc, topo, steps, seed)
    engine = SimEngine(svc, sim_cfg, limits)
    nm = np.asarray(topo.node_mask)
    sched = np.zeros(limits.scheduling_shape, np.float32)
    sched[:, :, :, nm] = 1.0 / nm.sum()
    placement = jnp.asarray(
        np.broadcast_to(nm[:, None], (max_nodes, limits.max_sfs)).copy())
    state = engine.init(jax.random.PRNGKey(seed), topo)
    rows = []
    metrics = None
    for _ in range(steps):
        state, metrics = engine.apply(state, topo, traffic,
                                      jnp.asarray(sched), placement)
        if per_step:
            rows.append({"processed": int(metrics.processed),
                         "dropped": int(metrics.dropped),
                         "e2e_sum": float(metrics.sum_e2e)})
    return metrics, int(nm.sum()), rows


def engine_curve(steps):
    """Cumulative series from the gsc_tpu engine (CPU), uniform
    schedule/placement, matched seed."""
    _, n_nodes, rows = uniform_engine_run(
        os.path.join(REFERENCE, NETWORK), steps, SEED, per_step=True)
    return {"side": "engine", "n_nodes": n_nodes, "rows": rows}


def rewards_from_cumulative(rows, n_nodes, steps):
    """Per-interval reward via compute_reward on cumulative deltas.
    Uniform place-everywhere -> [N,3] all-true placement on real nodes;
    prio-flow objective with the reference's auto target + EWMA chain."""
    import jax.numpy as jnp
    import numpy as np

    from gsc_tpu.config.schema import AgentConfig
    from gsc_tpu.env.rewards import compute_reward, reward_constants

    agent = AgentConfig(objective="prio-flow", episode_steps=steps)
    # abc chain: 3 x 5 ms processing means (abc.yaml)
    min_delay, diameter = reward_constants(agent, [5.0, 5.0, 5.0])
    node_mask = jnp.arange(24) < n_nodes
    placement = jnp.broadcast_to(
        node_mask[:, None], (24, 3))

    class _M:  # duck-typed SimMetrics view over one interval's deltas
        def __init__(self, proc, drop, e2e):
            self.run_processed = jnp.asarray(proc, jnp.float32)
            self.run_dropped = jnp.asarray(drop, jnp.float32)
            self._e2e = e2e

        def run_avg_e2e(self):
            return jnp.where(self.run_processed > 0,
                             self._e2e / jnp.maximum(self.run_processed, 1),
                             0.0)

    ewma = jnp.ones(())
    out = []
    prev = {"processed": 0, "dropped": 0, "e2e_sum": 0.0}
    for row in rows:
        m = _M(row["processed"] - prev["processed"],
               row["dropped"] - prev["dropped"],
               jnp.asarray(row["e2e_sum"] - prev["e2e_sum"], jnp.float32))
        r, ewma, _ = compute_reward(agent, m, placement, node_mask, 3,
                                    min_delay, diameter, ewma)
        out.append(float(np.asarray(r)))
        prev = row
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--side", choices=["reference", "engine", "both"],
                    default="both")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--out", default=None,
                    help="write the comparison JSON here")
    args = ap.parse_args()

    if args.side == "reference":
        print(json.dumps(reference_curve(args.steps)))
        return
    import jax  # engine/both sides: pin CPU before any backend touch
    jax.config.update("jax_platforms", "cpu")
    if args.side == "engine":
        print(json.dumps(engine_curve(args.steps)))
        return

    # both: reference in a clean subprocess (no jax/TPU registration)
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--side", "reference",
         "--steps", str(args.steps)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    if r.returncode != 0:
        raise SystemExit(f"reference side failed: {r.stderr[-2000:]}")
    ref = json.loads(r.stdout.strip().splitlines()[-1])
    eng = engine_curve(args.steps)

    import numpy as np
    rr = rewards_from_cumulative(ref["rows"], ref["n_nodes"], args.steps)
    re_ = rewards_from_cumulative(eng["rows"], eng["n_nodes"], args.steps)
    a, b = np.asarray(rr), np.asarray(re_)
    if a.std() > 0 and b.std() > 0:
        corr = float(np.corrcoef(a, b)[0, 1])
    else:
        # one-sided constancy is a shape MISMATCH, not a perfect match —
        # only two identical constant curves score 1.0 here
        corr = 1.0 if np.allclose(a, b, atol=1e-6) else 0.0
    result = {
        "scenario": "abilene-in4-rand-cap1-2 / abc / sample_config",
        "steps": args.steps, "seed": SEED,
        "reference_rewards": [round(x, 4) for x in rr],
        "engine_rewards": [round(x, 4) for x in re_],
        "max_abs_diff": round(float(np.max(np.abs(a - b))), 4),
        "mean_abs_diff": round(float(np.mean(np.abs(a - b))), 4),
        "pearson_r": round(corr, 4),
        "reference_mean": round(float(a.mean()), 4),
        "engine_mean": round(float(b.mean()), 4),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if not k.endswith("_rewards")}, indent=1))


if __name__ == "__main__":
    main()
