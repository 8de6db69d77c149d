"""Golden parity vs the ACTUAL reference simulator.

The frozen numbers below were produced by ``tools/run_reference.py`` — the
UNMODIFIED reference coordsim (SimPy process model) running under the
``tools/minisimpy`` shim — via::

    python tools/run_reference.py --mode interface --network <net> \
        --steps 50 --seed 1234

with the reference's own sample_config.yaml (deterministic arrivals every
10 ms per ingress, deterministic size, run_duration 100 ms, TTL 100) and
abc.yaml (3 x 5 ms SFs), driving the same uniform place-everywhere /
uniform-schedule action our ``cli simulate`` uses.

The jax engine must reproduce them within its documented fixed-step
quantization bounds (gsc_tpu/sim/engine.py divergence notes):
- generated flows: exact (deterministic arrival streams)
- processed/dropped: within +-2 flows of the oracle (in-flight flows at
  the horizon land on different sides of the boundary under 1 ms substeps)
- drop-reason split: exact
- avg e2e delay: within 2.5% relative (measured divergence: ~0.0% on
  triangle, ~1.8% on Abilene)

When the reference tree is present, ``test_oracle_numbers_are_current``
re-runs the oracle live and checks the frozen constants themselves, so the
oracle can't silently rot.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REFERENCE = os.environ.get("GSC_REFERENCE_DIR", "/root/reference")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(REPO, "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

pytestmark = pytest.mark.skipif(
    not os.path.isdir(REFERENCE),
    reason="reference tree not available")

SERVICE = "configs/service_functions/abc.yaml"
CONFIG = "configs/config/simulator/sample_config.yaml"

# frozen oracle outputs (reference coordsim, seed 1234, 50 control steps)
ORACLE = {
    "triangle": {
        "network": "configs/networks/triangle/"
                   "triangle-in2-cap10-delay10.graphml",
        "generated": 1000, "processed": 995, "dropped": 0,
        "drop_reasons": {"TTL": 0, "DECISION": 0, "LINK_CAP": 0,
                         "NODE_CAP": 0},
        "avg_e2e": 34.48743718592965,
    },
    "abilene": {
        "network": "configs/networks/abilene/"
                   "abilene-in4-rand-cap1-2.graphml",
        "generated": 2000, "processed": 599, "dropped": 1395,
        "drop_reasons": {"TTL": 0, "DECISION": 0, "LINK_CAP": 0,
                         "NODE_CAP": 1395},
        "avg_e2e": 38.51419031719533,
    },
    # BT-Europe cap1: heavily contended (node cap 1) with FRACTIONAL geo
    # link delays.  At dt=1 the quantization reorders same-substep
    # contenders (398 vs 349 processed); at dt=0.25 — which resolves the
    # fractional event times — the engine reproduces the reference
    # EXACTLY (flow counts equal, avg e2e to 7 significant digits),
    # demonstrating the divergence is pure time quantization, not
    # semantics.
    "bteurope": {
        "network": "configs/networks/BtEurope-in2-cap1.graphml",
        "generated": 1000, "processed": 349, "dropped": 649,
        "drop_reasons": {"TTL": 0, "DECISION": 0, "LINK_CAP": 0,
                         "NODE_CAP": 649},
        "avg_e2e": 22.570200573065904,
        "overrides": {"dt": 0.25, "release_horizon": 1024},
        "exact": True,
    },
    # tinet: the reference's 53-node mid-size real network (rand-cap0-2:
    # integer caps {0,1,2}, so heavy NODE_CAP contention), fractional geo
    # delays -> dt=0.25 like bteurope.  Extends the exact-parity evidence
    # beyond the 24-node padding limit.
    "tinet": {
        "network": "configs/networks/tinet/tinet-in2-rand-cap0-2.graphml",
        "generated": 1000, "processed": 48, "dropped": 946,
        "drop_reasons": {"TTL": 0, "DECISION": 0, "LINK_CAP": 0,
                         "NODE_CAP": 946},
        "avg_e2e": 66.0,
        "overrides": {"dt": 0.25, "release_horizon": 1024},
        "limits": (64, 96),
        "exact": True,
    },
    # line3-linkcap2 (repo asset, absolute paths): LinkFwdCap=2 line with
    # huge node caps, fast arrivals, 20 ms flow durations — the only
    # oracle whose drops are LINK_CAP, pinning the link-admission
    # comparison ordering (engine.py stage 5: prefix <= cap-used headroom
    # vs the reference's used+prefix <= cap; ADVICE r3 flagged that no
    # oracle would catch an admission flip at exact capacity ties).
    "linkcap": {
        "network": os.path.join(REPO, "tests", "assets",
                                "line3-linkcap2.graphml"),
        "config": os.path.join(REPO, "tests", "assets",
                               "linkcap_config.yaml"),
        "generated": 2500, "processed": 151, "dropped": 2348,
        "drop_reasons": {"TTL": 0, "DECISION": 0, "LINK_CAP": 2348,
                         "NODE_CAP": 0},
        "avg_e2e": 22.94701986754967,
        # saturated links make nearly every substep a same-timestamp
        # admission tie, resolved slot-order here vs SimPy-FIFO there
        # (documented divergence, engine.py module docstring) — counts
        # drift ~1% (engine: 169/2329) but a broken admission comparison
        # (e.g. off-by-one-flow headroom) would shift them by >10x this
        # tolerance, and every drop must still be LINK_CAP.
        "atol_flows": 30,
        "e2e_rel": 0.05,
    },
}
STEPS = 50
SEED = 1234

# dt=0.25 oracles (fractional geo delays) cost 4x the substeps —
# the ~2-minute tail of the suite; quick tier skips them
_PARAMS = [pytest.param(k, marks=pytest.mark.slow)
           if ORACLE[k].get("overrides") else k
           for k in sorted(ORACLE)]


def _run_engine(network_rel, overrides=None, max_nodes=24, max_edges=37,
                config=CONFIG):
    """The cli-simulate path, in-process: uniform schedule over real nodes,
    everything placed everywhere, 50 x 100 ms control intervals.  The
    harness itself lives in tools/reward_curve.py (uniform_engine_run) and
    is shared with the reward-curve anchor so the two can't diverge."""
    from gsc_tpu.config.schema import DROP_REASONS

    from reward_curve import uniform_engine_run

    metrics, _, _ = uniform_engine_run(
        os.path.join(REFERENCE, network_rel), STEPS, SEED,
        config=os.path.join(REFERENCE, config), overrides=overrides,
        max_nodes=max_nodes, max_edges=max_edges)
    return {
        "generated": int(metrics.generated),
        "processed": int(metrics.processed),
        "dropped": int(metrics.dropped),
        "drop_reasons": {k: int(v) for k, v in
                         zip(DROP_REASONS, np.asarray(metrics.drop_reasons))},
        "avg_e2e": float(metrics.avg_e2e()),
    }


@pytest.mark.parametrize("name", _PARAMS)
def test_engine_matches_reference(name):
    want = ORACLE[name]
    mn, me = want.get("limits", (24, 37))
    got = _run_engine(want["network"], want.get("overrides"),
                      max_nodes=mn, max_edges=me,
                      config=want.get("config", CONFIG))
    assert got["generated"] == want["generated"]
    if want.get("exact"):
        assert got["processed"] == want["processed"], (got, want)
        assert got["dropped"] == want["dropped"], (got, want)
        assert got["avg_e2e"] == pytest.approx(want["avg_e2e"], rel=1e-5)
        assert got["drop_reasons"] == want["drop_reasons"]
    elif "atol_flows" in want:
        atol = want["atol_flows"]
        assert abs(got["processed"] - want["processed"]) <= atol, (got, want)
        assert abs(got["dropped"] - want["dropped"]) <= atol, (got, want)
        assert got["avg_e2e"] == pytest.approx(want["avg_e2e"],
                                               rel=want["e2e_rel"])
        for reason, n in want["drop_reasons"].items():
            assert abs(got["drop_reasons"][reason] - n) <= atol, (got, want)
            if n == 0:  # no misclassification: unused reasons stay at zero
                assert got["drop_reasons"][reason] == 0, (got, want)
    else:
        assert abs(got["processed"] - want["processed"]) <= 2, (got, want)
        assert abs(got["dropped"] - want["dropped"]) <= 2, (got, want)
        assert got["avg_e2e"] == pytest.approx(want["avg_e2e"], rel=0.025)
        assert got["drop_reasons"] == want["drop_reasons"]


@pytest.mark.parametrize("name", _PARAMS)
def test_oracle_numbers_are_current(name):
    """Re-run the reference itself and verify the frozen constants."""
    want = ORACLE[name]
    env = dict(os.environ)   # the reference run is jax-free
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_reference.py"),
         "--mode", "interface", "--network", want["network"],
         "--config", want.get("config", CONFIG),
         "--steps", str(STEPS), "--seed", str(SEED)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["generated_flows"] == want["generated"]
    assert out["processed_flows"] == want["processed"]
    assert out["dropped_flows"] == want["dropped"]
    assert out["dropped_by_reason"] == want["drop_reasons"]
    assert out["avg_end2end_delay"] == pytest.approx(want["avg_e2e"],
                                                     rel=1e-9)


# ---------------------------------------------------------------- per-flow
# FlowController (per-flow external decisions) parity: local-processing
# policy on the line3-egress asset — place-on-decision, the per-flow
# decision loop, and egress routing, vs the reference's FlowController +
# ExternalDecisionMaker driven by tools/run_reference.py --mode perflow.
# Frozen reference output (duration 2000, seed 1234): generated 201
# (the reference also books the boundary arrival at t == horizon),
# processed 197, dropped 0, avg e2e 35.0 (3 x 5 ms SFs + 20 ms path).
PERFLOW = {
    "network": os.path.join(REPO, "tests", "assets", "line3-egress.graphml"),
    "config": os.path.join(REPO, "tests", "assets", "perflow_config.yaml"),
    "duration": 2000,
    "generated": 201, "processed": 197, "dropped": 0,
    "avg_e2e": 35.0,
}


def test_perflow_engine_matches_reference():
    import jax.numpy as jnp

    from gsc_tpu.config.loader import load_service, load_sim
    from gsc_tpu.config.schema import EnvLimits
    from gsc_tpu.sim.engine import SimEngine
    from gsc_tpu.sim.state import PH_DECIDE
    from gsc_tpu.sim.traffic import generate_traffic
    from gsc_tpu.topology.compiler import load_topology

    svc = load_service(os.path.join(REFERENCE, SERVICE))
    sim_cfg = load_sim(PERFLOW["config"])
    assert sim_cfg.controller == "per_flow"   # loader maps FlowController
    limits = EnvLimits.for_service(svc, max_nodes=8, max_edges=8)
    topo = load_topology(PERFLOW["network"], max_nodes=8, max_edges=8)
    steps = PERFLOW["duration"] // int(sim_cfg.run_duration)
    traffic = generate_traffic(sim_cfg, svc, topo, steps, SEED)
    engine = SimEngine(svc, sim_cfg, limits)

    def decide_local(st):
        return jnp.where(st.flows.phase == PH_DECIDE, st.flows.node, -1)

    state = engine.init(jax.random.PRNGKey(SEED), topo)
    for _ in range(steps):
        state, metrics = engine.apply_per_flow(state, topo, traffic,
                                               decide_local)
    assert abs(int(metrics.generated) - PERFLOW["generated"]) <= 2
    assert int(metrics.processed) == PERFLOW["processed"]
    assert int(metrics.dropped) == PERFLOW["dropped"]
    assert float(metrics.avg_e2e()) == pytest.approx(PERFLOW["avg_e2e"],
                                                     rel=1e-6)


def test_perflow_oracle_numbers_are_current():
    """Re-run the reference FlowController itself and verify the frozen
    constants."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_reference.py"),
         "--mode", "perflow", "--network", PERFLOW["network"],
         "--config", PERFLOW["config"],
         "--duration", str(PERFLOW["duration"]), "--seed", str(SEED)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["generated_flows"] == PERFLOW["generated"]
    assert out["processed_flows"] == PERFLOW["processed"]
    assert out["dropped_flows"] == PERFLOW["dropped"]
    assert out["avg_end2end_delay"] == pytest.approx(PERFLOW["avg_e2e"],
                                                     rel=1e-9)


def test_reward_curve_matches_reference():
    """Per-interval REWARD parity on the flagship config-1 scenario
    (BASELINE protocol: "reproduce the reference's reward curve"): both
    simulators' per-step flow metrics fed through the one compute_reward
    implementation must produce near-identical curves.  The residual is
    the documented dt=1 avg-e2e quantization (+1.8% delay -> ~0.05
    constant reward offset through the /15 diameter term); shape must
    match to r > 0.99.  tools/reward_curve.py is the measurement; 25
    steps keeps CI cost at half the 50-step exhibit."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "reward_curve.py"),
         "--steps", "25"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout[r.stdout.index("{"):])
    assert out["pearson_r"] > 0.99, out
    assert out["max_abs_diff"] < 0.1, out
