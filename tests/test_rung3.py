"""Ladder rung-3 end-to-end (BASELINE.md config 3): a real 24-node/37-edge
topology (BT Europe, Topology Zoo), a 5-SF chain with startup delay and a
non-identity resource function, and trace-driven + MMPP traffic — all wired
through ``cli init-configs`` -> ``cli train``."""
import json

import jax
import numpy as np
import pytest
import yaml
from click.testing import CliRunner

from gsc_tpu.cli import cli
from gsc_tpu.topology.compiler import compile_topology
from gsc_tpu.topology.synthetic import bteurope


def test_bteurope_shape():
    """24 nodes / 37 edges / 2 ingress — the BtEurope-in2 scenario scale
    (which is exactly the reference's padding limits,
    environment_limits.py:44-64)."""
    topo = compile_topology(bteurope(), max_nodes=24, max_edges=37)
    assert int(np.asarray(topo.node_mask).sum()) == 24
    assert int(np.asarray(topo.edge_mask).sum()) == 37
    assert int(np.asarray(topo.is_ingress).sum()) == 2
    # every node reaches every node (connected graph)
    pd = np.asarray(topo.path_delay)[:24, :24]
    assert np.isfinite(pd).all() and pd.max() < 1e8


@pytest.fixture(scope="module")
def assets(tmp_path_factory):
    out = tmp_path_factory.mktemp("cfg")
    r = CliRunner().invoke(cli, ["init-configs", "--out", str(out)])
    assert r.exit_code == 0, r.output
    # shrink the agent for CI speed
    ag = yaml.safe_load(open(out / "agent.yaml"))
    ag.update(episode_steps=3, mem_limit=64, batch_size=8,
              nb_steps_warmup_critic=3, GNN_features=4, GNN_num_layers=1,
              GNN_num_iter=1, actor_hidden_layer_nodes=[16],
              critic_hidden_layer_nodes=[16])
    yaml.safe_dump(ag, open(out / "agent_small.yaml", "w"))
    yaml.safe_dump({
        "training_network_files":
            [str(out / "networks/bteurope-in2-rand-cap1-2.graphml")],
        "inference_network":
            str(out / "networks/bteurope-in2-rand-cap1-2.graphml"),
    }, open(out / "scheduler_bteu.yaml", "w"))
    return out


def _train(out, sim_yaml, service_yaml):
    r = CliRunner().invoke(cli, [
        "train", str(out / "agent_small.yaml"), str(out / sim_yaml),
        str(out / service_yaml), str(out / "scheduler_bteu.yaml"),
        "--episodes", "2", "--result-dir", str(out / "res"), "--quiet"])
    assert r.exit_code == 0, (r.output, r.exception)
    return json.loads(r.output.strip().splitlines()[-1])


def test_train_bteurope_5sf_trace(assets):
    """2 episodes on BT Europe with the abcde chain + ramp-up trace: the
    full rung-3 scenario trains end-to-end and evaluates finitely."""
    out = _train(assets, "simulator_trace.yaml", "service_abcde.yaml")
    assert np.isfinite(out["mean_return"])
    assert 0.0 <= out["final_succ_ratio"] <= 1.0


def test_train_bteurope_5sf_mmpp(assets):
    """Same scenario under two-state MMPP bursty arrivals."""
    out = _train(assets, "simulator_mmpp.yaml", "service_abcde.yaml")
    assert np.isfinite(out["mean_return"])


def test_trace_changes_traffic(assets):
    """The trace actually reshapes traffic: pop0's arrival mean ramps
    10 -> 5 -> 2.5 while the untraced config keeps 10 throughout
    (trace_processor.py:29-38 semantics)."""
    from gsc_tpu.config.loader import load_service, load_sim
    from gsc_tpu.sim.traffic import TraceEvents, generate_traffic
    from gsc_tpu.topology.compiler import load_topology

    out = assets
    svc = load_service(str(out / "service_abcde.yaml"))
    cfg = load_sim(str(out / "simulator_trace.yaml"))
    topo = load_topology(str(out / "networks/bteurope-in2-rand-cap1-2.graphml"))
    from gsc_tpu.env.driver import _node_index
    trace = TraceEvents.from_csv(cfg.trace_path, _node_index)
    tr = generate_traffic(cfg, svc, topo, 20, seed=0, trace=trace)
    t = np.asarray(tr.arr_time)
    ing = np.asarray(tr.arr_ingress)
    real = np.isfinite(t)
    # flows at pop0 in [0,500) arrive every 10ms; in [1000,1500) every 2.5ms
    early = ((t >= 0) & (t < 500) & (ing == 0) & real).sum()
    late = ((t >= 1000) & (t < 1500) & (ing == 0) & real).sum()
    assert late >= 3 * early
    # the cap raise at t=1000 lands in the node_cap tensor
    nc = np.asarray(tr.node_cap)
    assert nc[12, 0] == 4.0 and nc[5, 0] != 4.0


# rung -> (builder in __graft_entry__, padded nodes, padded edges, flow
# slots, replay rows in all, action width): the sizes BASELINE.md's configs
# 4 and 5 and benchmarks/configs/interroute.json state
STACK_SHAPES = {
    "rung4": ("_rung4_stack", 64, 128, 512, 10000, 64 * 3 * 64),
    "interroute": ("_interroute_stack", 128, 192, 1024, 2048, 49152),
    "rung5": ("_rung5_stack", 256, 384, 1024, 1024, 256 * 2 * 3 * 256),
}


@pytest.mark.parametrize("rung", sorted(STACK_SHAPES))
def test_stack_shapes(rung):
    """Each ladder stack builds, and ``env.reset`` traces (no compile) to
    state and observation of the stated sizes."""
    import __graft_entry__ as ge
    from gsc_tpu.sim.traffic import generate_traffic

    builder, nodes, edges, slots, mem_limit, action = STACK_SHAPES[rung]
    env, agent, topo = getattr(ge, builder)(episode_steps=2)
    assert (topo.max_nodes, topo.max_edges) == (nodes, edges)
    assert env.sim_cfg.max_flows == slots
    assert agent.mem_limit == mem_limit
    assert env.limits.action_dim == action
    traffic = generate_traffic(env.sim_cfg, env.service, topo, 2, seed=0)
    state, obs = jax.eval_shape(env.reset, jax.random.PRNGKey(0), topo,
                                traffic)
    assert state.sim.flows.phase.shape == (slots,)
    assert obs.nodes.shape[0] == nodes
    assert obs.edge_index.shape == (2, 2 * edges)
    assert obs.mask.shape == (action,)


def test_rung4_random_network_trains():
    """Rung-4 entry (BASELINE.md config 4): a 64-node randomized topology
    trains through the parallel rollout + learn path at reduced replicas."""
    import jax.numpy as jnp

    from __graft_entry__ import _rung4_stack
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.sim.traffic import generate_traffic

    env, agent, topo = _rung4_stack(episode_steps=2)
    B = 2
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(env.sim_cfg, env.service, topo, 2, seed=s)
          for s in range(B)])
    pddpg = ParallelDDPG(env, agent, num_replicas=B, sample_mode="local")
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(0))
    state, metrics = pddpg.learn_burst(state, buffers)
    assert np.isfinite(float(stats["episodic_return"]))
    assert np.isfinite(float(metrics["critic_loss"]))
