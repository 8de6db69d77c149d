"""Ladder rung-5 entries (BASELINE.md config 5): mixed SFC catalog and a
200+-node synthetic topology under the sharded data-parallel path.  The
reference supports multiple SFCs structurally (dummy_data.py ships sfc_1/2/3
schedules) but its benchmark configs only ever exercise one chain; here the
multi-chain path is tested for real."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.config.catalog import mixed_service
from gsc_tpu.config.schema import AgentConfig, EnvLimits, SimConfig
from gsc_tpu.env.env import ServiceCoordEnv
from gsc_tpu.sim import SimEngine, generate_traffic
from gsc_tpu.topology.compiler import compile_topology
from gsc_tpu.topology.synthetic import random_network

pytestmark = pytest.mark.slow  # ~87 s: 200-node sharded step compile
from gsc_tpu.utils.debug import assert_invariants


def test_mixed_sfc_catalog_engine():
    """Both chains flow through one engine episode: arrivals split across
    SFC ids, flows of each chain complete, invariants hold, and the
    per-(node, sfc, sf) requested-traffic metric is populated on both
    chain slices."""
    service = mixed_service()
    limits = EnvLimits.for_service(service, max_nodes=16, max_edges=32)
    assert limits.num_sfcs == 2 and limits.max_sfs == 3
    cfg = SimConfig(ttl_choices=(200.0,), max_flows=256,
                    inter_arrival_mean=5.0)
    engine = SimEngine(service, cfg, limits)
    topo = compile_topology(random_network(12, seed=3), max_nodes=16,
                            max_edges=32)
    traffic = generate_traffic(cfg, service, topo, 10, seed=0)
    sfc_ids = np.asarray(traffic.arr_sfc)[np.isfinite(np.asarray(traffic.arr_time))]
    assert set(np.unique(sfc_ids)) == {0, 1}

    nm = np.asarray(topo.node_mask)
    sched = np.zeros(limits.scheduling_shape, np.float32)
    sched[:, :, :, nm] = 1.0 / nm.sum()
    placement = jnp.asarray(
        np.broadcast_to(nm[:, None], (16, limits.sf_pool)).copy())
    state = engine.init(jax.random.PRNGKey(0), topo)
    for _ in range(10):
        state, metrics = engine.apply(state, topo, traffic,
                                      jnp.asarray(sched), placement)
    assert_invariants(state, topo, engine.tables.chain_len)
    assert int(metrics.processed) > 0
    req = np.asarray(metrics.run_requested)        # [N, C, S]
    assert req[:, 0, :].sum() > 0, "no sfc_1 demand recorded"
    assert req[:, 1, :].sum() > 0, "no sfc_2 demand recorded"
    # chain 2 has length 2: position never exceeds its chain_len
    assert engine.tables.chain_len.tolist() == [3, 2]


def test_mixed_sfc_env_trains():
    """The RL env + parallel learner run on the 2-SFC catalog (action dim
    picks up the C axis: N*2*3*N)."""
    service = mixed_service()
    limits = EnvLimits.for_service(service, max_nodes=16, max_edges=32)
    agent = AgentConfig(graph_mode=True, episode_steps=2,
                        objective="prio-flow", gnn_features=4,
                        gnn_num_layers=1, gnn_num_iter=1,
                        actor_hidden_layer_nodes=(16,),
                        critic_hidden_layer_nodes=(16,), mem_limit=32,
                        batch_size=4)
    cfg = SimConfig(ttl_choices=(200.0,), max_flows=64)
    env = ServiceCoordEnv(service, cfg, agent, limits)
    assert env.limits.action_dim == 16 * 2 * 3 * 16
    topo = compile_topology(random_network(12, seed=3), max_nodes=16,
                            max_edges=32)
    from gsc_tpu.parallel import ParallelDDPG
    B = 2
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(cfg, service, topo, 2, seed=s) for s in range(B)])
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


def test_rung5_200_node_sharded_step():
    """A 200-node synthetic multi-cloud topology compiles and executes one
    sharded data-parallel step on the virtual 8-device mesh.  Runs in its
    own subprocess: the 200-node program is the largest XLA compile in the
    suite, and compiling it in a worker that already holds ~100 compiled
    programs can segfault XLA's CPU compiler under memory pressure (seen
    at suite position ~90; standalone it passes in ~60 s)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        f"import sys; sys.path.insert(0, {repo!r});"
        f"sys.path.insert(0, {os.path.join(repo, 'tests')!r});"
        "from test_rung5 import _run_rung5_sharded; _run_rung5_sharded();"
        "print('RUNG5_OK')"
    )
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=900,
                       capture_output=True, text=True)
    assert r.returncode == 0 and "RUNG5_OK" in r.stdout, r.stderr[-3000:]


def _run_rung5_sharded():
    from gsc_tpu.parallel import ParallelDDPG, make_mesh, put_replicated, put_sharded

    service = mixed_service()
    limits = EnvLimits.for_service(service, max_nodes=200, max_edges=400)
    agent = AgentConfig(graph_mode=True, episode_steps=1,
                        objective="prio-flow", gnn_features=4,
                        gnn_num_layers=1, gnn_num_iter=1,
                        actor_hidden_layer_nodes=(8,),
                        critic_hidden_layer_nodes=(8,), mem_limit=16,
                        batch_size=8)
    cfg = SimConfig(ttl_choices=(200.0,), max_flows=256, run_duration=10.0)
    env = ServiceCoordEnv(service, cfg, agent, limits)
    topo = compile_topology(random_network(200, seed=11), max_nodes=200,
                            max_edges=400)
    mesh = make_mesh(8)
    B = 8
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(cfg, service, topo, 1, seed=s) for s in range(B)])
    pddpg = ParallelDDPG(env, agent, num_replicas=B, sample_mode="local")
    with mesh:
        topo_d = put_replicated(topo, mesh)
        traffic = put_sharded(traffic, mesh)
        env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo_d,
                                          traffic)
        env_states = put_sharded(env_states, mesh)
        obs = put_sharded(obs, mesh)
        one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
        state = put_replicated(pddpg.init(jax.random.PRNGKey(1), one_obs),
                               mesh)
        buffers = put_sharded(pddpg.init_buffers(one_obs), mesh)
        state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
            state, buffers, env_states, obs, topo_d, traffic, jnp.int32(0))
        state, metrics = pddpg.learn_burst(state, buffers)
        jax.block_until_ready((stats, metrics))
    assert np.isfinite(float(stats["episodic_return"]))
    assert np.isfinite(float(metrics["critic_loss"]))


def test_bench_interroute_scenario_builds_and_steps():
    """The interroute stack (110n/146e, 1024 flow slots)
    constructs and rolls one 2-step episode through the parallel path."""
    import jax.numpy as jnp

    from __graft_entry__ import _interroute_stack
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.sim import generate_traffic

    env, agent, topo = _interroute_stack(episode_steps=2)
    assert int(np.asarray(topo.node_mask).sum()) == 110
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
    assert np.isfinite(float(stats["episodic_return"]))


def test_bench_rung5_scenario_matches_config5():
    """The rung5 stack IS BASELINE config 5: 200-node
    synthetic topology, mixed 2-chain catalog over a 5-SF pool."""
    from __graft_entry__ import _rung5_stack

    env, agent, topo = _rung5_stack(episode_steps=2)
    assert int(np.asarray(topo.node_mask).sum()) == 200
    assert env.limits.num_sfcs == 2 and env.limits.sf_pool == 5
    assert set(env.service.sfc_list) == {"sfc_1", "sfc_2"}
    assert env.sim_cfg.max_flows == 1024
    # FLAGSHIP architecture ports up the ladder: the factored head
    # auto-enables at this action dim, so the default 256/64 hidden sizes
    # and batch 100 carry over; only the replay BUDGET is scenario-sized
    # (a rung-5 transition is ~1.2M f32)
    from gsc_tpu.models.nets import use_factored_head
    assert use_factored_head(agent, env.limits.action_dim)
    assert agent.actor_hidden_layer_nodes == (256,)
    assert agent.critic_hidden_layer_nodes == (64,)
    assert agent.batch_size == 100
    assert agent.mem_limit == 1024
