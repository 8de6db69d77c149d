"""Mixed-topology batched training (`pytest -m mixtopo`).

The PR-9 contract: one device batch carries MANY networks.  Tests cover

- row independence under vmap: a B=4 mixed batch [A, A, B, B] reproduces
  two homogeneous B=2 runs of A and B bit-for-bit (replay rows, obs,
  per-replica returns) — topology threading adds diversity, never
  cross-talk;
- homogeneous bit-identity: the per-replica-topology path with a stacked
  [A, A] tree equals the historic unbatched-topology path bitwise;
- zero retrace across a 3-topology schedule: one warmup trace, then the
  whole mixture trains under ``assert_no_retrace`` — the "schedule
  switch" is per-replica data, not a compile axis;
- scenario-registry determinism (same seed -> same topology pytree),
  bucket/stack memoization, mix-grammar errors;
- mid-episode capacity faults: link/node rows zero at the planned
  interval inside the scanned episode, and a dead link actually drops
  flows with the LINK_CAP classification.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import __graft_entry__ as ge
from gsc_tpu.config.schema import SchedulerConfig
from gsc_tpu.env.driver import EpisodeDriver
from gsc_tpu.parallel import ParallelDDPG
from gsc_tpu.sim.traffic import generate_traffic
from gsc_tpu.topology import (DEFAULT_REGISTRY, TopologyBucket,
                              build_mix_entries, parse_topo_faults,
                              plan_mix, stack_topologies)
from gsc_tpu.topology.compiler import compile_topology
from gsc_tpu.topology.scenarios import (TRAFFIC_SHAPES, mix_traffic_host,
                                        shape_trace)
from gsc_tpu.topology.synthetic import line, ring, triangle

pytestmark = pytest.mark.mixtopo


def _det_env(episode_steps=2):
    """Tiny flagship stack with a deterministic post-warmup policy (zero
    exploration noise, deterministic sim) so per-replica trajectories are
    key-independent — the vmap row-independence framing."""
    env, agent, _, _ = ge._flagship(max_nodes=8, max_edges=8,
                                    episode_steps=episode_steps,
                                    max_flows=32)
    agent = dataclasses.replace(agent, rand_sigma=0.0, rand_mu=0.0)
    env.agent = agent
    return env, agent


def _rollout(env, agent, topo, traffic, B, per_replica, steps):
    pddpg = ParallelDDPG(env, agent, num_replicas=B,
                         per_replica_topology=per_replica)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    # far past warmup: deterministic policy branch, zero noise
    state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(10 ** 6))
    return buffers, obs, stats


def _rows(tree, idx):
    return jax.tree_util.tree_map(lambda x: np.asarray(x)[idx], tree)


def _assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


# ------------------------------------------------------- row independence
def test_mixed_batch_bit_equals_homogeneous_runs():
    """[A, B, A, B] at B=4 == homogeneous B=2 runs of A and B, row for
    row: replay contents (incl. the stored topo_idx), final obs and
    per-replica returns — vmapped topology threading is cross-talk-free."""
    steps = 2
    env, agent = _det_env(steps)
    tA = compile_topology(triangle(), max_nodes=8, max_edges=8, topo_id=0)
    tB = compile_topology(line(4), max_nodes=8, max_edges=8, topo_id=1)
    cap = 128
    tr = lambda t, s: generate_traffic(env.sim_cfg, env.service, t, steps,
                                       seed=s, capacity=cap)
    stack = lambda xs: jax.tree_util.tree_map(
        lambda *ys: jnp.stack(ys), *xs)

    mixed_topo = stack_topologies([tA, tB, tA, tB])
    mixed_traffic = stack([tr(tA, 0), tr(tB, 10), tr(tA, 1), tr(tB, 11)])
    mbuf, mobs, mstats = _rollout(env, agent, mixed_topo, mixed_traffic,
                                  4, True, steps)

    for topo, seeds, rows in ((tA, (0, 1), (0, 2)), (tB, (10, 11), (1, 3))):
        homo_topo = stack_topologies([topo, topo])
        homo_traffic = stack([tr(topo, s) for s in seeds])
        hbuf, hobs, hstats = _rollout(env, agent, homo_topo, homo_traffic,
                                      2, True, steps)
        idx = np.asarray(rows)
        # replay shard capacities differ (mem_limit / B) — compare the
        # written slots, which is the whole trajectory here
        _assert_tree_equal(
            jax.tree_util.tree_map(lambda x: np.asarray(x)[:, :steps],
                                   _rows(mbuf.data, idx)),
            jax.tree_util.tree_map(lambda x: np.asarray(x)[:, :steps],
                                   hbuf.data))
        _assert_tree_equal(_rows(mobs, idx), hobs)
        np.testing.assert_array_equal(
            np.asarray(mstats["per_replica_return"])[idx],
            np.asarray(hstats["per_replica_return"]))
    # stored network attribution follows the assignment
    np.testing.assert_array_equal(
        np.asarray(mbuf.data["topo_idx"])[:, 0], [0, 1, 0, 1])


def test_per_replica_path_bit_equals_unbatched_topology():
    """A stacked [A, A] per-replica run equals the historic unbatched-
    topology dispatch bitwise — the default path's math is untouched by
    the threading change."""
    steps = 2
    env, agent = _det_env(steps)
    tA = compile_topology(triangle(), max_nodes=8, max_edges=8)
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(env.sim_cfg, env.service, tA, steps, seed=s)
          for s in (0, 1)])
    pbuf, pobs, pstats = _rollout(env, agent, stack_topologies([tA, tA]),
                                  traffic, 2, True, steps)
    ubuf, uobs, ustats = _rollout(env, agent, tA, traffic, 2, False, steps)
    _assert_tree_equal(pbuf.data, ubuf.data)
    _assert_tree_equal(pobs, uobs)
    np.testing.assert_array_equal(
        np.asarray(pstats["per_replica_return"]),
        np.asarray(ustats["per_replica_return"]))


# ---------------------------------------------------------- zero retrace
def test_mix_zero_retrace_across_3_topology_schedule():
    """B=4 spanning 3 distinct topologies (2 schedule networks + 1
    registry scenario): after the warmup episode's single trace, episodes
    with fresh traffic — the full 'schedule' — run under
    ``assert_no_retrace``."""
    from gsc_tpu.analysis.sentinels import assert_no_retrace

    steps = 2
    env, agent = _det_env(steps)
    tA = compile_topology(triangle(), max_nodes=8, max_edges=8)
    tB = compile_topology(line(4), max_nodes=8, max_edges=8)
    sched = SchedulerConfig(training_network_files=("a.graphml",
                                                    "b.graphml"),
                            inference_network="a.graphml", period=1)
    driver = EpisodeDriver(sched, env.sim_cfg, env.service, steps,
                           max_nodes=8, max_edges=8,
                           topologies=[tA, tB], inference_topology=tA,
                           topo_mix="schedule,ring5")
    plan = driver.mix_plan(4)
    assert plan.num_entries == 3
    assert plan.names == ["a.graphml", "b.graphml", "ring5", "a.graphml"]
    # memoized plan -> the stacked tree is the SAME object every episode
    assert driver.mix_plan(4).topo is plan.topo

    pddpg = ParallelDDPG(env, agent, num_replicas=4,
                         per_replica_topology=True)
    traffic = driver.mix_traffic(0, plan)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), plan.topo,
                                      traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    # warmup episode: the ONE trace of the mixed program (learn fused)
    state, buffers, env_states, obs, _, _ = pddpg.chunk_step(
        state, buffers, env_states, obs, plan.topo, traffic,
        jnp.int32(0), None, True)
    with assert_no_retrace("chunk_step", "reset_all"):
        for ep in (1, 2):
            traffic = driver.mix_traffic(ep, plan)
            env_states, obs = pddpg.reset_all(
                jax.random.PRNGKey(ep), plan.topo, traffic)
            state, buffers, env_states, obs, stats, _ = pddpg.chunk_step(
                state, buffers, env_states, obs, plan.topo, traffic,
                jnp.int32(ep * steps), None, True)
    assert np.isfinite(float(stats["episodic_return"]))


# --------------------------------------------------- registry + bucketing
def test_registry_determinism_same_seed_same_pytree():
    b1 = TopologyBucket(16, 24)
    b2 = TopologyBucket(16, 24)
    for name, seed in (("random12", 7), ("abilene", 3), ("ring6", 0)):
        spec = DEFAULT_REGISTRY.spec(name, seed)
        again = DEFAULT_REGISTRY.spec(name, seed)
        _assert_tree_equal(b1.compile((name, seed), spec),
                           b2.compile((name, seed), again))
    # a different seed must actually change a randomized generator
    r7 = np.asarray(b1.compile(("random12", 7),
                               DEFAULT_REGISTRY.spec("random12", 7)).node_cap)
    r8 = np.asarray(b2.compile(("random12", 8),
                               DEFAULT_REGISTRY.spec("random12", 8)).node_cap)
    assert not np.array_equal(r7, r8)


def test_bucket_memoizes_compiles_and_stacks():
    bucket = TopologyBucket(8, 8)
    spec = triangle()
    t1 = bucket.compile(("triangle", 0), spec)
    assert bucket.compile(("triangle", 0), spec) is t1
    t2 = bucket.compile(("line3", 0), line(3), topo_id=1)
    s1 = bucket.stack([t1, t2, t1])
    assert bucket.stack([t1, t2, t1]) is s1
    assert np.asarray(s1.topo_id).tolist() == [0, 1, 0]
    with pytest.raises(ValueError, match="does not fit bucket"):
        bucket.compile(("ring64", 0), ring(64))


def test_mix_grammar_rejects_bad_entries():
    bad = ["", "nope_topology", "abilene+warp", "abilene~link@x",
           "abilene:notanint", "triangle~frob@1",
           # seeds on DETERMINISTIC generators are rejected, not silently
           # ignored: 'star8:1,star8:2' would be identical networks
           # labeled as distinct mixture members
           "star8:1", "triangle:2", "claranet:1"]
    for mix in bad:
        with pytest.raises(ValueError):
            DEFAULT_REGISTRY.parse_mix(mix)
    # round-robin needs every entry represented
    bucket = TopologyBucket(8, 8)
    entries = build_mix_entries("triangle,line3,ring5", DEFAULT_REGISTRY,
                                bucket)
    env, _ = _det_env(2)
    with pytest.raises(ValueError, match="round-robin"):
        plan_mix(entries, 2, bucket, env.sim_cfg, 2)


def test_load_topology_cached_returns_same_object(tmp_path):
    from gsc_tpu.topology.compiler import load_topology_cached
    from gsc_tpu.topology.synthetic import write_graphml

    p = str(tmp_path / "tri.graphml")
    write_graphml(triangle(), p)
    t1 = load_topology_cached(p, max_nodes=8, max_edges=8)
    assert load_topology_cached(p, max_nodes=8, max_edges=8) is t1
    assert load_topology_cached(p, max_nodes=9, max_edges=9) is not t1
    # the topo_id stamp is inside the memo: schedule position >= 1 gets
    # the SAME object across driver rebuilds too (id()-keyed downstream
    # caches stay warm), and stamping never leaks into the id=0 entry
    t2 = load_topology_cached(p, max_nodes=8, max_edges=8, topo_id=1)
    assert load_topology_cached(p, max_nodes=8, max_edges=8,
                                topo_id=1) is t2
    assert t2 is not t1
    assert int(np.asarray(t2.topo_id)) == 1
    assert int(np.asarray(t1.topo_id)) == 0


# ------------------------------------------------------- faults + shapes
def test_fault_plan_zeroes_capacity_tables():
    env, _ = _det_env(4)
    topo = compile_topology(line(3), max_nodes=8, max_edges=8)
    faults = parse_topo_faults("link@1.0&node@2.1")
    tr = generate_traffic(env.sim_cfg, env.service, topo, 4, seed=0,
                          faults=faults)
    assert tr.edge_cap_t is not None
    ecap = np.asarray(tr.edge_cap_t)
    np.testing.assert_array_equal(ecap[:, 0] == 0.0,
                                  [False, True, True, True])
    assert (ecap[:, 1] > 0).all()   # only the named link fails
    ncap = np.asarray(tr.node_cap)
    np.testing.assert_array_equal(ncap[:, 1] == 0.0,
                                  [False, False, True, True])
    # no faults and no forcing -> the legacy pytree, structurally
    plain = generate_traffic(env.sim_cfg, env.service, topo, 4, seed=0)
    assert plain.edge_cap_t is None
    # a fault aimed at a PADDING row (line3 has 3 real nodes / 2 real
    # edges in an 8/8 bucket) must be rejected, not silently never fire
    for spec in ("node@1.5", "link@1.3"):
        with pytest.raises(ValueError, match="out of range"):
            generate_traffic(env.sim_cfg, env.service, topo, 4, seed=0,
                             faults=parse_topo_faults(spec))
    with pytest.raises(ValueError, match="out of range"):
        build_mix_entries("line3~node@1.5", DEFAULT_REGISTRY,
                          TopologyBucket(8, 8))


def test_link_fault_drops_flows_with_linkcap_reason():
    """A dead link (interval 0 on line3's only ingress-adjacent edge)
    starves the network: flows drop as LINK_CAP inside the scanned
    episode, while the no-fault control processes traffic."""
    from gsc_tpu.sim.state import DROP_LINK_CAP

    env, _ = _det_env(4)
    topo = compile_topology(line(3, num_ingress=1), max_nodes=8,
                            max_edges=8)
    engine = env.engine
    nm = np.asarray(topo.node_mask)
    sched = np.zeros(env.limits.scheduling_shape, np.float32)
    # schedule everything to node 1: every flow must cross edge 0
    sched[:, :, :, 1] = 1.0
    placement = jnp.asarray(np.broadcast_to(
        nm[:, None], (8, env.limits.sf_pool)))

    def run(faults):
        tr = generate_traffic(env.sim_cfg, env.service, topo, 4, seed=0,
                              faults=faults)
        st = engine.init(jax.random.PRNGKey(0), topo)
        for _ in range(4):
            st, metrics = engine.apply(st, topo, tr, jnp.asarray(sched),
                                       placement)
        return metrics

    ok = run(())
    faulted = run(parse_topo_faults("link@0.0"))
    assert int(ok.processed) > 0
    assert int(ok.drop_reasons[DROP_LINK_CAP]) == 0
    assert int(faulted.processed) == 0
    assert int(faulted.drop_reasons[DROP_LINK_CAP]) > 0


def test_traffic_shapes_modulate_arrival_means():
    from gsc_tpu.sim.traffic_device import DeviceTraffic

    env, _ = _det_env(8)
    topo = compile_topology(triangle(), max_nodes=8, max_edges=8)
    base = env.sim_cfg.inter_arrival_mean
    for name, (profile_fn, factor) in TRAFFIC_SHAPES.items():
        trace = shape_trace(name, env.sim_cfg, topo, 8)
        sampler = DeviceTraffic(env.sim_cfg, env.service, topo, 8,
                                trace=trace)
        means = np.asarray(sampler.base_means)[:, 0]   # node 0 = ingress
        np.testing.assert_allclose(means, base * profile_fn(8), rtol=1e-6)
        assert factor >= 1.0
    # deterministic: the same shaped schedule twice is bit-identical
    trace = shape_trace("bursty", env.sim_cfg, topo, 8)
    t1 = generate_traffic(env.sim_cfg, env.service, topo, 8, seed=3,
                          trace=trace)
    t2 = generate_traffic(env.sim_cfg, env.service, topo, 8, seed=3,
                          trace=trace)
    _assert_tree_equal(t1, t2)


def test_mix_traffic_host_consistent_structure_and_faults():
    """A mix where only ONE member has link faults still stacks: every
    replica carries the edge_cap_t leaf (broadcast caps for the healthy
    ones), and only the faulted entry's rows zero."""
    env, _ = _det_env(3)
    bucket = TopologyBucket(8, 8)
    entries = build_mix_entries("triangle,line3~link@1.0", DEFAULT_REGISTRY,
                                bucket)
    plan = plan_mix(entries, 4, bucket, env.sim_cfg, 3)
    assert plan.has_link_faults
    tr = mix_traffic_host(plan, env.sim_cfg, env.service, 3,
                          seed_for=lambda r: r)
    assert tr.edge_cap_t.shape[:2] == (4, 3)
    ecap = np.asarray(tr.edge_cap_t)
    # replicas 1, 3 run the faulted line3 entry (round-robin over K=2)
    assert (ecap[1, 1:, 0] == 0.0).all() and (ecap[3, 1:, 0] == 0.0).all()
    assert (ecap[0, :, 0] > 0).all() and (ecap[2, :, 0] > 0).all()
