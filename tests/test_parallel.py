"""Scale-out tests on the virtual 8-device CPU mesh: sharded data-parallel
rollout + learn, and the driver-facing __graft_entry__ contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.parallel import ParallelDDPG, make_mesh, put_replicated, put_sharded


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = make_mesh()
    assert mesh.devices.shape == (8,)


def test_graft_entry_forward():
    import __graft_entry__ as ge
    fn, (params, obs) = ge.entry()
    out = jax.jit(fn)(params, obs)
    assert out.shape == (24 * 1 * 3 * 24,)
    assert np.isfinite(np.asarray(out)).all()


def test_dryrun_multichip_8():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)  # raises on any sharding/compile failure


def _deterministic_setup(episode_steps=2, B=2):
    """Flagship small env with zero exploration noise + identical traffic on
    every replica: post-warmup the policy is deterministic, so per-replica
    trajectories must match bitwise."""
    import dataclasses

    import __graft_entry__ as ge
    env, agent, topo, traffic0 = ge._flagship(
        max_nodes=8, max_edges=8, episode_steps=episode_steps, max_flows=32)
    agent = dataclasses.replace(agent, rand_sigma=0.0, rand_mu=0.0)
    env.agent = agent
    traffic = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * B), traffic0)
    pddpg = ParallelDDPG(env, agent, num_replicas=B)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    return pddpg, state, buffers, env_states, obs, topo, traffic


def test_parallel_matches_manual_replica():
    """B=2 with identical traffic and a deterministic post-warmup policy:
    the per-replica transition streams (obs, action, reward, done) must be
    identical across the vmap axis — real cross-replica determinism, not
    just finiteness."""
    pddpg, state, buffers, env_states, obs, topo, traffic = \
        _deterministic_setup(episode_steps=2)
    state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(10**6))
    assert int(buffers.size[0]) == 2 and int(buffers.size[1]) == 2
    jax.tree_util.tree_map(
        lambda x: np.testing.assert_array_equal(np.asarray(x[0]),
                                                np.asarray(x[1])),
        buffers.data)
    assert np.isfinite(float(stats["episodic_return"]))


def test_rollout_chunked_equals_straight():
    """A 4-step episode run as 2x 2-step chunked device calls (the bench /
    TPU operating mode — long single scans fault the chip) reproduces the
    one-call rollout exactly: same replay contents, same final obs."""
    pddpg, state, buffers, env_states, obs, topo, traffic = \
        _deterministic_setup(episode_steps=4)
    start = 10**6  # far past warmup: policy branch, zero noise
    _, b1, es1, ob1, _ = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(start))
    s2, b2, es2, ob2, _ = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(start), 2)
    s2, b2, es2, ob2, _ = pddpg.rollout_episodes(
        s2, b2, es2, ob2, topo, traffic, jnp.int32(start + 2), 2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        b1.data, b2.data)
    np.testing.assert_array_equal(np.asarray(b1.size), np.asarray(b2.size))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        ob1, ob2)


def test_parallel_shuffle_nodes_smoke():
    """shuffle_nodes works through the parallel rollout path too."""
    import dataclasses

    import __graft_entry__ as ge
    env, agent, topo, traffic0 = ge._flagship(max_nodes=8, max_edges=8,
                                              episode_steps=2, max_flows=32)
    agent = dataclasses.replace(agent, shuffle_nodes=True)
    env.agent = agent
    B = 2
    traffic = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), traffic0)
    pddpg = ParallelDDPG(env, agent, num_replicas=B)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(0))
    assert int(buffers.size[0]) == 2
    assert np.isfinite(float(stats["episodic_return"]))


def test_per_replica_topology_diversity():
    """Two replicas train on DIFFERENT topologies inside one rollout scan
    (stack_topologies + per_replica_topology=True) — beyond the reference's
    serial per-episode topology swapping (gym_env.py:103-128)."""
    import __graft_entry__ as ge
    from gsc_tpu.sim.traffic import generate_traffic
    from gsc_tpu.topology import stack_topologies
    from gsc_tpu.topology.compiler import compile_topology
    from gsc_tpu.topology.synthetic import line, triangle

    env, agent, _, _ = ge._flagship(max_nodes=8, max_edges=8,
                                    episode_steps=3, max_flows=32)
    t1 = compile_topology(triangle(), max_nodes=8, max_edges=8)
    t2 = compile_topology(line(4), max_nodes=8, max_edges=8)
    topos = stack_topologies([t1, t2])
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(env.sim_cfg, env.service, t, 3, seed=0)
          for t in (t1, t2)])
    pddpg = ParallelDDPG(env, agent, num_replicas=2,
                         per_replica_topology=True)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topos, traffic)
    # each replica observes its own network from the start
    assert not np.array_equal(np.asarray(obs.node_mask[0]),
                              np.asarray(obs.node_mask[1]))
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topos, traffic, jnp.int32(0))
    assert int(buffers.size[0]) == 3 and int(buffers.size[1]) == 3
    assert np.isfinite(float(stats["episodic_return"]))
    # the stored transitions reflect two different networks
    r0 = np.asarray(buffers.data["obs"].node_mask[0])
    r1 = np.asarray(buffers.data["obs"].node_mask[1])
    assert not np.array_equal(r0, r1)
    state, metrics = pddpg.learn_burst(state, buffers)
    assert np.isfinite(float(metrics["critic_loss"]))


def test_local_sampling_learn_burst():
    """sample_mode='local' draws each replica's contribution from its own
    shard (no cross-shard gather in the learning loop) and still learns:
    finite losses, params move."""
    import __graft_entry__ as ge
    from gsc_tpu.sim.traffic import generate_traffic

    env, agent, topo, traffic0 = ge._flagship(max_nodes=8, max_edges=8,
                                              episode_steps=2, max_flows=32)
    B = 2
    traffic = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), traffic0)
    pddpg = ParallelDDPG(env, agent, num_replicas=B, sample_mode="local")
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    state, buffers, env_states, obs, _ = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(0))
    new_state, metrics = pddpg.learn_burst(state, buffers)
    assert np.isfinite(float(metrics["critic_loss"]))
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()),
        state.critic_params, new_state.critic_params)
    assert max(jax.tree_util.tree_leaves(diff)) > 0


def test_pallas_gnn_selectable_from_config():
    """gnn_impl='pallas' flows from AgentConfig into the embedder and the
    forward runs (interpret mode on CPU)."""
    import dataclasses

    import __graft_entry__ as ge
    from gsc_tpu.models.nets import Actor

    env, agent, topo, traffic = ge._flagship(max_nodes=8, max_edges=8,
                                             episode_steps=2, max_flows=32)
    agent = dataclasses.replace(agent, gnn_impl="pallas")
    _, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    actor = Actor(agent=agent, action_dim=env.limits.action_dim,
                  gnn_impl=agent.gnn_impl)
    params = actor.init(jax.random.PRNGKey(1), obs)
    out = jax.jit(actor.apply)(params, obs)
    assert np.isfinite(np.asarray(out)).all()


def test_harness_global_step_offsets():
    """run_chunked_episodes threads the GLOBAL step into every rollout
    call: chunks advance within an episode, episodes advance within a
    call, and step_offset shifts the whole call — so per-episode drivers
    (Trainer.train_parallel) keep the agent's warmup schedule continuous
    instead of restarting it at 0 each episode."""
    import jax.numpy as jnp

    from gsc_tpu.parallel.harness import run_chunked_episodes

    class Spy:
        def __init__(self):
            self.starts = []
            self.learns = []

        def reset_all(self, rng, topo, traffic):
            return None, None

        def chunk_step(self, state, buffers, es, obs, topo, traffic,
                       start, chunk, learn=False):
            self.starts.append(int(start))
            self.learns.append(learn)
            stats = {"episodic_return": jnp.float32(1.0),
                     "mean_succ_ratio": jnp.float32(0.5),
                     "final_succ_ratio": jnp.float32(0.5)}
            metrics = {"critic_loss": jnp.float32(0.0)} if learn else None
            return state, buffers, es, obs, stats, metrics

    spy = Spy()
    run_chunked_episodes(spy, None, lambda ep: None, None, None,
                         episodes=2, episode_steps=4, chunk=2, seed=0)
    assert spy.starts == [0, 2, 4, 6]
    # the learn burst fuses into the LAST chunk of each episode only
    assert spy.learns == [False, True, False, True]
    spy.starts.clear()
    run_chunked_episodes(spy, None, lambda ep: None, None, None,
                         episodes=1, episode_steps=4, chunk=2, seed=0,
                         step_offset=8)
    assert spy.starts == [8, 10]


def test_chunked_rollout_rejects_shuffle():
    """Chunked rollouts open a fresh permutation frame per device call —
    only correct at episode boundaries — so combining num_steps <
    episode_steps with shuffle_nodes must raise instead of silently
    corrupting the obs<->action frame alignment."""
    import dataclasses

    pddpg, state, buffers, env_states, obs, topo, traffic = \
        _deterministic_setup(episode_steps=4)
    pddpg.agent = dataclasses.replace(pddpg.agent, shuffle_nodes=True)
    with pytest.raises(ValueError, match="shuffle_nodes"):
        pddpg.rollout_episodes(state, buffers, env_states, obs, topo,
                               traffic, jnp.int32(0), 2)
    # whole-episode calls with shuffling stay allowed
    pddpg.rollout_episodes(state, buffers, env_states, obs, topo, traffic,
                           jnp.int32(0), 4)


# ------------------------------------------------- the lockstep ring write
def _ring_stack(B, chunk, capacity, precision="f32", per_replica=False):
    """Tiny flagship stack whose rings hold ``capacity`` rows per replica,
    with traffic for ``chunk`` control steps."""
    import dataclasses

    import __graft_entry__ as ge
    from gsc_tpu.sim.traffic import generate_traffic
    from gsc_tpu.topology import stack_topologies
    from gsc_tpu.topology.compiler import compile_topology
    from gsc_tpu.topology.synthetic import line, triangle

    env, agent, topo, _ = ge._flagship(max_nodes=8, max_edges=8,
                                       episode_steps=chunk, max_flows=32,
                                       gen_traffic=False)
    agent = dataclasses.replace(agent, precision=precision,
                                mem_limit=capacity * B)
    env.agent = agent
    if per_replica:
        nets = [compile_topology(spec, max_nodes=8, max_edges=8)
                for spec in ([triangle(), line(4)] * B)[:B]]
        nets = [n.replace(topo_id=jnp.int32(k)) for k, n in enumerate(nets)]
        topo = stack_topologies(nets)
    else:
        nets = [topo] * B
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(env.sim_cfg, env.service, t, chunk, seed=k)
          for k, t in enumerate(nets)])
    pddpg = ParallelDDPG(env, agent, num_replicas=B,
                         per_replica_topology=per_replica)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    return pddpg, state, pddpg.init_buffers(one_obs), env_states, obs, \
        topo, traffic


def _rollout_with_vmapped_buffer_add(pddpg, state, buffers, env_states, obs,
                                     topo, traffic, episode_start_step,
                                     num_steps):
    """The replica rollout as it was before the one-slab write: the same
    key schedule, policy and env step, with ``buffer_add`` INSIDE the vmap
    over replicas, each replica's ring at its own ``pos`` — the reference
    the lockstep write is held to.  Returns the rings."""
    from gsc_tpu.agents.buffer import buffer_add
    from gsc_tpu.env.actions import action_mask
    from gsc_tpu.env.permutation import ShuffleOps

    env, ddpg, B = pddpg.env, pddpg.ddpg, pddpg.B
    _, sub = jax.random.split(state.rng)
    shuffle = ShuffleOps(pddpg.agent, env.limits)
    sub, k0 = jax.random.split(sub)
    perms0 = jax.vmap(shuffle.init_perm)(jax.random.split(k0, B))
    obs = jax.vmap(shuffle.permute_obs)(obs, perms0)

    def one_step(es, ob, perm, buf, tr, tp, key, i):
        mask = action_mask(tp.node_mask, env.limits.num_sfcs,
                           env.limits.max_sfs)
        action = env.process_action(ddpg.choose_action(
            state.actor_params, ob, shuffle.step_mask(ob, mask, perm),
            episode_start_step + i, key))
        es, next_ob, reward, done, _ = env.step(
            es, tp, tr, shuffle.env_action(action, perm))
        next_ob, next_perm = shuffle.advance(
            jax.random.fold_in(key, 1), next_ob, perm)
        buf = buffer_add(buf, {
            "obs": ob, "next_obs": next_ob, "action": action,
            "reward": reward, "done": done.astype(jnp.float32),
            "topo_idx": tp.topo_id})
        return es, next_ob, next_perm, buf

    def step_fn(carry, i):
        env_states, obs, perms, buffers = carry
        keys = jax.random.split(jax.random.fold_in(sub, i), B)
        return jax.vmap(
            one_step, in_axes=(0, 0, 0, 0, 0, pddpg._t_ax, 0, None))(
                env_states, obs, perms, buffers, traffic, topo, keys, i), None

    carry, _ = jax.lax.scan(step_fn, (env_states, obs, perms0, buffers),
                            jnp.arange(num_steps))
    return carry[3]


@pytest.mark.parametrize("case", [
    dict(id="wraps", B=3, chunk=5, calls=3, capacity=7),
    dict(id="capacity_one", B=2, chunk=3, calls=1, capacity=1),
    dict(id="bf16_replay", B=2, chunk=5, calls=2, capacity=7,
         precision="bf16"),
    dict(id="per_replica_topology", B=4, chunk=5, calls=2, capacity=7,
         per_replica=True),
    dict(id="chunk25", B=2, chunk=25, calls=2, capacity=40),
    dict(id="chunk50", B=2, chunk=50, calls=1, capacity=40),
], ids=lambda c: c["id"])
def test_lockstep_ring_write_equals_vmapped_buffer_add(case):
    """The ring after rollouts through the one-slab-per-leaf write equals,
    leaf for leaf and bit for bit, the ring ``jax.vmap(buffer_add)`` gives
    from the same transitions; ``pos``/``size`` stay ``[B]`` and read
    ``writes % capacity`` / ``min(writes, capacity)``."""
    B, chunk, cap = case["B"], case["chunk"], case["capacity"]
    pddpg, state, buffers, env_states, obs, topo, traffic = _ring_stack(
        B, chunk, cap, case.get("precision", "f32"),
        case.get("per_replica", False))
    assert jax.tree_util.tree_leaves(buffers.data)[0].shape[:2] == (B, cap)

    reference = jax.jit(_rollout_with_vmapped_buffer_add,
                        static_argnums=(0, 8))
    got = want = buffers
    for c in range(case["calls"]):
        # the same env state, traffic and learner key every call: only
        # the rings move on
        start = jnp.int32(c * chunk)
        got = pddpg.rollout_episodes(state, got, env_states, obs, topo,
                                     traffic, start, chunk)[1]
        want = reference(pddpg, state, want, env_states, obs, topo,
                         traffic, start, chunk)

    writes = chunk * case["calls"]
    for ring in (got, want):
        assert ring.pos.shape == ring.size.shape == (B,)
        assert ring.pos.dtype == ring.size.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(ring.pos), writes % cap)
        np.testing.assert_array_equal(np.asarray(ring.size),
                                      min(writes, cap))
    assert got.shapes == want.shapes == buffers.shapes
    flat_got = jax.tree_util.tree_leaves_with_path(got.data)
    flat_want = jax.tree_util.tree_leaves(want.data)
    assert len(flat_got) == len(flat_want) == 14
    for (path, g), w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32)),
            np.asarray(w.astype(jnp.float32)), err_msg=str(path))
    if case.get("precision") == "bf16":
        assert got.data["obs"].nodes.dtype == jnp.bfloat16
        assert got.data["reward"].dtype == jnp.float32
    if case.get("per_replica"):
        # each replica's rows carry its own network's index
        np.testing.assert_array_equal(
            np.asarray(got.data["topo_idx"][:, 0]), np.arange(B))
    # the rings hold something: the last row written is a real transition
    last = (writes - 1) % cap
    assert np.asarray(got.data["obs"].node_mask[:, last]).any()


def test_replay_write_has_no_loop_over_replicas():
    """Structure of the compiled ``chunk_step``: under ``replay_write``
    no ``scatter`` and no ``while`` (the vmapped ``buffer_add`` compiled to
    a scatter per leaf, on the TPU a loop over the replicas), and the same
    operations whatever the number of replicas."""
    from gsc_tpu.analysis.hlo import _walk_ops, scope_stats
    from gsc_tpu.obs.trace import DEVICE_SCOPES

    counts = {}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for B in (4, 16):
            pddpg, state, buffers, env_states, obs, topo, traffic = \
                _ring_stack(B, 2, 8)
            compiled = type(pddpg).chunk_step.lower(
                pddpg, state, buffers, env_states, obs, topo, traffic,
                np.int32(0), num_steps=2, learn=True).compile()
            text = compiled.as_text()
            ops = [op for _, op, _, path, *_ in _walk_ops(text,
                                                          DEVICE_SCOPES)
                   if "replay_write" in path]
            assert ops and "scatter" not in ops
            # a loop the compiler makes of a scatter is run by a `while`
            # that carries the scatter's name (no operation of its own
            # to `_walk_ops`, so looked for in the text)
            assert not [l for l in text.splitlines()
                        if "replay_write" in l and " while(" in l]
            counts[B] = scope_stats(compiled, DEVICE_SCOPES)[
                "replay_write"]["ops_incl"]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert counts[4] == counts[16] > 0


@pytest.mark.parametrize("pos,refused", [
    ([3, 3, 3, 3], None),
    ([3, 3, 5, 3], r"1 of 4 replicas .* other than 3: replicas 2 \(pos 5\)"),
    ([0, 2, 2, 1], r"2 of 4 replicas .* other than 2: replicas 0 \(pos 0\), "
                   r"3 \(pos 1\)"),
], ids=["lockstep", "one_off", "two_off"])
def test_rings_out_of_lockstep_are_refused_with_the_replicas_named(
        pos, refused):
    from gsc_tpu.agents.buffer import ReplayBuffer, lockstep_cursor

    rings = ReplayBuffer(data={"x": jnp.zeros((4, 8, 2))},
                         pos=jnp.asarray(pos, jnp.int32),
                         size=jnp.asarray(pos, jnp.int32))
    if refused is None:
        assert lockstep_cursor(rings) == 3
    else:
        with pytest.raises(ValueError, match=refused):
            lockstep_cursor(rings)
