"""A looped decoder stack as the torso of actor and critic
(``AgentConfig.torso``, models/torso.py) against its plain reference
(``benchmarks/reference/looplm.py``) at a tiny size on the CPU: d = 64, 4
heads of 16, MLP 176, L = 2 layers run T = 4 times, N = 8 slots with 5
real nodes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check
from benchmarks.drivers.train_parallel import (leaf_name, leaf_table,
                                               make_init_state, ring_rows)
from benchmarks.reference import looplm
from gsc_tpu.agents.trainer import Trainer
from gsc_tpu.analysis.hlo import scope_stats
from gsc_tpu.config import AgentConfig, TorsoConfig
from gsc_tpu.env.observations import GraphObs
from gsc_tpu.models.nets import Actor, QNetwork
from gsc_tpu.models.torso import (LoopedTorso, exit_distribution, exit_pass,
                                  take_pass)
from gsc_tpu.obs.trace import DEVICE_SCOPES, TORSO_SCOPES
from gsc_tpu.parallel import ParallelDDPG
from tests.test_agent import make_stack

TORSO = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_dim=16, intermediate_size=176, num_hidden_layers=2,
             total_ut_steps=4)
N, REAL, SHAPE = 8, 5, (8, 1, 3, 8)
A = int(np.prod(SHAPE))
MATH = looplm.Math("highest")


def graph_obs(batch=(), seed=0) -> GraphObs:
    nm = jnp.arange(N) < REAL
    edges = jnp.array([[0, 1, 2, 3, 1, 2, 3, 4], [1, 2, 3, 4, 0, 1, 2, 3]])
    mask = jnp.broadcast_to(nm[:, None, None, None] & nm[None, None, None],
                            SHAPE).reshape(-1).astype(jnp.float32)
    nodes = jax.random.uniform(jax.random.PRNGKey(seed),
                               batch + (N, 3)) * nm[:, None]
    wide = lambda x: jnp.broadcast_to(x, batch + x.shape)
    return GraphObs(nodes=nodes, node_mask=wide(nm), edge_index=wide(edges),
                    edge_mask=jnp.ones(batch + (8,), bool), mask=wide(mask))


def as_dict(obs: GraphObs) -> dict:
    return {k: getattr(obs, k) for k in
            ("nodes", "node_mask", "edge_index", "edge_mask", "mask")}


def reference_config(factored: bool, top=None, **torso) -> dict:
    """What ``looplm.spec_from_config`` reads, for the networks below
    (``top``: other sizes of the agent's)."""
    return {"max_nodes": N, "service": {"sfc_list": {"s": ["a", "b", "c"]}},
            "GNN_features": 22, "GNN_num_layers": 2, "GNN_num_iter": 2,
            "GNN_aggr": "mean", "observation_space": ["x", "y", "z"],
            "factored_head_threshold": 0 if factored else 10 ** 9,
            "factored_key_dim": 32, "gamma": 0.99,
            "target_model_update": 1e-4, "learning_rate": 1e-3,
            "batch_size": 4, "schedule_threshold": 0.1,
            "actor_hidden_layer_nodes": [256],
            "critic_hidden_layer_nodes": [64], "torso": {**TORSO, **torso},
            **(top or {})}


def networks(factored: bool, **torso):
    """Actor and critic with their parameters filled from the reference's
    ``init_weights``: (actor, critic, actor params, critic params, the
    weights by reference name)."""
    agent = AgentConfig(torso={**TORSO, **torso}, factored_head=factored)
    actor = Actor(agent=agent, action_dim=A, sched_shape=SHAPE)
    critic = QNetwork(agent=agent, sched_shape=SHAPE)
    one = graph_obs()
    shape_a = jax.eval_shape(actor.init, jax.random.PRNGKey(1), one)
    shape_c = jax.eval_shape(critic.init, jax.random.PRNGKey(2), one,
                             jnp.zeros(A))
    shapes = {f"{net}/{k}": v.shape for net, tree in
              (("actor", shape_a), ("critic", shape_c))
              for k, v in leaf_table(tree).items()}
    weights = looplm.init_weights(7, shapes)

    def fill(tree, net):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        return jax.tree_util.tree_unflatten(
            treedef, [weights[f"{net}/{leaf_name(p)}"] for p, _ in flat])

    return actor, critic, fill(shape_a, "actor"), fill(shape_c, "critic"), \
        weights


# -------------------------------------------------------------- forward
@pytest.mark.parametrize("net,factored,kv_heads", [
    ("actor", False, 4), ("critic", False, 4), ("actor", True, 4),
    ("critic", True, 4),
    # two key-value heads under four query heads, each read by its group
    # (the published model has as many of one as of the other)
    ("actor", False, 2)],
    ids=["actor-dense", "critic-dense", "actor-bilinear", "critic-bilinear",
         "actor-dense-grouped-kv"])
def test_forward_is_the_plain_reference(net, factored, kv_heads):
    actor, critic, pa, pc, weights = networks(
        factored, num_key_value_heads=kv_heads)
    assert pa["params"]["LoopedTorso_0"]["wk"].shape == (2, 64, kv_heads * 16)
    spec = looplm.spec_from_config(
        reference_config(factored, num_key_value_heads=kv_heads))
    obs = graph_obs((5,))
    if net == "actor":
        got, p = actor.apply(pa, obs, passes=True)
        want, want_p = looplm.actor(MATH, spec, weights, as_dict(obs))
        default = actor.apply(pa, obs)
    else:
        action = jax.random.uniform(jax.random.PRNGKey(3), (5, A))
        got, p = critic.apply(pc, obs, action, passes=True)
        got = got[..., 0]
        want, want_p = looplm.critic(MATH, spec, weights, as_dict(obs),
                                     action)
        default = critic.apply(pc, obs, action)[..., 0]
    assert got.shape[0] == TORSO["total_ut_steps"]
    scale = float(jnp.abs(jnp.stack(want)).max())
    assert scale > 0.1
    np.testing.assert_allclose(got, jnp.stack(want), atol=1e-5 * scale)
    np.testing.assert_allclose(p, want_p, atol=1e-6)
    # the published threshold of one: the default answer is the last pass's
    np.testing.assert_allclose(default, got[-1], atol=1e-5 * scale)


def test_candidate_actions_share_one_pass_of_the_critic():
    actor, critic, pa, pc, _ = networks(False)
    obs = graph_obs((5,))
    answers, _ = actor.apply(pa, obs, passes=True)          # [T, 5, A]
    together = critic.apply(pc, obs, answers)               # [T, 5, 1]
    for t in range(answers.shape[0]):
        np.testing.assert_allclose(
            together[t], critic.apply(pc, obs, answers[t]), atol=1e-6)


# --------------------------------------------------------- shared weights
def untied(spec, per_pass, x, node_mask):
    """The looped stack written out as T x L layers, pass ``t`` reading
    its own copy ``per_pass[t]`` of every leaf: the last pass's masked
    mean."""
    h = MATH.einsum("...i,io->...o", x, per_pass[0]["w_in"])
    for p in per_pass:
        for l in range(spec.layers):
            w = {k: v[l] for k, v in p.items() if k in looplm.LAYER_LEAVES}
            h = looplm.layer(MATH, spec, h, w, node_mask)
        h = looplm.rms_norm(h, p["final_norm"], spec.eps)
    real = node_mask.astype(jnp.float32)[..., None]
    return (h * real).sum(-2) / real.sum(-2)


def test_passes_share_weights_and_a_shared_leafs_gradient_is_their_sum():
    cfg = TorsoConfig(**TORSO)
    spec = looplm.spec_from_config(reference_config(False))
    torso = LoopedTorso(cfg)
    nm = jnp.arange(N) < REAL
    x = jax.random.normal(jax.random.PRNGKey(0), (3, N, 22)) * nm[:, None]
    mask = jnp.broadcast_to(nm, (3, N))
    params = torso.init(jax.random.PRNGKey(1), x, mask)
    params = jax.tree_util.tree_map(     # norms and gate off their init
        lambda v: v + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                              v.shape), params)
    leaves = params["params"]
    tied = [leaves] * cfg.total_ut_steps
    _, z, _ = torso.apply(params, x, mask)
    np.testing.assert_allclose(z[-1], untied(spec, tied, x, mask),
                               atol=2e-5)
    loss = lambda z_last: jnp.sum(jnp.sin(z_last))
    got = jax.grad(lambda p: loss(torso.apply(p, x, mask)[1][-1]))(params)
    each = jax.grad(lambda pp: loss(untied(spec, pp, x, mask)))(tied)
    for name in ("wq", "w_down", "norm_mlp_out", "final_norm"):
        want = sum(g[name] for g in each)
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got["params"][name], want,
                                   atol=2e-4 * scale)
        # every pass contributes: no single copy's gradient is the sum
        assert float(jnp.abs(each[0][name] - want).max()) > 1e-3 * scale


def contractions(jaxpr) -> int:
    """``dot_general`` equations in ``jaxpr`` and every jaxpr nested in its
    equations' parameters (``scan`` bodies, ``checkpoint``, ``pjit``), each
    counted once per occurrence in the text."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)        # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    n += contractions(sub)
    return n


def test_the_gradient_runs_no_second_forward_s_contractions():
    torso = LoopedTorso(TorsoConfig(**{**TORSO, "total_ut_steps": 2}))
    nm = jnp.arange(N) < REAL
    x = jax.random.normal(jax.random.PRNGKey(0), (3, N, 22)) * nm[:, None]
    mask = jnp.broadcast_to(nm, (3, N))
    params = torso.init(jax.random.PRNGKey(1), x, mask)

    def loss(p):
        _, z, exits = torso.apply(p, x, mask)
        return jnp.sum(jnp.sin(z)) + jnp.sum(exits * jnp.arange(2.0)[:, None])

    forward = contractions(jax.make_jaxpr(loss)(params).jaxpr)
    grad = contractions(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    # the layer's seven projections, its two attention products, the input
    # projection and the exit gate, once in the program text
    assert forward == 7 + 2 + 1 + 1
    # the forward once, the backward two contractions for each of it (one
    # where an operand is the input, which takes no gradient), and the two
    # attention products the layer recomputes; a wholly rematerialised
    # layer would run all nine a second time
    assert grad <= 3 * forward + 2, (grad, forward)


# ------------------------------------------------------------- exit gate
def test_exit_distribution_sums_to_one_and_the_last_takes_the_rest():
    lam = jax.random.uniform(jax.random.PRNGKey(0), (4, 6), minval=0.05,
                             maxval=0.6)
    p = exit_distribution(lam)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[0], lam[0])
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-6)
    np.testing.assert_allclose(p[-1], jnp.prod(1 - lam[:-1], axis=0),
                               rtol=1e-6)
    # a threshold of one is reached by the remainder alone: the last pass,
    # decided statically
    assert exit_pass(p, 1.0) is None
    x = jnp.arange(4 * 6 * 2, dtype=jnp.float32).reshape(4, 6, 2)
    np.testing.assert_array_equal(take_pass(x, None), x[-1])
    # below one: the first pass whose cumulative mass reaches it
    idx = exit_pass(p, 0.5)
    cum = np.cumsum(np.asarray(p), axis=0)
    want = [int(np.argmax(cum[:, b] >= 0.5)) if (cum[:, b] >= 0.5).any()
            else 3 for b in range(6)]
    assert idx.tolist() == want and len(set(want)) > 1
    np.testing.assert_array_equal(
        take_pass(x, idx), np.stack([x[t, b] for b, t in enumerate(want)]))


def test_a_threshold_below_one_picks_the_reference_s_pass():
    actor, _, pa, _, weights = networks(False, early_exit_threshold=0.4)
    spec = looplm.spec_from_config(
        reference_config(False, early_exit_threshold=0.4))
    obs = graph_obs((5,))
    answers, p = looplm.actor(MATH, spec, weights, as_dict(obs))
    assert len(set(exit_pass(p, 0.4).tolist())) >= 1
    np.testing.assert_allclose(actor.apply(pa, obs),
                               looplm.picked(spec, p, answers), atol=1e-5)


def test_padded_slots_change_no_real_nodes_output():
    actor, critic, pa, pc, _ = networks(True)
    obs = graph_obs((2,))
    nm = obs.node_mask
    noisy = obs.replace(nodes=jnp.where(
        nm[..., None], obs.nodes,
        jax.random.normal(jax.random.PRNGKey(9), obs.nodes.shape)))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, N, 22))
    torso = LoopedTorso(TorsoConfig(**TORSO))
    tp = torso.init(jax.random.PRNGKey(1), x, nm)
    h, z, p = torso.apply(tp, x, nm)
    x2 = jnp.where(nm[..., None], x, 7.0 * x + 3.0)
    h2, z2, p2 = torso.apply(tp, x2, nm)
    np.testing.assert_allclose(h[..., :REAL, :], h2[..., :REAL, :],
                               atol=1e-6)
    np.testing.assert_allclose(z, z2, atol=1e-6)
    np.testing.assert_allclose(p, p2, atol=1e-6)
    assert float(jnp.abs(h[..., REAL:, :] - h2[..., REAL:, :]).max()) > 1e-3
    # and through the networks, the embedder included
    np.testing.assert_allclose(actor.apply(pa, obs), actor.apply(pa, noisy),
                               atol=1e-6)
    act = jax.random.uniform(jax.random.PRNGKey(3), (2, A))
    np.testing.assert_allclose(critic.apply(pc, obs, act),
                               critic.apply(pc, noisy, act), atol=1e-6)


def test_without_a_torso_the_parameter_tree_is_as_it_was():
    agent = AgentConfig()
    assert agent.torso is None
    actor = Actor(agent=agent, action_dim=A, sched_shape=SHAPE)
    critic = QNetwork(agent=agent, sched_shape=SHAPE)
    one = graph_obs()
    conv = ["att", "b_l", "b_r", "bias", "w_l", "w_r"]
    embedder = [f"params/GNNEmbedder_0/{layer}/{leaf}"
                for layer in ("encoder", "process_0") for leaf in conv]
    dense = lambda n: [f"params/MLP_0/Dense_{i}/{leaf}" for i in range(n)
                       for leaf in ("bias", "kernel")]
    pa = jax.eval_shape(actor.init, jax.random.PRNGKey(1), one)
    pc = jax.eval_shape(critic.init, jax.random.PRNGKey(2), one,
                        jnp.zeros(A))
    assert sorted(leaf_table(pa)) == sorted(embedder + dense(2))
    assert sorted(leaf_table(pc)) == sorted(embedder + dense(2))
    assert leaf_table(pa)["params/MLP_0/Dense_0/kernel"].shape == \
        (22 + A, 256)
    # and with one, the heads read its width
    with_torso = networks(False)[2]
    assert leaf_table(with_torso)["params/MLP_0/Dense_0/kernel"].shape == \
        (64 + A, 256)


def test_config_refuses_what_it_does_not_know():
    with pytest.raises(ValueError, match="unknown torso key"):
        AgentConfig(torso={**TORSO, "hidden": 64})
    with pytest.raises(ValueError, match="graph_mode"):
        AgentConfig(torso=TORSO, graph_mode=False)
    agent = AgentConfig(torso=TORSO)
    assert isinstance(agent.torso, TorsoConfig) and hash(agent)
    assert agent == AgentConfig(torso=TorsoConfig(**TORSO))
    with pytest.raises(SystemExit, match="LoopedTorso_0/w_in"):
        looplm.init_weights(0, {"actor/params/MLP_0/Dense_0/kernel": (2, 2)})


# ------------------------------------------------- the learner, end to end
@pytest.fixture(scope="module")
def stack():
    env, agent, topo, traffic = make_stack(
        episode_steps=4, warmup=4,
        agent_kwargs={"torso": TORSO, "learn_steps": 3})
    b = 2
    from gsc_tpu.obs.learning import LearnLedgerSpec
    pddpg = ParallelDDPG(env, agent, num_replicas=b, donate=False,
                         learn_ledger=LearnLedgerSpec(num_topos=1))
    batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *[traffic] * b)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, batch)
    _, one = env.reset(jax.random.PRNGKey(1), topo, traffic)
    shape = jax.eval_shape(pddpg.init, jax.random.PRNGKey(0), one)
    state, weights, _ = make_init_state(5, shape, looplm.init_weights)
    buffers = pddpg.init_buffers(one)
    return pddpg, agent, (state, buffers, env_states, obs, topo, batch), \
        weights


def test_three_learn_steps_are_the_reference_s(stack):
    pddpg, agent, (state, buffers, env_states, obs, topo, batch), weights = \
        stack
    steps = agent.episode_steps
    state, buffers, *_ = pddpg.rollout_episodes(
        state, buffers, env_states, obs, topo, batch, np.int32(0), steps)
    key = state.rng
    after, metrics = pddpg.learn_burst(state, buffers)
    rows = {k: jnp.asarray(v) for k, v in ring_rows(
        buffers, (slice(None), slice(steps))).items()}
    cfg = reference_config(False, top={
        "max_nodes": pddpg.env.limits.max_nodes, "GNN_features": 8,
        "gamma": agent.gamma, "learning_rate": agent.learning_rate,
        "actor_hidden_layer_nodes": [16], "critic_hidden_layer_nodes": [16]})
    spec = looplm.spec_from_config(cfg)
    want, out = looplm.learn_burst("highest", spec, weights, key, rows,
                                   2, steps, 3)
    np.testing.assert_allclose(float(metrics["critic_loss"]),
                               out["critic_loss"], rtol=1e-4)
    np.testing.assert_allclose(float(metrics["actor_loss"]),
                               out["actor_loss"], rtol=1e-4)
    sig = metrics["learn_signal"]
    td = float(sig["td_abs_sum"].sum() / sig["td_count"].sum())
    np.testing.assert_allclose(td, out["td_abs_mean"], rtol=1e-4)
    # the end state by the benchmark's own measures (benchmarks/check.py):
    # leaf by leaf, norms of Adam's moments and of the parameters' change
    prog = check.program_side({k: np.asarray(v) for k, v in
                               leaf_table(after).items()}, [])
    host = lambda table: {k: np.asarray(v) for k, v in table.items()}
    ref = {"td_abs_mean": out["td_abs_mean"], **{
        k: host(want[k]) for k in ("params", "mu", "nu")}}
    prog["td_abs_mean"] = td
    numbers = check.learner_numbers(prog, ref, host(weights))
    assert numbers["td_gap"] < 1e-4, numbers
    assert numbers["moment_mid_gap"] < 1e-4, numbers
    assert numbers["moment2_mid_gap"] < 1e-4, numbers
    assert numbers["change_gap"] < 1e-2, numbers
    # and the change itself, leaf against leaf (an element whose gradient
    # is rounding noise moves by a whole Adam step either way)
    for net in ("actor", "critic"):
        for leaf in ("wq", "w_down", "w_in", "gate_w"):
            name = f"{net}/params/LoopedTorso_0/{leaf}"
            moved = ref["params"][name] - np.asarray(weights[name])
            assert np.abs(moved).max() > 1e-3, name     # the burst trained
            off = prog["params"][name] - np.asarray(weights[name]) - moved
            assert np.linalg.norm(off) < 1e-2 * np.linalg.norm(moved), name
    # where the exit distributions put their mass rides the learn signal
    for net in ("actor", "critic"):
        stats = sig["exits"][net]
        assert 1.0 <= float(stats["exit_step_mean"]) <= 4.0
        assert 0.0 < float(stats["exit_entropy"]) <= np.log(4.0) + 1e-6


@pytest.fixture(scope="module")
def chunk_scopes(stack):
    pddpg, _, (state, buffers, env_states, obs, topo, batch), _ = stack
    jax.config.update("jax_enable_compilation_cache", False)
    try:    # past the persistent cache: it keys a program without names
        compiled = type(pddpg).chunk_step.lower(
            pddpg, state, buffers, env_states, obs, topo, batch,
            np.int32(0), num_steps=2, learn=True).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    return scope_stats(compiled, DEVICE_SCOPES)


@pytest.mark.parametrize("scope", TORSO_SCOPES)
def test_torso_scopes_stand_in_the_compiled_chunk_step(chunk_scopes, scope):
    assert chunk_scopes[scope]["ops"] > 0
    assert chunk_scopes["torso_pass"]["ops_incl"] >= \
        chunk_scopes["torso_pass"]["ops"] \
        + chunk_scopes["torso_attention"]["ops"] \
        + chunk_scopes["torso_mlp"]["ops"]
    # the torso runs under the policy and under both updates
    assert chunk_scopes["policy_forward"]["ops_incl"] > \
        chunk_scopes["policy_forward"]["ops"]


# ------------------------------------------------- the boundary's verdict
@pytest.mark.parametrize("poisoned", [None, "actor_opt", "critic_params"])
def test_device_finite_check_gives_the_host_scans_verdict(stack, poisoned):
    state = stack[2][0]
    if poisoned is not None:
        tree = getattr(state, poisoned)
        flat, treedef = jax.tree_util.tree_flatten(tree)
        at = max(range(len(flat)), key=lambda i: flat[i].size)
        flat[at] = flat[at].at[(0,) * flat[at].ndim].set(jnp.nan)
        state = state.replace(
            **{poisoned: jax.tree_util.tree_unflatten(treedef, flat)})
    want = poisoned is None
    assert Trainer._finite_host(jax.device_get(state)) is want
    assert Trainer._finite_device(state) is want
