"""Mixed-precision policy tests (``pytest -m precision``).

Three contracts (ISSUE 3):

- the "f32" policy is BIT-identical to the dtype-unaware stack — the
  policy plumbing must take the legacy code paths verbatim, so the fused
  episode step still equals the two-call rollout+learn path exactly;
- the bf16 Pallas kernel matches the bf16 branch of the dense XLA
  attention bit-for-bit in interpret mode (same op sequence, f32
  logits/softmax accumulators), forward AND backward;
- bf16 training stays sane: f32 master params/optimizer state, f32
  network outputs, finite losses, returns within tolerance of f32, and
  replay storage (plus ``buffer_nbytes``) honestly halved.

All tests run on CPU (Pallas in interpret mode) and are tier-1 fast.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.agents import DDPG
from gsc_tpu.agents.buffer import buffer_init, buffer_nbytes
from gsc_tpu.config.schema import (AgentConfig, PRECISION_POLICIES,
                                   PrecisionPolicy, precision_policy)
from gsc_tpu.models.gnn import GATv2Conv
from gsc_tpu.ops.gat import attention_dense, dense_adj, project
from gsc_tpu.ops.pallas_gat import gatv2_pallas

from tests.test_agent import make_stack
from tests.test_models import random_graph

pytestmark = pytest.mark.precision


def _tree_bits_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


# ------------------------------------------------------------------ policy
def test_policy_registry_and_validation():
    assert AgentConfig().precision == "f32"        # default = legacy stack
    assert not PRECISION_POLICIES["f32"].mixed
    bf16 = precision_policy("bf16")
    assert bf16.mixed
    assert bf16.param_dtype == "float32"           # masters never leave f32
    assert (bf16.gnn_dtype, bf16.mlp_dtype, bf16.replay_cast_dtype) == \
        ("bfloat16", "bfloat16", "bfloat16")
    # f32 slots resolve to None = "take the legacy exact path"
    f32 = precision_policy("f32")
    assert (f32.gnn_dtype, f32.mlp_dtype, f32.replay_cast_dtype) == \
        (None, None, None)
    with pytest.raises(ValueError, match="unknown precision"):
        AgentConfig(precision="fp8")
    with pytest.raises(ValueError, match="param_dtype"):
        PrecisionPolicy(name="bad", param_dtype="bfloat16")
    with pytest.raises(ValueError, match="gnn_compute"):
        PrecisionPolicy(name="bad", gnn_compute="float16")


def test_loader_parses_precision(tmp_path):
    from gsc_tpu.config.loader import load_agent
    p = tmp_path / "agent.yaml"
    p.write_text("graph_mode: true\nprecision: bf16\n")
    assert load_agent(str(p)).precision == "bf16"
    assert load_agent(str(p), precision="f32").precision == "f32"


# --------------------------------------------------------- f32 exactness
def test_project_f32_is_verbatim():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (4, 8, 3))
    w = jax.random.normal(k2, (3, 16))
    b = jax.random.normal(k3, (16,))
    np.testing.assert_array_equal(np.asarray(project(x, w, b, None)),
                                  np.asarray(x @ w + b))
    assert project(x, w, b, "bfloat16").dtype == jnp.bfloat16


def test_f32_fused_step_bit_identical_to_two_call_path():
    """The exact-resume contract (test_pipeline) re-asserted THROUGH the
    precision plumbing: with the default f32 policy, episode_step ==
    rollout_episode + learn_burst bit-for-bit."""
    env, agent, topo, traffic = make_stack()
    assert agent.precision == "f32"
    ddpg = DDPG(env, agent)   # donate=False: same inputs used twice
    env_state, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    state = ddpg.init(jax.random.PRNGKey(1), obs)
    buf = ddpg.init_buffer(obs)
    assert all(l.dtype != jnp.bfloat16
               for l in jax.tree_util.tree_leaves(buf.data))
    s1, b1, e1, o1, st1 = ddpg.rollout_episode(
        state, buf, env_state, obs, topo, traffic, jnp.int32(0))
    s1, m1 = ddpg.learn_burst(s1, b1)
    s2, b2, e2, o2, st2, m2 = ddpg.episode_step(
        state, buf, env_state, obs, topo, traffic, jnp.int32(0), learn=True)
    _tree_bits_equal((s1, b1, e1, o1, st1, m1), (s2, b2, e2, o2, st2, m2))


# --------------------------------------------- pallas-bf16 vs dense-bf16
@pytest.mark.parametrize("mean_aggr", [True, False])
def test_pallas_bf16_dense_bf16_parity(mean_aggr):
    """Interpret-mode BIT parity: the bf16 kernel rounds to bf16 exactly
    where the bf16 branch of attention_dense does (pairwise features,
    LeakyReLU product, attention weights, output) with f32
    logits/softmax/accumulators in between."""
    _, ei, em, nm = random_graph(jax.random.PRNGKey(0), batch=(5,))
    adj = dense_adj(ei, em, nm)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    F = 16
    xl = jax.random.normal(k1, (5, 8, F)).astype(jnp.bfloat16)
    xr = jax.random.normal(k2, (5, 8, F)).astype(jnp.bfloat16)
    att = jax.random.normal(k3, (F,))
    bias = jax.random.normal(k4, (F,))
    dense = attention_dense(xl, xr, att, bias, adj, mean_aggr)
    # tile_b=None → tiles sized from the shapes (pallas_gat.tile_shape)
    fused = gatv2_pallas(xl, xr, att, bias, adj, mean_aggr,
                         tile_b=None, interpret=True)
    assert dense.dtype == fused.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(fused))

    # backward parity: the kernel's custom VJP differentiates through the
    # SAME bf16 dense branch, so gradients are bit-equal too
    def loss(fn):
        def f(xl_, xr_, att_, bias_):
            return jnp.sum(fn(xl_, xr_, att_, bias_).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2, 3))(xl, xr, att, bias)

    g_dense = loss(lambda *a: attention_dense(*a, adj, mean_aggr))
    g_fused = loss(lambda *a: gatv2_pallas(*a, adj, mean_aggr,
                                           tile_b=None, interpret=True))
    _tree_bits_equal(g_dense, g_fused)


def test_bf16_conv_tracks_f32():
    """One bf16 GATv2 layer stays within bf16 rounding of the f32 layer on
    the SAME parameters (sanity bound, not bit parity)."""
    nodes, ei, em, nm = random_graph(jax.random.PRNGKey(1))
    adj = dense_adj(ei, em, nm)
    conv32 = GATv2Conv(features=16, mean_aggr=True, impl="dense")
    params = conv32.init(jax.random.PRNGKey(2), nodes, adj=adj)
    out32 = conv32.apply(params, nodes, adj=adj)
    conv16 = GATv2Conv(features=16, mean_aggr=True, impl="dense",
                       compute_dtype="bfloat16")
    out16 = conv16.apply(params, nodes, adj=adj)
    assert out16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out32),
                               np.asarray(out16, np.float32),
                               rtol=0.05, atol=0.05)


# ---------------------------------------------------------- bf16 training
def test_bf16_masters_f32_outputs_and_masking():
    env, agent, topo, traffic = make_stack(
        agent_kwargs={"precision": "bf16"})
    env.agent = agent
    ddpg = DDPG(env, agent)
    _, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    state = ddpg.init(jax.random.PRNGKey(1), obs)
    # master params AND optimizer state stay f32 under the bf16 policy
    for tree in (state.actor_params, state.critic_params,
                 state.target_actor_params, state.target_critic_params,
                 state.actor_opt, state.critic_opt):
        for leaf in jax.tree_util.tree_leaves(tree):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                assert leaf.dtype == jnp.float32, leaf.dtype
    action = ddpg.actor.apply(state.actor_params, obs)
    q = ddpg.critic.apply(state.critic_params, obs, action)
    # network outputs leave in f32 (noise/TD targets run full precision)
    assert action.dtype == jnp.float32 and q.dtype == jnp.float32
    # masked (padded) action entries are exactly zero even through bf16
    masked = np.asarray(action)[np.asarray(obs.mask) == 0]
    assert not masked.any()


def test_bf16_replay_storage_and_nbytes():
    """The bf16 policy halves replay float leaves; reward/done stay f32;
    buffer_nbytes reports the ACTUAL per-leaf storage dtype (the mixed-
    dtype accounting the `replay bytes` gauge reads)."""
    env, agent, topo, traffic = make_stack()
    _, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    ddpg32 = DDPG(env, agent)
    agent16 = dataclasses.replace(agent, precision="bf16")
    ddpg16 = DDPG(env, agent16)
    buf32, buf16 = ddpg32.init_buffer(obs), ddpg16.init_buffer(obs)
    assert buf16.data["reward"].dtype == jnp.float32
    assert buf16.data["done"].dtype == jnp.float32
    assert buf16.data["action"].dtype == jnp.bfloat16
    assert buf16.data["obs"].nodes.dtype == jnp.bfloat16
    assert buf16.data["obs"].node_mask.dtype == jnp.bool_   # non-float kept
    # nbytes must track per-leaf dtypes, never a blanket element size
    for buf in (buf32, buf16):
        expected = sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(buf.data))
        assert buffer_nbytes(buf) == expected
    assert buffer_nbytes(buf16) < buffer_nbytes(buf32)
    # generic mixed-dtype buffer: 2-byte and 4-byte leaves side by side
    buf = buffer_init({"a": jnp.zeros(4, jnp.bfloat16),
                       "b": jnp.zeros(4, jnp.float32)}, capacity=8)
    assert buffer_nbytes(buf) == 8 * (4 * 2 + 4 * 4)


def test_bf16_learning_sanity_dummy_sim():
    """Short training over the canned dummy backend: bf16 losses finite,
    episodic return finite and within tolerance of the f32 run."""
    from tests.test_dummy_backend import build

    def run(precision):
        env, topo, traffic, limits = build()
        agent = dataclasses.replace(
            env.agent, nb_steps_warmup_critic=3, mem_limit=32, batch_size=4,
            gnn_features=8, actor_hidden_layer_nodes=(16,),
            critic_hidden_layer_nodes=(16,), precision=precision)
        env.agent = agent
        ddpg = DDPG(env, agent)
        env_state, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
        state = ddpg.init(jax.random.PRNGKey(1), obs)
        buf = ddpg.init_buffer(obs)
        rets = []
        for ep in range(2):
            state, buf, env_state, obs, stats, metrics = ddpg.episode_step(
                state, buf, env_state, obs, topo, traffic,
                jnp.int32(ep * agent.episode_steps), learn=True)
            rets.append(float(stats["episodic_return"]))
        return rets, {k: float(v) for k, v in metrics.items()}

    rets32, _ = run("f32")
    rets16, metrics16 = run("bf16")
    assert all(np.isfinite(rets16))
    assert all(np.isfinite(v) for v in metrics16.values())
    # bf16 rounding must not derail the short-horizon returns
    np.testing.assert_allclose(rets16, rets32, rtol=0.1, atol=0.5)


def test_bf16_parallel_chunk_step():
    """The replica-parallel fused path (ParallelDDPG.chunk_step) runs
    under bf16: sharded replay stores bf16, learn burst finite."""
    from gsc_tpu.parallel import ParallelDDPG

    env, agent, topo, traffic = make_stack(
        agent_kwargs={"precision": "bf16"})
    env.agent = agent
    B = 2
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * B), traffic)
    pddpg = ParallelDDPG(env, agent, num_replicas=B)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, stacked)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    assert buffers.data["action"].dtype == jnp.bfloat16
    state, buffers, env_states, obs, stats, metrics = pddpg.chunk_step(
        state, buffers, env_states, obs, topo, stacked, jnp.int32(0),
        num_steps=agent.episode_steps, learn=True)
    assert np.isfinite(float(stats["episodic_return"]))
    assert np.isfinite(float(metrics["critic_loss"]))
    for leaf in jax.tree_util.tree_leaves(state.actor_params):
        assert leaf.dtype == jnp.float32


# ----------------------------------------------------- checkpoint metadata
def test_checkpoint_precision_meta_roundtrip(tmp_path):
    """Checkpoints record their precision policy in a JSON sidecar, so a
    resume/infer can adopt the right policy BEFORE building the (dtype-
    sensitive) restore templates; pre-meta checkpoints read as {}."""
    from gsc_tpu.utils.checkpoint import (read_checkpoint_meta,
                                          save_checkpoint)

    env, agent, topo, traffic = make_stack(
        agent_kwargs={"precision": "bf16"})
    env.agent = agent
    ddpg = DDPG(env, agent)
    _, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    state = ddpg.init(jax.random.PRNGKey(1), obs)
    ck = save_checkpoint(str(tmp_path / "ck"), state,
                         buffer=ddpg.init_buffer(obs),
                         meta={"precision": agent.precision})
    assert read_checkpoint_meta(ck) == {"precision": "bf16"}
    # sidecar sits NEXT to the orbax dir (orbax rewrites the dir itself)
    assert (tmp_path / "ck.meta.json").exists()
    assert read_checkpoint_meta(str(tmp_path / "nonexistent")) == {}
    # a corrupt/truncated sidecar reads as pre-meta, never raises
    (tmp_path / "ck.meta.json").write_text('{"precision": "bf')
    assert read_checkpoint_meta(ck) == {}
    # a meta-less re-save must drop the stale sidecar — otherwise the old
    # policy would describe the new checkpoint
    save_checkpoint(str(tmp_path / "ck"), state)
    assert not (tmp_path / "ck.meta.json").exists()
    assert read_checkpoint_meta(ck) == {}


# -------------------------------------------------------------- obs gauges
def test_record_precision_gauges(tmp_path):
    from gsc_tpu.obs import RunObserver

    obs = RunObserver(str(tmp_path), snapshot_interval=1)
    obs.start(meta={"precision": "bf16"})
    obs.record_precision(precision_policy("bf16"))
    assert obs.hub.get_gauge("dtype_bits", role="param") == 32
    assert obs.hub.get_gauge("dtype_bits", role="gnn_compute") == 16
    assert obs.hub.get_gauge("dtype_bits", role="mlp_compute") == 16
    assert obs.hub.get_gauge("dtype_bits", role="replay") == 16
    obs.close()
    # the event stream carries the policy for the report header
    import json
    events = [json.loads(l) for l in
              (tmp_path / "events.jsonl").read_text().splitlines()]
    prec = [e for e in events if e.get("event") == "precision"]
    assert prec and prec[0]["replay_dtype"] == "bfloat16"
    # obs_report surfaces it in the per-run summary
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    summary = mod.summarize(mod.load_events(str(tmp_path)))
    assert summary["precision"]["name"] == "bf16"
