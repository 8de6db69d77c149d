"""Multi-chip pjit sharding tests: partition rules over the DDPG
param/opt pytree, shard/gather roundtrips, mesh-carving bit-equality of
the final learner state WITH params actually sharded, the replicated
no-op fallback, and the subprocess elastic-resume roundtrip across a
device-count change.

All marked ``multichip`` — ``pytest -m multichip -q`` is the standalone
smoke group for gsc_tpu/parallel/partition.py and the sharded dispatch.
Everything runs on the conftest's 8-device virtual CPU mesh in ONE
process (1-core box: the suite is serialized anyway); the elastic test
launches its cli subprocesses through the shared .jax_cache.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gsc_tpu.parallel import (
    ParallelDDPG,
    ShardingPlan,
    make_shard_and_gather_fns,
    make_train_mesh,
    match_partition_rules,
    parse_mesh_shape,
    sharded_rules,
    spec_summary,
)
from gsc_tpu.parallel.partition import (
    REPLICATED_RULES,
    apply_fns,
    clamp_specs_to_mesh,
    leaf_path_names,
)

pytestmark = pytest.mark.multichip

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ rule matching
def test_parse_mesh_shape():
    assert parse_mesh_shape("8x1") == (8, 1)
    assert parse_mesh_shape("4x2") == (4, 2)
    assert parse_mesh_shape("8") == (8, 1)      # bare N means Nx1
    assert parse_mesh_shape(" 2X4 ") == (2, 4)  # case/space tolerant
    for bad in ("", "axb", "0x2", "2x0", "2x2x2", "-1"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


def test_match_partition_rules_paths_scalars_and_default():
    tree = {"actor": {"MLP_0": {"kernel": jnp.zeros((4, 8)),
                                "bias": jnp.zeros(8)}},
            "gnn": {"w_l": jnp.zeros((4, 8)), "att": jnp.zeros((8, 1))},
            "step": jnp.zeros((), jnp.int32)}
    specs = match_partition_rules(sharded_rules(), tree)
    assert specs["actor"]["MLP_0"]["kernel"] == P(None, "mp")
    assert specs["gnn"]["w_l"] == P(None, "mp")
    # biases and attention vectors fall through to replication
    assert specs["actor"]["MLP_0"]["bias"] == P()
    assert specs["gnn"]["att"] == P()
    # scalars are never partitioned, whatever the rules say
    assert specs["step"] == P()
    scalar_only = {"kernel": jnp.zeros(())}
    assert match_partition_rules(
        ((r".*", P("mp")),), scalar_only)["kernel"] == P()
    # a leaf no rule matches is an error, not silent replication
    with pytest.raises(ValueError, match="no partition rule"):
        match_partition_rules(((r"kernel$", P(None, "mp")),),
                              {"other": jnp.zeros((2, 2))})


@pytest.mark.parametrize("book", ["sharded", "tp"])
def test_a_looped_torsos_leaves_stay_replicated(book):
    """The books' rules name 2-D leaves (``kernel``, ``w_l``, ``w_r``): on a
    stacked ``[layers, in, out]`` leaf ``P(None, 'mp')`` would split the
    contraction axis, so the torso's leaf names match none of them."""
    from gsc_tpu.config import TorsoConfig
    from gsc_tpu.models.torso import LoopedTorso
    from gsc_tpu.parallel.partition import tp_rules

    torso = LoopedTorso(TorsoConfig(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, intermediate_size=176, num_hidden_layers=2,
        total_ut_steps=4))
    tree = jax.eval_shape(torso.init, jax.random.PRNGKey(0),
                          jnp.zeros((8, 22)), jnp.ones(8, bool))
    tree = {"actor_params": {"params": {"LoopedTorso_0": tree["params"]}}}
    rules = sharded_rules() if book == "sharded" else tp_rules()
    specs = match_partition_rules(rules, tree)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == 15 and all(spec == P() for spec in leaves)


def test_clamp_specs_to_mesh_indivisible_widths():
    mesh = make_train_mesh(4, 2)
    tree = {"wide": {"kernel": jnp.zeros((4, 8))},    # 8 % 2 == 0: stays
            "narrow": {"kernel": jnp.zeros((4, 7))},  # 7 % 2 != 0: clamps
            "vec": {"kernel": jnp.zeros(6)}}          # out-ranked: clamps
    specs = match_partition_rules(sharded_rules(), tree)
    assert specs["vec"]["kernel"] == P(None, "mp")    # matched pre-clamp
    clamped, n = clamp_specs_to_mesh(specs, tree, mesh)
    assert clamped["wide"]["kernel"] == P(None, "mp")
    assert clamped["narrow"]["kernel"] == P()
    assert clamped["vec"]["kernel"] == P()
    assert n == 2
    counts = spec_summary(clamped)
    assert counts == {"PartitionSpec()": 2,
                      "PartitionSpec(None, 'mp')": 1}


def test_leaf_path_names_join():
    tree = {"a": {"b": [jnp.zeros(1), jnp.zeros(2)]}, "c": jnp.zeros(3)}
    names = leaf_path_names(tree)
    assert any(n.endswith("a/b/0") for n in names)
    assert any(n.endswith("a/b/1") for n in names)


def test_plan_rulebook_validation():
    mesh = make_train_mesh(4, 2)
    assert not ShardingPlan(mesh, "replicated").is_sharded
    assert ShardingPlan(mesh, "sharded").is_sharded
    assert not ShardingPlan(make_train_mesh(8, 1), "sharded").is_sharded
    with pytest.raises(ValueError, match="unknown rulebook"):
        ShardingPlan(mesh, "zigzag")


# --------------------------------------------------------- shard / gather
def test_shard_gather_roundtrip_identity():
    """place_state puts a host tree into the plan's (genuinely sharded)
    residency; gather_state returns bit-identical host arrays."""
    plan = ShardingPlan.from_spec("4x2", rules="sharded")
    rng = np.random.default_rng(0)
    host = {"layer": {"kernel": rng.normal(size=(6, 8)).astype(np.float32),
                      "bias": rng.normal(size=(8,)).astype(np.float32)},
            "step": np.asarray(3, np.int32)}
    placed = plan.place_state(host)
    assert not placed["layer"]["kernel"].sharding.is_fully_replicated
    assert placed["layer"]["bias"].sharding.is_fully_replicated
    back = plan.gather_state(placed)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        back, host)
    # gather is also exact straight off a HOST tree (the no-mesh path
    # checkpoints take when a run was never sharded)
    back2 = plan.gather_state(host)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        back2, host)
    # the summary the CLI records: one sharded leaf, two replicated
    assert plan.summary(host) == {"PartitionSpec()": 2,
                                  "PartitionSpec(None, 'mp')": 1}


def test_make_shard_and_gather_fns_per_leaf():
    plan = ShardingPlan.from_spec("2x4", rules="sharded")
    tree = {"kernel": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
    shardings = plan.state_shardings(tree)
    shard_fns, gather_fns = make_shard_and_gather_fns(shardings)
    placed = apply_fns(shard_fns, tree)
    assert placed["kernel"].sharding == shardings["kernel"]
    back = apply_fns(gather_fns, placed)
    assert isinstance(back["kernel"], np.ndarray)
    np.testing.assert_array_equal(back["kernel"], np.asarray(tree["kernel"]))


# ----------------------------------------------------- dispatch bit-equality
def _tiny_leg(plan, episodes=1, replicas=8, episode_steps=2):
    """One chunked-training leg under ``plan`` (None = today's
    single-device dispatch); returns (digest of the host-gathered final
    learner state, count of actually-sharded state leaves).  The recipe
    itself lives in ``__graft_entry__.sharded_training_leg`` — the ONE
    definition of the bit-equality witness, shared with the
    dryrun_multihost mesh-matrix legs so the CI verdict and this test
    can never diverge on what "bit-identical" means."""
    from __graft_entry__ import sharded_training_leg

    leg = sharded_training_leg(plan, episodes=episodes, replicas=replicas,
                               episode_steps=episode_steps)
    return leg["digest"], leg["sharded_leaves"]


def test_carving_bit_equality_with_sharded_params():
    """Tentpole acceptance: the final learner state is BIT-identical
    across mesh carvings of the same 8 devices — with the sharded
    rulebook genuinely splitting parameter leaves over mp (asserted, so
    the equality is not vacuously about replicated copies)."""
    d42, n42 = _tiny_leg(ShardingPlan.from_spec("4x2", rules="sharded"))
    d24, n24 = _tiny_leg(ShardingPlan.from_spec("2x4", rules="sharded"))
    assert n42 > 0 and n24 > 0, "sharded rules split no leaf — vacuous"
    assert d42 == d24
    # the extreme carving: no data-parallel axis at all, every shardable
    # leaf split over mp=8 (widths that don't divide 8 clamp to P())
    d18, n18 = _tiny_leg(ShardingPlan.from_spec("1x8", rules="sharded"))
    assert n18 > 0, "1x8 sharded no leaf — vacuous"
    assert d18 == d42
    # the 8x1 carving (mp=1: nothing shardable) must land the same state
    d81, n81 = _tiny_leg(ShardingPlan.from_spec("8x1", rules="sharded"))
    assert n81 == 0
    assert d81 == d42
    # and the rulebook must not matter for the result, only the layout:
    # the replicated book on a 4x2 mesh is the same bits again
    dr, nr = _tiny_leg(ShardingPlan.from_spec("4x2", rules="replicated"))
    assert nr == 0
    assert dr == d42


def test_replicated_fallback_bit_identical_to_plain_stack():
    """The no-op fallback contract: a 1-device plan (where the SPMD
    partitioner has nothing to partition) is bit-identical to the plain
    pre-partition dispatch — plan=None and plan=1x1 produce the same
    final learner state, byte for byte.  (On >1 devices the partitioned
    executable's fusion boundaries legitimately reorder float
    reductions at ~1e-7 — carving-INVARIANCE is the multi-device
    guarantee, asserted above.)"""
    d_plain, n_plain = _tiny_leg(None)
    d_11, n_11 = _tiny_leg(ShardingPlan.from_spec("1x1", rules="sharded"))
    assert n_plain == 0 and n_11 == 0
    assert d_plain == d_11


def test_plan_replica_divisibility_checked():
    from __graft_entry__ import _flagship

    env, agent, _, _ = _flagship(max_nodes=8, max_edges=8,
                                 episode_steps=2, max_flows=32,
                                 gen_traffic=False)
    with pytest.raises(ValueError, match="divisible"):
        ParallelDDPG(env, agent, num_replicas=6,
                     plan=ShardingPlan.from_spec("4x2"))


def test_lockstep_ring_write_partitions_with_no_collective_in_the_scan():
    """The one-slab replay write under a ``4x1`` plan: the ring is sharded
    on its replica axis and the scalar cursor replicated, so the compiled
    ``chunk_step`` holds no collective under the rollout's scan (the
    parent's had none either: its collectives are the learn burst's batch
    gathers and the entry's reductions); the cursor's read is one scalar
    all-reduce in the entry; the ring leaves the program in the plan's
    ``data_sharding``."""
    from __graft_entry__ import _flagship
    from gsc_tpu.analysis.hlo import COLLECTIVE_OPS, _shape_bytes, _walk_ops
    from gsc_tpu.obs.trace import DEVICE_SCOPES

    B = 8
    env, agent, topo, one_traffic = _flagship(
        max_nodes=8, max_edges=8, episode_steps=2, max_flows=32)
    plan = ShardingPlan.from_spec("4x1", rules="sharded")
    pddpg = ParallelDDPG(env, agent, num_replicas=B, plan=plan, donate=True)
    traffic = jax.tree_util.tree_map(lambda x: jnp.stack([x] * B),
                                     one_traffic)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    fn = pddpg.sharded_lowerable("chunk_step", state)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = fn.func.lower(pddpg, state, buffers, env_states, obs,
                                 topo, traffic, np.int32(0), 2,
                                 True).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    kinds = COLLECTIVE_OPS + tuple(f"{op}-start" for op in COLLECTIVE_OPS)
    collectives = [(name, op, type_text, path)
                   for name, op, type_text, path, *_ in _walk_ops(
                       text, DEVICE_SCOPES) if op in kinds]
    assert collectives                               # it IS partitioned
    in_scan = [c for c in collectives if "rollout_step" in c[3]
               or "replay_write" in c[3]]
    assert in_scan == []
    # outside the learn burst: the cursor and the episode's statistics,
    # scalars and [B] vectors — never a ring leaf gathered
    outside = [c for c in collectives if "learn_burst" not in c[3]]
    assert 1 <= len(outside) <= 4
    assert max(_shape_bytes(c[2]) for c in outside) <= 4 * B
    # the update is a per-shard slab: B / 4 replicas' rows a device
    cap = jax.tree_util.tree_leaves(buffers.data)[0].shape[1]
    assert f"f32[{B // 4},{cap}]" in text and f"f32[{B},{cap}]" not in text

    out = pddpg.chunk_step(state, buffers, env_states, obs, topo, traffic,
                           np.int32(0), num_steps=2, learn=True)
    for leaf in jax.tree_util.tree_leaves(out[1]):
        assert leaf.sharding.is_equivalent_to(plan.data_sharding, leaf.ndim)
    np.testing.assert_array_equal(np.asarray(out[1].pos), 2)
    np.testing.assert_array_equal(np.asarray(out[1].size), 2)


# ------------------------------------------------------------ elastic resume
def test_subprocess_elastic_resume_8_to_4_devices(tmp_path):
    """Satellite acceptance: a run checkpointed on an 8-device 4x2 mesh
    resumes and completes in a FRESH process that only has 4 devices
    (mesh 4x1) via --resume auto, with a monotone episode counter —
    the lost-hosts scenario end to end through the real CLI."""
    from tests.test_agent import write_tiny_configs

    args = write_tiny_configs(tmp_path)
    res = str(tmp_path / "res")

    def run(n_devices, extra):
        env = {k: v for k, v in os.environ.items()
               if k != "XLA_FLAGS"}
        env.update(
            JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
            XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
        return subprocess.run(
            [sys.executable, "-m", "gsc_tpu.cli", "train", *args,
             "--replicas", "8", "--chunk", "3",
             "--partition-rules", "sharded", "--result-dir", res, *extra],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=420)

    r1 = run(8, ["--mesh", "4x2", "--episodes", "2",
                 "--ckpt-interval", "1"])
    assert r1.returncode == 0, (r1.stdout[-2000:], r1.stderr[-2000:])
    r2 = run(4, ["--mesh", "4x1", "--episodes", "4", "--resume", "auto"])
    assert r2.returncode == 0, (r2.stdout[-2000:], r2.stderr[-2000:])

    # the resumed run continues exactly where the checkpoint stopped,
    # and its run_start meta records the NEW mesh + partition summary
    runs = []
    for root, _, files in os.walk(res):
        if "events.jsonl" in files:
            with open(os.path.join(root, "events.jsonl")) as f:
                events = [json.loads(line) for line in f]
            start = [e for e in events if e["event"] == "run_start"][0]
            eps = [e["episode"] for e in events if e["event"] == "episode"]
            runs.append((start, eps))
    assert len(runs) == 2
    by_mesh = {s["mesh"]: eps for s, eps in runs}
    assert by_mesh["4x2"] == [0, 1]
    assert by_mesh["4x1"] == [2, 3]       # monotone across the resume
    for start, _ in runs:
        assert start["partition_rules"] == "sharded"
        assert sum(start["partition_specs"].values()) > 0
