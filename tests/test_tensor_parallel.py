"""True tensor-parallel (`tp` rulebook) tests: contraction-dim partition
rules, psum-partial-product numerics vs the unsharded reference WITHIN
tolerance, the no-layout-move resident-sharding contract on the dispatch
path, carving-invariance WITHIN the bench_diff curve bands (2x2 vs 1x4
digests need not agree — curves must), the jax-free meshspec grammar,
and collective-op HLO mining into the cost ledger.

All marked ``tensor_parallel`` — ``pytest -m tensor_parallel -q`` is the
standalone smoke group for the tp dispatch path.  Everything runs on the
conftest's 8-device virtual CPU mesh in ONE process; the bit-exactness
of the ``replicated``/``sharded`` books across this refactor is guarded
by ``tests/test_multichip.py`` (same witness recipe,
``__graft_entry__.sharded_training_leg``).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from gsc_tpu.meshspec import (PARTITION_RULEBOOKS, canonical_mesh,
                              validate_partition_rules)
from gsc_tpu.parallel import (ParallelDDPG, ShardingPlan,
                              match_partition_rules, tp_rules)
from gsc_tpu.parallel.partition import clamp_specs_to_mesh, make_train_mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import bench_diff  # noqa: E402  (stdlib-only)

pytestmark = pytest.mark.tensor_parallel


def _leg(plan):
    """The shared carving witness (tiny stack, 1 episode, 4 replicas) —
    the SAME recipe tests/test_multichip.py and the dryrun legs use, so
    'within tolerance of the reference' is measured against the exact
    program the bit-exact books digest."""
    from __graft_entry__ import sharded_training_leg

    return sharded_training_leg(plan, episodes=1, replicas=4,
                                episode_steps=2)


@pytest.fixture(scope="module")
def ref_leg():
    return _leg(None)


@pytest.fixture(scope="module")
def tp12_leg():
    return _leg(ShardingPlan.from_spec("1x2", rules="tp"))


# --------------------------------------------------------------- rulebook
def test_tp_rules_shard_contraction_dims():
    """Megatron-style split: Dense_0 column-parallel (output dim),
    deeper Dense kernels ROW-parallel (the contraction dim — the psum
    source), GAT projections column-parallel; att/biases/scalars
    replicated."""
    tree = {"MLP_0": {"Dense_0": {"kernel": jnp.zeros((6, 8)),
                                  "bias": jnp.zeros(8)},
                      "Dense_1": {"kernel": jnp.zeros((8, 4)),
                                  "bias": jnp.zeros(4)}},
            "gnn": {"w_l": jnp.zeros((4, 8)), "att": jnp.zeros((8, 1))},
            "step": jnp.zeros((), jnp.int32)}
    specs = match_partition_rules(tp_rules(), tree)
    assert specs["MLP_0"]["Dense_0"]["kernel"] == P(None, "mp")
    assert specs["MLP_0"]["Dense_0"]["bias"] == P("mp")
    assert specs["MLP_0"]["Dense_1"]["kernel"] == P("mp", None)
    assert specs["MLP_0"]["Dense_1"]["bias"] == P()
    assert specs["gnn"]["w_l"] == P(None, "mp")
    assert specs["gnn"]["att"] == P()
    assert specs["step"] == P()
    # indivisible contraction dims clamp to replication like any rule
    mesh = make_train_mesh(2, 4)
    narrow = {"MLP_0": {"Dense_1": {"kernel": jnp.zeros((6, 4))}}}
    clamped, n = clamp_specs_to_mesh(
        match_partition_rules(tp_rules(), narrow), narrow, mesh)
    assert clamped["MLP_0"]["Dense_1"]["kernel"] == P() and n == 1


def test_plan_tp_book_and_residency_flags():
    mesh = make_train_mesh(4, 2)
    tp = ShardingPlan(mesh, "tp")
    assert tp.resident_sharded and tp.is_sharded
    assert tp.rules_name == "tp"
    for book in ("replicated", "sharded"):
        assert not ShardingPlan(mesh, book).resident_sharded
    with pytest.raises(ValueError, match="unknown rulebook"):
        ShardingPlan(mesh, "zigzag")


# ------------------------------------------------------- meshspec grammar
def test_meshspec_is_the_one_grammar():
    """The jax-free helper the launcher and partition.py both import:
    canonical spellings, validation errors, the rulebook vocabulary —
    and partition.parse_mesh_shape IS meshspec's (no third copy)."""
    import gsc_tpu.meshspec as ms
    from gsc_tpu.parallel import partition

    assert partition.parse_mesh_shape is ms.parse_mesh_shape
    assert canonical_mesh("8") == "8x1"
    assert canonical_mesh(" 2X4 ") == "2x4"
    for bad in ("", "axb", "0x2", "2x0", "2x2x2", "-1", None):
        with pytest.raises(ValueError):
            canonical_mesh(bad)
    assert PARTITION_RULEBOOKS == ("replicated", "sharded", "tp")
    for name in PARTITION_RULEBOOKS:
        assert validate_partition_rules(name) == name
    with pytest.raises(ValueError, match="unknown rulebook"):
        validate_partition_rules("zerO")
    # jax-free by contract: no import statement in the module (or the
    # package __init__ it pulls in) may touch jax — the jax-free dryrun
    # launcher validates mesh specs before any backend exists
    import ast
    import importlib

    for mod in ("gsc_tpu", "gsc_tpu.meshspec"):
        origin = importlib.util.find_spec(mod).origin
        tree = ast.parse(open(origin).read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.split(".")[0] in ("jax", "jaxlib")
                           for n in names), (mod, names)


# ------------------------------------------------------- numerics (banded)
def test_tp_numerics_within_tolerance_of_reference(ref_leg, tp12_leg):
    """psum-accumulated partial products vs the unsharded reference:
    every float leaf of the final learner state agrees within the banded
    tolerance (documented floor ~1e-7/mp per gradient step; the band
    here is 1e-3, generous for 1 episode but far below any wrong-psum
    failure, which is O(1)).  Bit-equality is deliberately NOT asserted
    — that contract belongs to the replicated/sharded books."""
    assert tp12_leg["sharded_leaves"] > 0, "tp split no leaf — vacuous"
    for a, b in zip(jax.tree_util.tree_leaves(ref_leg["state"]),
                    jax.tree_util.tree_leaves(tp12_leg["state"])):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.inexact):
            np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-3)
    # the rollout itself is identical here (warmup actions), so the
    # curve must agree exactly — drift lives in the learner state
    assert tp12_leg["returns"] == ref_leg["returns"]


def test_tp_carving_invariance_within_bands(tp12_leg):
    """2x2 vs 1x4: digests need NOT be bit-equal (psum order is
    carving-dependent) but the learning-curve envelope must gate clean
    under the same bench_diff bands CI applies to curves.json rows."""
    from gsc_tpu.obs.curves import extract_curves

    tp14 = _leg(ShardingPlan.from_spec("1x4", rules="tp"))
    tp22 = _leg(ShardingPlan.from_spec("2x2", rules="tp"))
    assert tp14["sharded_leaves"] > 0 and tp22["sharded_leaves"] > 0

    def curves_row(leg, name):
        events = [{"event": "episode", "episode": i, "episodic_return": r}
                  for i, r in enumerate(leg["returns"])]
        return {**bench_diff._curves_row(extract_curves(events)),
                "name": name}

    verdict = bench_diff.diff_rows(curves_row(tp22, "tp22"),
                                   curves_row(tp14, "tp14"))
    assert verdict["verdict"] == "ok", verdict
    assert verdict["gated_metrics"] > 0, verdict
    # and tp vs the 1x2 leg too — a different device COUNT, still inside
    # the envelope
    verdict = bench_diff.diff_rows(curves_row(tp22, "tp22"),
                                   curves_row(tp12_leg, "tp12"))
    assert verdict["verdict"] == "ok", verdict


# ------------------------------------------- resident sharding / no moves
def test_tp_no_layout_moves_on_dispatch_path():
    """The deleted entry-allgather/exit-slice contract: across an
    episode of chunked dispatches the state is placed into the plan's
    layout EXACTLY once (the caller-fresh init) and then flows
    resident-sharded — no device_put touches it again, and every carry
    leaf comes back in the plan's sharding with the split leaves
    genuinely distributed."""
    from gsc_tpu.sim.traffic import generate_traffic
    from __graft_entry__ import _flagship

    plan = ShardingPlan.from_spec("1x2", rules="tp")
    env, agent, topo, _ = _flagship(max_nodes=8, max_edges=8,
                                    episode_steps=2, max_flows=32,
                                    gen_traffic=False)
    traffic = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(env.sim_cfg, env.service, topo, 2, seed=s)
          for s in range(4)])
    pddpg = ParallelDDPG(env, agent, num_replicas=4, sample_mode="local",
                         donate=True, plan=plan)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one)
    buffers = pddpg.init_buffers(one)
    assert pddpg.entry_state_moves == 0
    for c in range(2):
        state, buffers, env_states, obs, _, _ = pddpg.chunk_step(
            state, buffers, env_states, obs, topo, traffic,
            jnp.int32(c), 1, learn=(c == 1))
    # a second episode's worth of calls on the SAME carry: still zero
    # new placements
    state, buffers, env_states, obs, _, _ = pddpg.chunk_step(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(2), 1)
    jax.block_until_ready(state)
    assert pddpg.entry_state_moves == 1, \
        "state re-placed on the steady-state dispatch path"
    # resident between dispatches, in the plan's layout, genuinely split
    ss_leaves = jax.tree_util.tree_leaves(
        plan.state_shardings(state),
        is_leaf=lambda x: hasattr(x, "spec"))
    leaves = jax.tree_util.tree_leaves(state)
    assert len(leaves) == len(ss_leaves)
    assert all(l.sharding == s for l, s in zip(leaves, ss_leaves))
    n_split = sum(1 for l in leaves
                  if not l.sharding.is_fully_replicated)
    assert n_split > 0
    # the host boundary still exists exactly where it should: gather
    gathered = plan.gather_state(state)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(gathered))


# --------------------------------------------------- collective-op mining
def test_collective_stats_parser_synthetic():
    from gsc_tpu.analysis.hlo import collective_stats

    text = "\n".join([
        "  %ar = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %p0), "
        "replica_groups={}, to_apply=%add",
        "  %ag.1 = (f32[16]{0}, f32[16]{0}) all-gather(f32[8]{0} %x, "
        "f32[8]{0} %y), dimensions={0}",
        # real async form: tuple (operand, result) — payload must count
        # ONCE (largest element), and -done must not count at all
        "  %ars = (bf16[32]{0}, bf16[32]{0}) all-reduce-start("
        "bf16[32]{0} %z)",
        "  %ard = bf16[32]{0} all-reduce-done(bf16[32]{0} %ars)",
        "  %rs = f32[2]{0} reduce-scatter(f32[4]{0} %w), dimensions={0}",
        "  %plain = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)",
    ])
    cs = collective_stats(text)
    assert cs["ops"]["all-reduce"] == {"count": 2,
                                       "bytes": 4 * 8 * 4 + 32 * 2}
    assert cs["ops"]["all-gather"] == {"count": 1, "bytes": 2 * 16 * 4}
    assert cs["ops"]["reduce-scatter"] == {"count": 1, "bytes": 8}
    assert cs["count"] == 4
    assert cs["bytes"] == sum(r["bytes"] for r in cs["ops"].values())
    # single-device program: clean zeros, not noise
    empty = collective_stats("%f = f32[4]{0} add(f32[4]{0} %a)")
    assert empty == {"ops": {}, "count": 0, "bytes": 0}


def test_cost_ledger_mines_collectives_from_partitioned_program():
    """A genuinely partitioned executable (row-sharded contraction =>
    psum) lands in the ledger with a non-empty collectives block, and
    bench_diff surfaces it as informational per-entry metrics."""
    from gsc_tpu.obs.perf import CostLedger
    from jax.sharding import NamedSharding

    mesh = make_train_mesh(1, 2)
    w_sh = NamedSharding(mesh, P("mp", None))
    rep = NamedSharding(mesh, P())

    fn = jax.jit(lambda x, w: x @ w,
                 in_shardings=(rep, w_sh), out_shardings=rep)
    ledger = CostLedger()
    entry = ledger.capture("row_dot", fn,
                           (jnp.ones((4, 8)), jnp.ones((8, 6))))
    assert entry["available"], entry
    col = entry["collectives"]
    assert col["count"] >= 1 and col["bytes"] > 0, col
    assert "all-reduce" in col["ops"], col
    row = bench_diff._perf_row(ledger.summary())
    assert row["metrics"]["row_dot_collective_count"] == col["count"]
    assert row["metrics"]["row_dot_collective_bytes"] == col["bytes"]
    # informational, never banded: collective payload moves with the
    # rulebook by design
    assert bench_diff.metric_rule("row_dot_collective_bytes") is None
