"""Engine semantics tests on hand-computable deterministic scenarios.

The reference's own tests only cover the interface contract
(src/tests/test_simulatorInterface.py); these go further and pin the
simulator's *semantics* — per-flow timelines, drop classification, WRR splits —
on scenarios small enough to verify by hand against the reference's rules
(coordsim/simulation/flowsimulator.py:72-128 and its components).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.config.schema import (
    EnvLimits,
    ServiceConfig,
    ServiceFunction,
    SimConfig,
)
from gsc_tpu.sim import SimEngine, generate_traffic
from gsc_tpu.topology.compiler import NetworkSpec, compile_topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.environ.get("GSC_REFERENCE_DIR", "/root/reference")

N, E = 8, 8  # small padded dims for fast tests


def make_service(std=0.0, startup=0.0):
    sf = lambda n: ServiceFunction(name=n, processing_delay_mean=5.0,
                                   processing_delay_stdev=std,
                                   startup_delay=startup)
    return ServiceConfig(sfc_list={"sfc_1": ("a", "b", "c")},
                         sf_list={n: sf(n) for n in "abc"})


def line_topo(node_cap=10.0, link_cap=100.0, link_delay=3.0):
    """0(Ingress) -- 1 -- 2, integer link delays."""
    spec = NetworkSpec(
        node_caps=[node_cap] * 3,
        node_types=["Ingress", "Normal", "Normal"],
        edges=[(0, 1, link_cap, link_delay), (1, 2, link_cap, link_delay)],
    )
    return compile_topology(spec, max_nodes=N, max_edges=E)


def triangle_topo():
    """0(Ingress) adjacent to 1 and 2, so a 50/50 row can split."""
    spec = NetworkSpec(
        node_caps=[20.0, 20.0, 20.0],
        node_types=["Ingress", "Normal", "Normal"],
        edges=[(0, 1, 100.0, 1.0), (0, 2, 100.0, 1.0), (1, 2, 100.0, 1.0)],
    )
    return compile_topology(spec, max_nodes=N, max_edges=E)


def make_cfg(**kw):
    kw.setdefault("ttl_choices", (100.0,))
    return SimConfig(**kw)


def schedule_all_to(limits, dst):
    """Every (node, sfc, sf) row sends everything to dst."""
    sched = np.zeros(limits.scheduling_shape, np.float32)
    sched[:, :, :, dst] = 1.0
    return jnp.asarray(sched)


def schedule_wrr_split(limits):
    """SF a from the ingress splits 50/50 over nodes 1 / 2; later SFs
    stay where they are."""
    sched = np.zeros(limits.scheduling_shape, np.float32)
    sched[0, 0, 0, 1] = 0.5
    sched[0, 0, 0, 2] = 0.5
    for n in (1, 2):
        sched[n, 0, 1, n] = 1.0
        sched[n, 0, 2, n] = 1.0
    return jnp.asarray(sched)


ALL_AT_1 = [(1, 0), (1, 1), (1, 2)]
ALL_AT_1_AND_2 = ALL_AT_1 + [(2, 0), (2, 1), (2, 2)]


def placement_at(limits, nodes_sfs):
    p = np.zeros((limits.max_nodes, limits.max_sfs), bool)
    for n, s in nodes_sfs:
        p[n, s] = True
    return jnp.asarray(p)


def run_intervals(engine, topo, traffic, schedule, placement, k, seed=0):
    state = engine.init(jax.random.PRNGKey(seed), topo)
    out = []
    for _ in range(k):
        state, metrics = engine.apply(state, topo, traffic, schedule, placement)
        out.append(metrics)
    return state, out


@pytest.fixture(scope="module")
def base():
    service = make_service()
    limits = EnvLimits(max_nodes=N, max_edges=E, num_sfcs=1, max_sfs=3)
    return service, limits


def test_single_flow_timeline(base):
    """Flow: ingress 0 -> all SFs at node 1 -> departs at node 1.

    e2e = path_delay(0,1) + 3 * 5ms processing = 3 + 15 = 18 ms
    (default_forwarder.py:83-86 path credit + base_processor.py:37-49).
    """
    service, limits = base
    cfg = make_cfg()
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=4, seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1), (1, 2)])

    _, out = run_intervals(engine, topo, traffic, sched, place, 2)
    m1, m2 = out
    # interval 1: arrivals at 0,10,...,90; flow k departs at 10k+18
    assert int(m1.run_generated) == 10
    assert int(m1.run_processed) == 9          # arrival@90 departs at 108
    assert int(m1.run_dropped) == 0
    assert int(m1.active) == 1
    assert float(m1.run_avg_e2e()) == pytest.approx(18.0)
    assert float(m1.run_e2e_max) == pytest.approx(18.0)
    # interval 2: 10 new arrivals, 10 departures (the straggler + 9 own)
    assert int(m2.run_generated) == 10
    assert int(m2.run_processed) == 10
    assert int(m2.generated) == 20
    assert int(m2.processed) == 19
    # requested traffic: every decision at node 0 (sf a) and node 1 (sf b, c)
    req = np.asarray(m2.run_requested)
    assert req[0, 0, 0] == pytest.approx(10.0)   # 10 flows x dr 1.0 at sf a
    assert req[1, 0, 1] == pytest.approx(10.0)
    assert req[1, 0, 2] == pytest.approx(10.0)
    # processed traffic at node 1 for all three SFs
    proc = np.asarray(m2.run_processed_traffic)
    assert proc[1].sum() == pytest.approx(30.0)


def test_node_cap_drop(base):
    """Node capacity below demand -> NODE_CAP drops
    (base_processor.py:98-101, metrics.py:144-164)."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo(node_cap=0.5)
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1), (1, 2)])
    _, out = run_intervals(engine, topo, traffic, sched, place, 1)
    (m,) = out
    assert int(m.run_dropped) == 10
    assert int(m.drop_reasons[3]) == 10        # NODE_CAP
    assert int(m.run_processed) == 0
    # drops recorded at the processing node (metrics.py:150-157)
    assert int(m.run_dropped_per_node[1]) == 10


def test_unplaced_sf_drop(base):
    """SF missing from placement -> NODE_CAP drop (default_processor.py:48-50)."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1)])  # no SF c
    _, out = run_intervals(engine, topo, traffic, sched, place, 1)
    (m,) = out
    assert int(m.drop_reasons[3]) >= 8
    assert int(m.run_processed) == 0


def test_link_cap_drop(base):
    """Link capacity below demand -> LINK_CAP drops
    (default_forwarder.py:95-111)."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo(link_cap=0.5)
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1), (1, 2)])
    _, out = run_intervals(engine, topo, traffic, sched, place, 1)
    (m,) = out
    assert int(m.run_dropped) == 10
    assert int(m.drop_reasons[2]) == 10        # LINK_CAP


def test_ttl_drop(base):
    """TTL shorter than the service time -> TTL drops; a drop with ttl<=0 is
    always recorded as TTL (metrics.py:158-160)."""
    service, limits = base
    cfg = make_cfg(ttl_choices=(10.0,))
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1), (1, 2)])
    _, out = run_intervals(engine, topo, traffic, sched, place, 1)
    (m,) = out
    assert int(m.run_dropped) == 10
    assert int(m.drop_reasons[0]) == 10        # TTL
    assert int(m.run_processed) == 0


def test_wrr_split(base):
    """50/50 schedule row -> weighted round robin alternates destinations
    (default_decision_maker.py:42-66)."""
    service, limits = base
    cfg = make_cfg()
    topo = triangle_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = schedule_wrr_split(limits)
    place = placement_at(limits, ALL_AT_1_AND_2)
    _, out = run_intervals(engine, topo, traffic, sched, place, 1)
    (m,) = out
    counts = np.asarray(m.run_flow_counts)[0, 0, 0]
    assert counts[1] == 5 and counts[2] == 5
    assert int(m.run_dropped) == 0


def test_empty_schedule_quirk(base):
    """All-zero schedule row: the reference's argmax over all -1 diffs picks
    the first node (default_decision_maker.py:55-61) — flows go to node 0 and
    drop there because nothing is placed."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = jnp.zeros(limits.scheduling_shape, jnp.float32)
    place = placement_at(limits, [])
    _, out = run_intervals(engine, topo, traffic, sched, place, 1)
    (m,) = out
    assert int(m.run_dropped) == 10
    assert int(m.drop_reasons[3]) == 10        # NODE_CAP at node 0
    assert int(m.run_dropped_per_node[0]) == 10


def test_load_and_release(base):
    """Node load rises while flows process and releases duration ms after
    processing ends (base_processor.py:103-112)."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1), (1, 2)])
    state, out = run_intervals(engine, topo, traffic, sched, place, 1)
    # traffic covers 2 intervals; after a 3rd (drain) interval every flow has
    # departed and all held capacity is back
    state2, _ = engine.apply(state, topo, traffic, sched, place)
    state2, _ = engine.apply(state2, topo, traffic, sched, place)
    assert float(jnp.abs(state2.node_load).max()) < 1e-3
    assert float(jnp.abs(state2.edge_used).max()) < 1e-3
    # max node usage observed during interval 1 should be >= 1 flow's demand
    assert float(out[0].run_max_node_usage[1]) >= 1.0


def test_onehot_helpers_match_native_indexing():
    """_onehot/_take/_pick (the TPU one-hot data-movement primitives)
    reproduce native gather semantics exactly — f32/i32/bool tables,
    out-of-range drop rows, and permutation transpose-scatter."""

    from gsc_tpu.sim.engine import _onehot, _pick, _take

    rng = np.random.default_rng(0)
    M, N, P = 37, 11, 5
    idx = jnp.asarray(rng.integers(0, N, M), jnp.int32)
    ftab = jnp.asarray(rng.normal(size=(N, P)), jnp.float32)
    itab = jnp.asarray(rng.integers(-3, 99, (N, P)), jnp.int32)
    btab = jnp.asarray(rng.integers(0, 2, (N, P)).astype(bool))
    oh = _onehot(idx, N)
    for tab in (ftab, itab, btab):
        got = np.asarray(_take(tab, oh))
        want = np.asarray(tab)[np.asarray(idx)]
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    # out-of-range index -> all-zero row (mode="drop" analogue)
    oh_drop = _onehot(jnp.full((3,), N, jnp.int32), N)
    np.testing.assert_array_equal(np.asarray(_take(ftab, oh_drop)), 0.0)
    # _pick: per-row column select
    cols = jnp.asarray(rng.integers(0, P, M), jnp.int32)
    rows = _take(ftab, oh)                       # [M, P]
    got = np.asarray(_pick(rows, _onehot(cols, P)))
    want = np.asarray(rows)[np.arange(M), np.asarray(cols)]
    np.testing.assert_array_equal(got, want)
    # permutation: P @ v sorts, v^T @ P inverse-scatters back
    perm = jnp.asarray(rng.permutation(M), jnp.int32)
    pm = _onehot(perm, M)
    v = jnp.asarray(rng.normal(size=M), jnp.float32)
    sorted_v = jnp.dot(pm, v, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(np.asarray(sorted_v),
                                  np.asarray(v)[np.asarray(perm)])
    back = jnp.dot(sorted_v, pm, precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(v))


# ------------------------------------------------------------ release rings
# The line scenario's timeline is fixed (test_single_flow_timeline): a flow
# arriving at substep g0 takes link 0 at g0 and is processed at node 1 by
# SF j from g0 + 3 + 5 j, so the capacity-release rings can be stated
# outright — row g mod H is released and cleared, a hold of `off` substeps
# is booked at row (g + off) mod H — and the engine held to that statement
# bit for bit while the row index wraps around the horizon.
RING_SCENARIOS = {
    # 43 ms flows: a dozen rows live at once, every release inside H
    "holds_inside_horizon": dict(flow_dr_mean=0.7, flow_size_shape=0.0301),
    # 300 ms flows: every hold clips to H - 1, the row just behind ridx
    "holds_clipped_to_horizon": dict(flow_dr_mean=0.7, flow_size_shape=0.21),
}
RING_INTERVALS = 3          # 300 substeps > H = 256: the index wraps


def ring_statement(traffic, horizon, num_nodes, num_sfs, num_edges,
                   checkpoints):
    """The four arrays after each substep count in ``checkpoints``, in
    plain numpy float32 (dt = 1, link delay 3, processing delay 5)."""
    f32 = np.float32
    link, node = 0, 1          # every flow crosses link 0 to node 1
    edge_book, node_book = {}, {}
    arr = zip(np.asarray(traffic.arr_time), np.asarray(traffic.arr_dr),
              np.asarray(traffic.arr_duration))
    for t0, dr, dur in arr:
        if not np.isfinite(t0):
            continue
        g0 = int(round(float(t0)))
        off = int(np.clip(np.ceil(3.0 + dur), 1, horizon - 1))
        edge_book.setdefault(g0, []).append((link, f32(dr), off))
        off = int(np.clip(np.ceil(5.0 + dur), 1, horizon - 1))
        for sf in range(num_sfs):
            node_book.setdefault(g0 + 3 + 5 * sf, []).append(
                (node * num_sfs + sf, f32(dr), off))
    node_load = np.zeros(num_nodes * num_sfs, f32)
    edge_used = np.zeros(num_edges, f32)
    rel_node = np.zeros((horizon, num_nodes * num_sfs), f32)
    rel_edge = np.zeros((horizon, num_edges), f32)
    out = {}
    for g in range(max(checkpoints)):
        row = g % horizon
        node_load = np.maximum(node_load - rel_node[row], f32(0))
        edge_used = np.maximum(edge_used - rel_edge[row], f32(0))
        rel_node[row] = 0
        rel_edge[row] = 0
        for held, rel, book in ((node_load, rel_node, node_book),
                                (edge_used, rel_edge, edge_book)):
            for cell, dr, off in book.get(g, ()):
                held[cell] += dr
                rel[(g + off) % horizon, cell] += dr
        if g + 1 in checkpoints:
            out[g + 1] = dict(
                node_load=node_load.reshape(num_nodes, num_sfs).copy(),
                edge_used=edge_used.copy(), rel_node=rel_node.copy(),
                rel_edge=rel_edge.copy())
    return out


@pytest.mark.parametrize("scenario", sorted(RING_SCENARIOS))
@pytest.mark.parametrize("mode", ["unbatched", "vmap_b4", "vmap_own_ridx"])
def test_release_rings_match_plain_statement(base, scenario, mode):
    """``rel_node``, ``rel_edge``, ``node_load`` and ``edge_used`` equal
    the plain statement bit for bit after every interval of a run that
    wraps the ring index — alone, under ``jax.vmap``, and under
    ``jax.vmap`` over replicas whose clocks differ by whole intervals, so
    that each replica reads and clears a row of its own."""
    service, limits = base
    cfg = make_cfg(**RING_SCENARIOS[scenario])
    topo = line_topo(node_cap=100.0)
    engine = SimEngine(service, cfg, limits)
    assert engine.H < RING_INTERVALS * engine.substeps
    ahead = {"unbatched": (0,), "vmap_b4": (0,) * 4,
             "vmap_own_ridx": (0, 1, 2, 3)}[mode]   # intervals run before
    traffic = generate_traffic(cfg, service, topo,
                               episode_steps=RING_INTERVALS + max(ahead),
                               seed=0)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [(1, 0), (1, 1), (1, 2)])
    want = ring_statement(
        traffic, engine.H, N, limits.max_sfs, E,
        {engine.substeps * k
         for k in range(1, RING_INTERVALS + max(ahead) + 1)})

    def interval(state):
        return engine.apply.__wrapped__(engine, state, topo, traffic, sched,
                                        place)[0]

    def check(state, intervals_done):
        for name, ref in want[engine.substeps * intervals_done].items():
            np.testing.assert_array_equal(
                np.asarray(getattr(state, name)), ref,
                err_msg=f"{name} after {intervals_done} intervals")

    starts = []
    for k in ahead:
        state = engine.init(jax.random.PRNGKey(0), topo)
        for _ in range(k):
            state = engine.apply(state, topo, traffic, sched, place)[0]
        starts.append(state)
    if mode == "unbatched":
        state = starts[0]
        for k in range(1, RING_INTERVALS + 1):
            state = engine.apply(state, topo, traffic, sched, place)[0]
            check(state, k)
        return
    states = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *starts)
    step = jax.jit(jax.vmap(interval))
    for k in range(1, RING_INTERVALS + 1):
        states = step(states)
        for b, k0 in enumerate(ahead):
            check(jax.tree_util.tree_map(lambda x: x[b], states), k0 + k)


INDEXED = ("scatter", "scatter-add", "dynamic_update_slice", "dynamic_slice",
           "gather")


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _vmapped_primitives(fn, one, replicas, whole):
    """Primitive names of ``jax.vmap(fn)`` over ``replicas`` copies of the
    state ``one``; no indexed primitive may take an operand whose
    per-replica shape is in ``whole``."""
    states = jax.tree_util.tree_map(lambda x: jnp.stack([x] * replicas), one)
    names = set()
    for eqn in _walk(jax.make_jaxpr(jax.vmap(fn))(states).jaxpr):
        names.add(eqn.primitive.name)
        if eqn.primitive.name in INDEXED:
            shapes = {tuple(v.aval.shape[1:]) for v in eqn.invars
                      if hasattr(v.aval, "shape")}
            assert not shapes & whole, (
                f"{eqn.primitive.name} on {shapes & whole} at B = "
                f"{replicas}: {eqn}")
    return names


def _substep_primitives_agree(base, whole, ext_decisions=None):
    """The vmapped substep traces to the same primitives at B = 4 and
    B = 16, none of them indexed on an operand of a shape in
    ``whole(engine)``."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    if ext_decisions is not None:
        ext_decisions = jnp.full(engine.M, ext_decisions, jnp.int32)

    def substep(s):
        return engine._substep(s, topo, traffic.window(s.cursor, 1),
                               traffic.node_cap[0],
                               ext_decisions=ext_decisions)

    one = engine.init(jax.random.PRNGKey(0), topo)
    shapes = whole(engine)
    assert (_vmapped_primitives(substep, one, 4, shapes)
            == _vmapped_primitives(substep, one, 16, shapes))


def test_vmapped_substep_keeps_rings_whole(base):
    """Under ``jax.vmap`` the ring index is a per-replica vector; the
    substep must still touch a ring only through elementwise operations
    and contractions over the whole array (a row-indexed read or write
    forces a second, row-contiguous layout of it on the TPU), and trace
    to the same primitives whatever the number of replicas."""
    _substep_primitives_agree(
        base, lambda e: {(e.H, N * base[1].max_sfs), (e.H, E)})


@pytest.mark.parametrize("ext_decisions", [None, 1],
                         ids=["wrr", "ext_decisions"])
def test_vmapped_substep_writes_arrivals_without_a_scatter(base,
                                                           ext_decisions):
    """Under ``jax.vmap`` the slot a record lands in is a per-replica
    index, and a scatter at it is serial on the TPU and keeps layout
    copies of the packed slot blocks around it: stage 3 must write the
    arrivals (and the per-node requested rate) through masks alone — no
    indexed primitive on a packed ``[M, 6]`` / ``[M, 5]`` block, a slot
    field or the ``[N]`` node counter — and trace to the same primitives
    whatever the number of replicas."""
    _substep_primitives_agree(
        base, lambda e: {(e.M, 6), (e.M, 5), (e.M,), (N,)}, ext_decisions)


def test_vmapped_interval_reads_arrivals_without_an_index(base):
    """Under ``jax.vmap`` the arrival cursor is a per-replica vector, and
    an indexed read at it is serial on the TPU (one gather of 8 x B
    scalars per field, or a loop over the replicas per slice): the whole
    interval — taking the window, reading the run out of it — must reach
    the arrival table, the window and the two rows of a run through masks
    alone, and trace to the same primitives whatever the number of
    replicas."""
    service, limits = base
    cfg = make_cfg()
    topo = line_topo()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=20, seed=0,
                               capacity=4000)
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, ALL_AT_1)
    one = engine.init(jax.random.PRNGKey(0), topo)
    fields, rows, lanes = traffic.arr.shape
    window = traffic.window(one.cursor, (engine.substeps - 1) * 8 + 1
                            )[0].shape
    assert window[1] < rows         # the table is longer than a window
    arrival_shapes = {(fields, rows, lanes), window, (fields, 2, lanes),
                      (fields, rows * lanes), (rows * lanes,)}

    def interval(s):
        return engine.apply.__wrapped__(engine, s, topo, traffic, sched,
                                        place)[0]

    assert (_vmapped_primitives(interval, one, 4, arrival_shapes)
            == _vmapped_primitives(interval, one, 16, arrival_shapes))


# ---------------------------------------------------------- arrival cursor
# Stage 3 against a plain statement.  With nothing placed, every flow of the
# line scenario lives exactly ARRIVAL_HOLD substeps whatever its record
# says: spawned at substep g, it takes link 0 (delay 3), reaches node 1 at
# g + 3 and is dropped there (SF not placed), so its slot is free again
# from g + 4 on.  That fixes the free slots of every substep, and with them
# which record spawns when, into which slot, and every counter of the stage.
ARRIVAL_HOLD = 4
ARRIVAL_SUBSTEPS = 10       # a short interval: a checkpoint every 10 ms
ARRIVAL_INTERVALS = 6


def _records(rng, times, ingress=0):
    """Seven per-record arrays for hand-made arrival ``times``: distinct
    data rates, so a slot's ``dr`` names the record that landed in it."""
    f = len(times)
    dr = rng.uniform(0.5, 1.5, f).astype(np.float32)
    return dict(
        arr_time=np.asarray(times, np.float32),
        arr_ingress=np.full(f, ingress, np.int32), arr_dr=dr,
        arr_duration=(rng.uniform(1.0, 30.0, f)).astype(np.float32),
        arr_ttl=np.full(f, 100.0, np.float32),
        arr_sfc=np.zeros(f, np.int32), arr_egress=np.full(f, -1, np.int32))


def _hand_table(cfg, topo, times, pad=0, seed=0):
    """A schedule from hand-made arrival times, ``pad`` unused records
    (time inf) behind them."""
    from gsc_tpu.sim.state import TrafficSchedule

    rec = _records(np.random.default_rng(seed), np.sort(times))
    fill = dict(arr_time=np.inf, arr_egress=-1)
    rec = {k: np.concatenate([v, np.full(pad, fill.get(k, 0), v.dtype)])
           for k, v in rec.items()}
    steps = ARRIVAL_INTERVALS + 4
    return TrafficSchedule.pack(
        ingress_active=jnp.ones((steps, N), bool),
        node_cap=jnp.broadcast_to(topo.node_cap, (steps, N)), **rec)


ARRIVAL_SCENARIOS = {
    # one ingress, a flow every 10 ms (the benchmark cells' arrivals)
    "deterministic": dict(cfg=dict(inter_arrival_mean=10.0)),
    # exponential gaps of mean 0.7 ms: several records due in one substep
    "poisson": dict(cfg=dict(inter_arrival_mean=0.7,
                             deterministic_arrival=False)),
    # 30 records due in substep 3, 20 more in substep 31: each spills over
    # the 8 a substep admits, the later records counted as truncated
    "burst_spills": dict(times=[3.0] * 30 + [31.0] * 20 + [45.0, 52.5],
                         pad=40),
    # two records a millisecond held 4 substeps each want 8 slots of 6:
    # arrivals wait for a slot, the cursor stalls, and catches up
    "slot_exhaustion": dict(times=np.arange(0, 40, 0.5), pad=30,
                            cfg=dict(max_flows=6)),
    # every record of the table is real: the cursor reaches its capacity
    # in mid-interval and the run reads into the padding behind it
    "table_end": dict(times=np.arange(0, 37, 0.25), pad=0),
    # 100-substep intervals, ten records a millisecond: the cursor moves
    # the 8 a substep allows, 800 an interval, across the whole window
    "full_window": dict(times=np.arange(0, 250, 0.1), pad=60,
                        cfg=dict(run_duration=100.0), intervals=3),
    # 100-substep intervals over a table shorter than one interval's
    # window: the window is the whole table
    "short_table": dict(times=np.arange(0, 250, 2.5), pad=3,
                        cfg=dict(run_duration=100.0), intervals=3),
}


def arrival_statement(traffic, max_flows, substeps, intervals):
    """Stage 3 in plain numpy float32: after each interval the cursor, the
    counters, the interval's ``run_requested_node``, the slots in use and
    the last record that landed in each slot."""
    f32, eps, dt = np.float32, np.float32(1e-4), np.float32(1.0)
    time = np.asarray(traffic.arr_time)
    ing = np.asarray(traffic.arr_ingress)
    dr = np.asarray(traffic.arr_dr)
    cursor = generated = truncated = 0
    busy_until = np.full(max_flows, -1)
    landed = np.full(max_flows, -1)
    t = f32(0.0)
    out = []
    for g in range(substeps * intervals):
        if g % substeps == 0:
            req_node = np.zeros(N, f32)
        free = [s for s in range(max_flows) if busy_until[s] < g]
        for slot, r in zip(free, range(cursor, cursor + 8)):
            if not (r < len(time) and np.isfinite(time[r])
                    and time[r] < f32(t + dt) - eps):
                break
            truncated += bool(time[r] < t - eps)
            req_node[ing[r]] += dr[r]
            busy_until[slot], landed[slot] = g + ARRIVAL_HOLD - 1, r
            cursor += 1
            generated += 1
        t = f32(t + dt)
        if (g + 1) % substeps == 0:
            out.append(dict(cursor=cursor, generated=generated,
                            truncated=truncated, req_node=req_node.copy(),
                            in_use=busy_until > g, landed=landed.copy()))
    return out


@pytest.mark.parametrize("mode", ["unbatched", "vmap_own_cursor"])
@pytest.mark.parametrize("scenario", sorted(ARRIVAL_SCENARIOS))
def test_arrival_cursor_matches_plain_statement(base, scenario, mode):
    """Which record spawns at which substep into which slot, ``generated``,
    ``truncated_arrivals``, ``run_requested_node`` and the cursor equal the
    plain statement bit for bit after every interval — alone, and as the
    rows of a ``jax.vmap`` batch whose replicas run whole intervals apart,
    so that each reads the table at a cursor of its own."""
    service, limits = base
    spec = ARRIVAL_SCENARIOS[scenario]
    cfg = make_cfg(**{"run_duration": float(ARRIVAL_SUBSTEPS),
                      **spec.get("cfg", {})})
    topo = line_topo(link_cap=1e6)
    engine = SimEngine(service, cfg, limits)
    intervals = spec.get("intervals", ARRIVAL_INTERVALS)
    ahead = (0,) if mode == "unbatched" else (0, 1, 2, 3)
    if "times" in spec:
        traffic = _hand_table(cfg, topo, spec["times"], spec["pad"])
    else:
        traffic = generate_traffic(cfg, service, topo,
                                   episode_steps=intervals + max(ahead),
                                   seed=1)
    want = arrival_statement(traffic, cfg.max_flows, engine.substeps,
                             intervals + max(ahead))
    assert want[-1]["generated"] > 0
    if scenario in ("burst_spills", "slot_exhaustion", "full_window"):
        assert want[-1]["truncated"] > 0
    if scenario == "full_window":
        assert want[0]["cursor"] == 8 * engine.substeps
    if scenario == "table_end":
        assert want[-1]["cursor"] == traffic.capacity
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, [])

    def interval(state):
        return engine.apply.__wrapped__(engine, state, topo, traffic, sched,
                                        place)[0]

    def check(state, done):
        ref = want[done - 1]
        note = f"after {done} intervals"
        assert int(state.cursor) == ref["cursor"], note
        assert int(state.metrics.generated) == ref["generated"], note
        assert int(state.truncated_arrivals) == ref["truncated"], note
        np.testing.assert_array_equal(
            np.asarray(state.metrics.run_requested_node), ref["req_node"],
            err_msg=note)
        np.testing.assert_array_equal(
            np.asarray(state.flows.phase) != 0, ref["in_use"], err_msg=note)
        hit = ref["landed"] >= 0
        for name in ("arr_dr", "arr_duration", "arr_sfc"):
            got = np.asarray(getattr(state.flows, name[4:]))
            rec = np.asarray(getattr(traffic, name))[ref["landed"][hit]]
            np.testing.assert_array_equal(got[hit], rec,
                                          err_msg=f"{name} {note}")
            assert not got[~hit].any(), note

    starts = []
    for k in ahead:
        state = engine.init(jax.random.PRNGKey(0), topo)
        for _ in range(k):
            state = engine.apply(state, topo, traffic, sched, place)[0]
        starts.append(state)
    if mode == "unbatched":
        state = starts[0]
        for k in range(1, intervals + 1):
            state = engine.apply(state, topo, traffic, sched, place)[0]
            check(state, k)
        return
    states = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *starts)
    step = jax.jit(jax.vmap(interval))
    for k in range(1, intervals + 1):
        states = step(states)
        for b, k0 in enumerate(ahead):
            check(jax.tree_util.tree_map(lambda x: x[b], states), k0 + k)


# ------------------------------------------------- the program shape the chip runs
# The battery above drives one environment.  The chip runs ``jax.vmap`` of
# ``engine.apply`` over replicas that differ in everything that is not static
# — topology capacities, schedule, placement, traffic, rng — and a
# per-replica index under ``vmap`` compiles to something else than the
# unbatched program does.  So every drop and decision branch of the battery
# also runs as one row of such a mixed batch, and the row must equal the
# scenario's own unbatched run over the whole state and metrics pytree,
# bit for bit.
def assert_tree_bitequal(a, b):
    """Same structure, shapes, dtypes and values (no tolerance)."""
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype, \
            (jax.tree_util.keystr(path), x.dtype, y.dtype)
        np.testing.assert_array_equal(
            x, y, err_msg=f"leaf {jax.tree_util.keystr(path)} diverged")


def _clean(m):
    assert int(m.processed) > 0 and int(m.dropped) == 0


def _dropped(m):
    assert int(m.dropped) > 0       # the branch under test actually fired


def _wrr_alternated(m):
    counts = np.asarray(m.run_flow_counts)[0, 0, 0]
    assert counts[1] == counts[2]   # the split actually alternated


def _link_cap_pressure(m):
    assert int(m.drop_reasons[2]) > 0


def _generated(m):
    assert int(m.generated) > 0


# scenario -> (batch it rides in, what its own metrics must show); a batch
# is one static (service, SimConfig) and so one pair of compiled programs
BATTERY = {
    "line_default": ("deterministic", _clean),
    "node_cap": ("deterministic", _dropped),
    "link_cap": ("deterministic", _dropped),
    "unplaced_sf": ("deterministic", _dropped),
    "empty_schedule": ("deterministic", _dropped),
    "wrr_collisions": ("deterministic", _wrr_alternated),
    "stochastic_startup": ("stochastic", _generated),
    "ttl": ("ttl10", _dropped),
    "line3_linkcap2_asset": ("linkcap2", _link_cap_pressure),
    "triangle": ("reference_triangle", _generated),
    "abilene": ("reference_abilene", _generated),
}
REFERENCE_NETS = {
    "reference_triangle": "configs/networks/triangle/"
                          "triangle-in2-cap10-delay10.graphml",
    "reference_abilene": "configs/networks/abilene/"
                         "abilene-in4-rand-cap1-2.graphml",
}


def _line_rows(limits):
    """name -> (topology, schedule, placement) on the three-node stacks."""
    to1 = schedule_all_to(limits, 1)
    all1 = placement_at(limits, ALL_AT_1)
    return {
        "line_default": (line_topo(), to1, all1),
        "node_cap": (line_topo(node_cap=0.5), to1, all1),
        "link_cap": (line_topo(link_cap=0.5), to1, all1),
        "unplaced_sf": (line_topo(), to1,
                        placement_at(limits, [(1, 0), (1, 1)])),
        "empty_schedule": (line_topo(),
                           jnp.zeros(limits.scheduling_shape, jnp.float32),
                           placement_at(limits, [])),
        "wrr_collisions": (triangle_topo(), schedule_wrr_split(limits),
                           placement_at(limits, ALL_AT_1_AND_2)),
    }


def _batch_spec(batch, base):
    """-> (service, cfg, limits, intervals, {row name: (topo, sched,
    place)}).  Rows no scenario names only make the batch mixed."""
    service, limits = base
    rows = _line_rows(limits)
    mixers = {"mix_node_cap": rows["node_cap"],
              "mix_wrr": rows["wrr_collisions"]}
    if batch == "deterministic":
        return service, make_cfg(), limits, 2, rows
    if batch == "stochastic":
        return (make_service(std=1.0, startup=2.0), make_cfg(), limits, 2,
                {"stochastic_startup": rows["line_default"], **mixers})
    if batch == "ttl10":
        return (service, make_cfg(ttl_choices=(10.0,)), limits, 2,
                {"ttl": rows["line_default"], **mixers})
    from gsc_tpu.config.loader import load_sim
    from gsc_tpu.topology.compiler import load_topology
    if batch == "linkcap2":
        # the in-repo LINK_CAP-dominated oracle of test_reference_parity:
        # saturated links make nearly every substep a same-substep
        # admission tie
        from gsc_tpu.config.catalog import abc_service
        service = abc_service()
        cfg = load_sim(os.path.join(REPO, "tests", "assets",
                                    "linkcap_config.yaml"))
        nets = [load_topology(os.path.join(REPO, "tests", "assets",
                                           "line3-linkcap2.graphml"),
                              max_nodes=N, max_edges=E)] * 3
        names = ["line3_linkcap2_asset", "mix_to_1", "mix_to_0"]
        targets = [2, 1, 0]     # the asset: all toward the line's far end
        intervals = 6
    else:
        # the frozen reference-parity scenarios through the uniform-action
        # harness of tools/reward_curve.py (uniform schedule over real
        # nodes, everything placed everywhere), one seed per row
        from gsc_tpu.config.loader import load_service
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from reward_curve import CONFIG, SERVICE
        service = load_service(os.path.join(REFERENCE, SERVICE))
        cfg = load_sim(os.path.join(REFERENCE, CONFIG))
        nets = [load_topology(os.path.join(REFERENCE, REFERENCE_NETS[batch]),
                              max_nodes=24, max_edges=37, seed=1234 + k)
                for k in range(3)]
        names = [batch[len("reference_"):], "mix_seed_1", "mix_seed_2"]
        targets = [None] * 3
        intervals = 25
    limits = EnvLimits.for_service(service, max_nodes=nets[0].max_nodes,
                                   max_edges=nets[0].max_edges)
    rows = {}
    for name, topo, dst in zip(names, nets, targets):
        real = np.asarray(topo.node_mask)
        sched = np.zeros(limits.scheduling_shape, np.float32)
        if dst is None:
            sched[:, :, :, real] = 1.0 / real.sum()
        else:
            sched[:, :, :, dst] = 1.0
        place = np.broadcast_to(real[:, None],
                                (limits.max_nodes, limits.max_sfs)).copy()
        rows[name] = (topo, jnp.asarray(sched), jnp.asarray(place))
    return service, cfg, limits, intervals, rows


@pytest.fixture(scope="module")
def mixed_batch(base):
    """batch name -> {row name: ((state, metrics) alone, (state, metrics)
    as its row of the batch)}; each batch is built and run once."""
    done = {}

    def run(batch):
        if batch in done:
            return done[batch]
        service, cfg, limits, intervals, rows = _batch_spec(batch, base)
        engine = SimEngine(service, cfg, limits)
        inputs = []
        for seed, (topo, sched, place) in enumerate(rows.values()):
            traffic = generate_traffic(cfg, service, topo,
                                       episode_steps=intervals, seed=seed)
            inputs.append((engine.init(jax.random.PRNGKey(seed), topo),
                           topo, traffic, sched, place))
        alone = []
        for state, *args in inputs:
            for _ in range(intervals):
                state, metrics = engine.apply(state, *args)
            alone.append((state, metrics))
        states, *args = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *inputs)
        step = jax.jit(jax.vmap(
            lambda *a: engine.apply.__wrapped__(engine, *a)))
        for _ in range(intervals):
            states, metrics = step(states, *args)
        done[batch] = {
            name: (alone[b], jax.tree_util.tree_map(lambda x: x[b],
                                                    (states, metrics)))
            for b, name in enumerate(rows)}
        return done[batch]

    return run


@pytest.mark.parametrize("scenario", [
    pytest.param(name, marks=pytest.mark.skipif(
        BATTERY[name][0] in REFERENCE_NETS and not os.path.isdir(REFERENCE),
        reason="reference tree not available"))
    for name in BATTERY])
def test_battery_row_in_mixed_batch_equals_alone(mixed_batch, scenario):
    batch, shows = BATTERY[scenario]
    alone, row = mixed_batch(batch)[scenario]
    assert_tree_bitequal(alone, row)
    shows(alone[1])


def test_scan_unroll_bit_identical(base):
    """cfg.scan_unroll only restructures the substep loop: unroll=4 must
    be BIT-identical to unroll=1 (the precondition for promoting a swept
    unroll winner)."""
    service, limits = base
    topo = line_topo()
    sched = schedule_all_to(limits, 1)
    place = placement_at(limits, ALL_AT_1)
    out = []
    for cfg in (make_cfg(), make_cfg(scan_unroll=4)):
        engine = SimEngine(service, cfg, limits)
        traffic = generate_traffic(cfg, service, topo, episode_steps=4,
                                   seed=0)
        state, metrics = run_intervals(engine, topo, traffic, sched, place, 2)
        out.append((state, metrics[-1]))
    assert_tree_bitequal(*out)


# --------------------------------------------------------- fusion budget
# Pinned compiled-HLO fusion count of the flagship-interval engine.apply
# (abc service, Abilene limits 24/37, M=128, 100 substeps) on the CPU
# backend, jaxlib 0.9.0: 273 (273 too when re-measured and re-pinned in PR 21;
# the same program counted 191 under the previous jaxlib — the compiler's
# fusion decisions moved, the engine did not).  PR 29 re-pinned 273 -> 276:
# stage 1 of the substep reads and clears the release rings' due row
# through a mask over the whole ring instead of by index, which the CPU
# compiler counts as three more fusions of this unbatched program — while
# the TPU's vmapped program loses four whole-ring layout copies and two
# scatters per substep.  PR 31 re-pinned 276 -> 279: stage 3 fetches its
# eight candidate records through masks (the interval's window of the
# arrival table once per interval, the run out of it per substep) in place
# of seven per-field gathers and the rank gather, which this unbatched CPU
# program counts as three more fusions — while the TPU's vmapped program
# loses eight serial gathers per substep and half its operations
# (`substep_device_ops` 612 -> 310).  PR 36 re-pinned 279 -> 273: stage 3
# writes the arrivals into their slots as selects through the match mask
# and folds the per-node requested rate, in place of two packed scatters
# with their stack / unstack and a scatter-add — six fusions fewer here,
# 38 operations fewer in the TPU's vmapped program (310 -> 272, sandbox
# compile for a described v5e).  The budget adds NO headroom on purpose — a
# 281->294-style regression (the round-5 scatter-merge: bit-exact, yet
# slower) is ~+13, so any slack would swallow exactly the class of change
# this gate exists to catch.  If a toolchain upgrade moves the count,
# re-measure and re-pin in the same commit as the upgrade (the assertion
# message carries the recipe).
#
# What the pin protects: the engine's op count as the CPU compiler sees it
# — a proxy, not the chip's count (the TPU compiler fuses differently; the
# benchmark's `substep_device_ops` is the chip's own).
FUSION_BUDGET = 273


def _flagship_interval_compiled():
    from gsc_tpu.config.catalog import abc_service
    from gsc_tpu.topology.synthetic import abilene

    service = abc_service()
    limits = EnvLimits(max_nodes=24, max_edges=37, num_sfcs=1, max_sfs=3)
    topo = compile_topology(abilene(), max_nodes=24, max_edges=37)
    cfg = make_cfg()
    engine = SimEngine(service, cfg, limits)
    traffic = generate_traffic(cfg, service, topo, episode_steps=2, seed=0)
    sched = np.zeros(limits.scheduling_shape, np.float32)
    for n_ in range(24):
        sched[n_, 0, :, n_] = 1.0
    place = jnp.ones((24, 3), bool)
    state = engine.init(jax.random.PRNGKey(0), topo)
    return jax.jit(engine.apply.__wrapped__, static_argnums=0).lower(
        engine, state, topo, traffic, jnp.asarray(sched), place).compile()


def test_fusion_budget_flagship_interval():
    """Tier-1 op-count gate: the substep within the pinned budget."""
    from gsc_tpu.analysis.hlo import count_fusions

    n = count_fusions(_flagship_interval_compiled())
    assert n <= FUSION_BUDGET, (
        f"substep fusion count regressed: {n} > pinned {FUSION_BUDGET}.  "
        "If this is an intended engine change, re-measure with "
        "tests/test_engine.py::_flagship_interval_compiled and re-pin "
        "FUSION_BUDGET in the same commit, saying why beside the pin.")


def test_ops_do_not_import_the_simulator():
    """``gsc_tpu.ops`` (kernels) sits below ``gsc_tpu.sim`` (the engine
    that calls them): importing it must not pull the simulator in."""
    code = ("import sys, gsc_tpu.ops; "
            "assert 'gsc_tpu.sim.engine' not in sys.modules")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
