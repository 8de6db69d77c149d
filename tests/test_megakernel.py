"""Substep-megakernel correctness and fusion-budget gates.

Two independent bars, mirroring how the pallas_gat kernel is held:

1. BIT-exact interpret-mode parity: ``SimConfig.substep_impl="pallas"``
   must reproduce the XLA engine's full post-interval state pytree —
   every flow slot, metric counter, release ring and the rng leaf —
   bit for bit, across the semantics battery (drop-reason sets, WRR
   collisions, stochastic delays + startup waits, link contention) and,
   when the reference tree is present, the frozen reference-parity
   scenarios.  ``np.array_equal`` equality, not approx.
2. The fusion-count budget: the compiled flagship-interval
   ``engine.apply`` on the CPU backend must not exceed a PINNED fusion
   count for the XLA path, and the pallas path must land STRICTLY BELOW
   the XLA path.  This encodes the round-5 lesson (the scatter-merge was
   bit-exact yet regressed 281->294 fusions): correctness alone does not
   gate a substep change — op count does.

``pytest -m megakernel`` is the standalone smoke target for
ops/pallas_substep.py / engine-dispatch changes.
"""
import dataclasses
import os

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

pytestmark = pytest.mark.megakernel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.environ.get("GSC_REFERENCE_DIR", "/root/reference")

N, E = 8, 8


def make_service(std=0.0, startup=0.0):
    sf = lambda n: ServiceFunction(name=n, processing_delay_mean=5.0,
                                   processing_delay_stdev=std,
                                   startup_delay=startup)
    return ServiceConfig(sfc_list={"sfc_1": ("a", "b", "c")},
                         sf_list={n: sf(n) for n in "abc"})


LIMITS = EnvLimits(max_nodes=N, max_edges=E, num_sfcs=1, max_sfs=3)


def line_topo(node_cap=10.0, link_cap=100.0):
    spec = NetworkSpec(
        node_caps=[node_cap] * 3,
        node_types=["Ingress", "Normal", "Normal"],
        edges=[(0, 1, link_cap, 3.0), (1, 2, link_cap, 3.0)],
    )
    return compile_topology(spec, max_nodes=N, max_edges=E)


def triangle_topo():
    spec = NetworkSpec(
        node_caps=[20.0] * 3,
        node_types=["Ingress", "Normal", "Normal"],
        edges=[(0, 1, 100.0, 1.0), (0, 2, 100.0, 1.0), (1, 2, 100.0, 1.0)],
    )
    return compile_topology(spec, max_nodes=N, max_edges=E)


def sched_to(dst):
    s = np.zeros(LIMITS.scheduling_shape, np.float32)
    s[:, :, :, dst] = 1.0
    return jnp.asarray(s)


def place_at(pairs):
    p = np.zeros((N, LIMITS.max_sfs), bool)
    for n_, s_ in pairs:
        p[n_, s_] = True
    return jnp.asarray(p)


PLACE_ALL1 = [(1, 0), (1, 1), (1, 2)]


def run_engine(service, cfg, topo, sched, place, intervals=2, steps=4):
    engine = SimEngine(service, cfg, LIMITS)
    traffic = generate_traffic(cfg, service, topo, episode_steps=steps,
                               seed=0)
    state = engine.init(jax.random.PRNGKey(0), topo)
    metrics = None
    for _ in range(intervals):
        state, metrics = engine.apply(state, topo, traffic, sched, place)
    return state, metrics


def assert_tree_bitequal(a, b):
    """Full-pytree equality: same structure, shapes, dtypes, VALUES (the
    megakernel contract is bit-exactness, not tolerance)."""
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype, \
            (jax.tree_util.keystr(path), x.dtype, y.dtype)
        np.testing.assert_array_equal(
            x, y, err_msg=f"leaf {jax.tree_util.keystr(path)} diverged")


def compare_impls(service, topo, sched, place, ttl=100.0, intervals=2):
    cfg_x = SimConfig(ttl_choices=(ttl,))
    cfg_p = dataclasses.replace(cfg_x, substep_impl="pallas")
    sx, mx = run_engine(service, cfg_x, topo, sched, place, intervals)
    sp, mp = run_engine(service, cfg_p, topo, sched, place, intervals)
    assert_tree_bitequal(sx, sp)
    assert_tree_bitequal(mx, mp)
    return mx


# ----------------------------------------------------------------- parity
def test_megakernel_parity_smoke():
    """The ci_check.sh interpret-parity smoke: clean line-topo flow
    lifecycle, full state + metrics bit-equal across impls."""
    m = compare_impls(make_service(), line_topo(), sched_to(1),
                      place_at(PLACE_ALL1))
    assert int(m.processed) > 0 and int(m.dropped) == 0


# every branch of the substep's drop/decision classification, pallas vs xla
SCENARIOS = {
    "stochastic_startup": dict(service=make_service(std=1.0, startup=2.0)),
    "node_cap": dict(topo_kw={"node_cap": 0.5}, want_drops=True),
    "link_cap": dict(topo_kw={"link_cap": 0.5}, want_drops=True),
    "ttl": dict(ttl=10.0, want_drops=True),
    "unplaced_sf": dict(place=[(1, 0), (1, 1)], want_drops=True),
    "empty_schedule": dict(sched="zeros", place=[], want_drops=True),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_megakernel_parity_scenarios(name):
    sc = SCENARIOS[name]
    service = sc.get("service", make_service())
    topo = line_topo(**sc.get("topo_kw", {}))
    sched = (jnp.zeros(LIMITS.scheduling_shape, jnp.float32)
             if sc.get("sched") == "zeros" else sched_to(1))
    place = place_at(sc.get("place", PLACE_ALL1))
    m = compare_impls(service, topo, sched, place, ttl=sc.get("ttl", 100.0))
    if sc.get("want_drops"):
        assert int(m.dropped) > 0   # the branch under test actually fired


def test_megakernel_parity_wrr_collisions():
    """50/50 WRR split on a triangle: same-substep same-cell collisions
    exercise the rank/counter pipeline; counters must match bit-for-bit
    (they are part of the compared metrics tree)."""
    sched = np.zeros(LIMITS.scheduling_shape, np.float32)
    sched[0, 0, 0, 1] = 0.5
    sched[0, 0, 0, 2] = 0.5
    for n_ in (1, 2):
        sched[n_, 0, 1, n_] = 1.0
        sched[n_, 0, 2, n_] = 1.0
    place = place_at([(1, 0), (1, 1), (1, 2), (2, 0), (2, 1), (2, 2)])
    m = compare_impls(make_service(), triangle_topo(), jnp.asarray(sched),
                      place)
    counts = np.asarray(m.run_flow_counts)[0, 0, 0]
    assert counts[1] == counts[2]   # the split actually alternated


def test_megakernel_parity_link_contention_asset():
    """The in-repo line3-linkcap2 scenario (the only LINK_CAP-dominated
    oracle, frozen in test_reference_parity): saturated links make nearly
    every substep a same-substep admission tie, hammering the sorted
    cumsum-difference pipeline the kernel must reproduce exactly."""
    from gsc_tpu.config.catalog import abc_service
    from gsc_tpu.config.loader import load_sim
    from gsc_tpu.topology.compiler import load_topology

    service = abc_service()
    cfg_x = load_sim(os.path.join(REPO, "tests", "assets",
                                  "linkcap_config.yaml"))
    cfg_p = dataclasses.replace(cfg_x, substep_impl="pallas")
    topo = load_topology(os.path.join(REPO, "tests", "assets",
                                      "line3-linkcap2.graphml"),
                         max_nodes=N, max_edges=E)
    limits = EnvLimits.for_service(service, max_nodes=N, max_edges=E)
    sched = np.zeros(limits.scheduling_shape, np.float32)
    sched[:, :, :, 2] = 1.0   # everything toward the far end of the line
    sched = jnp.asarray(sched)
    place = jnp.asarray(np.broadcast_to(
        np.asarray(topo.node_mask)[:, None], (N, limits.max_sfs)).copy())
    results = []
    for cfg in (cfg_x, cfg_p):
        engine = SimEngine(service, cfg, limits)
        traffic = generate_traffic(cfg, service, topo, episode_steps=6,
                                   seed=0)
        state = engine.init(jax.random.PRNGKey(0), topo)
        for _ in range(6):
            state, metrics = engine.apply(state, topo, traffic, sched,
                                          place)
        results.append((state, metrics))
    (sx, mx), (sp, mp) = results
    assert_tree_bitequal(sx, sp)
    assert_tree_bitequal(mx, mp)
    assert int(mx.drop_reasons[2]) > 0   # LINK_CAP pressure was real


@pytest.mark.skipif(not os.path.isdir(REFERENCE),
                    reason="reference tree not available")
@pytest.mark.parametrize("name", [
    "triangle", "abilene", pytest.param("bteurope", marks=pytest.mark.slow)])
def test_megakernel_parity_reference_scenarios(name):
    """Pallas vs XLA on the frozen reference-parity scenarios themselves
    (triangle / abilene / BtEurope dt=0.25) through the canonical
    uniform-action harness — final metrics bit-equal, so the megakernel
    inherits the XLA engine's oracle parity by transitivity."""
    import sys

    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    from reward_curve import uniform_engine_run

    nets = {
        "triangle": ("configs/networks/triangle/"
                     "triangle-in2-cap10-delay10.graphml", None),
        "abilene": ("configs/networks/abilene/"
                    "abilene-in4-rand-cap1-2.graphml", None),
        "bteurope": ("configs/networks/BtEurope-in2-cap1.graphml",
                     {"dt": 0.25, "release_horizon": 1024}),
    }
    net, overrides = nets[name]
    out = []
    for impl in ("xla", "pallas"):
        metrics, _, _ = uniform_engine_run(
            os.path.join(REFERENCE, net), 25, 1234,
            overrides={**(overrides or {}), "substep_impl": impl})
        out.append(metrics)
    assert_tree_bitequal(out[0], out[1])
    assert int(out[0].generated) > 0


# --------------------------------------------------- kernel-call parity
def test_pallas_call_equals_inline_body():
    """The CPU default inlines the kernel body (no ref-discharge copies);
    a FORCED interpret-mode pallas_call must produce the identical state,
    pinning kernel == body so the TPU call path can't drift from what
    the parity suite actually validates."""
    from gsc_tpu.ops.pallas_substep import substep_megakernel

    service = make_service()
    cfg = SimConfig(ttl_choices=(100.0,), substep_impl="pallas")
    engine = SimEngine(service, cfg, LIMITS)
    topo = line_topo()
    traffic = generate_traffic(cfg, service, topo, episode_steps=4, seed=0)
    state = engine.init(jax.random.PRNGKey(0), topo)
    # advance one interval so the flow table is occupied, then one manual
    # substep both ways
    state, _ = engine.apply(state, topo, traffic, sched_to(1),
                            place_at(PLACE_ALL1))
    rng, _ = jax.random.split(state.rng)
    staged = state.replace(rng=rng)
    cap_now = traffic.node_cap[
        jnp.clip(state.run_idx, 0, traffic.node_cap.shape[0] - 1)]
    noise = jnp.zeros((cfg.max_flows,), jnp.float32)
    kw = dict(tables=engine.tables, cfg=cfg, limits=LIMITS, det=True)
    inline = substep_megakernel(staged, topo, traffic, cap_now, noise, **kw)
    kernel = substep_megakernel(staged, topo, traffic, cap_now, noise,
                                interpret=True, **kw)
    assert_tree_bitequal(inline, kernel)
    # and the substep did real work
    assert not np.array_equal(np.asarray(inline.flows.phase),
                              np.asarray(state.flows.phase))


# ------------------------------------------------------------ scan_unroll
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_scan_unroll_bit_identical(impl):
    """cfg.scan_unroll only restructures the substep loop: unroll=4 must
    be BIT-identical to unroll=1 on both substep impls (the precondition
    for promoting any swept unroll winner per rung)."""
    service = make_service()
    topo = line_topo()
    base = SimConfig(ttl_choices=(100.0,), substep_impl=impl)
    s1, m1 = run_engine(service, base, topo, sched_to(1),
                        place_at(PLACE_ALL1))
    s4, m4 = run_engine(service, dataclasses.replace(base, scan_unroll=4),
                        topo, sched_to(1), place_at(PLACE_ALL1))
    assert_tree_bitequal(s1, s4)
    assert_tree_bitequal(m1, m4)


# --------------------------------------------------------- fusion budget
# Pinned compiled-HLO fusion count of the flagship-interval engine.apply
# (abc service, Abilene limits 24/37, M=128, 100 substeps) on the CPU
# backend, jaxlib 0.9.0: xla 276, pallas 270 (xla 273 when re-measured
# and re-pinned in PR 21; the same programs counted 191 / 185 under the
# previous jaxlib — the compiler's fusion decisions moved, the engine did
# not).  PR 29 re-pinned 273 -> 276: stage 1 of the XLA substep reads and
# clears the release rings' due row through a mask over the whole ring
# instead of by index, which the CPU compiler counts as three more
# fusions of this unbatched program — while the TPU's vmapped program
# loses four whole-ring layout copies and two scatters per substep.  The
# pallas twin keeps the indexed form (its bit-exact parity cases above
# are the old form against the new), so its count stays.  The budget adds
# NO headroom on purpose — a 281->294-style regression is ~+13, so any
# slack would swallow exactly the class of change this gate exists to
# catch.  If a toolchain upgrade moves the count, re-measure and re-pin in
# the same commit as the upgrade (the assertion message carries the
# recipe).
#
# What the pin protects: the XLA engine's op count as the CPU compiler
# sees it — a proxy, not the chip's count (the TPU compiler fuses
# differently; a chip run's perf.json carries its own `fusions`).  The
# pallas < xla half protects only the megakernel's CPU role: TPU Pallas
# refused to lower it (PR 21), so it never runs on a chip, and whether
# the twin survives at all is ROADMAP Queue 3 item 3.
XLA_FUSION_BUDGET = 276


def _flagship_interval_compiled(impl):
    from gsc_tpu.config.catalog import abc_service
    from gsc_tpu.topology.synthetic import abilene

    service = abc_service()
    limits = EnvLimits(max_nodes=24, max_edges=37, num_sfcs=1, max_sfs=3)
    topo = compile_topology(abilene(), max_nodes=24, max_edges=37)
    cfg = SimConfig(ttl_choices=(100.0,), substep_impl=impl)
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
    """Tier-1 op-count gate: XLA path within the pinned budget, pallas
    path STRICTLY below the XLA path (the ISSUE acceptance bar)."""
    from gsc_tpu.analysis.hlo import count_fusions

    n_xla = count_fusions(_flagship_interval_compiled("xla"))
    n_pallas = count_fusions(_flagship_interval_compiled("pallas"))
    assert n_xla <= XLA_FUSION_BUDGET, (
        f"XLA substep fusion count regressed: {n_xla} > pinned "
        f"{XLA_FUSION_BUDGET}.  If this is an intended engine change, "
        "re-measure with tests/test_megakernel.py::"
        "_flagship_interval_compiled and re-pin XLA_FUSION_BUDGET in the "
        "same commit — with a BENCH_NOTES line saying why.")
    assert n_pallas < n_xla, (
        f"megakernel path must stay strictly below the XLA engine's "
        f"fusion count (pallas={n_pallas}, xla={n_xla}) — that delta IS "
        "the knob's reason to exist (round-5 roofline: the substep is "
        "op-count bound)")


# ------------------------------------------------------------ validation
def test_pallas_rejects_per_flow_controller():
    """Fail-fast contract: the megakernel covers only the duration
    controller; a per-flow config must be rejected at SimConfig
    validation, never silently fall back."""
    with pytest.raises(ValueError, match="per.flow|duration"):
        SimConfig(ttl_choices=(100.0,), controller="per_flow",
                  substep_impl="pallas")
    with pytest.raises(ValueError, match="substep_impl"):
        SimConfig(ttl_choices=(100.0,), substep_impl="mosaic")
