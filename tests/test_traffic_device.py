"""On-device traffic generator tests: bitwise parity with the host
generator on deterministic configs, distributional parity on stochastic
ones, trace/MMPP semantics, engine compatibility, and the batched
sampler's one-jit contract (``traffic_sample``: same schedule as the
un-jitted ``vmap``, one trace per (sampler, B))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.analysis.sentinels import DEFAULT_WATCH, CompileMonitor
from gsc_tpu.config.schema import EnvLimits, MMPPState, SimConfig
from gsc_tpu.sim.engine import SimEngine
from gsc_tpu.sim.traffic import TraceEvents, generate_traffic
from gsc_tpu.sim.traffic_device import DeviceTraffic

from tests.test_traffic import service, topo


def test_deterministic_bitwise_matches_host():
    """Fully deterministic config: the device sampler reproduces the host
    schedule bit-for-bit (every random draw is degenerate, so the RNG
    difference is invisible)."""
    cfg = SimConfig(ttl_choices=(100.0,), inter_arrival_mean=10.0)
    host = generate_traffic(cfg, service(), topo(2), episode_steps=5, seed=0)
    dev = jax.jit(DeviceTraffic(cfg, service(), topo(2), 5).sample)(
        jax.random.PRNGKey(0))
    for field in ("arr_time", "arr_ingress", "arr_dr", "arr_duration",
                  "arr_ttl", "ingress_active", "node_cap"):
        np.testing.assert_array_equal(np.asarray(getattr(host, field)),
                                      np.asarray(getattr(dev, field)),
                                      err_msg=field)


def test_poisson_rates_match_host_distribution():
    cfg = SimConfig(ttl_choices=(100.0,), deterministic_arrival=False,
                    inter_arrival_mean=10.0)
    dt = DeviceTraffic(cfg, service(), topo(1), episode_steps=20)
    sample = jax.jit(dt.sample)
    counts, gaps = [], []
    for s in range(8):
        tr = sample(jax.random.PRNGKey(s))
        t = np.asarray(tr.arr_time)
        t = t[np.isfinite(t)]
        counts.append(len(t))
        gaps.append(np.diff(np.sort(t)))
    # horizon/mean = 200 expected arrivals; 8 seeds of Poisson(200)
    assert abs(np.mean(counts) - 200) < 25
    assert abs(np.concatenate(gaps).mean() - 10.0) < 1.5
    # distinct seeds -> distinct streams
    assert counts[0] != counts[1] or not np.array_equal(gaps[0], gaps[1])


def test_pareto_sizes_and_dr_rejection():
    cfg = SimConfig(ttl_choices=(100.0,), deterministic_size=False,
                    flow_size_shape=2.0, flow_dr_mean=1.0, flow_dr_stdev=0.3)
    dt = DeviceTraffic(cfg, service(), topo(1), episode_steps=10)
    tr = jax.jit(dt.sample)(jax.random.PRNGKey(0))
    fin = np.isfinite(np.asarray(tr.arr_time))
    dr = np.asarray(tr.arr_dr)[fin]
    dur = np.asarray(tr.arr_duration)[fin]
    assert (dr >= 0).all()                      # rejection semantics
    sizes = dur * dr / 1000.0
    assert (sizes >= 1.0 - 1e-5).all()          # Pareto support
    # Pareto(2) mean is 2; loose check over ~100 draws
    assert 1.3 < sizes.mean() < 3.5


def test_mmpp_density_and_interval_means():
    cfg = SimConfig(
        ttl_choices=(100.0,), deterministic_arrival=True,
        use_states=True, init_state="s0", rand_init_state=False,
        states=(MMPPState(name="s0", inter_arr_mean=5.0, switch_p=0.5),
                MMPPState(name="s1", inter_arr_mean=50.0, switch_p=0.5)))
    dt = DeviceTraffic(cfg, service(), topo(1), episode_steps=40)
    tr = jax.jit(dt.sample)(jax.random.PRNGKey(7))
    t = np.asarray(tr.arr_time)
    t = t[np.isfinite(t)]
    counts = np.histogram(t, bins=40, range=(0, 4000))[0]
    # both dense (~20/interval) and sparse (~2/interval) states visited
    assert counts.max() >= 15 and counts.min() <= 3
    # the chain is per-episode randomness: two keys give different paths
    tr2 = jax.jit(dt.sample)(jax.random.PRNGKey(8))
    t2 = np.asarray(tr2.arr_time)
    assert not np.array_equal(t, t2[np.isfinite(t2)])


def test_trace_deactivation_and_caps():
    """Trace rows deactivate/reactivate an ingress and raise node caps
    exactly like the host generator (trace_processor.py:23-54)."""
    rows = [(200.0, 0, None, None), (400.0, 0, 10.0, 5000.0)]
    cfg = SimConfig(ttl_choices=(100.0,), inter_arrival_mean=10.0)
    trace = TraceEvents(rows)
    host = generate_traffic(cfg, service(), topo(1), 6, seed=0, trace=trace)
    dev = jax.jit(DeviceTraffic(cfg, service(), topo(1), 6,
                                trace=trace).sample)(jax.random.PRNGKey(0))
    for field in ("arr_time", "arr_ingress", "ingress_active", "node_cap"):
        np.testing.assert_array_equal(np.asarray(getattr(host, field)),
                                      np.asarray(getattr(dev, field)),
                                      err_msg=field)
    t = np.asarray(dev.arr_time)
    t = t[np.isfinite(t)]
    assert not ((t >= 200.0) & (t < 400.0)).any()   # silent window
    assert (t >= 400.0).any()                        # reactivated
    assert np.asarray(dev.node_cap)[4:, 0].max() == 5000.0


def test_engine_consumes_device_traffic():
    """The sim engine runs on a device-sampled schedule and books flows."""
    cfg = SimConfig(ttl_choices=(100.0,), inter_arrival_mean=10.0,
                    max_flows=32)
    svc = service()
    limits = EnvLimits(max_nodes=8, max_edges=8, num_sfcs=1, max_sfs=2)
    tp = topo(2)
    dt = DeviceTraffic(cfg, svc, tp, episode_steps=3)
    traffic = jax.jit(dt.sample)(jax.random.PRNGKey(0))
    engine = SimEngine(svc, cfg, limits)
    sched = np.zeros(limits.scheduling_shape, np.float32)
    nm = np.asarray(tp.node_mask)
    sched[:, :, :, nm] = 1.0 / nm.sum()
    placement = jnp.asarray(np.broadcast_to(nm[:, None], (8, 2)).copy())
    state = engine.init(jax.random.PRNGKey(0), tp)
    for _ in range(3):
        state, metrics = engine.apply(state, tp, traffic,
                                      jnp.asarray(sched), placement)
    assert int(metrics.generated) > 0
    assert int(metrics.generated) == (int(metrics.processed)
                                      + int(metrics.dropped)
                                      + int(metrics.active))


def test_batch_sampling_shapes_and_divergence():
    cfg = SimConfig(ttl_choices=(100.0,), deterministic_arrival=False)
    dt = DeviceTraffic(cfg, service(), topo(2), episode_steps=4)
    b = jax.jit(lambda k: dt.sample_batch(k, 4))(jax.random.PRNGKey(0))
    assert b.arr_time.shape == (4, dt.capacity)
    assert b.ingress_active.shape == (4, 4, 8)
    t = np.asarray(b.arr_time)
    assert not np.array_equal(t[0], t[1])       # per-replica streams


def test_trace_overrides_mmpp_means():
    """Trace rows override the MMPP chain from their timestamp on (host
    semantics: means filled by the chain, then trace rows overwrite,
    traffic.py:131-142) — the deactivation window must be silent even
    though the chain keeps running."""
    cfg = SimConfig(
        ttl_choices=(100.0,), deterministic_arrival=True,
        use_states=True, init_state="s0", rand_init_state=False,
        states=(MMPPState(name="s0", inter_arr_mean=5.0, switch_p=0.5),
                MMPPState(name="s1", inter_arr_mean=50.0, switch_p=0.5)))
    trace = TraceEvents([(500.0, 0, None, None), (1500.0, 0, 5.0, None)])
    dt = DeviceTraffic(cfg, service(), topo(1), episode_steps=20,
                       trace=trace)
    tr = jax.jit(dt.sample)(jax.random.PRNGKey(3))
    t = np.asarray(tr.arr_time)
    t = t[np.isfinite(t)]
    assert not ((t >= 500.0) & (t < 1500.0)).any()   # silent window
    assert (t < 500.0).any() and (t >= 1500.0).any()
    # post-reactivation the overridden FIXED mean applies: dense 5 ms
    # arrivals regardless of chain state
    post = np.sort(t[t >= 1500.0])
    gaps = np.diff(post)
    assert np.allclose(gaps, 5.0)


# ------------------------------------------------ sample_batch: one jit
# the benchmark cells' simulator document (benchmarks/configs/*.json):
# every draw degenerate, so the schedule must not move by a bit
_CELLS = dict(ttl_choices=(100.0,), inter_arrival_mean=10.0,
              deterministic_arrival=True, deterministic_size=True,
              flow_dr_mean=1.0, flow_dr_stdev=0.0, flow_size_shape=0.001)
_STOCHASTIC = dict(
    ttl_choices=(50.0, 100.0), deterministic_arrival=False,
    deterministic_size=False, flow_size_shape=2.0, flow_dr_mean=1.0,
    flow_dr_stdev=0.3, use_states=True, init_state="s0",
    rand_init_state=True,
    states=(MMPPState(name="s0", inter_arr_mean=5.0, switch_p=0.5),
            MMPPState(name="s1", inter_arr_mean=50.0, switch_p=0.5)))


@pytest.mark.parametrize("kwargs,bitwise", [(_CELLS, True),
                                            (_STOCHASTIC, False)],
                         ids=["cells_deterministic", "stochastic"])
def test_sample_batch_equals_unjitted_vmap(kwargs, bitwise):
    """The jitted batch sampler draws what a bare ``vmap(sample)`` over the
    split key draws, dispatched operation by operation: every field bit for
    bit on the cells' configuration and the integer fields always, the
    float fields to 1e-6 relative where real draws pass through fused
    arithmetic."""
    dt = DeviceTraffic(SimConfig(**kwargs), service(), topo(2),
                       episode_steps=6)
    B, key = 3, jax.random.PRNGKey(2**31 + 11)
    got = dt.sample_batch(key, B)
    # the eager path the loops ran before: each jnp operation dispatched
    # on its own, the merge scan as its own `jit_scan`
    want = jax.vmap(dt.sample)(jax.random.split(key, B))
    assert int(np.isfinite(np.asarray(got.arr_time)).sum()) > B * 10
    for field in ("arr_time", "arr_ingress", "arr_dr", "arr_duration",
                  "arr_ttl", "arr_sfc", "arr_egress", "ingress_active",
                  "node_cap"):
        g, w = np.asarray(getattr(got, field)), np.asarray(
            getattr(want, field))
        assert g.dtype == w.dtype and g.shape == w.shape, field
        if bitwise or g.dtype.kind in "ib":
            np.testing.assert_array_equal(g, w, err_msg=field)
        else:
            np.testing.assert_allclose(g, w, rtol=1e-6, err_msg=field)
    assert got.edge_cap_t is None and want.edge_cap_t is None


def test_sample_batch_traces_once_per_sampler_and_batch():
    """``traffic_sample`` is traced on first use and never again for fresh
    keys; a second ``num_replicas`` on the same sampler costs exactly one
    more trace."""
    dt = DeviceTraffic(SimConfig(**_CELLS), service(), topo(2),
                       episode_steps=4)
    with CompileMonitor(watch=("traffic_sample",)) as mon:
        first = dt.sample_batch(jax.random.PRNGKey(0), 4)
        with mon.assert_no_retrace("traffic_sample"):
            for seed in (1, 2):
                again = dt.sample_batch(jax.random.PRNGKey(seed), 4)
        assert mon.trace_counts["traffic_sample"] == 1
        assert again.arr_time.shape == first.arr_time.shape
        other = dt.sample_batch(jax.random.PRNGKey(3), 2)
        dt.sample_batch(jax.random.PRNGKey(4), 2)
        assert mon.trace_counts["traffic_sample"] == 2
    assert other.arr_time.shape == (2, dt.capacity)


def test_traffic_sample_is_a_default_watched_entry_point():
    """The observer's default monitor turns the sampler's trace into a
    ``compile`` event and a ``jit_traces_total{fn=traffic_sample}``
    count: the sampler is covered by ``window_compiles``."""
    assert "traffic_sample" in DEFAULT_WATCH
    dt = DeviceTraffic(SimConfig(**_CELLS), service(), topo(1),
                       episode_steps=2)
    with CompileMonitor() as mon:
        dt.sample_batch(jax.random.PRNGKey(0), 2)
    assert [e["fn"] for e in mon.events if e["kind"] == "trace"
            ] == ["traffic_sample"]
