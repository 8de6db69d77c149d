"""Traffic pre-generation tests: deterministic/Poisson arrivals, MMPP state
switching, trace-driven scenario changes (reference semantics:
simulatorparams.py:100-247, trace_processor.py:23-54,
default_generator.py:18-60)."""
import numpy as np
import pytest

from gsc_tpu.config.schema import MMPPState, ServiceConfig, ServiceFunction, SimConfig
from gsc_tpu.sim.traffic import TraceEvents, generate_traffic
from gsc_tpu.topology.compiler import NetworkSpec, compile_topology
from gsc_tpu.utils.experiment import select_best_agent

N, E = 8, 8


def service():
    sf = lambda n: ServiceFunction(name=n)
    return ServiceConfig(sfc_list={"sfc_1": ("a", "b")},
                         sf_list={n: sf(n) for n in "ab"})


def topo(n_ingress=2):
    types = ["Ingress"] * n_ingress + ["Normal"] * (3 - n_ingress)
    spec = NetworkSpec(node_caps=[10.0] * 3, node_types=types,
                       edges=[(0, 1, 100.0, 1.0), (1, 2, 100.0, 1.0)])
    return compile_topology(spec, max_nodes=N, max_edges=E)


def test_deterministic_arrivals():
    cfg = SimConfig(ttl_choices=(100.0,), inter_arrival_mean=10.0)
    tr = generate_traffic(cfg, service(), topo(1), episode_steps=1, seed=0)
    times = np.asarray(tr.arr_time)
    real = times[np.isfinite(times)]
    np.testing.assert_allclose(real, np.arange(10) * 10.0)


def test_poisson_arrivals_differ_by_seed():
    cfg = SimConfig(ttl_choices=(100.0,), deterministic_arrival=False)
    t1 = np.asarray(generate_traffic(cfg, service(), topo(1), 2, seed=1).arr_time)
    t2 = np.asarray(generate_traffic(cfg, service(), topo(1), 2, seed=2).arr_time)
    assert not np.array_equal(t1[np.isfinite(t1)], t2[np.isfinite(t2)])


def test_mmpp_switches_rate():
    """Two-state MMPP: arrival density follows the per-interval Markov state
    (simulatorparams.py:143-176)."""
    cfg = SimConfig(
        ttl_choices=(100.0,), deterministic_arrival=True,
        use_states=True, init_state="s0", rand_init_state=False,
        states=(MMPPState(name="s0", inter_arr_mean=5.0, switch_p=0.5),
                MMPPState(name="s1", inter_arr_mean=50.0, switch_p=0.5)))
    tr = generate_traffic(cfg, service(), topo(1), episode_steps=40, seed=3)
    times = np.asarray(tr.arr_time)
    real = times[np.isfinite(times)]
    # per-interval counts must take both dense (~20/interval) and sparse
    # (~2/interval) values
    counts = np.histogram(real, bins=40, range=(0, 4000))[0]
    assert counts.max() >= 15 and counts.min() <= 3


def test_trace_deactivates_and_caps():
    """Trace rows change a node's arrival mean / deactivate it and can raise
    node capacity mid-episode (trace_processor.py:29-46)."""
    cfg = SimConfig(ttl_choices=(100.0,))
    tp = topo(2)
    trace = TraceEvents([(200.0, 0, None, None),      # ingress 0 off at t=200
                         (300.0, 1, 5.0, 99.0)])      # ingress 1 denser + cap
    tr = generate_traffic(cfg, service(), tp, episode_steps=5, seed=0,
                          trace=trace)
    times = np.asarray(tr.arr_time)
    ing = np.asarray(tr.arr_ingress)
    fin = np.isfinite(times)
    # no arrivals from node 0 after t=200
    assert not ((ing == 0) & fin & (times >= 200.0)).any()
    assert ((ing == 0) & fin & (times < 200.0)).any()
    # node 1 arrives twice as densely from t=300
    n1_before = ((ing == 1) & fin & (times >= 100) & (times < 200)).sum()
    n1_after = ((ing == 1) & fin & (times >= 300) & (times < 400)).sum()
    assert n1_after >= 2 * n1_before - 1
    # activity mask + cap schedule reflect the trace
    active = np.asarray(tr.ingress_active)
    assert active[1, 0] and not active[2, 0]
    caps = np.asarray(tr.node_cap)
    assert caps[2, 1] == 10.0 and caps[3, 1] == 99.0


def test_select_best_agent(tmp_path):
    for name, rewards in [("a", [1, 2]), ("b", [5, 6]), ("c", [])]:
        d = tmp_path / name
        d.mkdir()
        with open(d / "rewards.csv", "w") as f:
            f.write("r\n" + "".join(f"{r}\n" for r in rewards))
    best = select_best_agent([str(tmp_path / n) for n in "abc"])
    assert best.endswith("b")
    with pytest.raises(ValueError):
        select_best_agent([str(tmp_path / "missing")])


# ------------------------------------------------- the packed arrival table
def _host_records(native):
    def build(monkeypatch):
        if not native:
            monkeypatch.setattr("gsc_tpu.native.generate_flows_native",
                                lambda **kw: None)
        cfg = SimConfig(ttl_choices=(100.0, 200.0),
                        deterministic_arrival=False)
        return generate_traffic(cfg, service(), topo(2), episode_steps=3,
                                seed=5, capacity=300)
    return build


def _device_records(monkeypatch):
    import jax

    from gsc_tpu.sim.traffic_device import DeviceTraffic

    cfg = SimConfig(ttl_choices=(100.0, 200.0), deterministic_arrival=False)
    sampler = DeviceTraffic(cfg, service(), topo(2), 3, capacity=300)
    return sampler.sample(jax.random.PRNGKey(5))


def _factory_records(monkeypatch):
    import jax
    import jax.numpy as jnp

    import __graft_entry__ as ge
    from gsc_tpu.topology.factory import ScenarioFactory, parse_factory

    env, _, _, _ = ge._flagship(max_nodes=8, max_edges=8, episode_steps=2,
                                max_flows=32)
    factory = ScenarioFactory(parse_factory("factory:star-line+shapes"),
                              env.sim_cfg, env.service, 2, max_nodes=8,
                              max_edges=8)
    return factory.sample(jax.random.PRNGKey(5), jnp.full((2,), 0.5))[1]


@pytest.mark.parametrize("producer", [
    pytest.param(_host_records(native=True), id="host_native"),
    pytest.param(_host_records(native=False), id="host_numpy"),
    pytest.param(_device_records, id="device_sampler"),
    pytest.param(_factory_records, id="scenario_factory"),
])
def test_producer_records_come_through_the_packed_table(producer,
                                                        monkeypatch):
    """Each of the four producers builds its schedule through
    ``TrafficSchedule.pack``, and the per-field views give back the very
    records it handed over: bit for bit, in their dtypes, at the capacity
    asked for, sorted by time, with the table's padding behind them."""
    from gsc_tpu.sim.state import (ARRIVAL_LANES, ARRIVAL_RUN,
                                   TrafficSchedule)

    handed = []
    pack = TrafficSchedule.pack.__func__

    def spy(cls, **kw):
        handed.append({k: np.asarray(v) for k, v in kw.items()
                       if k.startswith("arr_")})
        return pack(cls, **kw)

    monkeypatch.setattr(TrafficSchedule, "pack", classmethod(spy))
    tr = producer(monkeypatch)
    records = handed[-1]     # (building the factory's env packs one too)
    assert sorted(records) == ["arr_dr", "arr_duration", "arr_egress",
                               "arr_ingress", "arr_sfc", "arr_time",
                               "arr_ttl"]
    capacity = tr.capacity
    rows = -(-(capacity + ARRIVAL_RUN) // ARRIVAL_LANES)
    assert tr.arr.shape == (7, rows, ARRIVAL_LANES)
    assert tr.arr.dtype == np.int32
    times = np.asarray(tr.arr_time)
    assert np.isfinite(times).sum() > 10
    assert (np.diff(times[np.isfinite(times)]) >= 0).all()
    assert not np.isfinite(times[np.isfinite(times).sum():]).any()
    for name, raw in records.items():
        view = np.asarray(getattr(tr, name))
        want = np.float32 if name in ("arr_time", "arr_dr", "arr_duration",
                                      "arr_ttl") else np.int32
        assert view.dtype == want and view.shape == (capacity,), name
        assert view.tobytes() == raw.astype(want).tobytes(), name
    # behind the records: never due, no egress, zeros
    table = np.asarray(tr.arr).reshape(7, -1)[:, capacity:]
    fills = [np.float32(np.inf).view(np.int32), 0, 0, 0, 0, 0, -1]
    np.testing.assert_array_equal(
        table, np.broadcast_to(np.asarray(fills, np.int32)[:, None],
                               table.shape))
