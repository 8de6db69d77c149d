"""Host-side traffic pre-generation -> TrafficSchedule tensors.

The reference generates flow arrivals *during* simulation: an ``init_arrival``
SimPy process per ingress samples inter-arrival/dr/size inline
(flowsimulator.py:59-70, default_generator.py:18-60) with per-node arrival
means that may change over the episode via the two-state MMPP
(simulatorparams.py:143-176) or a CSV trace (trace_processor.py:23-54).
Data-dependent arrival loops are unmappable to XLA, and the reference itself
already pre-generates per-episode flow lists (simulatorparams.py:185-247) —
we take that idea to its conclusion: the *entire* episode's traffic (arrival
times, rates, sizes, TTLs, SFC/egress choices, per-interval ingress activity
and node-capacity overrides) is sampled host-side with numpy into one dense
sorted ``TrafficSchedule`` that the on-device engine merely consumes.

Distribution semantics preserved:
- deterministic vs Poisson arrivals: inter-arrival = mean or Exp(mean)
  (default_generator.py:21-25); first flow at t=0 (flowsimulator.py:63-70).
- dr ~ Normal(dr_mean, dr_stdev); size = shape (deterministic) or
  Pareto(shape)+1; joint rejection-resampling of negatives
  (default_generator.py:47-60).
- duration = size/dr * 1000 ms (flow.py:33).
- SFC ~ uniform choice; egress ~ uniform choice of egress nodes (or none);
  TTL ~ uniform choice of ttl_choices (default_generator.py:30-40).
- MMPP: per-ingress two-state Markov chain switching with prob switch_p at
  every run_duration boundary; arrival mean follows the current state
  (simulatorparams.py:143-176).  Initial state: init_state, or random per
  node when rand_init_state (simulatorparams.py:108-116).
- trace: rows (time, node, inter_arrival_mean) set a node's arrival mean
  from that time on; 'None' deactivates the ingress; optional cap column
  raises node capacity (trace_processor.py:23-54).
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..config.schema import ServiceConfig, SimConfig
from ..topology.compiler import Topology
from .state import TrafficSchedule


def traffic_capacity(cfg: SimConfig, num_ingress: int, episode_steps: int,
                     pad_factor: float = 1.6) -> int:
    """Static upper bound on flows per episode (keeps shapes fixed across
    episodes so nothing recompiles)."""
    horizon = episode_steps * cfg.run_duration
    mean = cfg.inter_arrival_mean
    if cfg.use_states:
        mean = min(s.inter_arr_mean for s in cfg.states)
    expected = horizon / max(mean, 1e-6) * max(num_ingress, 1)
    cap = int(expected * pad_factor) + 8 * max(num_ingress, 1)
    # round up to a multiple of 64 for nicer TPU layouts
    return ((cap + 63) // 64) * 64


class TraceEvents:
    """Parsed trace CSV (reference format: time,node,inter_arrival_mean[,cap]
    — configs/traces/*.csv, trace_processor.py:29-46)."""

    def __init__(self, rows: Sequence[Tuple[float, int, Optional[float], Optional[float]]]):
        # each row: (time, node_index, inter_arrival_mean or None, cap or None)
        self.rows = sorted(rows, key=lambda r: r[0])

    @classmethod
    def from_csv(cls, path: str, node_name_to_idx) -> "TraceEvents":
        import csv

        rows = []
        with open(path) as f:
            for rec in csv.DictReader(f):
                t = float(rec["time"])
                node = rec["node"]
                idx = node_name_to_idx(node)
                mean_raw = rec.get("inter_arrival_mean")
                mean = (None if mean_raw in (None, "", "None") else float(mean_raw))
                cap = rec.get("cap")
                cap = None if cap in (None, "", "None") else float(cap)
                rows.append((t, idx, mean, cap))
        return cls(rows)


def _mmpp_interval_means(cfg: SimConfig, ing_idx: np.ndarray, steps: int,
                         rng: np.random.Generator) -> np.ndarray:
    """Per-(interval, ingress) arrival means from the two-state MMPP chain.
    State updates happen at every run_duration boundary with switch
    probability of the current state (simulatorparams.py:152-176)."""
    names = [s.name for s in cfg.states]
    means = {s.name: s.inter_arr_mean for s in cfg.states}
    switch = {s.name: s.switch_p for s in cfg.states}
    n_ing = len(ing_idx)
    if cfg.rand_init_state:
        cur = [names[rng.integers(len(names))] for _ in range(n_ing)]
    else:
        cur = [cfg.init_state] * n_ing
    out = np.zeros((steps, n_ing), np.float64)
    for t in range(steps):
        out[t] = [means[c] for c in cur]
        # switch decision at the end of the interval (start_mmpp waits one
        # run_duration before the first update, simulatorparams.py:146-151)
        cur = [
            (names[1 - names.index(c)] if rng.random() < switch[c] else c)
            for c in cur
        ]
    return out


def generate_traffic(
    cfg: SimConfig,
    service: ServiceConfig,
    topo: Topology,
    episode_steps: int,
    seed: int,
    trace: Optional[TraceEvents] = None,
    capacity: Optional[int] = None,
    faults: Sequence = (),
    with_edge_cap: bool = False,
) -> TrafficSchedule:
    """Sample one episode of traffic into a TrafficSchedule.

    ``faults`` (topology.scenarios.TopoFault sequence): deterministic
    mid-episode capacity faults — node faults zero rows of the
    per-interval ``node_cap`` table, link faults materialize (and zero
    rows of) the per-interval ``edge_cap_t`` table the engine
    row-selects.  ``with_edge_cap`` forces ``edge_cap_t`` even without a
    link fault, so a mixed batch where only SOME members have link
    faults still stacks into one consistent pytree structure."""
    rng = np.random.default_rng(seed)
    n = topo.max_nodes
    node_cap = np.asarray(topo.node_cap)
    ing_mask = np.asarray(topo.is_ingress) & np.asarray(topo.node_mask)
    eg_idx = np.nonzero(np.asarray(topo.is_egress) & np.asarray(topo.node_mask))[0]
    ing_idx = np.nonzero(ing_mask)[0]
    sfc_ids = np.arange(len(service.sfc_names))
    horizon = episode_steps * cfg.run_duration

    # --- per-(interval, node) arrival means & activity -----------------------
    means = np.full((episode_steps, n), np.nan)
    if cfg.use_states and len(ing_idx):
        means[:, ing_idx] = _mmpp_interval_means(cfg, ing_idx, episode_steps, rng)
    else:
        means[:, ing_idx] = cfg.inter_arrival_mean
    caps = np.broadcast_to(node_cap, (episode_steps, n)).copy()
    if trace is not None:
        for (t0, node, mean, cap) in trace.rows:
            k0 = min(int(t0 // cfg.run_duration), episode_steps)
            if node in ing_idx:
                means[k0:, node] = np.nan if mean is None else mean
            if cap is not None:
                caps[k0:, node] = cap
    active = ~np.isnan(means)

    edge_cap_t = None
    if faults or with_edge_cap:
        from ..topology.scenarios import apply_faults
        caps, edge_cap_t = apply_faults(topo, caps, episode_steps, faults,
                                        with_edge_cap)

    cap_f = capacity if capacity is not None else traffic_capacity(
        cfg, len(ing_idx), episode_steps)

    # --- flow records: native C++ sampler when available ---------------------
    from ..native import generate_flows_native

    native = generate_flows_native(
        seed=seed, means=means, run_duration=cfg.run_duration,
        dr_mean=cfg.flow_dr_mean, dr_stdev=cfg.flow_dr_stdev,
        size_shape=cfg.flow_size_shape,
        det_arrival=cfg.deterministic_arrival, det_size=cfg.deterministic_size,
        ttl_choices=np.asarray(cfg.ttl_choices), n_sfcs=len(sfc_ids),
        egress_nodes=eg_idx, capacity=cap_f)
    if native is not None:
        n_times, n_ing, n_drs, n_durs, n_ttls, n_sfcs_a, n_egs = native

        def pad_native(vals, fill, dtype):
            out = np.full(cap_f, fill, dtype)
            out[:len(vals)] = np.asarray(vals, dtype)
            return out

        return TrafficSchedule.pack(
            arr_time=jnp.asarray(pad_native(n_times, np.inf, np.float32)),
            arr_ingress=jnp.asarray(pad_native(n_ing, 0, np.int32)),
            arr_dr=jnp.asarray(pad_native(n_drs, 0.0, np.float32)),
            arr_duration=jnp.asarray(pad_native(n_durs, 0.0, np.float32)),
            arr_ttl=jnp.asarray(pad_native(n_ttls, 0.0, np.float32)),
            arr_sfc=jnp.asarray(pad_native(n_sfcs_a, 0, np.int32)),
            arr_egress=jnp.asarray(pad_native(n_egs, -1, np.int32)),
            ingress_active=jnp.asarray(active),
            node_cap=jnp.asarray(caps, np.float32),
            edge_cap_t=edge_cap_t,
        )

    # --- numpy fallback ------------------------------------------------------
    times: List[float] = []
    ingress: List[int] = []
    drs: List[float] = []
    durs: List[float] = []
    ttls: List[float] = []
    sfcs: List[int] = []
    egs: List[int] = []

    def sample_dr_size() -> Tuple[float, float]:
        # joint rejection-resample (default_generator.py:47-60)
        while True:
            dr = rng.normal(cfg.flow_dr_mean, cfg.flow_dr_stdev)
            if cfg.deterministic_size:
                size = cfg.flow_size_shape
            else:
                size = rng.pareto(cfg.flow_size_shape) + 1
            if dr >= 0.0 and size >= 0.0:
                return float(dr), float(size)

    for node in ing_idx:
        t = 0.0
        while t < horizon:
            k = int(t // cfg.run_duration)
            mean = means[k, node]
            if math.isnan(mean):
                # ingress deactivated: jump to the next interval where a trace
                # row might reactivate it (arrival loop stops on None,
                # flowsimulator.py:63; only a later trace row restarts it)
                nxt = np.nonzero(active[k:, node])[0]
                if len(nxt) == 0:
                    break
                t = float((k + nxt[0]) * cfg.run_duration)
                continue
            # flow generated first, then inter-arrival sleep
            # (flowsimulator.py:63-70): first arrival at t
            dr, size = sample_dr_size()
            dur = (size / dr) * 1000.0 if dr > 0 else 0.0
            times.append(t)
            ingress.append(int(node))
            drs.append(dr)
            durs.append(dur)
            ttls.append(float(cfg.ttl_choices[rng.integers(len(cfg.ttl_choices))]))
            sfcs.append(int(sfc_ids[rng.integers(len(sfc_ids))]))
            egs.append(int(eg_idx[rng.integers(len(eg_idx))]) if len(eg_idx) else -1)
            if cfg.deterministic_arrival:
                t += mean
            else:
                t += rng.exponential(mean)

    order = np.argsort(np.asarray(times, np.float64), kind="stable")
    f = len(order)
    if f > cap_f:  # should not happen with the default pad factor
        order = order[:cap_f]
        f = cap_f

    def pad_f(vals, fill, dtype):
        out = np.full(cap_f, fill, dtype)
        if f:
            out[:f] = np.asarray(vals, dtype)[order]
        return out

    return TrafficSchedule.pack(
        arr_time=jnp.asarray(pad_f(times, np.inf, np.float32)),
        arr_ingress=jnp.asarray(pad_f(ingress, 0, np.int32)),
        arr_dr=jnp.asarray(pad_f(drs, 0.0, np.float32)),
        arr_duration=jnp.asarray(pad_f(durs, 0.0, np.float32)),
        arr_ttl=jnp.asarray(pad_f(ttls, 0.0, np.float32)),
        arr_sfc=jnp.asarray(pad_f(sfcs, 0, np.int32)),
        arr_egress=jnp.asarray(pad_f(egs, -1, np.int32)),
        ingress_active=jnp.asarray(active),
        node_cap=jnp.asarray(caps, np.float32),
        edge_cap_t=edge_cap_t,
    )
