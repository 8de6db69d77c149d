"""Batched fixed-step flow simulation engine.

The functional replacement for the reference's SimPy discrete-event core
(coordsim/simulation/flowsimulator.py + forwarders/processors/decision_maker).
One *control interval* (= one RL step, ``run_duration`` ms) is a ``lax.scan``
over ``run_duration/dt`` fixed substeps; each substep advances every flow slot
in the preallocated ``FlowTable`` in parallel.  There is no data-dependent
Python control flow — the whole episode jits, vmaps over env replicas, and
shards over device meshes.

Per-substep pipeline (mirroring the reference's per-flow state machine,
flowsimulator.py:72-128):
 1. release capacities whose hold time elapsed (ring buffers; the analogue of
    the delayed ``return_link_resources`` / ``finish_processing`` SimPy
    processes, default_forwarder.py:112-125, base_processor.py:103-135);
    the due row is read and cleared by a mask over the whole ring, so that
    every ring operation of the substep is elementwise or a contraction
    and the ring keeps one device layout under ``vmap``
 2. advance HOP/PROC timers; completed PROC flows advance their SFC position
    (base_processor.py:104-107) and re-enter decision; completed hops either
    continue the path, arrive for processing, or depart at egress
 3. admit new arrivals from the pre-generated TrafficSchedule into free
    slots; the candidates are always the contiguous run of eight records at
    the cursor, so they are fetched as one run, for all fields at once and
    through masks instead of indices (under ``vmap`` the cursor is a
    per-replica vector, and an indexed read at it is serial on the TPU):
    once per interval the few table rows the cursor can reach, every
    substep the run out of those rows
 4. decisions: egress routing for finished flows (default_decision_maker.py:
    27-31) and weighted-round-robin next-node selection against the
    scheduling table with per-(node,SFC,SF) realized-ratio counters
    (default_decision_maker.py:42-66); same-substep collisions in one cell
    are serialized over ``wrr_rank_levels`` rounds
 5. forwarding: upfront whole-path TTL check (default_forwarder.py:35-39),
    then hop-by-hop traversal with per-edge capacity admission
    (default_forwarder.py:95-111); same-substep contention on an edge is
    resolved greedily in slot order via iterative prefix-sum refinement
 6. processing: SF-placement check (default_processor.py:30-50), processing
    delay sampling |N(mean, stdev)| with TTL check (base_processor.py:37-49),
    node capacity admission through per-SF resource functions
    (base_processor.py:24-35, 51-101), startup-delay wait, delayed load
    release after the flow duration
 7. departures and drops with the reference's 4-reason classification
    (metrics.py:144-164; a drop with TTL<=0 is always recorded as TTL)

Known, documented divergences from the event-driven reference:
- time is quantized to ``dt`` (default 1 ms — exact for the default integer-
  delay configs); sampled delays are credited to metrics exactly, only state
  transitions snap to substep boundaries
- same-instant orderings inside one substep follow flow-slot order instead of
  SimPy's FIFO queue order
- same-substep capacity contention uses ``admission_iters`` refinement
  rounds, which equals greedy slot-order admission except in pathological
  cascades
- a flow whose TTL expires during a VNF startup wait releases its node load
  (the reference leaks it, base_processor.py:86-97)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config.registry import get_resource_function
from ..config.schema import EnvLimits, ServiceConfig, SimConfig
from ..topology.compiler import Topology
from .state import (
    ARRIVAL_RUN,
    DROP_DECISION,
    DROP_LINK_CAP,
    DROP_NODE_CAP,
    DROP_TTL,
    PH_DECIDE,
    PH_FREE,
    PH_HOP,
    PH_PROC,
    FlowTable,
    SimMetrics,
    SimState,
    TrafficSchedule,
    init_state,
)

_EPS = 1e-4
# arrivals admitted per substep; later arrivals spill to the next substep
# (with default dt=1ms this is never binding outside extreme overload)
_ARRIVALS_PER_SUBSTEP = ARRIVAL_RUN


@dataclass(frozen=True)
class ServiceTables:
    """Static per-service tensors derived from ServiceConfig."""

    chain_sf: np.ndarray      # [C, S_pos] i32 SF id per chain position (-1 pad)
    chain_len: np.ndarray     # [C] i32
    proc_mean: np.ndarray     # [P] f32, P = size of the SF catalog
    proc_std: np.ndarray      # [P] f32
    startup_delay: np.ndarray  # [P] f32
    resource_fns: Tuple[Callable, ...]  # per SF id

    @classmethod
    def build(cls, service: ServiceConfig, limits: EnvLimits) -> "ServiceTables":
        sf_names = list(service.sf_names)
        s = limits.max_sfs
        c = limits.num_sfcs
        pool = limits.sf_pool
        if len(sf_names) > pool:
            raise ValueError(
                f"SF catalog has {len(sf_names)} SFs but limits.sf_pool is "
                f"{pool}; set EnvLimits.num_sfs (EnvLimits.for_service does)")
        chain_sf = np.full((c, s), -1, np.int32)
        chain_len = np.zeros(c, np.int32)
        for ci, name in enumerate(service.sfc_names):
            chain = service.sfc_list[name]
            chain_len[ci] = len(chain)
            for si, sf in enumerate(chain):
                chain_sf[ci, si] = sf_names.index(sf)
        proc_mean = np.zeros(pool, np.float32)
        proc_std = np.zeros(pool, np.float32)
        startup = np.zeros(pool, np.float32)
        fns = []
        for i, name in enumerate(sf_names[:pool]):
            sf = service.sf_list[name]
            proc_mean[i] = sf.processing_delay_mean
            proc_std[i] = sf.processing_delay_stdev
            startup[i] = sf.startup_delay
            fns.append(get_resource_function(sf.resource_function_id))
        while len(fns) < pool:
            fns.append(get_resource_function("default"))
        return cls(chain_sf=chain_sf, chain_len=chain_len, proc_mean=proc_mean,
                   proc_std=proc_std, startup_delay=startup,
                   resource_fns=tuple(fns))


_HI = jax.lax.Precision.HIGHEST


def _onehot(idx: jnp.ndarray, n: int, dtype=jnp.float32) -> jnp.ndarray:
    """[M] i32 -> [M, n] one-hot rows; out-of-range indices give all-zero
    rows (the ``mode="drop"`` analogue).

    TPU rationale: vmapped gathers/scatters lower to per-index serial
    updates (~2 ns/element, linear in B*M — measured to dominate the
    substep at B>=256), while one-hot contractions run on the MXU/VPU.
    With ``Precision.HIGHEST`` a one-hot dot is EXACT: each output is a
    single 1.0*x product (bf16x3 splits a f32 mantissa exactly; all other
    terms are 0), so gather/scatter semantics are reproduced bit-for-bit
    up to f32 summation order in the scatter-add cases."""
    return (idx[:, None] == jnp.arange(n, dtype=idx.dtype)[None, :]
            ).astype(dtype)


def _take(table: jnp.ndarray, oh: jnp.ndarray) -> jnp.ndarray:
    """rows ``table[idx]`` via a precomputed one-hot [M, n] @ [n, ...]."""
    t = table.astype(jnp.float32)
    flat = t.reshape(t.shape[0], -1)
    out = jnp.dot(oh, flat, precision=_HI).reshape((oh.shape[0],) + t.shape[1:])
    if table.dtype == jnp.bool_:
        return out > 0.5
    if jnp.issubdtype(table.dtype, jnp.integer):
        return jnp.round(out).astype(table.dtype)
    return out


def _pick(rows: jnp.ndarray, oh_col: jnp.ndarray) -> jnp.ndarray:
    """rows[m, idx[m]] for per-row column indices as a masked VPU reduce:
    [M, n] rows x [M, n] one-hot -> [M]."""
    out = (rows.astype(jnp.float32) * oh_col).sum(-1)
    if rows.dtype == jnp.bool_:
        return out > 0.5
    if jnp.issubdtype(rows.dtype, jnp.integer):
        return jnp.round(out).astype(rows.dtype)
    return out


def _group_order(cell_id: jnp.ndarray) -> jnp.ndarray:
    """Permutation sorting flows by (cell, slot) — groups each cell's flows
    contiguously in slot order.  Keys are made unique with the slot index,
    so no stability assumption is needed.  Division of labor on TPU: the
    SORT does the grouping (vectorized bitonic network), while all data
    movement along the resulting permutation runs as [M, M] one-hot dots
    (see ``_onehot``) — deliberately O(M^2) MXU work per substep, which
    beats the serial per-index gathers/scatters it replaces by ~8x on the
    measured chip."""
    m = cell_id.shape[0]
    return jnp.argsort(cell_id * m + jnp.arange(m))


def _run_starts(sorted_cell: jnp.ndarray) -> jnp.ndarray:
    """For each sorted position, the first position of its cell's run."""
    idx = jnp.arange(sorted_cell.shape[0])
    new = jnp.concatenate([jnp.ones((1,), bool),
                           sorted_cell[1:] != sorted_cell[:-1]])
    return jax.lax.cummax(jnp.where(new, idx, 0))


def _rank_in_cell(cell_id: jnp.ndarray, mask: jnp.ndarray,
                  num_cells: int) -> jnp.ndarray:
    """rank[m] = #(flows m'<m with mask and same cell).  [M] i32.
    Only meaningful under ``mask`` (masked-out flows rank in a sentinel
    cell).  Permutation gathers/scatters run as one-hot dots (see
    ``_onehot``)."""
    m = cell_id.shape[0]
    key = jnp.where(mask, cell_id, num_cells)
    order = _group_order(key)
    perm = _onehot(order, m)
    key_sorted = jnp.round(jnp.dot(perm, key.astype(jnp.float32),
                                   precision=_HI)).astype(key.dtype)
    starts = _run_starts(key_sorted)
    rank_sorted = (jnp.arange(m) - starts).astype(jnp.float32)
    return jnp.round(jnp.dot(rank_sorted, perm, precision=_HI)
                     ).astype(jnp.int32)


class SimEngine:
    """Factory-built engine closing over static config.

    ``init(rng, topo)`` -> SimState (the analogue of SimulatorInterface.init,
    spinterface.py:199-218, without running any events — matching the
    reference's init which only executes the t=0 bookkeeping event,
    duration_controller.py:20-33).

    ``apply(state, topo, traffic, schedule, placement)`` -> (state', metrics)
    runs one control interval (SimulatorInterface.apply / DurationController.
    get_next_state, duration_controller.py:35-77).
    """

    def __init__(self, service: ServiceConfig, cfg: SimConfig, limits: EnvLimits):
        self.service = service
        self.cfg = cfg
        self.limits = limits
        self.tables = ServiceTables.build(service, limits)
        self.substeps = cfg.substeps_per_run
        self.dt = cfg.dt
        self.M = cfg.max_flows
        self.H = cfg.release_horizon
        self.N = limits.max_nodes
        self.C = limits.num_sfcs
        self.S = limits.max_sfs     # chain-position axis (schedule tensor)
        self.P = limits.sf_pool     # SF-id axis (placement/load/proc tables)
        self.E = limits.max_edges
        max_hold = (self.H - 1) * self.dt
        if cfg.run_duration > max_hold:
            raise ValueError("release_horizon must cover at least one run_duration")
        # static deterministic-processing-delay flag (read in stage 6)
        self._det_proc = float(np.max(self.tables.proc_std)) == 0.0

    # ------------------------------------------------------------------ init
    def init(self, rng, topo: Topology) -> SimState:
        del topo  # shapes are static; topology enters at apply()
        return init_state(rng, self.M, self.N, self.C, self.S, self.E,
                          self.H, p=self.P)

    # ------------------------------------------------------- demanded capacity
    def _demanded(self, load_plus: jnp.ndarray, avail: jnp.ndarray) -> jnp.ndarray:
        """Total demanded node capacity given per-SF loads [..., P] summed over
        available SFs through per-SF resource functions
        (base_processor.py:24-35)."""
        cols = []
        for s, fn in enumerate(self.tables.resource_fns):
            cols.append(jnp.where(avail[..., s], fn(load_plus[..., s]), 0.0))
        return jnp.stack(cols, axis=-1).sum(axis=-1)

    # ------------------------------------------------------------- one interval
    @partial(jax.jit, static_argnums=0)
    def apply(self, state: SimState, topo: Topology, traffic: TrafficSchedule,
              schedule: jnp.ndarray, placement: jnp.ndarray
              ) -> Tuple[SimState, SimMetrics]:
        # --- apply the action (duration_controller.py:44-64) ---
        available = placement | (state.node_load > _EPS)
        newly = available & ~state.sf_available
        state = state.replace(
            placed=placement,
            schedule=schedule,
            sf_available=available,
            sf_startup=jnp.where(newly, state.t, state.sf_startup),
            # fresh instances start their idle clock now ('last_active':
            # env.now at creation, duration_controller.py:55-59)
            sf_last_active=jnp.where(newly, state.t, state.sf_last_active),
            # run metrics reset at interval start (writer.py:222-225)
            metrics=state.metrics.reset_run(),
        )
        t_steps = traffic.node_cap.shape[0]
        idx_now = jnp.clip(state.run_idx, 0, t_steps - 1)
        cap_now = traffic.node_cap[idx_now]
        # link-fault scenarios (topology.scenarios): when the schedule
        # carries a per-interval edge-capacity table, this interval's row
        # REPLACES the static edge caps for every substep below — the
        # structural check is trace-time (None = the historic program,
        # byte for byte), the row select is device work
        if traffic.edge_cap_t is not None:
            topo = topo.replace(edge_cap=traffic.edge_cap_t[idx_now])

        # the table rows this interval's arrivals can come from, masked out
        # of the episode-long table once, here, not per substep: a cursor
        # moves at most _ARRIVALS_PER_SUBSTEP records a substep
        with jax.named_scope("traffic_arrivals"):
            arrivals = traffic.window(
                state.cursor, (self.substeps - 1) * _ARRIVALS_PER_SUBSTEP + 1)

        def sub(st, _):
            return self._substep(st, topo, arrivals, cap_now), None

        # unroll trades compile time for per-iteration scan overhead — the
        # substep is a chain of small fusions, so on TPU the loop machinery
        # is a visible fraction of the wall (cfg.scan_unroll, default 1)
        state, _ = jax.lax.scan(sub, state, None, length=self.substeps,
                                unroll=self.cfg.scan_unroll)
        state = state.replace(run_idx=state.run_idx + 1)
        return state, state.metrics

    # ------------------------------------------------------ per-flow control
    @partial(jax.jit, static_argnums=0)
    def apply_substep(self, state: SimState, topo: Topology,
                      traffic: TrafficSchedule,
                      ext_decisions: jnp.ndarray) -> SimState:
        """One substep under *per-flow* control (the reference's
        FlowController / ExternalDecisionMaker granularity,
        coordsim/controller/flow_controller.py:21-92).

        ``ext_decisions`` [M] i32: destination node for each flow slot, or -1
        to leave the flow waiting.  Flows at a decision point without a
        decision stay parked in the DECIDE phase (the analogue of blocking on
        ``flow_trigger``, external_decision_maker.py:45-53); the chosen SF is
        placed on the decided node if absent (place-on-decision,
        flow_controller.py:46-60).  ``run_idx`` tracks wall sim-time so
        trace-driven caps/activity stay aligned; run metrics reset at the
        *start* of each new interval (writer.py:222-225), so after an
        interval's final substep its run counters remain readable."""
        # integer substep counter (round() absorbs float32 drift in t)
        g = jnp.round(state.t / self.dt).astype(jnp.int32)
        new_idx = g // self.substeps
        starts_interval = (g % self.substeps == 0) & (g > 0)
        metrics = jax.tree_util.tree_map(
            lambda a, b: jnp.where(starts_interval, a, b),
            state.metrics.reset_run(), state.metrics)
        state = state.replace(run_idx=jnp.maximum(new_idx, state.run_idx),
                              metrics=metrics)
        t_steps = traffic.node_cap.shape[0]
        idx = jnp.clip(state.run_idx, 0, t_steps - 1)
        cap_now = traffic.node_cap[idx]
        if traffic.edge_cap_t is not None:
            # same link-fault row select as apply() — per-flow control
            # sees the identical capacity timeline
            topo = topo.replace(edge_cap=traffic.edge_cap_t[idx])
        return self._substep(state, topo, traffic.window(state.cursor, 1),
                             cap_now, ext_decisions=ext_decisions)

    def apply_per_flow(self, state: SimState, topo: Topology,
                       traffic: TrafficSchedule, decide_fn
                       ) -> Tuple[SimState, SimMetrics]:
        """One control interval with a *jitted* per-flow policy:
        ``decide_fn(state) -> [M] i32`` (-1 = no decision) is invoked every
        substep — the TPU-native form of the per-flow control loop, keeping
        the whole interval on device."""
        def sub(st, _):
            return self.apply_substep(st, topo, traffic, decide_fn(st)), None

        state, _ = jax.lax.scan(sub, state, None, length=self.substeps)
        return state, state.metrics

    # ---------------------------------------------------------------- substep
    @jax.named_scope("sim_substep")
    def _substep(self, state: SimState, topo: Topology, arrivals,
                 cap_now: jnp.ndarray,
                 ext_decisions: jnp.ndarray | None = None) -> SimState:
        """One fixed substep of every flow slot: the hand-fused one-hot
        pipeline of the module docstring, stages 1-7.  ``arrivals`` is a
        ``traffic.window`` that holds this substep's run of candidate
        records (stage 3); ``ext_decisions`` (per-flow control) replaces
        stage 4's weighted-round-robin choice."""
        F = state.flows
        m = state.metrics
        dt = self.dt
        t = state.t
        g = jnp.round(t / dt).astype(jnp.int32)       # global substep index
        ridx = jnp.mod(g, self.H)                      # ring-buffer index
        rng, k_proc = jax.random.split(state.rng)

        # --- 1. capacity releases ------------------------------------------
        # Row ``ridx`` of each ring is read and cleared through a mask over
        # the whole [H, K] array, not by index: under vmap ``ridx`` is a
        # per-replica vector, and a row-indexed read (gather) and clear
        # (scatter) make the TPU compiler keep a second, row-contiguous
        # layout of the ring beside the time-minor one the contractions of
        # stages 5 and 6 write — two whole-ring layout copies per ring per
        # substep.  Masked, every ring operation is elementwise or a
        # contraction, one layout serves the whole loop and the clear
        # fuses into the contraction.  Same bits: the masked sum has one
        # non-zero term, and release offsets are clipped to [1, H - 1], so
        # nothing is ever booked on row ``ridx`` itself.
        at_r = (jnp.arange(self.H) == ridx)[:, None]               # [H, 1]
        due_node = jnp.where(at_r, state.rel_node, 0.0).sum(0)     # [N*P]
        due_edge = jnp.where(at_r, state.rel_edge, 0.0).sum(0)     # [E]
        node_load = jnp.maximum(
            state.node_load - due_node.reshape(self.N, self.P), 0.0)
        edge_used = jnp.maximum(state.edge_used - due_edge, 0.0)
        rel_node = jnp.where(at_r, 0.0, state.rel_node)
        rel_edge = jnp.where(at_r, 0.0, state.rel_edge)
        # graceful SF removal once drained and unplaced (base_processor.py:115-118)
        sf_available = state.sf_available & (state.placed | (node_load > _EPS))

        # --- 2. timers ------------------------------------------------------
        running = (F.phase == PH_HOP) | (F.phase == PH_PROC)
        timer = jnp.where(running, F.timer - dt, F.timer)
        proc_done = (F.phase == PH_PROC) & (timer <= _EPS)
        hop_done = (F.phase == PH_HOP) & (timer <= _EPS)

        # PROC completion: advance chain position, re-decide this substep
        # (position increments when processing delay elapses,
        # base_processor.py:103-107 at spawn time)
        position = F.position + proc_done.astype(jnp.int32)
        phase = jnp.where(proc_done, PH_DECIDE, F.phase)

        # HOP completion: move to hop endpoint
        node = jnp.where(hop_done, F.hop_next, F.node)
        arrived = hop_done & (node == F.dest)
        cont = hop_done & ~arrived                     # continue multi-hop path
        # credit whole-path delay on arrival (default_forwarder.py:83-86)
        e2e = F.e2e + jnp.where(arrived, F.pend_path, 0.0)
        ttl = F.ttl - jnp.where(arrived, F.pend_path, 0.0)
        n_arr = arrived.sum()
        path_add = jnp.where(arrived, F.pend_path, 0.0).sum()
        m = m.replace(
            sum_path_delay=m.sum_path_delay + path_add,
            num_path_delay=m.num_path_delay + n_arr,
            run_path_delay_sum=m.run_path_delay_sum + path_add,
        )
        # un-clipped one-hot: an out-of-range SFC id gives an all-zero row
        # (chain_len = 0), so a corrupt-sfc flow heads to egress instead of
        # being silently attributed to chain C-1; stage 4 reads chain_len
        # the same way so the two lookups agree on the flow's chain
        chain_len = _take(jnp.asarray(self.tables.chain_len),
                          _onehot(F.sfc, self.C))
        to_eg_flag = position >= chain_len             # forward_to_eg
        depart_hop = arrived & to_eg_flag              # reached egress: success
        need_proc_a = arrived & ~to_eg_flag

        # --- 3. arrivals ----------------------------------------------------
        with jax.named_scope("traffic_arrivals"):
            # The candidates are the contiguous run cursor … cursor + 7 of
            # the time-sorted table, fetched ONCE for all seven fields and
            # through masks, not indices (``TrafficSchedule.read_run``);
            # every later use in this stage reads these [8] arrays.  Under
            # vmap ``cursor`` is a per-replica vector: a field-by-field,
            # record-by-record read compiles on the TPU to one serial
            # gather of 8 x B scalars per field, and the table is far too
            # long to mask every substep — so the caller hands in the few
            # rows of it the cursor can reach (``arrivals``).
            (a_time, a_dr, a_duration, a_ttl, a_ingress, a_sfc,
             a_egress) = TrafficSchedule.read_run(*arrivals, state.cursor)
            # (records behind the table's capacity are padding, time inf)
            due = (a_time < t + dt - _EPS) & jnp.isfinite(a_time)
            free = phase == PH_FREE
            free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
            n_free = free.sum()
            arr_rank = jnp.cumsum(due.astype(jnp.int32)) - 1
            spawn = due & (arr_rank < n_free)
            # land[a, m]: record a lands in slot m, the arr_rank[a]-th free
            # one (a record whose rank has no free slot does not spawn).
            # Rows are disjoint with at most one true slot each and a free
            # slot holds no live flow, so the write is a select per field and
            # record in the [M] layout every later stage reads.  A scatter
            # at the per-replica slot indices would be serial under vmap and
            # keep layout copies of the slot fields, packed for it, around
            # it on the TPU — every substep, a record due or none.
            land = (spawn[:, None] & free[None, :]
                    & (free_rank[None, :] == arr_rank[:, None]))
            hit = land.any(0)

            def landed(cur, new):
                for a in range(_ARRIVALS_PER_SUBSTEP):
                    cur = jnp.where(land[a], new[a], cur)
                return cur

            phase = jnp.where(hit, PH_DECIDE, phase)
            node = landed(node, a_ingress)
            position = jnp.where(hit, 0, position)
            sfc = landed(F.sfc, a_sfc)
            egress = landed(F.egress, a_egress)
            dest = jnp.where(hit, -1, F.dest)
            dr = landed(F.dr, a_dr)
            duration = landed(F.duration, a_duration)
            ttl = landed(ttl, a_ttl)
            e2e = jnp.where(hit, 0.0, e2e)
            pend_path = jnp.where(hit, 0.0, F.pend_path)
            hop_next = F.hop_next
            n_spawn = spawn.sum()
            cursor = state.cursor + n_spawn
            # arrivals spawning after their scheduled substep were delayed
            # by slot exhaustion / the per-substep arrival budget — count
            # each once
            late = spawn & (a_time < t - _EPS)
            truncated = state.truncated_arrivals + late.sum()
            # per ingress node, a left fold in record order: the float sum
            # a scatter-add makes, with its order fixed
            req_node = m.run_requested_node
            for a in range(_ARRIVALS_PER_SUBSTEP):
                req_node = req_node + jnp.where(
                    spawn[a] & (jnp.arange(self.N) == a_ingress[a]),
                    a_dr[a], 0.0)
            m = m.replace(
                generated=m.generated + n_spawn,
                run_generated=m.run_generated + n_spawn,
                active=m.active + n_spawn,
                run_requested_node=req_node,
            )

        # recompute flags after arrivals.  The UN-clipped one-hot zero-rows
        # out-of-range SFC ids (reachable only with corrupt traffic data):
        # chain_len reads 0, so such a flow takes the to-egress path and
        # never reaches the WRR tables — a clamp would instead silently
        # attribute it to chain C-1 in run_requested / flow_counts.
        sfc_c = jnp.clip(sfc, 0, self.C - 1)
        oh_sfc = _onehot(sfc, self.C)
        chain_len = _take(jnp.asarray(self.tables.chain_len), oh_sfc)
        to_eg_flag = position >= chain_len

        # --- 4. decisions ---------------------------------------------------
        deciding = phase == PH_DECIDE
        # TTL exhausted at decision time -> drop (decide_next_node returns
        # None at ttl<=0, default_decision_maker.py:24-26; recorded as TTL,
        # metrics.py:158-160)
        drop_ttl0 = deciding & (ttl <= _EPS)
        decide = deciding & ~drop_ttl0
        to_eg = decide & to_eg_flag
        # flows with no egress depart at their current node
        # (default_decision_maker.py:28-31)
        egress = jnp.where(to_eg & (egress < 0), node, egress)
        wrr = decide & ~to_eg_flag

        sf_pos = jnp.clip(position, 0, self.S - 1)
        oh_cs = _onehot(sfc_c * self.S + sf_pos, self.C * self.S)
        sf_now = _take(jnp.asarray(self.tables.chain_sf).reshape(-1), oh_cs)
        sf_now = jnp.clip(sf_now, 0)
        oh_node = _onehot(node, self.N)                # [M, N]
        oh_sf = _onehot(sf_now, self.P)                # [M, P]
        # (node, sfc, sf_pos) cell one-hot, shared by the WRR table reads,
        # the counter updates, and the requested-traffic metric
        cell = (node * self.C + sfc_c) * self.S + sf_pos
        ncs = self.N * self.C * self.S
        oh_cell = _onehot(cell, ncs)                   # [M, NCS]
        placed = state.placed
        sf_startup = state.sf_startup
        sf_last_active = state.sf_last_active
        if ext_decisions is None:
            # requested-traffic metric for every WRR decision, before the
            # schedule lookup (add_requesting_flow,
            # default_decision_maker.py:35-36)
            req_add = jnp.dot(jnp.where(wrr, dr, 0.0), oh_cell,
                              precision=_HI).reshape(m.run_requested.shape)
            m = m.replace(run_requested=m.run_requested + req_add)

            # WRR over the schedule row with realized-ratio counters
            # (default_decision_maker.py:42-66); same-cell same-substep
            # collisions run in slot-order rounds so later flows see updated
            # counters
            rank = _rank_in_cell(cell, wrr, ncs)
            flow_counts = m.run_flow_counts
            # schedule rows are loop-invariant (indexed by chain POSITION;
            # its SF axis mirrors the action layout, environment_limits.py:
            # 44-51)
            probs = _take(state.schedule.reshape(ncs, self.N), oh_cell)
            R = self.cfg.wrr_rank_levels
            for r in range(R):
                sel = wrr & ((rank == r) if r < R - 1 else (rank >= r))
                counts = _take(flow_counts.reshape(ncs, self.N), oh_cell)
                total = counts.sum(-1, keepdims=True)
                ratios = jnp.where(total > 0, counts / jnp.maximum(total, 1), 0.0)
                diffs = jnp.where(probs > 0, probs - ratios, -1.0)
                choice = jnp.argmax(diffs, axis=-1).astype(jnp.int32)
                dest = jnp.where(sel, choice, dest)
                cnt_add = jnp.einsum(
                    "mc,mn->cn", oh_cell * sel[:, None].astype(jnp.float32),
                    _onehot(choice, self.N), precision=_HI)
                flow_counts = flow_counts + jnp.round(cnt_add).astype(
                    flow_counts.dtype).reshape(flow_counts.shape)
            m = m.replace(run_flow_counts=flow_counts)
        else:
            # per-flow external control: only flows with a provided decision
            # proceed; the rest stay parked in DECIDE (flow_trigger blocking,
            # external_decision_maker.py:45-53)
            has_dec = ext_decisions >= 0
            wrr = wrr & has_dec
            dest = jnp.where(wrr, jnp.clip(ext_decisions, 0, self.N - 1), dest)
            req_add = jnp.dot(jnp.where(wrr, dr, 0.0), oh_cell,
                              precision=_HI).reshape(m.run_requested.shape)
            m = m.replace(run_requested=m.run_requested + req_add)
            # place-on-decision (flow_controller.py:46-60): install the SF at
            # the decided node if absent, stamping its startup time
            newly_placed = jnp.einsum(
                "mn,mp->np", _onehot(dest, self.N) * wrr[:, None].astype(
                    jnp.float32), oh_sf, precision=_HI) > 0.5
            newly_placed = newly_placed & ~placed
            placed = placed | newly_placed
            fresh = newly_placed & ~sf_available
            sf_startup = jnp.where(fresh, t, sf_startup)
            sf_last_active = jnp.where(newly_placed, t, sf_last_active)
            sf_available = sf_available | newly_placed
        dest = jnp.where(to_eg, egress, dest)

        # --- 5. forwarding --------------------------------------------------
        fwd = (to_eg | wrr) if ext_decisions is not None else decide
        stay = fwd & (dest == node)
        depart_stay = to_eg & stay                    # at egress already
        need_proc_b = wrr & stay
        start_path = fwd & ~stay
        # All node-indexed table rows come out of ONE wide one-hot dot:
        # [path_delay | next_hop | adj_edge_id | cap_now] is loop-invariant
        # (XLA hoists the concat out of the substep scan), so 4 gather-dots
        # collapse into a single [M,N]@[N,3N+1] contraction.  inf path
        # delays (unreachable) become a big finite value so the 0*inf=NaN
        # dot hazard never arises — every use compares against TTL
        # (<= 1e4), for which 1e30 and inf behave identically.
        oh_dest = _onehot(jnp.clip(dest, 0), self.N)
        pd_tab = jnp.where(jnp.isfinite(topo.path_delay), topo.path_delay,
                           1e30)
        static_tab = jnp.concatenate(
            [pd_tab, topo.next_hop.astype(jnp.float32),
             topo.adj_edge_id.astype(jnp.float32), cap_now[:, None]],
            axis=1)                                    # [N, 3N+1]
        rows = jnp.dot(oh_node, static_tab, precision=_HI)  # [M, 3N+1]
        pd_rows = rows[:, :self.N]
        nh_rows = rows[:, self.N:2 * self.N]
        adj_rows = rows[:, 2 * self.N:3 * self.N]
        cap_mine = rows[:, 3 * self.N]
        pd_path = (pd_rows * oh_dest).sum(-1)
        # upfront whole-path TTL check (default_forwarder.py:35-39);
        # unreachable destinations have inf path delay and also drop here
        drop_ttl_path = start_path & (ttl - pd_path <= _EPS)
        ttl = jnp.where(drop_ttl_path, 0.0, ttl)
        start_path = start_path & ~drop_ttl_path

        # hop starts this substep: fresh paths + mid-path continuations
        hop_req = cont | start_path
        nh = jnp.round((nh_rows * oh_dest).sum(-1)).astype(jnp.int32)
        nh = jnp.clip(nh, 0)
        eid = jnp.round((adj_rows * _onehot(nh, self.N)).sum(-1)
                        ).astype(jnp.int32)
        eid_c = jnp.clip(eid, 0)
        oh_e = _onehot(eid_c, self.E)                  # [M, E]
        edge_rows = _take(jnp.stack(
            [topo.edge_cap - edge_used + _EPS, topo.edge_delay],
            axis=-1), oh_e)                            # [M, 2]
        headroom = edge_rows[:, 0]

        # Hoisted stage-6 pre-sort work: the node-admission pipeline's sort
        # inputs (want/dr/cap_mine) do not depend on LINK admission, so
        # both grouping pipelines batch into ONE vmapped argsort + ONE
        # [2,M,M]x[2,M,4] permutation contraction + ONE run-starts pass —
        # halving the per-substep op count of the sort machinery (op count,
        # not bytes, bounds the substep on the measured chip).
        need_proc = need_proc_a | need_proc_b
        # [placed | sf_startup] rows in one dot (loop-variant in per-flow
        # control mode, so kept separate from the static table above)
        ps_rows = jnp.dot(oh_node, jnp.concatenate(
            [placed.astype(jnp.float32), sf_startup], axis=1),
            precision=_HI)                             # [M, 2P]
        sf_ok = (ps_rows[:, :self.P] * oh_sf).sum(-1) > 0.5
        # SF not in placement -> drop (default_processor.py:48-50 ->
        # NODE_CAP, flowsimulator.py:114-118)
        drop_unplaced = need_proc & ~sf_ok
        want = need_proc & sf_ok
        proc_tab = _take(jnp.stack(
            [jnp.asarray(self.tables.proc_mean),
             jnp.asarray(self.tables.proc_std),
             jnp.asarray(self.tables.startup_delay)], axis=-1), oh_sf)
        pmean = proc_tab[:, 0]
        pstd = proc_tab[:, 1]
        if self._det_proc:
            # fully deterministic processing delays (the flagship abc.yaml
            # case): |N(mean, 0)| == mean, so skip the per-substep threefry
            # draw entirely — measured ~10% of substep wall (r3 profile).
            # The k_proc split above still happens, so the rng STREAM of
            # every other consumer is unchanged (bit-exact goldens).
            pdel = jnp.abs(pmean)   # |N(mean, 0)| — abs matters if a
            # config carries a negative delay mean (nothing rejects one)
        else:
            pdel = jnp.abs(jax.random.normal(k_proc, (self.M,)) * pstd
                           + pmean)
        # TTL check before the delay is credited (base_processor.py:37-44);
        # want-flows are disjoint from every stage-5 ttl write, so the
        # check reads the same values it did when it lived in stage 6
        drop_ttl_pd = want & (ttl - pdel <= _EPS)
        want = want & ~drop_ttl_pd

        # batched slot-order grouping for link (b=0) and node (b=1)
        # admission (deduct_link_resources, default_forwarder.py:95-111;
        # request_resources, base_processor.py:51-101).  Groupings are
        # fixed across refinement iterations (only ``admitted`` changes):
        # sort once, redo only the masked cumsum per iteration; all
        # permutation gathers/scatters are one-hot dots.
        keys2 = jnp.stack([eid_c, node])               # [2, M]
        orders2 = jax.vmap(_group_order)(keys2)
        perms2 = jax.vmap(lambda o: _onehot(o, self.M))(orders2)
        sort_ins = jnp.stack([
            jnp.stack([eid_c.astype(jnp.float32),
                       (hop_req & (eid >= 0)).astype(jnp.float32),
                       dr, headroom], axis=-1),
            jnp.stack([node.astype(jnp.float32), want.astype(jnp.float32),
                       dr, cap_mine], axis=-1)])       # [2, M, 4]
        sorted2 = jnp.einsum("bmn,bnk->bmk", perms2, sort_ins,
                             precision=_HI)
        keys_sorted = jnp.round(sorted2[:, :, 0]).astype(jnp.int32)
        starts2 = jax.vmap(_run_starts)(keys_sorted)
        oh_starts2 = jax.vmap(lambda s: _onehot(s, self.M))(starts2)

        perm_e = perms2[0]
        eid_s = keys_sorted[0]
        req_s = sorted2[0, :, 1] > 0.5
        dr_s = sorted2[0, :, 2]
        headroom_s = sorted2[0, :, 3]
        oh_starts_e = oh_starts2[0]
        adm_s = req_s
        for _ in range(self.cfg.admission_iters):
            v = jnp.where(adm_s, dr_s, 0.0)
            cs = jnp.cumsum(v)
            bound = jnp.dot(oh_starts_e, jnp.stack([cs, v], axis=-1),
                            precision=_HI)
            adm_s = req_s & (cs - (bound[:, 0] - bound[:, 1]) <= headroom_s)
        admitted = jnp.dot(adm_s.astype(jnp.float32), perm_e,
                           precision=_HI) > 0.5
        drop_link = hop_req & ~admitted
        add_e = jnp.where(admitted, dr, 0.0)
        edge_add = jnp.dot(add_e, oh_e, precision=_HI)  # [E]
        edge_used = edge_used + edge_add
        m = m.replace(run_passed_traffic=m.run_passed_traffic + edge_add)
        hop_delay = edge_rows[:, 1]
        # release link capacity hop_delay + duration after the hop starts
        # (default_forwarder.py:112-125)
        off_e = jnp.clip(jnp.ceil((hop_delay + duration) / dt).astype(jnp.int32),
                         1, self.H - 1)
        oh_off_e = _onehot(jnp.where(admitted, jnp.mod(ridx + off_e, self.H),
                                     self.H), self.H)  # [M, H]
        rel_edge = rel_edge + jnp.einsum(
            "mh,me->he", oh_off_e, oh_e * add_e[:, None], precision=_HI)
        pend_path = jnp.where(start_path & admitted, pd_path, pend_path)
        hop_next = jnp.where(admitted, nh, hop_next)
        timer = jnp.where(admitted, hop_delay, timer)
        phase = jnp.where(admitted, PH_HOP, phase)

        # --- 6. processing --------------------------------------------------
        # (need_proc/sf_ok/want/pdel and the node grouping were computed
        # with the batched sort machinery above, before link admission)
        ttl = jnp.where(drop_ttl_pd, 0.0, ttl)
        e2e = e2e + jnp.where(want, pdel, 0.0)
        ttl = ttl - jnp.where(want, pdel, 0.0)
        n_want = want.sum()
        m = m.replace(
            sum_proc_delay=m.sum_proc_delay + jnp.where(want, pdel, 0.0).sum(),
            num_proc_delay=m.num_proc_delay + n_want,
        )
        # node capacity admission via resource functions, greedy slot order
        # (request_resources, base_processor.py:51-101).  Every candidate
        # sees the base load plus the same-substep admitted drs of flows
        # m'<=m at its node, per SF column: one (node, slot) grouping reused
        # across refinement iters, with a single [M,P] cumsum per iter — no
        # [M, N*S] materialization, no per-SF Python loop.
        perm_n = perms2[1]
        node_sorted = keys_sorted[1]
        want_s = sorted2[1, :, 1] > 0.5
        dr_col_s = sorted2[1, :, 2][:, None]
        cap_s = sorted2[1, :, 3]
        oh_starts_n = oh_starts2[1]
        oh_ns = _onehot(node_sorted, self.N)
        la_rows = jnp.dot(oh_ns, jnp.concatenate(
            [node_load, sf_available.astype(jnp.float32)], axis=1),
            precision=_HI)                             # [M, 2P]
        base_load_s = la_rows[:, :self.P]
        avail_s = la_rows[:, self.P:] > 0.5
        sf_onehot_s = jnp.dot(perm_n, oh_sf, precision=_HI) > 0.5
        adm_ns = want_s
        dem_s = jnp.zeros(self.M, jnp.float32)
        for _ in range(self.cfg.admission_iters):
            v = jnp.where(adm_ns[:, None] & sf_onehot_s, dr_col_s, 0.0)
            cs = jnp.cumsum(v, axis=0)
            b_cs = jnp.dot(oh_starts_n, cs, precision=_HI)
            b_v = jnp.dot(oh_starts_n, v, precision=_HI)
            dem_s = self._demanded(base_load_s + cs - (b_cs - b_v), avail_s)
            adm_ns = want_s & (dem_s <= cap_s + _EPS)
        unsorted = jnp.dot(
            jnp.stack([adm_ns.astype(jnp.float32), dem_s], axis=-1).T,
            perm_n, precision=_HI)                             # [2, M]
        admitted_n = unsorted[0] > 0.5
        demanded = unsorted[1]
        drop_nodecap = want & ~admitted_n
        add_n = jnp.where(admitted_n, dr, 0.0)
        node_add = jnp.einsum("mn,mp->np", oh_node * add_n[:, None], oh_sf,
                              precision=_HI)                   # [N, P]
        node_load = node_load + node_add
        m = m.replace(
            run_processed_traffic=m.run_processed_traffic + node_add,
            run_max_node_usage=jnp.maximum(
                m.run_max_node_usage,
                (oh_node * jnp.where(admitted_n, demanded, 0.0)[:, None]
                 ).max(axis=0)),
        )
        # startup wait (base_processor.py:79-97); a TTL expiry here releases
        # the load immediately (divergence: the reference leaks it)
        sw = jnp.maximum(
            (ps_rows[:, self.P:] * oh_sf).sum(-1)
            + proc_tab[:, 2] - t, 0.0)
        drop_ttl_sw = admitted_n & (ttl - sw <= _EPS) & (sw > _EPS)
        ttl = jnp.where(drop_ttl_sw, 0.0, ttl)
        started = admitted_n & ~drop_ttl_sw
        e2e = e2e + jnp.where(started, sw, 0.0)
        ttl = ttl - jnp.where(started, sw, 0.0)
        busy = jnp.where(started, sw + pdel, 0.0)
        timer = jnp.where(started, busy, timer)
        phase = jnp.where(started, PH_PROC, phase)
        # release node load busy + duration after processing starts
        # (finish_processing waits flow.duration after the delay elapses,
        # base_processor.py:103-112); TTL-in-startup drops release now
        hold = jnp.where(started, busy + duration, dt)
        rel_who = started | drop_ttl_sw
        off_n = jnp.clip(jnp.ceil(hold / dt).astype(jnp.int32), 1, self.H - 1)
        oh_off_n = _onehot(jnp.where(rel_who, jnp.mod(ridx + off_n, self.H),
                                     self.H), self.H)          # [M, H]
        rel_vals = jnp.where(rel_who, dr, 0.0)
        np_flat = jnp.einsum("mn,mp->mnp", oh_node * rel_vals[:, None],
                             oh_sf, precision=_HI
                             ).reshape(self.M, self.N * self.P)
        rel_node = rel_node + jnp.einsum("mh,mk->hk", oh_off_n, np_flat,
                                         precision=_HI)

        # --- 7. departures & drops -----------------------------------------
        depart = depart_hop | depart_stay
        n_dep = depart.sum()
        dep_e2e = jnp.where(depart, e2e, 0.0)
        m = m.replace(
            processed=m.processed + n_dep,
            run_processed=m.run_processed + n_dep,
            sum_e2e=m.sum_e2e + dep_e2e.sum(),
            run_e2e_sum=m.run_e2e_sum + dep_e2e.sum(),
            run_e2e_max=jnp.maximum(m.run_e2e_max, dep_e2e.max()),
            active=m.active - n_dep,
        )
        drops = [
            (drop_ttl0, DROP_DECISION),
            (drop_ttl_path, DROP_LINK_CAP),
            (drop_link, DROP_LINK_CAP),
            (drop_unplaced, DROP_NODE_CAP),
            (drop_ttl_pd, DROP_NODE_CAP),
            (drop_nodecap, DROP_NODE_CAP),
            (drop_ttl_sw, DROP_NODE_CAP),
        ]
        any_drop = jnp.zeros(self.M, bool)
        n_reasons = m.drop_reasons.shape[0]
        adds = [jnp.zeros((), m.drop_reasons.dtype)] * n_reasons
        for mask, reason in drops:
            any_drop = any_drop | mask
            # ttl<=0 always recorded as TTL (metrics.py:158-160)
            is_ttl = mask & (ttl <= _EPS)
            adds[DROP_TTL] = adds[DROP_TTL] + is_ttl.sum()
            adds[reason] = adds[reason] + (mask & ~is_ttl).sum()
        reasons = m.drop_reasons + jnp.stack(adds)
        n_drop = any_drop.sum()
        m = m.replace(
            drop_reasons=reasons,
            dropped=m.dropped + n_drop,
            run_dropped=m.run_dropped + n_drop,
            active=m.active - n_drop,
            run_dropped_per_node=m.run_dropped_per_node + jnp.round(
                jnp.dot(any_drop.astype(jnp.float32), oh_node,
                        precision=_HI)).astype(m.run_dropped_per_node.dtype),
        )
        gone = depart | any_drop
        phase = jnp.where(gone, PH_FREE, phase)

        # idle-VNF bookkeeping: instances with load refresh last_active; in
        # per-flow control mode instances idle past vnf_timeout are removed
        # (update_vnf_active_status, flow_controller.py:94-112 — the
        # reference only garbage-collects under FlowController)
        active_sf = node_load > _EPS
        sf_last_active = jnp.where(active_sf, t, sf_last_active)
        if self.cfg.controller == "per_flow":
            expire = sf_available & ~active_sf & (
                sf_last_active < t - self.cfg.vnf_timeout)
            sf_available = sf_available & ~expire
            placed = placed & ~expire

        flows = FlowTable(phase=phase, sfc=sfc, position=position, node=node,
                          dest=dest, hop_next=hop_next, egress=egress, dr=dr,
                          duration=duration, ttl=ttl, e2e=e2e,
                          pend_path=pend_path, timer=timer)
        return state.replace(
            t=t + dt, flows=flows, cursor=cursor, node_load=node_load,
            sf_available=sf_available, edge_used=edge_used,
            placed=placed, sf_startup=sf_startup,
            sf_last_active=sf_last_active,
            rel_node=rel_node, rel_edge=rel_edge, metrics=m, rng=rng,
            truncated_arrivals=truncated,
        )
