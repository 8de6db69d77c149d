"""Simulator state pytrees.

The reference keeps per-flow state in Python ``Flow`` objects driven by SimPy
processes (coordsim/network/flow.py:10-48, coordsim/simulation/
flowsimulator.py:59-128) and network state as networkx node/edge attribute
dicts.  Here the whole simulation is a fixed-shape pytree so it can live in
TPU HBM, be advanced by ``lax.scan`` and batched with ``vmap``:

- ``FlowTable``: a preallocated table of MAX_FLOWS flow slots (struct of
  arrays), the functional replacement for dynamically spawned SimPy processes.
- ``SimMetrics``: the counters of coordsim/metrics/metrics.py:15-230 as flat
  arrays, with the same cumulative vs per-run split (run metrics reset each
  control interval, coordsim/writer/writer.py:222-225).
- ``SimState``: everything that changes during an episode — flow table, per
  (node, SF) load/availability/startup bookkeeping (the reference's
  ``available_sf`` node attribute, simulatorparams.py:66-73), per-edge in-
  flight data rate (``remaining_cap`` edge attribute,
  default_forwarder.py:100-125), capacity-release ring buffers (the
  functional analogue of the reference's delayed ``return_link_resources`` /
  ``finish_processing`` SimPy processes), the active scheduling/placement
  tensors and the RNG key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

# Flow phases (flow lifecycle, reference: flowsimulator.py:72-128).
PH_FREE = 0     # slot unused
PH_DECIDE = 1   # at a node, waiting for a next-node decision this substep
PH_HOP = 2      # traversing an edge (timer = remaining hop delay)
PH_PROC = 3     # processing at an SF (timer = startup wait + processing delay)

# Drop reasons (metrics.py:33-38).
DROP_TTL = 0
DROP_DECISION = 1
DROP_LINK_CAP = 2
DROP_NODE_CAP = 3
DROP_REASONS = ("TTL", "DECISION", "LINK_CAP", "NODE_CAP")


@struct.dataclass
class FlowTable:
    """Preallocated flow slots [M] (reference: Flow, flow.py:10-48)."""

    phase: jnp.ndarray      # [M] i32 PH_*
    sfc: jnp.ndarray        # [M] i32
    position: jnp.ndarray   # [M] i32 index into the SFC chain; == chain_len -> to egress
    node: jnp.ndarray       # [M] i32 current node
    dest: jnp.ndarray       # [M] i32 decided destination node (while forwarding)
    hop_next: jnp.ndarray   # [M] i32 node at the end of the in-flight hop
    egress: jnp.ndarray     # [M] i32 egress node id or -1
    dr: jnp.ndarray         # [M] f32 data rate
    duration: jnp.ndarray   # [M] f32 flow duration in ms (= size/dr*1000, flow.py:33)
    ttl: jnp.ndarray        # [M] f32 remaining TTL in ms
    e2e: jnp.ndarray        # [M] f32 accumulated end-to-end delay
    pend_path: jnp.ndarray  # [M] f32 path delay of the in-flight path, credited on arrival
                            #     (the reference adds the whole path delay once after the
                            #     final hop, default_forwarder.py:83-86)
    timer: jnp.ndarray      # [M] f32 remaining time in current phase

    @property
    def active(self) -> jnp.ndarray:
        return self.phase != PH_FREE

    @classmethod
    def empty(cls, max_flows: int) -> "FlowTable":
        zi = jnp.zeros(max_flows, jnp.int32)
        zf = jnp.zeros(max_flows, jnp.float32)
        return cls(phase=zi, sfc=zi, position=zi, node=zi, dest=zi, hop_next=zi,
                   egress=zi - 1, dr=zf, duration=zf, ttl=zf, e2e=zf,
                   pend_path=zf, timer=zf)


@struct.dataclass
class SimMetrics:
    """Counters (reference: metrics.py:22-95).  ``run_*`` fields reset at the
    start of every control interval (writer.py:222-225); the rest accumulate
    over the episode."""

    # cumulative
    generated: jnp.ndarray          # [] i32 (metrics.py:'generated_flows')
    processed: jnp.ndarray          # [] i32
    dropped: jnp.ndarray            # [] i32
    active: jnp.ndarray             # [] i32 ('total_active_flows')
    drop_reasons: jnp.ndarray       # [4] i32 (TTL, DECISION, LINK_CAP, NODE_CAP)
    sum_proc_delay: jnp.ndarray     # [] f32
    num_proc_delay: jnp.ndarray     # [] i32
    sum_path_delay: jnp.ndarray     # [] f32
    num_path_delay: jnp.ndarray     # [] i32
    sum_e2e: jnp.ndarray            # [] f32 (over processed flows)
    # per-run
    run_generated: jnp.ndarray      # [] i32
    run_processed: jnp.ndarray      # [] i32
    run_dropped: jnp.ndarray        # [] i32
    run_dropped_per_node: jnp.ndarray   # [N] i32
    run_e2e_sum: jnp.ndarray        # [] f32
    run_e2e_max: jnp.ndarray        # [] f32
    run_path_delay_sum: jnp.ndarray  # [] f32
    run_requested: jnp.ndarray      # [N,C,S_pos] f32 ('run_total_requested_traffic';
                                    #     indexed by chain POSITION, which maps 1:1
                                    #     to the reference's per-SF-name keying
                                    #     within a chain)
    run_requested_node: jnp.ndarray  # [N] f32 (ingress-generated dr per node)
    run_processed_traffic: jnp.ndarray  # [N,P] f32 (per node per SF id)
    run_flow_counts: jnp.ndarray    # [N,C,S_pos,N] i32 (WRR state, metrics.py:92-95)
    run_max_node_usage: jnp.ndarray  # [N] f32
    run_passed_traffic: jnp.ndarray  # [E] f32 (per-edge, simulatorparams.py:249-257)

    @classmethod
    def zeros(cls, n: int, c: int, s: int, e: int,
              p: int = None) -> "SimMetrics":
        if p is None:
            p = s  # single-chain configs: position axis == id axis
        i = lambda *shape: jnp.zeros(shape, jnp.int32)
        f = lambda *shape: jnp.zeros(shape, jnp.float32)
        return cls(
            generated=i(), processed=i(), dropped=i(), active=i(),
            drop_reasons=i(4), sum_proc_delay=f(), num_proc_delay=i(),
            sum_path_delay=f(), num_path_delay=i(), sum_e2e=f(),
            run_generated=i(), run_processed=i(), run_dropped=i(),
            run_dropped_per_node=i(n), run_e2e_sum=f(), run_e2e_max=f(),
            run_path_delay_sum=f(), run_requested=f(n, c, s),
            run_requested_node=f(n), run_processed_traffic=f(n, p),
            run_flow_counts=i(n, c, s, n), run_max_node_usage=f(n),
            run_passed_traffic=f(e),
        )

    def reset_run(self) -> "SimMetrics":
        """Per-interval reset (reference: metrics.py:64-95 reset_run_metrics,
        fired by the writer process each run_duration, writer.py:222-225)."""
        z = SimMetrics.zeros(self.run_dropped_per_node.shape[0],
                             self.run_requested.shape[1],
                             self.run_requested.shape[2],
                             self.run_passed_traffic.shape[0],
                             p=self.run_processed_traffic.shape[1])
        return self.replace(
            run_generated=z.run_generated, run_processed=z.run_processed,
            run_dropped=z.run_dropped,
            run_dropped_per_node=z.run_dropped_per_node,
            run_e2e_sum=z.run_e2e_sum, run_e2e_max=z.run_e2e_max,
            run_path_delay_sum=z.run_path_delay_sum,
            run_requested=z.run_requested,
            run_requested_node=z.run_requested_node,
            run_processed_traffic=z.run_processed_traffic,
            run_flow_counts=z.run_flow_counts,
            run_max_node_usage=z.run_max_node_usage,
            run_passed_traffic=z.run_passed_traffic,
        )

    def avg_e2e(self) -> jnp.ndarray:
        """'avg_end2end_delay': cumulative e2e over processed flows
        (metrics.py:203-209)."""
        return jnp.where(self.processed > 0,
                         self.sum_e2e / jnp.maximum(self.processed, 1), 0.0)

    def run_avg_e2e(self) -> jnp.ndarray:
        """'run_avg_end2end_delay' (metrics.py:210-215)."""
        return jnp.where(self.run_processed > 0,
                         self.run_e2e_sum / jnp.maximum(self.run_processed, 1), 0.0)


# Records the engine reads per substep (stage 3): the contiguous run
# ``cursor … cursor + ARRIVAL_RUN - 1`` of the time-sorted arrival table.
ARRIVAL_RUN = 8
# Records per row of the arrival table (the TPU's lane count: a row is one
# full vector register row, so row masks and lane masks tile without waste)
ARRIVAL_LANES = 128
# Row order of the arrival table, with the value each field's padding holds
_ARRIVAL_FIELDS = (
    ("arr_time", jnp.float32, np.inf),    # sorted ascending
    ("arr_dr", jnp.float32, 0.0),
    ("arr_duration", jnp.float32, 0.0),   # size/dr*1000
    ("arr_ttl", jnp.float32, 0.0),
    ("arr_ingress", jnp.int32, 0),
    ("arr_sfc", jnp.int32, 0),
    ("arr_egress", jnp.int32, -1),        # -1: none
)


def _masked_rows(x: jnp.ndarray, first: jnp.ndarray, count: int,
                 axis: int) -> jnp.ndarray:
    """``count`` consecutive entries of ``x`` along ``axis`` from the traced
    index ``first`` on, as a masked sum over the whole axis (entries past
    the end read 0).  Integer ``x``: the sum has one non-zero term, so any
    bit pattern comes through unchanged — and, unlike ``dynamic_slice``, a
    per-replica ``first`` under ``vmap`` stays elementwise + reduce: no
    gather, no loop over the replicas on the TPU."""
    x = jnp.moveaxis(x, axis, -1)
    want = first + jnp.arange(count)
    hit = want[:, None] == jnp.arange(x.shape[-1])        # [count, n]
    out = jnp.where(hit, x[..., None, :], 0).sum(-1)      # [..., count]
    return jnp.moveaxis(out, -1, axis)


@struct.dataclass
class TrafficSchedule:
    """Pre-generated per-episode traffic, the tensor analogue of the
    reference's per-episode flow lists (simulatorparams.py:185-247) extended
    to cover SFC/egress/TTL choice (default_generator.py:18-60), MMPP state
    switching (simulatorparams.py:143-176) and trace-driven scenario changes
    (trace_processor.py:23-54) — all host-precomputed into dense arrays.

    Flow records are sorted by arrival time; the engine keeps a cursor and
    reads, every substep, the ``ARRIVAL_RUN`` records from the cursor on.
    The seven per-record fields live in ONE table ``arr`` of 32-bit
    patterns (float fields bit-cast, which is exact), field-major, the
    record axis cut into rows of ``ARRIVAL_LANES``, so that the run is
    fetched once for all fields and without an index: under ``vmap`` the
    cursor is a per-replica vector, and any indexed read of it (a gather
    per field and record, a ``dynamic_slice`` per replica) is serial on
    the TPU.  Instead :meth:`window` masks out, once per control interval,
    the few rows the cursor can reach in that interval, and
    :meth:`read_run` masks the run out of those rows every substep — both
    elementwise + reduce, the first streaming the table once, the second
    touching the window only.  The table is padded (time ``inf``: never
    due) to whole rows that hold at least ``ARRIVAL_RUN`` records behind
    its ``capacity``, so a run that starts at the last record, or past it,
    stays inside.  Build with :meth:`pack`; the ``arr_*`` properties are
    the per-field ``[..., F]`` views for everything but the substep.
    """

    arr: jnp.ndarray   # [7, rows, ARRIVAL_LANES] i32, fields _ARRIVAL_FIELDS
    # number of records F (static: the table's shape only knows whole rows)
    capacity: int = struct.field(pytree_node=False)
    # Per control interval [T, N]: which ingresses generate flows (trace rows
    # can deactivate an ingress, trace_processor.py:37-38; affects placement
    # derivation via get_active_ingress_nodes, siminterface/simulator.py:261-263)
    ingress_active: jnp.ndarray  # [T, N] bool
    # Per control interval node capacity (traces may raise caps mid-episode,
    # trace_processor.py:44-46); row = topology node_cap when unchanged.
    node_cap: jnp.ndarray     # [T, N] f32
    # Per control interval EDGE capacity — the link twin of node_cap, used
    # by mid-episode link-fault scenarios (topology.scenarios): the engine
    # swaps topo.edge_cap for this table's current row at each interval
    # start, entirely inside the scanned episode (no host sync).  None
    # (the default, and every pre-fault producer) keeps the pytree
    # structure — and therefore every compiled program — byte-identical
    # to the fault-unaware stack.
    edge_cap_t: jnp.ndarray = None   # [T, E] f32 or None

    @classmethod
    def pack(cls, *, ingress_active, node_cap, edge_cap_t=None,
             **fields) -> "TrafficSchedule":
        """The one constructor: the seven ``[F]`` per-record arrays
        (``arr_time``, sorted ascending with ``inf`` behind the last
        record, ``arr_dr``, ``arr_duration``, ``arr_ttl``, ``arr_ingress``,
        ``arr_sfc``, ``arr_egress``) into the table."""
        capacity = np.shape(fields["arr_time"])[-1]
        rows = -(-(capacity + ARRIVAL_RUN) // ARRIVAL_LANES)
        pad = rows * ARRIVAL_LANES - capacity

        def bits(name, dtype, fill):
            x = jnp.concatenate([jnp.asarray(fields.pop(name), dtype),
                                 jnp.full((pad,), fill, dtype)])
            return jax.lax.bitcast_convert_type(x, jnp.int32)

        arr = jnp.stack([bits(*f) for f in _ARRIVAL_FIELDS])
        if fields:
            raise TypeError(f"unknown arrival fields: {sorted(fields)}")
        return cls(arr=arr.reshape(len(_ARRIVAL_FIELDS), rows, ARRIVAL_LANES),
                   ingress_active=ingress_active, node_cap=node_cap,
                   edge_cap_t=edge_cap_t, capacity=capacity)

    def _field(self, k: int) -> jnp.ndarray:
        _, dtype, _ = _ARRIVAL_FIELDS[k]
        x = self.arr[..., k, :, :]
        x = x.reshape(x.shape[:-2] + (-1,))[..., :self.capacity]
        return jax.lax.bitcast_convert_type(x, dtype)

    arr_time = property(lambda self: self._field(0))
    arr_dr = property(lambda self: self._field(1))
    arr_duration = property(lambda self: self._field(2))
    arr_ttl = property(lambda self: self._field(3))
    arr_ingress = property(lambda self: self._field(4))
    arr_sfc = property(lambda self: self._field(5))
    arr_egress = property(lambda self: self._field(6))

    def window(self, cursor: jnp.ndarray, records: int):
        """``(rows, base)``: the table rows that hold every run starting in
        ``[cursor, cursor + records)`` — the whole table where that is no
        more — and the record index of the first of them."""
        total = self.arr.shape[-2]
        count = min(total,
                    -(-(ARRIVAL_LANES - 1 + records + ARRIVAL_RUN - 1)
                      // ARRIVAL_LANES))
        first = jnp.clip(cursor // ARRIVAL_LANES, 0, total - count)
        return (_masked_rows(self.arr, first, count, axis=-2),
                first * ARRIVAL_LANES)

    @staticmethod
    def read_run(rows: jnp.ndarray, base: jnp.ndarray, cursor: jnp.ndarray):
        """The seven ``[ARRIVAL_RUN]`` field arrays (order of
        ``_ARRIVAL_FIELDS``) of the records ``cursor … cursor +
        ARRIVAL_RUN - 1``, out of a :meth:`window` that holds them: first
        the two rows the run can touch, then its lanes out of those."""
        row, lane = jnp.divmod(cursor - base, ARRIVAL_LANES)
        two = _masked_rows(rows, row, 2, axis=-2)         # [7, 2, LANES]
        offset = jnp.arange(2 * ARRIVAL_LANES).reshape(2, ARRIVAL_LANES)
        hit = offset == (lane + jnp.arange(ARRIVAL_RUN))[:, None, None]
        run = jnp.where(hit, two[:, None], 0).sum((-2, -1))   # [7, RUN]
        return tuple(jax.lax.bitcast_convert_type(run[k], dtype)
                     for k, (_, dtype, _) in enumerate(_ARRIVAL_FIELDS))


@struct.dataclass
class SimState:
    """Complete per-episode mutable simulator state."""

    t: jnp.ndarray            # [] f32 current sim time (ms)
    run_idx: jnp.ndarray      # [] i32 control intervals completed
    flows: FlowTable          # [M] slots
    cursor: jnp.ndarray       # [] i32 next unconsumed traffic-schedule record
    # per (node, SF) bookkeeping (reference 'available_sf' dicts,
    # simulatorparams.py:66-73, duration_controller.py:46-60)
    node_load: jnp.ndarray    # [N,P] f32 current processed load (SF-id axis)
    sf_available: jnp.ndarray  # [N,P] bool placed or still draining
    sf_startup: jnp.ndarray   # [N,P] f32 startup_time of the instance
    sf_last_active: jnp.ndarray  # [N,P] f32 last time the instance had load
                                 #     ('last_active', flow_controller.py:94-112)
    placed: jnp.ndarray       # [N,P] bool current placement action (SF-id axis)
    schedule: jnp.ndarray     # [N,C,S,N] f32 current scheduling weights
    edge_used: jnp.ndarray    # [E] f32 in-flight dr per undirected edge
    # capacity release ring buffers, indexed by substep mod horizon
    # The substep touches a ring only elementwise and by contraction
    # over the whole [H, K] array (engine stage 1 reads and clears row
    # ``ridx`` through a mask): a per-replica row index under vmap forces a
    # row-contiguous device layout that the release contractions do not
    # want, i.e. two layout copies of the whole ring per substep.  Storing
    # the rings transposed ([K, H]) compiles to the same layouts.
    rel_node: jnp.ndarray     # [H,N*P] f32 — flat trailing dim ([N,P]
                              # flattened), one contraction axis
    rel_edge: jnp.ndarray     # [H,E] f32
    metrics: SimMetrics
    rng: jnp.ndarray          # PRNG key
    # Arrivals admitted LATER than their scheduled substep because every
    # flow slot (or the per-substep arrival budget) was taken — the
    # engine's visible divergence signal from the reference's unbounded
    # concurrent-flow model.  Each delayed arrival is counted once, when it
    # finally spawns; surfaced by utils.debug.check_invariants.
    truncated_arrivals: jnp.ndarray  # [] i32


def init_state(rng, max_flows: int, n: int, c: int, s: int, e: int,
               horizon: int, p: int = None) -> SimState:
    if p is None:
        p = s
    return SimState(
        t=jnp.zeros((), jnp.float32),
        run_idx=jnp.zeros((), jnp.int32),
        flows=FlowTable.empty(max_flows),
        cursor=jnp.zeros((), jnp.int32),
        node_load=jnp.zeros((n, p), jnp.float32),
        sf_available=jnp.zeros((n, p), bool),
        sf_startup=jnp.zeros((n, p), jnp.float32),
        sf_last_active=jnp.zeros((n, p), jnp.float32),
        placed=jnp.zeros((n, p), bool),
        schedule=jnp.zeros((n, c, s, n), jnp.float32),
        edge_used=jnp.zeros(e, jnp.float32),
        rel_node=jnp.zeros((horizon, n * p), jnp.float32),
        rel_edge=jnp.zeros((horizon, e), jnp.float32),
        metrics=SimMetrics.zeros(n, c, s, e, p=p),
        rng=rng,
        truncated_arrivals=jnp.zeros((), jnp.int32),
    )
