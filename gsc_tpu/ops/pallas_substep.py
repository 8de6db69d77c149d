"""Substep megakernel — the whole ``SimEngine._substep`` as ONE Pallas call.

The round-5 MFU/roofline table proved the substep regime decisively: a
chain of ~60 small fusions at ~30 µs apiece, ~100x above the HBM roof and
~10,000x above the MXU roof — op COUNT, never arithmetic, is the cost.
The XLA engine already fights that with the one-hot idiom (gathers and
scatters as MXU contractions so XLA fuses them); this module takes the
same lesson one level deeper and collapses the entire admission/release
chain — the one-hot contraction + packed-scatter + run-starts pipeline of
``gsc_tpu/sim/engine.py`` — into a single kernel invocation per substep,
selected by ``SimConfig.substep_impl = "pallas"`` (mirroring the
``gnn_impl`` switch and the ``ops/pallas_gat.py`` template; the engine is
not differentiated, so unlike the GAT kernel no custom VJP is needed).

Bit-exactness contract (the ``pytest -m megakernel`` parity suite pins it
against the XLA engine on the reference-parity scenarios):

- pure DATA-MOVEMENT one-hot dots — row lookups (``_take``/``_pick``),
  permutation matmuls, transpose-scatters — are replaced by native
  gathers/scatters.  Each such dot has exactly ONE nonzero term per
  output (1.0 * x plus exact zeros), so the gather produces the same
  VALUE; out-of-range "drop" rows map to ``mode="fill"`` gathers /
  ``mode="drop"`` scatters.
- every float reduction whose accumulation ORDER matters — the
  fractional segment-sums (requested/passed/processed traffic, the
  release-ring einsums), the admission pipelines' sorted global cumsum
  minus run-start difference, and the masked scalar sums — keeps the
  engine's exact op sequence (same ``jnp.dot``/``einsum``/``cumsum``
  primitives on the same operand arrays), so results are bit-identical,
  not merely close.
- integer reductions (WRR counters, drop counts, ranks, run starts) are
  exact under any order and use scatter-adds.
- the grouping SORT stays ``argsort`` over unique integer keys — exact.

Execution model: this kernel has a CPU role only.  On the CPU backend
``interpret=None`` inlines the kernel body into the XLA program as ONE
straight-line block — measurably FEWER fusions than the hand-fused
engine (the fusion-budget test in ``tests/test_megakernel.py`` asserts
pallas < xla on the compiled flagship interval).  On a TPU backend
native Mosaic lowering was tried on the chip (PR 21, TPU v5 lite,
jax 0.9.0) and refused: ``Unimplemented primitive in Pallas TPU lowering
for KernelType.TC: dynamic_slice`` — the ``argsort`` grouping and the
dynamic gathers are not expressible there.  ``SimEngine`` therefore
rejects ``substep_impl="pallas"`` at build time on any non-CPU backend;
chip runs use ``substep_impl="xla"``.  Whether this twin survives is
ROADMAP Queue 3 item 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..sim.engine import (_ARRIVALS_PER_SUBSTEP, _EPS, _HI, _group_order,
                          _onehot, _rank_in_cell, _run_starts)
from ..sim.state import (
    DROP_DECISION,
    DROP_LINK_CAP,
    DROP_NODE_CAP,
    DROP_TTL,
    PH_DECIDE,
    PH_FREE,
    PH_HOP,
    PH_PROC,
    FlowTable,
    SimState,
)

# state fields the substep mutates — the exact ``state.replace(...)`` set
# of SimEngine._substep (run_idx and rng are handled by the caller)
_OUT_KEYS = ("t", "flows", "cursor", "node_load", "sf_available",
             "edge_used", "placed", "sf_startup", "sf_last_active",
             "rel_node", "rel_edge", "metrics", "truncated_arrivals")


def _rows(tab: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``tab[idx]`` rows with out-of-range indices giving ZERO rows — the
    gather twin of the engine's un-clipped one-hot dots (an OOR index
    there matches no ``arange`` column, so the dot returns exact zeros)."""
    return jnp.take(tab, idx, axis=0, mode="fill", fill_value=0)


def _substep_body(sdict, topo_arrs, traf, tabs, cap_now, noise, *, tables,
                  cfg, dims, det):
    """One substep, gather-idiom transcription of ``SimEngine._substep``
    (duration-controller branch).  Stage numbering and comments track the
    engine body line by line; see the module docstring for which ops are
    transcribed verbatim vs re-idiomized."""
    M, N, C, S, P, E, H = dims
    dt = cfg.dt
    path_delay, next_hop, adj_edge_id, edge_cap, edge_delay = topo_arrs
    (arr_time, arr_ingress, arr_dr, arr_duration, arr_ttl, arr_sfc,
     arr_egress) = traf
    # service tables as kernel INPUTS (Pallas forbids captured array
    # constants); values identical to tables.* — `tables` itself only
    # contributes the static resource_fns callables
    chain_len_tab, chain_sf_flat, proc_mean_tab, proc_std_tab, \
        startup_tab = tabs
    capacity = arr_time.shape[0]

    F: FlowTable = sdict["flows"]
    m = sdict["metrics"]
    t = sdict["t"]
    g = jnp.round(t / dt).astype(jnp.int32)       # global substep index
    ridx = jnp.mod(g, H)                           # ring-buffer index
    slots = jnp.arange(M)

    def _demanded(load_plus, avail):
        # twin of SimEngine._demanded: per-SF resource functions
        cols = []
        for si, fn in enumerate(tables.resource_fns):
            cols.append(jnp.where(avail[..., si], fn(load_plus[..., si]),
                                  0.0))
        return jnp.stack(cols, axis=-1).sum(axis=-1)

    # --- 1. capacity releases ------------------------------------------
    node_load = jnp.maximum(
        sdict["node_load"] - sdict["rel_node"][ridx].reshape(N, P), 0.0)
    edge_used = jnp.maximum(sdict["edge_used"] - sdict["rel_edge"][ridx],
                            0.0)
    rel_node = sdict["rel_node"].at[ridx].set(0.0)
    rel_edge = sdict["rel_edge"].at[ridx].set(0.0)
    sf_available = sdict["sf_available"] & (sdict["placed"]
                                            | (node_load > _EPS))

    # --- 2. timers ------------------------------------------------------
    running = (F.phase == PH_HOP) | (F.phase == PH_PROC)
    timer = jnp.where(running, F.timer - dt, F.timer)
    proc_done = (F.phase == PH_PROC) & (timer <= _EPS)
    hop_done = (F.phase == PH_HOP) & (timer <= _EPS)

    position = F.position + proc_done.astype(jnp.int32)
    phase = jnp.where(proc_done, PH_DECIDE, F.phase)

    node = jnp.where(hop_done, F.hop_next, F.node)
    arrived = hop_done & (node == F.dest)
    cont = hop_done & ~arrived
    e2e = F.e2e + jnp.where(arrived, F.pend_path, 0.0)
    ttl = F.ttl - jnp.where(arrived, F.pend_path, 0.0)
    n_arr = arrived.sum()
    path_add = jnp.where(arrived, F.pend_path, 0.0).sum()
    m = m.replace(
        sum_path_delay=m.sum_path_delay + path_add,
        num_path_delay=m.num_path_delay + n_arr,
        run_path_delay_sum=m.run_path_delay_sum + path_add,
    )

    # --- 3. arrivals ----------------------------------------------------
    cand = sdict["cursor"] + jnp.arange(_ARRIVALS_PER_SUBSTEP)
    cand_c = jnp.clip(cand, 0, capacity - 1)
    # ONE packed [A]-row gather per dtype family (the engine's per-array
    # reads, batched; values identical)
    w_flt = jnp.stack([arr_time, arr_dr, arr_duration, arr_ttl],
                      axis=-1)[cand_c]                     # [A, 4]
    w_int = jnp.stack([arr_ingress, arr_sfc, arr_egress],
                      axis=-1)[cand_c]                     # [A, 3]
    w_time, w_dr, w_duration, w_ttl = (w_flt[:, 0], w_flt[:, 1],
                                       w_flt[:, 2], w_flt[:, 3])
    w_ingress, w_sfc, w_egress = w_int[:, 0], w_int[:, 1], w_int[:, 2]
    due = (w_time < t + dt - _EPS) & (cand < capacity) \
        & jnp.isfinite(w_time)
    free = phase == PH_FREE
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    n_free = free.sum()
    arr_rank = jnp.cumsum(due.astype(jnp.int32)) - 1
    spawn = due & (arr_rank < n_free)
    # slot_of_rank: VERBATIM engine transpose-scatter dot — a native
    # scatter lowers to a serial while-loop on the CPU backend, undoing
    # the fusion-count win this body exists for
    oh_rank = _onehot(jnp.where(free, free_rank, M), M)
    slot_of_rank = jnp.round(jnp.dot(slots.astype(jnp.float32), oh_rank,
                                     precision=_HI,
                  preferred_element_type=jnp.float32)).astype(jnp.int32)
    tgt = slot_of_rank[jnp.clip(arr_rank, 0, M - 1)]

    arr_idx = jnp.where(spawn, tgt, M)
    a_i32 = jnp.zeros_like(cand)
    int_cur = jnp.stack([phase, node, position, F.sfc, F.egress, F.dest],
                        axis=-1)                           # [M, 6]
    int_new = jnp.stack([a_i32 + PH_DECIDE, w_ingress,
                         a_i32, w_sfc, w_egress, a_i32 - 1],
                        axis=-1)                           # [A, 6]
    int_cur = int_cur.at[arr_idx].set(int_new, mode="drop")
    phase, node, position, sfc, egress, dest = (
        int_cur[:, 0], int_cur[:, 1], int_cur[:, 2], int_cur[:, 3],
        int_cur[:, 4], int_cur[:, 5])
    a_f32 = jnp.zeros(cand.shape, jnp.float32)
    flt_cur = jnp.stack([F.dr, F.duration, ttl, e2e, F.pend_path],
                        axis=-1)                           # [M, 5]
    flt_new = jnp.stack([w_dr, w_duration, w_ttl, a_f32, a_f32],
                        axis=-1)                           # [A, 5]
    flt_cur = flt_cur.at[arr_idx].set(flt_new, mode="drop")
    dr, duration, ttl, e2e, pend_path = (
        flt_cur[:, 0], flt_cur[:, 1], flt_cur[:, 2], flt_cur[:, 3],
        flt_cur[:, 4])
    hop_next = F.hop_next
    n_spawn = spawn.sum()
    cursor = sdict["cursor"] + n_spawn
    late = spawn & (w_time < t - _EPS)
    truncated = sdict["truncated_arrivals"] + late.sum()
    m = m.replace(
        generated=m.generated + n_spawn,
        run_generated=m.run_generated + n_spawn,
        active=m.active + n_spawn,
        run_requested_node=m.run_requested_node.at[
            jnp.where(spawn, w_ingress, N)
        ].add(jnp.where(spawn, w_dr, 0.0), mode="drop"),
    )

    # recompute flags after arrivals (OOR sfc -> zero chain_len row, the
    # engine's un-clipped one-hot semantics, via mode="fill")
    sfc_c = jnp.clip(sfc, 0, C - 1)
    chain_len = _rows(chain_len_tab, sfc)
    to_eg_flag = position >= chain_len

    # --- 4. decisions ---------------------------------------------------
    deciding = phase == PH_DECIDE
    drop_ttl0 = deciding & (ttl <= _EPS)
    decide = deciding & ~drop_ttl0
    to_eg = decide & to_eg_flag
    egress = jnp.where(to_eg & (egress < 0), node, egress)
    wrr = decide & ~to_eg_flag

    sf_pos = jnp.clip(position, 0, S - 1)
    sf_now = chain_sf_flat[sfc_c * S + sf_pos]   # index always in range
    sf_now = jnp.clip(sf_now, 0)
    oh_node = _onehot(node, N)                 # [M, N]  (segment-sum dots)
    oh_sf = _onehot(sf_now, P)                 # [M, P]
    cell = (node * C + sfc_c) * S + sf_pos
    ncs = N * C * S
    oh_cell = _onehot(cell, ncs)               # [M, NCS] (requested dot)
    placed = sdict["placed"]
    sf_startup = sdict["sf_startup"]
    sf_last_active = sdict["sf_last_active"]
    # requested-traffic metric: fractional segment-sum — VERBATIM dot
    req_add = jnp.dot(jnp.where(wrr, dr, 0.0), oh_cell,
                      precision=_HI,
                  preferred_element_type=jnp.float32).reshape(m.run_requested.shape)
    m = m.replace(run_requested=m.run_requested + req_add)

    # WRR with realized-ratio counters: rank + counter updates VERBATIM
    # (engine helpers / einsum — the scatter forms while-loop on CPU)
    rank = _rank_in_cell(cell, wrr, ncs)
    flow_counts = m.run_flow_counts
    # _rows, not plain indexing: an OOR cell (corrupt node id) must read
    # ZERO rows exactly like the engine's un-clipped oh_cell dots
    probs = _rows(sdict["schedule"].reshape(ncs, N), cell)
    R = cfg.wrr_rank_levels
    for r in range(R):
        sel = wrr & ((rank == r) if r < R - 1 else (rank >= r))
        counts = _rows(flow_counts.reshape(ncs, N), cell)
        total = counts.sum(-1, keepdims=True)
        ratios = jnp.where(total > 0, counts / jnp.maximum(total, 1), 0.0)
        diffs = jnp.where(probs > 0, probs - ratios, -1.0)
        choice = jnp.argmax(diffs, axis=-1).astype(jnp.int32)
        dest = jnp.where(sel, choice, dest)
        cnt_add = jnp.einsum(
            "mc,mn->cn", oh_cell * sel[:, None].astype(jnp.float32),
            _onehot(choice, N), precision=_HI,
                  preferred_element_type=jnp.float32)
        flow_counts = flow_counts + jnp.round(cnt_add).astype(
            flow_counts.dtype).reshape(flow_counts.shape)
    m = m.replace(run_flow_counts=flow_counts)
    dest = jnp.where(to_eg, egress, dest)

    # --- 5. forwarding --------------------------------------------------
    fwd = decide
    stay = fwd & (dest == node)
    depart_stay = to_eg & stay
    need_proc_b = wrr & stay
    start_path = fwd & ~stay
    # the engine's wide [M,N]@[N,3N+1] contraction becomes ONE wide row
    # GATHER; the per-row column picks stay the engine's masked VPU
    # reduces (fusable, and bit-equal by the single-nonzero argument)
    oh_dest = _onehot(jnp.clip(dest, 0), N)
    pd_tab = jnp.where(jnp.isfinite(path_delay), path_delay, 1e30)
    # ALL node-indexed rows in one gather: the engine's loop-invariant
    # [path_delay | next_hop | adj_edge_id | cap_now] block plus its
    # loop-variant [placed | sf_startup] block
    static_tab = jnp.concatenate(
        [pd_tab, next_hop.astype(jnp.float32),
         adj_edge_id.astype(jnp.float32), cap_now[:, None],
         placed.astype(jnp.float32), sf_startup],
        axis=1)                                    # [N, 3N+1+2P]
    rows = _rows(static_tab, node)                 # [M, 3N+1+2P]
    pd_rows = rows[:, :N]
    nh_rows = rows[:, N:2 * N]
    adj_rows = rows[:, 2 * N:3 * N]
    cap_mine = rows[:, 3 * N]
    ps_rows = rows[:, 3 * N + 1:]                  # [M, 2P]
    pd_path = (pd_rows * oh_dest).sum(-1)
    drop_ttl_path = start_path & (ttl - pd_path <= _EPS)
    ttl = jnp.where(drop_ttl_path, 0.0, ttl)
    start_path = start_path & ~drop_ttl_path

    hop_req = cont | start_path
    nh = jnp.round((nh_rows * oh_dest).sum(-1)).astype(jnp.int32)
    nh = jnp.clip(nh, 0)
    eid = jnp.round((adj_rows * _onehot(nh, N)).sum(-1)).astype(jnp.int32)
    eid_c = jnp.clip(eid, 0)
    oh_e = _onehot(eid_c, E)                   # [M, E] (segment-sum dots)
    edge_rows = _rows(jnp.stack(
        [edge_cap - edge_used + _EPS, edge_delay], axis=-1), eid_c)  # [M, 2]
    headroom = edge_rows[:, 0]

    # Hoisted stage-6 pre-sort work (want/pdel before link admission, as
    # in the engine's batched-sort hoist)
    need_proc_a = arrived & ~to_eg_flag
    need_proc = need_proc_a | need_proc_b
    sf_ok = (ps_rows[:, :P] * oh_sf).sum(-1) > 0.5
    drop_unplaced = need_proc & ~sf_ok
    want = need_proc & sf_ok
    proc_tab = _rows(jnp.stack([proc_mean_tab, proc_std_tab, startup_tab],
                               axis=-1), sf_now)   # [M, 3]
    pmean = proc_tab[:, 0]
    pstd = proc_tab[:, 1]
    if det:
        # deterministic processing delays: |N(mean, 0)| == mean (engine's
        # threefry-skip fast path; ``noise`` is unused)
        pdel = jnp.abs(pmean)
    else:
        pdel = jnp.abs(noise * pstd + pmean)
    drop_ttl_pd = want & (ttl - pdel <= _EPS)
    want = want & ~drop_ttl_pd

    # slot-order grouping for link (e) and node (n) admission — the
    # engine's batched argsort + permutation einsum, as two argsorts and
    # ONE packed row gather per pipeline
    orders2 = jax.vmap(_group_order)(jnp.stack([eid_c, node]))   # [2, M]
    order_e, order_n = orders2[0], orders2[1]
    sort_ins = jnp.stack([
        jnp.stack([eid_c.astype(jnp.float32),
                   (hop_req & (eid >= 0)).astype(jnp.float32),
                   dr, headroom], axis=-1),
        jnp.stack([node.astype(jnp.float32), want.astype(jnp.float32),
                   dr, cap_mine], axis=-1)])                     # [2, M, 4]
    sorted2 = jnp.take_along_axis(sort_ins, orders2[:, :, None],
                                  axis=1)          # ONE batched gather
    sorted_e, sorted_n = sorted2[0], sorted2[1]
    eid_s = jnp.round(sorted_e[:, 0]).astype(jnp.int32)
    node_sorted = jnp.round(sorted_n[:, 0]).astype(jnp.int32)
    starts_e = _run_starts(eid_s)
    starts_n = _run_starts(node_sorted)

    req_s = sorted_e[:, 1] > 0.5
    dr_s = sorted_e[:, 2]
    headroom_s = sorted_e[:, 3]
    adm_s = req_s
    for _ in range(cfg.admission_iters):
        # sorted global cumsum minus run-start prefix: VERBATIM float
        # sequence (cs, the run-start row pick, the subtract/compare);
        # only the data movement is gathers
        v = jnp.where(adm_s, dr_s, 0.0)
        cs = jnp.cumsum(v)
        bound = jnp.stack([cs, v], axis=-1)[starts_e]            # [M, 2]
        adm_s = req_s & (cs - (bound[:, 0] - bound[:, 1]) <= headroom_s)
    perm_e = _onehot(order_e, M)
    admitted = jnp.dot(adm_s.astype(jnp.float32), perm_e,
                       precision=_HI,
                  preferred_element_type=jnp.float32) > 0.5        # VERBATIM unsort dot
    drop_link = hop_req & ~admitted
    add_e = jnp.where(admitted, dr, 0.0)
    edge_add = jnp.dot(add_e, oh_e, precision=_HI,
                  preferred_element_type=jnp.float32)   # [E] — VERBATIM dot
    edge_used = edge_used + edge_add
    m = m.replace(run_passed_traffic=m.run_passed_traffic + edge_add)
    hop_delay = edge_rows[:, 1]
    off_e = jnp.clip(jnp.ceil((hop_delay + duration) / dt).astype(jnp.int32),
                     1, H - 1)
    oh_off_e = _onehot(jnp.where(admitted, jnp.mod(ridx + off_e, H), H), H)
    rel_edge = rel_edge + jnp.einsum(
        "mh,me->he", oh_off_e, oh_e * add_e[:, None], precision=_HI,
                  preferred_element_type=jnp.float32)
    pend_path = jnp.where(start_path & admitted, pd_path, pend_path)
    hop_next = jnp.where(admitted, nh, hop_next)
    timer = jnp.where(admitted, hop_delay, timer)
    phase = jnp.where(admitted, PH_HOP, phase)

    # --- 6. processing --------------------------------------------------
    ttl = jnp.where(drop_ttl_pd, 0.0, ttl)
    e2e = e2e + jnp.where(want, pdel, 0.0)
    ttl = ttl - jnp.where(want, pdel, 0.0)
    n_want = want.sum()
    m = m.replace(
        sum_proc_delay=m.sum_proc_delay + jnp.where(want, pdel, 0.0).sum(),
        num_proc_delay=m.num_proc_delay + n_want,
    )
    want_s = sorted_n[:, 1] > 0.5
    dr_col_s = sorted_n[:, 2][:, None]
    cap_s = sorted_n[:, 3]
    la_rows = _rows(jnp.concatenate(
        [node_load, sf_available.astype(jnp.float32)],
        axis=1), node_sorted)                          # [M, 2P]
    base_load_s = la_rows[:, :P]
    avail_s = la_rows[:, P:] > 0.5
    sf_onehot_s = oh_sf[order_n] > 0.5                 # [M, P]
    adm_ns = want_s
    dem_s = jnp.zeros(M, jnp.float32)
    for _ in range(cfg.admission_iters):
        v = jnp.where(adm_ns[:, None] & sf_onehot_s, dr_col_s, 0.0)
        cs = jnp.cumsum(v, axis=0)
        b = jnp.concatenate([cs, v], axis=1)[starts_n]  # [M, 2P]
        dem_s = _demanded(base_load_s + cs - (b[:, :P] - b[:, P:]),
                          avail_s)
        adm_ns = want_s & (dem_s <= cap_s + _EPS)
    perm_n = _onehot(order_n, M)
    unsorted = jnp.dot(
        jnp.stack([adm_ns.astype(jnp.float32), dem_s], axis=-1).T,
        perm_n, precision=_HI,
                  preferred_element_type=jnp.float32)                     # VERBATIM unsort dot
    admitted_n = unsorted[0] > 0.5
    demanded = unsorted[1]
    drop_nodecap = want & ~admitted_n
    add_n = jnp.where(admitted_n, dr, 0.0)
    node_add = jnp.einsum("mn,mp->np", oh_node * add_n[:, None], oh_sf,
                          precision=_HI,
                  preferred_element_type=jnp.float32)               # [N, P] — VERBATIM
    node_load = node_load + node_add
    m = m.replace(
        run_processed_traffic=m.run_processed_traffic + node_add,
        run_max_node_usage=jnp.maximum(
            m.run_max_node_usage,
            (oh_node * jnp.where(admitted_n, demanded, 0.0)[:, None]
             ).max(axis=0)),
    )
    sw = jnp.maximum(
        (ps_rows[:, P:] * oh_sf).sum(-1) + proc_tab[:, 2] - t, 0.0)
    drop_ttl_sw = admitted_n & (ttl - sw <= _EPS) & (sw > _EPS)
    ttl = jnp.where(drop_ttl_sw, 0.0, ttl)
    started = admitted_n & ~drop_ttl_sw
    e2e = e2e + jnp.where(started, sw, 0.0)
    ttl = ttl - jnp.where(started, sw, 0.0)
    busy = jnp.where(started, sw + pdel, 0.0)
    timer = jnp.where(started, busy, timer)
    phase = jnp.where(started, PH_PROC, phase)
    hold = jnp.where(started, busy + duration, dt)
    rel_who = started | drop_ttl_sw
    off_n = jnp.clip(jnp.ceil(hold / dt).astype(jnp.int32), 1, H - 1)
    oh_off_n = _onehot(jnp.where(rel_who, jnp.mod(ridx + off_n, H), H), H)
    rel_vals = jnp.where(rel_who, dr, 0.0)
    np_flat = jnp.einsum("mn,mp->mnp", oh_node * rel_vals[:, None],
                         oh_sf, precision=_HI,
                  preferred_element_type=jnp.float32).reshape(M, N * P)
    rel_node = rel_node + jnp.einsum("mh,mk->hk", oh_off_n, np_flat,
                                     precision=_HI,
                  preferred_element_type=jnp.float32)    # VERBATIM einsums

    # --- 7. departures & drops -----------------------------------------
    depart = (arrived & to_eg_flag) | depart_stay
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
    any_drop = jnp.zeros(M, bool)
    n_reasons = m.drop_reasons.shape[0]
    adds = [jnp.zeros((), m.drop_reasons.dtype)] * n_reasons
    for mask, reason in drops:
        any_drop = any_drop | mask
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
                    precision=_HI,
                  preferred_element_type=jnp.float32)).astype(m.run_dropped_per_node.dtype),
    )
    gone = depart | any_drop
    phase = jnp.where(gone, PH_FREE, phase)

    # idle-VNF bookkeeping (duration controller: no GC, per-flow control
    # is rejected at SimConfig validation for the pallas impl)
    active_sf = node_load > _EPS
    sf_last_active = jnp.where(active_sf, t, sf_last_active)

    flows = FlowTable(phase=phase, sfc=sfc, position=position, node=node,
                      dest=dest, hop_next=hop_next, egress=egress, dr=dr,
                      duration=duration, ttl=ttl, e2e=e2e,
                      pend_path=pend_path, timer=timer)
    return {
        "t": t + dt, "flows": flows, "cursor": cursor,
        "node_load": node_load, "sf_available": sf_available,
        "edge_used": edge_used, "placed": placed, "sf_startup": sf_startup,
        "sf_last_active": sf_last_active, "rel_node": rel_node,
        "rel_edge": rel_edge, "metrics": m, "truncated_arrivals": truncated,
    }


def _megakernel(*refs, tree_in, scal_in, n_in, tree_out, scal_out, tables,
                cfg, dims, det):
    """Pallas kernel: read every input ref, run the substep body, write
    every output ref.  Scalars travel as (1,) blocks (TPU refs are >=1-d);
    ``scal_*`` records which leaves to re/un-squeeze."""
    vals = [r[...] for r in refs[:n_in]]
    vals = [v[0] if sc else v for v, sc in zip(vals, scal_in)]
    sdict, topo_arrs, traf, tabs, cap_now, noise = \
        jax.tree_util.tree_unflatten(tree_in, vals)
    out = _substep_body(sdict, topo_arrs, traf, tabs, cap_now, noise,
                        tables=tables, cfg=cfg, dims=dims, det=det)
    flat, td = jax.tree_util.tree_flatten(out)
    assert td == tree_out, (td, tree_out)   # trace-time structure check
    for ref, val, sc in zip(refs[n_in:], flat, scal_out):
        ref[...] = val[None] if sc else val


def substep_megakernel(state: SimState, topo, traffic, cap_now: jnp.ndarray,
                       noise: jnp.ndarray, *, tables, cfg, limits, det: bool,
                       interpret: bool | None = None) -> SimState:
    """One simulator substep as a single ``pallas_call``.

    ``state.rng`` must already be advanced by the caller (the engine
    splits and, for stochastic processing delays, draws ``noise`` with
    the SAME key/shape as the XLA path, so the rng STREAM is identical);
    ``run_idx`` is untouched here exactly as in ``SimEngine._substep``.
    ``det`` is the engine's static deterministic-processing-delay flag
    (``noise`` is ignored when set).

    Execution selection:

    - ``interpret=None`` (default): on the CPU backend the kernel BODY is
      inlined as plain XLA — bit-identical to interpret mode (the Pallas
      interpreter executes exactly these jnp ops) but without the
      ref-discharge copies, so the compiled flagship interval lands
      BELOW the hand-fused XLA engine's fusion count (270 vs 273 on
      jaxlib 0.9.0; the fusion-budget test pins it) and runs ~25% faster per
      interval on CPU.  Other backends take the native ``pallas_call``,
      which TPU Pallas refuses (module docstring) — the engine never
      builds this impl there.
    - ``interpret=True``: force a REAL interpret-mode ``pallas_call``
      (the parity suite uses this to pin kernel == inlined body).
    - ``interpret=False``: force native lowering.
    """
    inline = interpret is None and jax.default_backend() == "cpu"
    if interpret is None:
        interpret = False
    M = cfg.max_flows
    dims = (M, limits.max_nodes, limits.num_sfcs, limits.max_sfs,
            limits.sf_pool, limits.max_edges, cfg.release_horizon)
    sdict = {k: getattr(state, k) for k in
             ("t", "cursor", "flows", "node_load", "sf_available",
              "sf_startup", "sf_last_active", "placed", "schedule",
              "edge_used", "rel_node", "rel_edge", "metrics",
              "truncated_arrivals")}
    topo_arrs = (topo.path_delay, topo.next_hop, topo.adj_edge_id,
                 topo.edge_cap, topo.edge_delay)
    traf = (traffic.arr_time, traffic.arr_ingress, traffic.arr_dr,
            traffic.arr_duration, traffic.arr_ttl, traffic.arr_sfc,
            traffic.arr_egress)
    tabs = (jnp.asarray(tables.chain_len),
            jnp.asarray(tables.chain_sf).reshape(-1),
            jnp.asarray(tables.proc_mean), jnp.asarray(tables.proc_std),
            jnp.asarray(tables.startup_delay))
    if inline:
        out = _substep_body(sdict, topo_arrs, traf, tabs, cap_now, noise,
                            tables=tables, cfg=cfg, dims=dims, det=det)
        return state.replace(**out)
    ins = (sdict, topo_arrs, traf, tabs, cap_now, noise)
    flat_in, tree_in = jax.tree_util.tree_flatten(ins)
    scal_in = tuple(x.ndim == 0 for x in flat_in)
    out_struct = {k: sdict[k] for k in _OUT_KEYS}
    flat_out, tree_out = jax.tree_util.tree_flatten(out_struct)
    scal_out = tuple(x.ndim == 0 for x in flat_out)
    out_shape = tuple(
        jax.ShapeDtypeStruct((1,) if sc else x.shape, x.dtype)
        for x, sc in zip(flat_out, scal_out))
    # every output is an in-place update of the matching state input:
    # alias them (in-VMEM updates on TPU; on CPU it kills the interpret
    # discharge's defensive copies).  The map is built STRUCTURALLY from
    # the dict flatten order (sorted keys; sdict leads the `ins` tuple),
    # never by tracer identity — init-time states can share leaf objects.
    offs, off = {}, 0
    for key in sorted(sdict):
        n_leaves = len(jax.tree_util.tree_leaves(sdict[key]))
        offs[key] = off
        off += n_leaves
    aliases, out_off = {}, 0
    for key in sorted(out_struct):
        for k in range(len(jax.tree_util.tree_leaves(out_struct[key]))):
            aliases[offs[key] + k] = out_off
            out_off += 1
    kern = functools.partial(
        _megakernel, tree_in=tree_in, scal_in=scal_in, n_in=len(flat_in),
        tree_out=tree_out, scal_out=scal_out, tables=tables, cfg=cfg,
        dims=dims, det=det)
    outs = pl.pallas_call(kern, out_shape=out_shape, interpret=interpret,
                          input_output_aliases=aliases)(
        *[x[None] if sc else x for x, sc in zip(flat_in, scal_in)])
    new = jax.tree_util.tree_unflatten(
        tree_out, [o[0] if sc else o for o, sc in zip(outs, scal_out)])
    return state.replace(**new)
