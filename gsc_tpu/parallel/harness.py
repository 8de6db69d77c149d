"""Chunked-episode measurement harness shared by the throughput/quality
tools (tools/learning_curve.py, tools/quality_sweep.py).

Episodes execute as several shorter fused ``chunk_step`` device calls
(the TPU operating mode — see ParallelDDPG.rollout_episodes for the
chunking contract), the LAST one carrying the end-of-episode learn burst
in the same device program, and per-episode metrics are aggregated over
ALL chunks: ``episodic_return`` sums across chunks and the success ratio
averages them — a single chunk's stats cover only that chunk's steps, so
reading the last chunk would score episodes on an end-of-episode slice.

With ``hub`` (a :class:`gsc_tpu.obs.MetricsHub`) the harness streams
replica-resolved telemetry: per-replica episode returns and replay-shard
fill as gauges tagged ``replica=<i>``, plus one ``harness_episode`` event
per episode — a collapsing replica or a starved replay shard is invisible
in the cross-replica means the quality tools report.  ``timer`` (a
``PhaseTimer``) attributes the chunk-dispatch loop vs the metric-sync wall
exactly like the single-env trainer's dispatch/drain phases.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def run_chunked_episodes(pddpg, topo, episode_traffic: Callable,
                         state, buffers, episodes: int, episode_steps: int,
                         chunk: int, seed: int,
                         on_episode: Optional[Callable] = None,
                         step_offset: int = 0,
                         hub=None, timer=None,
                         topo_names: Optional[list] = None,
                         learn_names: Optional[list] = None
                         ) -> Tuple[object, object, list, list, list]:
    """Train for ``episodes`` full episodes; returns (state, buffers,
    per-episode returns, per-episode MEAN success ratios, per-episode
    FINAL-step success ratios).  The mean averages every step of the
    episode; the final-step value is the end-of-episode slice that the
    Trainer's ``final_succ_ratio`` and the historical quality bars
    (BENCH_NOTES: 0.48 -> 0.64) report — compare against the right one.

    ``episode_traffic(ep)`` supplies the [B]-stacked TrafficSchedule for
    episode ``ep``; ``on_episode(ep, ret, succ, learn_metrics)`` is called
    after each episode's learn burst.

    ``step_offset`` is the GLOBAL step of this call's first rollout step —
    callers that drive the harness one episode at a time (e.g.
    Trainer.train_parallel) must pass ``ep * episode_steps``, or the
    agent's warmup gate (global_step < nb_steps_warmup_critic selects
    random actions) would restart at 0 every episode and the policy would
    never act.

    ``topo_names`` ([B] per-replica topology names, mixed-topology runs):
    the hub additionally gets per-topology return gauges (tag
    ``topology=<name>``, mean over that topology's replicas) and the
    ``harness_episode`` event carries the per-replica ``topology`` list +
    a ``per_topology_return`` dict — a mixture member that collapses is
    visible by name, not just as one cold row in the replica vector.

    ``learn_names`` (topo_id -> name, from the driver): when the agent
    was built with a learn ledger (obs.learning), each episode's drained
    ``learn_signal`` — per-topology |TD| segments, Q moments, layer
    norms, replay fill — is emitted through the hub with these names;
    ledger-free agents produce no signal and nothing is emitted."""
    from ..obs.learning import emit_learn_signal
    from ..obs.trace import phase_span

    assert episode_steps % chunk == 0, (episode_steps, chunk)
    returns, succ, final_succ = [], [], []
    for ep in range(episodes):
        traffic = episode_traffic(ep)
        with phase_span("reset_enqueue", timer, hub):
            env_states, obs = pddpg.reset_all(
                jax.random.fold_in(jax.random.PRNGKey(seed + 2), ep),
                topo, traffic)
        chunk_stats = []
        n_chunks = episode_steps // chunk
        with phase_span("dispatch", timer, hub):
            for c in range(n_chunks):
                start = jnp.int32(step_offset + ep * episode_steps
                                  + c * chunk)
                # the FINAL chunk fuses the end-of-episode learn burst into
                # the same device program (ParallelDDPG.chunk_step) — no
                # host round-trip between the last rollout call and the
                # learner; results are bit-identical to the two-call path
                state, buffers, env_states, obs, stats, metrics = \
                    pddpg.chunk_step(state, buffers, env_states, obs, topo,
                                     traffic, start, chunk,
                                     learn=(c == n_chunks - 1))
                chunk_stats.append(stats)   # device scalars: convert AFTER
                # the episode is dispatched — a float() here would sync the
                # host every chunk and depress the measured wall rate
        with phase_span("drain", timer, hub):
            returns.append(sum(float(s["episodic_return"])
                               for s in chunk_stats))
            succ.append(sum(float(s["mean_succ_ratio"])
                            for s in chunk_stats) / len(chunk_stats))
            # end-of-episode slice: the final step's success ratio,
            # comparable to Trainer stats / the historical BENCH quality
            # bars
            final_succ.append(float(chunk_stats[-1]["final_succ_ratio"]))
        # everything the harness does for the hub after the drain, and the
        # caller's per-episode hook
        with phase_span("harness_observe", timer, hub):
            if hub is not None:
                # replica-resolved telemetry (the harness's own series — the
                # episodes_* counters belong to whoever drives the run).  The
                # event carries the GLOBAL episode index: per-episode drivers
                # (train_parallel) call with episodes=1 and a step_offset, so
                # the loop-local ep alone would stamp every record episode=0.
                global_ep = step_offset // episode_steps + ep
                per_rep = [np.asarray(s["per_replica_return"])
                           for s in chunk_stats if "per_replica_return" in s]
                rep_returns = (np.sum(per_rep, axis=0).tolist()
                               if per_rep else None)
                if rep_returns is not None:
                    for r, v in enumerate(rep_returns):
                        hub.gauge("replica_return", v, replica=str(r))
                per_topo = None
                if rep_returns is not None and topo_names:
                    groups = {}
                    for name, v in zip(topo_names, rep_returns):
                        groups.setdefault(name, []).append(v)
                    per_topo = {name: float(np.mean(vs))
                                for name, vs in groups.items()}
                    for name, v in per_topo.items():
                        hub.gauge("topology_return", v, topology=name)
                if buffers is not None and hasattr(buffers, "size"):
                    fills = np.asarray(buffers.size).tolist()
                    for r, fill in enumerate(fills):
                        hub.gauge("replica_replay_fill", fill, replica=str(r))
                # divergence-guard verdict for the episode: the rollout flags
                # (state entering each chunk) AND the learn burst's post-update
                # flag — all device scalars already synced by the drain;
                # absent on fakes/legacy stats (None, not a false alarm)
                finite = None
                flags = [s["state_finite"] for s in chunk_stats
                         if "state_finite" in s]
                if metrics is not None and "state_finite" in metrics:
                    flags.append(metrics["state_finite"])
                if flags:
                    finite = bool(min(float(f) for f in flags) > 0)
                hub.event("harness_episode", episode=global_ep,
                          episodic_return=returns[-1],
                          mean_succ_ratio=succ[-1],
                          final_succ_ratio=final_succ[-1],
                          per_replica_return=rep_returns,
                          state_finite=finite,
                          # mixed-topology attribution; absent (not null-
                          # spammed) on homogeneous runs to keep the legacy
                          # event schema byte-stable
                          **({"topology": list(topo_names),
                              "per_topology_return": per_topo}
                             if topo_names else {}))
                signal = (metrics or {}).get("learn_signal") \
                    if isinstance(metrics, dict) else None
                replay = chunk_stats[-1].get("replay") \
                    if isinstance(chunk_stats[-1], dict) else None
                if signal is not None or replay is not None:
                    # everything here was synced by the drain above — the
                    # emit is pure host bookkeeping, never a device wait
                    emit_learn_signal(hub, global_ep, signal=signal,
                                      replay=replay,
                                      segment_names=learn_names)
            if on_episode is not None:
                on_episode(ep, returns[-1], succ[-1], metrics)
    return state, buffers, returns, succ, final_succ
