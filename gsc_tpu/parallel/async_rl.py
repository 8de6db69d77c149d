"""Decoupled actor/learner training — the Sebulba shape (arXiv:2104.06272).

``Trainer.train_parallel`` interleaves acting and learning on ONE dispatch
path: the learner idles while rollouts run and vice versa.  This module
splits them:

- **Actor threads** run the jitted replica rollout continuously: each
  actor owns its own env replicas, PRNG stream and a small per-dispatch
  SCRATCH ring (capacity = one chunk), and ships every finished chunk's
  transition block — device-resident ``[B, chunk, ...]`` leaves, never a
  host copy — into the replay channel.  Between rollout dispatches the
  actor adopts newly published weights through an in-process
  :class:`~gsc_tpu.serve.fleet.VersionWatcher` (same between-dispatch
  swap discipline as the serving fleet: no batch ever mixes policy
  versions, because adoption only happens at chunk boundaries in the
  actor's own thread).

- The **learner loop** (the calling thread) owns the shared ``[B, cap]``
  replay ring: it folds queued transition blocks in via one jitted
  ``replay_ingest`` call per block (a donated in-place scatter — the
  MindSpeed-RL-style device-resident replay service; transition tensors
  never round-trip through the host on the steady path), runs
  ``learn_burst``s back-to-back on the freshest buffer state whenever its
  update budget allows, and publishes actor weights every
  ``publish_bursts`` bursts through the :class:`WeightPublisher` bus.

Off-policy staleness is the risk, so it is BOUNDED and MEASURED instead
of assumed away: ``max_staleness`` caps how many produced-but-uningested
env steps the actors may run ahead (the channel blocks the producer —
backpressure — and the wait is the ``actor_idle`` phase), the
``policy_lag`` gauge records how many published versions behind each
ingested block's acting policy was, and ``replay_lag`` gauges the
outstanding-step backlog at every ingest.  ``learn_ratio`` paces the
learner's update budget against ingested env steps (1.0 = the sync
control's one burst per B*episode_steps steps, so learning curves are
compared at matched gradient-step budgets); while the budget is unspent
the bursts dispatch back-to-back, and waiting for acting to unlock the
next burst is the ``learner_idle`` phase the ASYNC bench bounds.

Donation discipline across threads: the ParallelDDPG here must be built
with ``donate=False`` — actors hand their scratch blocks to the learner
by reference, so a donating rollout would consume buffers another thread
still reads.  The ONLY donated call is ``replay_ingest`` on the shared
ring, which exactly one thread (the learner) owns and always rebinds.

Mesh composition (``--async --mesh``): when the ParallelDDPG carries a
:class:`~gsc_tpu.parallel.partition.ShardingPlan`, the replay ring lives
dp-SHARDED on the learner mesh (``plan.ring_sharding`` — the same row
layout the sharded rollout already emits blocks in), and ``run_async``
kills the lazy-build race the old refusal guarded by pre-building
EVERYTHING before the first actor thread exists: the plan-bound dispatch
jits, then the sharded donated ingest — AOT-lowered so its partitioned
HLO can be mined and asserted collective-free (row-aligned ring/block/
cursor shardings make the scatter one independent per-shard donated
write; a block lands on the mesh once, in its final shard, and never
moves again).  Learn-bursts
dispatch through the same plan-bound binding the sync path uses (tp
rulebooks compose unchanged), and publishes gather params to host ONCE
so the actor watchers and the serving fleet's hot-swap read the same
weight bytes.

Self-healing (``fault_plan`` / ``rollback``): production fleets assume
workers die and restart routinely (Podracer, MindSpeed RL), so the loop
is SUPERVISED rather than fail-fast.  An :class:`ActorSupervisor` tracks
each actor's uncompleted episodes; a dead actor thread (exception or
injected ``actor_die``) is restarted from its episode counter within a
bounded per-actor restart budget, past which the fleet DEGRADES — the
dead actor's episodes are reassigned to survivors and the default
staleness cap is re-derived for the smaller fleet (never a hang: with
zero survivors and episodes unrun, the run raises the last actor error).
With ``rollback`` on, the learner finite-checks every popped block at
its drain boundary and QUARANTINES poisoned blocks (an evidence event
instead of an ingest — the ring never holds a NaN), and folds the
per-burst ``state_finite`` flag into a :class:`RollbackGuard`-backed
last-verified snapshot with one-burst-deferred verification, restoring
(state, ring) and continuing when a burst lands non-finite.  All of it
costs NOTHING when off: ``rollback=False`` + ``fault_plan=None`` (the
default for direct callers) adds no device dispatch, no sync and no
extra event to the fault-free path.  Every recovery flows through the
caller's ``on_recovery`` (the Trainer routes it to
``RunObserver.recovery``, same as the serial resilience ladder).
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..agents.buffer import ReplayBuffer, buffer_advance, buffer_nbytes
from ..resilience.faults import FaultInjected
from ..resilience.guard import RollbackGuard, all_finite, poison_tree
from ..resilience.retry import (RetryPolicy, TransientDispatchError,
                                call_with_retry)
from .partition import actor_shard_assignment, ring_shard_rows

log = logging.getLogger("gsc_tpu.parallel.async_rl")


@lru_cache(maxsize=None)
def make_replay_ingest(num_replicas: int, capacity: int, sharding=None):
    """The jitted replay service insert: fold one ``[B, T, ...]``
    transition block (an actor's scratch ring in insertion order) into
    the shared ``[B, cap, ...]`` ring at each replica's write cursor.

    The ring is DONATED — XLA scatters the block into the multi-MB replay
    in place instead of copying it per ingest — so the caller must own
    the ring exclusively and always rebind from the return (the learner
    loop does).  ``T`` is static (the actors' chunk size), so the whole
    async interleaving runs through ONE trace of this function.
    Memoized by ``(B, cap, sharding)``: a warmup ``run_async`` followed
    by a measured one (the bench split) reuses the SAME jit — the steady
    window stays zero-retrace across calls.

    With ``sharding`` (a plan's ``ring_sharding``): ring, block AND the
    per-replica cursors all carry the same row layout, and the fold runs
    under ``shard_map`` — each device scatters its OWN contiguous row
    block with LOCAL indices.  (Plain GSPMD cannot row-partition this
    scatter: the global ``[B, T]`` index arrays make it all-gather the
    ring — measured 28 all-gathers at 4 shards — while the shard_map
    body is collective-free by construction.)  The caller (``run_async``
    prewarm) AOT-lowers this jit and asserts the partitioned program
    contains ZERO collective ops."""
    B = int(num_replicas)

    def _fold(buffers: ReplayBuffer, block: Any, rows) -> ReplayBuffer:
        T = jax.tree_util.tree_leaves(block)[0].shape[1]
        # per-replica wrapped slot indices [rows, T] from the write cursor
        idx = (buffers.pos[:, None] + jnp.arange(T)[None, :]) % capacity
        data = jax.tree_util.tree_map(
            lambda d, s: d.at[rows, idx].set(s.astype(d.dtype)),
            buffers.data, block)
        return buffer_advance(buffers, data, T)

    if sharding is None:
        @partial(jax.jit, donate_argnums=(0,))
        def replay_ingest(buffers: ReplayBuffer,
                          block: Any) -> ReplayBuffer:
            return _fold(buffers, block, jnp.arange(B)[:, None])

        return replay_ingest

    mesh, spec = sharding.mesh, sharding.spec

    def _local_fold(buffers: ReplayBuffer, block: Any) -> ReplayBuffer:
        # runs per-device on the shard's own rows: cursors/ring/block all
        # arrive pre-sliced, so the row indices are a local iota
        return _fold(buffers, block,
                     jnp.arange(buffers.pos.shape[0])[:, None])

    # check_vma off: every output is fully row-partitioned — there is
    # nothing replicated for the varying-axes checker to validate
    sharded_fold = jax.shard_map(_local_fold, mesh=mesh,
                                 in_specs=(spec, spec), out_specs=spec,
                                 check_vma=False)

    @partial(jax.jit, donate_argnums=(0,),
             in_shardings=(sharding, sharding), out_shardings=sharding)
    def replay_ingest(buffers: ReplayBuffer, block: Any) -> ReplayBuffer:
        return sharded_fold(buffers, block)

    return replay_ingest


def _finite_host(tree) -> bool:
    """Host-side all-finite verdict (syncs the tree — publish cadence
    only, same discipline as train_parallel's pre-publish gate)."""
    return all(bool(np.isfinite(np.asarray(l)).all())
               for l in jax.tree_util.tree_leaves(tree))


# the quarantine probe: ONE device-side reduction per popped block, read
# as a single host scalar — the verdict lands host-side (the drain
# boundary's `_finite_host` discipline) without transferring the block.
# Module-level jit so a warmup/measured run pair shares the trace.
_block_finite = jax.jit(all_finite)


@dataclass
class AsyncConfig:
    """Knobs for the decoupled actor/learner loop."""

    actor_threads: int = 2
    # learner->actor weight publish cadence, in learn bursts
    publish_bursts: int = 1
    # max produced-but-uningested env steps the actors may run ahead of
    # the learner (the off-policy staleness bound; the channel BLOCKS the
    # producer past it).  0 = two full episodes per actor, the default
    # that keeps a slow learner from unbounded off-policy drift without
    # throttling a healthy fleet (one episode being acted plus one queued
    # behind the learner's ingest dispatch).
    max_staleness: int = 0
    # learner update budget per ingested env step, relative to the sync
    # control (1.0 = one burst per B*episode_steps ingested steps — the
    # matched-gradient-budget setting the curve-equivalence bands assume)
    learn_ratio: float = 1.0
    # test hook: artificial per-burst learner delay (the staleness-bound
    # tests slow the learner down to force backpressure); 0 in production
    throttle_s: float = 0.0
    # seconds the learner waits per idle poll (granularity of the
    # learner_idle phase, not a rate limit)
    idle_wait_s: float = 0.002
    # supervised restarts per ACTOR before the fleet degrades to fewer
    # actors (the dead actor's episodes are reassigned to survivors)
    restart_budget: int = 2


class _Channel:
    """Bounded actor->learner conduit of device-resident transition
    blocks.  ``put`` blocks while the outstanding (produced - ingested)
    step backlog would exceed ``max_outstanding`` — that wait IS the
    staleness backpressure.  Every block carries a global FIFO ``seq``
    (the flight recorder's put->pop flow-arrow key) plus its enqueue
    wall time and the backpressure wait it paid."""

    def __init__(self, max_outstanding: int):
        self.max_outstanding = int(max_outstanding)
        self._cond = threading.Condition()
        self._blocks: deque = deque()   # guarded-by: self._cond
        self.produced_steps = 0         # guarded-by: self._cond
        self.ingested_steps = 0         # guarded-by: self._cond
        self.max_observed_lag = 0       # guarded-by: self._cond
        self._seq = 0                   # guarded-by: self._cond
        self._stop = False              # guarded-by: self._cond

    def outstanding(self) -> int:
        # writers call this under the cond; the learner/drain monitoring
        # reads tolerate one-block staleness (ints, GIL-atomic)
        return self.produced_steps - self.ingested_steps  # gsc-lint: disable=R7 -- put() holds the cond; monitor reads tolerate staleness

    def put(self, block, steps: int, version: int, shard: int = 0,
            timer=None,
            on_wait: Optional[Callable[[float], None]] = None) -> int:
        """Enqueue one block; returns its seq (>=1, truthy), or 0 when
        the run is stopping.  ``shard`` is the producing actor's stable
        dp-shard assignment (0 on an unsharded ring) — it rides the
        queue so the learner's per-shard ingest heartbeats and the
        flight recorder's ``replay_shard`` tags attribute each block
        without a host sync.  ``on_wait(seconds)`` receives each
        backpressure slice (the per-actor idle the flight recorder
        attributes)."""
        with self._cond:
            while (not self._stop and self._blocks
                   and self.outstanding() + steps > self.max_outstanding):
                t0 = time.perf_counter()
                self._cond.wait(0.05)
                waited = time.perf_counter() - t0
                if timer is not None:
                    timer.add("actor_idle", waited)
                if on_wait is not None:
                    on_wait(waited)
            if self._stop:
                return 0
            self._seq += 1
            self._blocks.append((block, int(steps), int(version),
                                 self._seq, int(shard)))
            self.produced_steps += int(steps)
            self.max_observed_lag = max(self.max_observed_lag,
                                        self.outstanding())
            self._cond.notify_all()
            return self._seq

    def get_nowait(self):
        with self._cond:
            if not self._blocks:
                return None
            item = self._blocks.popleft()
            self.ingested_steps += item[1]
            self._cond.notify_all()
            return item

    def wait_for_data(self, timeout: float):
        with self._cond:
            if not self._blocks:
                self._cond.wait(timeout)

    def set_max_outstanding(self, n: int):
        """Re-derive the backpressure cap (fleet degrade path): blocked
        producers wake and re-check against the new bound, so shrinking
        the cap can never wedge a putter mid-wait."""
        with self._cond:
            self.max_outstanding = int(n)
            self._cond.notify_all()

    def stop(self):
        with self._cond:
            self._stop = True
            self._cond.notify_all()


class _ActorPolicy:
    """In-process 'server' end of the VersionWatcher protocol for one
    actor: ``apply_weights`` runs IN the actor's own thread (poll_once is
    called between rollout dispatches), so the adopted params can never
    reach a batch mid-flight — the actor-side analogue of the fleet's
    flush-lock discipline."""

    def __init__(self, treedef):
        self.treedef = treedef
        self.policy_version = 0
        self.params = None

    def apply_weights(self, leaves, version: int, fingerprint,
                      meta: Optional[Dict] = None):
        self.params = jax.tree_util.tree_unflatten(self.treedef,
                                                   list(leaves))
        self.policy_version = int(version)


class ActorSupervisor:
    """Per-actor episode bookkeeping + the restart/degrade policy.

    Each actor owns an ordered queue of its UNCOMPLETED episodes (seeded
    with its strided assignment).  ``claim`` returns the head WITHOUT
    popping — an actor that dies mid-episode re-runs that episode from
    its start on restart (``complete`` pops only after the episode's
    stats are staged, so a finished episode is never re-run; chunks a
    dying actor already shipped are ingested twice on the re-run —
    benign replay duplicates, never corruption, and drained records
    never duplicate because stats only append at completion).

    Failures queue here and the LEARNER loop supervises: within the
    per-actor ``restart_budget`` it spawns a fresh thread resuming from
    the dead actor's episode counter; past it the actor is degraded out
    — its remaining episodes move to the orphan queue that surviving
    actors drain after their own assignments (episode data is
    scenario/seed-keyed by GLOBAL index, so WHO runs an episode never
    changes WHAT it trains on).  With zero survivors and episodes still
    unrun the learner raises the last actor error — the fleet never
    hangs and never silently under-runs."""

    def __init__(self, assignments: Dict[int, List[int]],
                 restart_budget: int):
        self._lock = threading.Lock()
        # aid -> uncompleted episodes in run order (head = next to
        # (re)run); guarded-by: self._lock
        self._remaining = {aid: deque(eps)
                           for aid, eps in assignments.items()}
        self._orphans: deque = deque()     # guarded-by: self._lock
        self._failures: deque = deque()    # guarded-by: self._lock
        self.restart_budget = int(restart_budget)
        # restarts/dead/errors: mutated by the learner thread only (the
        # single supervisor), read post-join — no extra locking needed
        self.restarts = {aid: 0 for aid in assignments}
        self.dead: set = set()
        self.errors: List[BaseException] = []

    def claim(self, aid: int) -> Optional[int]:
        """The actor's next episode (head, not popped), refilled from a
        degraded actor's orphans once its own queue drains; None when
        there is nothing left to run."""
        with self._lock:
            q = self._remaining[aid]
            if not q and self._orphans:
                q.append(self._orphans.popleft())
            return q[0] if q else None

    def complete(self, aid: int, episode: int):
        with self._lock:
            q = self._remaining[aid]
            if q and q[0] == episode:
                q.popleft()

    def report_failure(self, aid: int, episode: int, exc: BaseException):
        """Called from the dying actor thread; the learner's supervise
        pass decides restart vs degrade."""
        with self._lock:
            self._failures.append((aid, episode, exc))

    def pop_failure(self):
        with self._lock:
            return self._failures.popleft() if self._failures else None

    def note_restart(self, aid: int) -> int:
        self.restarts[aid] += 1
        return self.restarts[aid]

    def degrade(self, aid: int, exc: BaseException) -> int:
        """Move the dead actor's episodes to the orphan queue; returns
        the number of actors still alive."""
        with self._lock:
            self.dead.add(aid)
            self._orphans.extend(self._remaining[aid])
            self._remaining[aid].clear()
            self.errors.append(exc)
            return len(self._remaining) - len(self.dead)

    def unrun(self) -> int:
        with self._lock:
            return (sum(len(q) for q in self._remaining.values())
                    + len(self._orphans))

    def total_restarts(self) -> int:
        return sum(self.restarts.values())


class _FlightLedger:
    """Host-side flight recorder for one ``run_async``: actor threads and
    the learner append plain tuples (one lock, one list append — no
    device syncs, no event emission on the dispatch path); the run end
    flushes everything as compact deferred events (``async_actor_ep``,
    ``async_learner_spans``) that :func:`gsc_tpu.obs.trace.build_trace`
    reconstructs per-actor / channel / learner tracks plus put->pop and
    publish->adopt flow arrows from.  Timestamps are ``time.time()``
    (the event stream's wall base, so the reconstructed spans land on
    the same axis as every other track).

    Row shapes (positional, kept terse because they land in JSONL):

    - actor episode: ``{ep, actor, shard, chunks: [[t0, t1, ver], ...],
      puts: [[t_enq, wait_s, steps, ver, seq], ...],
      adopts: [[ts, ver], ...]}``
    - ingest: ``[t0, t1, steps, ver, lag, seq, shard]`` (``shard`` is
      the producing actor's dp-shard assignment — the ``replay_shard``
      tag on the reconstructed learner spans; 0 on an unsharded ring)
    - burst: ``[t0, t1, n]`` / publish: ``[ts, ver]``
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.actor_eps: List[Dict] = []   # guarded-by: self._lock
        self.ingests: List[List] = []     # guarded-by: self._lock
        self.bursts: List[List] = []      # guarded-by: self._lock
        self.publishes: List[List] = []   # guarded-by: self._lock

    def note_actor_episode(self, rec: Dict):
        with self._lock:
            self.actor_eps.append(rec)

    def note_ingest(self, t0, t1, steps, version, lag, seq, shard=0):
        with self._lock:
            self.ingests.append([round(t0, 6), round(t1, 6), int(steps),
                                 int(version), int(lag), int(seq),
                                 int(shard)])

    def note_burst(self, t0, t1, n):
        with self._lock:
            self.bursts.append([round(t0, 6), round(t1, 6), int(n)])

    def note_publish(self, ts, version):
        with self._lock:
            self.publishes.append([round(ts, 6), int(version)])

    def flush_deferred(self, hub, chunk_rows: int = 256):
        """Emit the deferred event records (run end, learner thread).
        Learner spans chunk at ``chunk_rows`` rows per event so one
        record never outgrows the rotating sink's line budget."""
        with self._lock:
            actor_eps = list(self.actor_eps)
            ingests = list(self.ingests)
            bursts = list(self.bursts)
            publishes = list(self.publishes)
        for rec in actor_eps:
            hub.event("async_actor_ep", **rec)
        total = max(len(ingests), len(bursts), len(publishes))
        parts = max(1, -(-total // chunk_rows))
        for p in range(parts):
            lo, hi = p * chunk_rows, (p + 1) * chunk_rows
            hub.event("async_learner_spans", part=p, parts=parts,
                      ingests=ingests[lo:hi], bursts=bursts[lo:hi],
                      publishes=publishes[lo:hi])


@dataclass
class AsyncResult:
    """What one decoupled run produced, for the caller's bookkeeping."""

    state: Any
    buffers: Any
    episodes: List[Dict] = field(default_factory=list)   # completion order
    info: Dict = field(default_factory=dict)


def run_async(pddpg, scenario_fn: Callable, state, buffers,
              episodes: int, episode_steps: int, chunk: int, seed: int,
              cfg: AsyncConfig, publisher=None, hub=None, timer=None,
              on_episode: Optional[Callable] = None,
              on_burst: Optional[Callable] = None,
              should_stop: Optional[Callable] = None,
              start_episode: int = 0, checkpoint_every: int = 0,
              checkpoint_fn: Optional[Callable] = None,
              fault_plan=None, rollback: bool = False,
              on_recovery: Optional[Callable] = None,
              retry_policy=None) -> AsyncResult:
    """Drive ``episodes - start_episode`` episodes through
    ``cfg.actor_threads`` rollout threads feeding the learner loop (the
    calling thread).  ``scenario_fn(ep) -> (topo, traffic)`` supplies
    episode ``ep``'s scenario (called from actor threads under one shared
    lock — host scenario production stays serialized and
    episode-deterministic).  ``on_episode(record, buffers)`` fires in
    the LEARNER thread as each actor episode's stats drain (record
    carries episode / return / succ ratios / policy_version / actor;
    buffers is the live ring, for fill/bytes gauges).  ``on_burst(n,
    state, metrics)`` fires after each learn burst (metrics are live
    device values — callers must not sync them in the hot loop).
    ``should_stop()`` polled at episode boundaries (preemption).
    ``checkpoint_fn(state, buffers, episodes_drained)`` fires in the
    learner thread every ``checkpoint_every`` drained episodes — the
    only thread that owns the carries, so a save can never race a
    rebind.

    With a plan-carrying ``pddpg`` (``--async --mesh``) a prewarm
    builds every jit before the first actor thread starts: the
    plan-bound dispatch, then the dp-sharded donated ingest (AOT-lowered
    and asserted collective-free).  The ring is placed into
    ``plan.ring_sharding`` residency here, so callers may hand a
    single-device ring.  Tp-only meshes (no dp axis) are refused up
    front via ``plan.assert_async_capable()``.

    Self-healing: ``fault_plan`` (a
    :class:`~gsc_tpu.resilience.faults.FaultPlan`) arms the fleet's
    injection sites (``actor_die``/``ring_poison``/``watcher_stall``
    keyed by actor episode, ``nan_grads``/``learner_transient`` keyed by
    learn-burst index); ``rollback=True`` arms the drain-boundary block
    quarantine and the burst-deferred :class:`RollbackGuard` snapshot;
    ``on_recovery(episode, site=, action=, fault=, attempt=, detail=)``
    receives every recovery (the Trainer routes it to
    ``RunObserver.recovery``); ``retry_policy`` bounds the transient
    learn-burst retries.  Actor supervision (restart within
    ``cfg.restart_budget``, then degrade) is ALWAYS on — a dead actor
    only kills the run once the whole fleet is exhausted.  The module
    docstring has the full ladder; everything here is free when the
    knobs stay at their defaults.

    Returns an :class:`AsyncResult`; ``info`` carries the drain-proved
    accounting: produced == ingested steps (no transition lost), the
    learner idle fraction, burst count, publish count, the observed
    policy/replay lag extrema, the self-healing ledger
    (``actor_restarts``/``actors_degraded``/``blocks_quarantined``/
    ``rollbacks``) and — under a plan — ``ring_shards`` and the
    AOT-mined ``ingest_collectives`` (always 0, by assertion)."""
    plan = getattr(pddpg, "plan", None)
    if plan is not None:
        plan.assert_async_capable()
    return _run_async_impl(
        pddpg, scenario_fn, state, buffers, episodes, episode_steps,
        chunk, seed, cfg, publisher=publisher, hub=hub, timer=timer,
        on_episode=on_episode, on_burst=on_burst, should_stop=should_stop,
        start_episode=start_episode, checkpoint_every=checkpoint_every,
        checkpoint_fn=checkpoint_fn, fault_plan=fault_plan,
        rollback=rollback, on_recovery=on_recovery,
        retry_policy=retry_policy)


def _run_async_impl(pddpg, scenario_fn: Callable, state, buffers,
                    episodes: int, episode_steps: int, chunk: int,
                    seed: int, cfg: AsyncConfig, publisher=None, hub=None,
                    timer=None, on_episode: Optional[Callable] = None,
                    on_burst: Optional[Callable] = None,
                    should_stop: Optional[Callable] = None,
                    start_episode: int = 0, checkpoint_every: int = 0,
                    checkpoint_fn: Optional[Callable] = None,
                    fault_plan=None, rollback: bool = False,
                    on_recovery: Optional[Callable] = None,
                    retry_policy=None) -> AsyncResult:
    """The loop body of :func:`run_async` (which owns the plan
    validation and the run-wide compile-cache guard)."""
    from ..serve.fleet import VersionWatcher, WeightPublisher

    B = pddpg.B
    if episode_steps % chunk != 0:
        raise ValueError(f"chunk ({chunk}) must divide episode_steps "
                         f"({episode_steps})")
    cap = int(jax.tree_util.tree_leaves(buffers.data)[0].shape[1])
    if cap < chunk:
        raise ValueError(
            f"replay capacity per replica ({cap}) must be >= chunk "
            f"({chunk}) — a single ingest would wrap past itself")
    n_actors = max(1, int(cfg.actor_threads))
    total_eps = episodes - start_episode
    if total_eps <= 0:
        return AsyncResult(state=state, buffers=buffers)
    # default backlog cap: TWO episodes' worth of steps per actor — one
    # being acted plus one queued behind the learner's ingest dispatch
    # (which can wait on the ring's in-flight burst readers); a
    # one-episode cap throttles a healthy fleet into device bubbles
    # while the policy-version lag stays burst-paced (~1-2) either way
    max_stale = (int(cfg.max_staleness) if cfg.max_staleness > 0
                 else 2 * n_actors * B * episode_steps)
    channel = _Channel(max_stale)
    results: deque = deque()
    results_lock = threading.Lock()
    stop_event = threading.Event()
    # the actors' first dispatches serialize under this lock so each
    # entry point traces exactly once (two threads racing an empty jit
    # cache would both trace — the zero-retrace contract forbids that)
    compile_lock = threading.Lock()
    scenario_lock = threading.Lock()
    # quarantine + burst-rollback machinery only exists on guarded runs:
    # the bare path (no plan, no rollback) dispatches nothing extra
    guarded = rollback or fault_plan is not None

    def recover(episode, site, action, fault=None, attempt=None,
                detail=None):
        if on_recovery is not None:
            on_recovery(episode, site=site, action=action, fault=fault,
                        attempt=attempt, detail=detail)
        else:
            log.warning("recovery: site=%s action=%s fault=%s "
                        "episode=%s %s", site, action, fault, episode,
                        detail or "")

    if publisher is None:
        # in-process channel only; the plan rides along so
        # publish_corrupt@v<N> can corrupt the zero-copy path too
        publisher = WeightPublisher(hub=hub, fault_plan=fault_plan)
    elif fault_plan is not None and getattr(publisher, "fault_plan",
                                            None) is None:
        publisher.fault_plan = fault_plan

    plan = getattr(pddpg, "plan", None)
    n_shards = plan.n_devices if plan is not None else 1
    # stable actor->dp-shard assignment (observability contract: which
    # shard's heartbeat each actor's blocks bump — see partition.py)
    shard_of = actor_shard_assignment(n_actors, n_shards)
    # the multi-device enqueue-order serializer (see
    # ParallelDDPG.dispatch_lock): rollout/learn_burst dispatches
    # already hold it inside their wrappers; the learner's ingest
    # dispatch below shares it.  Single-device runs hold nothing.
    dispatch_lock = getattr(pddpg, "dispatch_lock", None) \
        if plan is not None else None
    if dispatch_lock is None:
        dispatch_lock = _noop()
    ingest_collectives = None
    if plan is not None:
        # ---- prewarm: every jit exists BEFORE the first actor thread —
        # the lazy-build race the old --mesh refusal guarded is dead
        # code on this path.  (1) the plan-bound dispatch binding (one
        # build populates rollout/chunk/learn jits);
        pddpg.sharded_lowerable("rollout_episodes", state)
        # (2) the ring's resident layout: rows carved over the dp grid
        # exactly like the blocks the sharded rollout emits (a no-op
        # when the caller already placed it);
        buffers = jax.device_put(buffers, plan.ring_sharding)
        ring_shard_rows(B, n_shards)   # validates B % shards == 0
        # (3) the per-shard donated ingest, AOT-lowered so the
        # PARTITIONED program's HLO proves the hot path moves nothing:
        # zero gather/reshard/collective ops, just each shard's own
        # row-aligned scatter.  The compiled executable IS the dispatch
        # handle — block shapes are static, so the steady state cannot
        # retrace by construction.
        from ..analysis.hlo import collective_stats
        ingest_jit = make_replay_ingest(B, cap,
                                        sharding=plan.ring_sharding)

        def _placed_zeros(leaf_shape_fn, tree):
            return jax.tree_util.tree_map(
                lambda l: jax.device_put(
                    jnp.zeros(leaf_shape_fn(l), l.dtype),
                    plan.ring_sharding), tree)

        warm_ring = _placed_zeros(lambda l: l.shape, buffers)
        warm_block = _placed_zeros(
            lambda l: (l.shape[0], chunk) + l.shape[2:], buffers.data)
        compiled = ingest_jit.lower(warm_ring, warm_block).compile()
        stats = collective_stats(compiled.as_text())
        ingest_collectives = int(stats["count"])
        if ingest_collectives:
            raise RuntimeError(
                f"dp-sharded replay_ingest compiled with "
                f"{ingest_collectives} collective op(s) "
                f"({sorted(stats['ops'])}) — the ingest hot path must "
                f"be a pure per-shard write; the ring/block shardings "
                f"have diverged from plan.ring_sharding")
        replay_ingest = compiled
        del warm_ring, warm_block   # donation fodder, never dispatched
    else:
        replay_ingest = make_replay_ingest(B, cap)
    treedef = jax.tree_util.tree_structure(state.actor_params)
    base = jax.random.PRNGKey(seed)

    # episode ownership: actor a runs global episodes start+a, start+a+A,
    # ... — deterministic regardless of thread scheduling
    def actor_episodes(aid):
        return range(start_episode + aid, episodes, n_actors)

    supervisor = ActorSupervisor(
        {a: list(actor_episodes(a)) for a in range(n_actors)},
        restart_budget=cfg.restart_budget)
    # last successful publish (version, params): a restarted actor seeds
    # its policy from here — its fresh watcher inbox only sees FUTURE
    # publishes.  Written by the learner thread, read by (re)starting
    # actors; the tuple rebind is atomic and the params tree immutable.
    latest_pub: List = [None]

    policy_lags: List[int] = []
    # flight recorder: the ledger only exists when the hub keeps series
    # history — with it off, run_async emits not one extra event and the
    # stream stays byte-identical to the pre-recorder pipeline
    ledger = (_FlightLedger() if hub is not None
              and getattr(hub, "series_store", None) is not None else None)
    # per-actor backpressure wait accumulators (each slot written by its
    # own actor thread only) — the live actor_idle_frac probes read them
    actor_wait_s = [0.0] * n_actors
    learner_idle_acc = [0.0]

    # the actors' starting point, bound BEFORE the learner loop ever
    # rebinds `state`: a restarted actor must stage from the same
    # published-or-initial params as a first start, never from whatever
    # unpublished learner state happens to be live at restart time
    # (donate=False on this path keeps these buffers valid for the whole
    # run)
    init_state = state

    def actor_loop(aid: int):
        tname = f"actor{aid}"
        policy = _ActorPolicy(treedef)
        watcher = VersionWatcher(None, policy, hub=hub,
                                 publisher=publisher)
        # every actor starts from the published-or-initial params with
        # its OWN rng stream (identical streams would collapse the
        # exploration the replica axis exists to diversify)
        a_state = init_state.replace(
            rng=jax.random.fold_in(init_state.rng, 1000 + aid))
        pub = latest_pub[0]
        if pub is not None:
            # a RESTARTED actor re-adopts the latest published weights
            # instead of regressing to the initial params (its fresh
            # inbox only sees future publishes); on the first start
            # nothing has been published and this is a no-op
            policy.apply_weights(
                jax.tree_util.tree_leaves(pub[1]), pub[0], None)
            a_state = a_state.replace(actor_params=policy.params)
        first = True
        n_chunks = episode_steps // chunk
        ep = -1   # the episode in flight, for the failure report

        def on_wait(waited: float):
            # one slot per actor, written only by this thread
            actor_wait_s[aid] += waited
            if hub is not None:
                hub.beat(tname)   # a backpressured actor is NOT wedged

        try:
            while True:
                if stop_event.is_set():
                    return
                nxt = supervisor.claim(aid)
                if nxt is None:
                    return
                ep = nxt
                if fault_plan is not None and fault_plan.fire(
                        "actor_die", ep, actor=aid) is not None:
                    raise FaultInjected(
                        f"injected actor death: actor_die@a{aid}:{ep}")
                with scenario_lock:
                    topo, traffic = scenario_fn(ep)
                lock = compile_lock if first else None
                if lock is not None:
                    lock.acquire()
                try:
                    env_states, obs = pddpg.reset_all(
                        jax.random.fold_in(
                            jax.random.PRNGKey(seed + ep + 2), 0),
                        topo, traffic)
                    if first:
                        one_obs = jax.tree_util.tree_map(
                            lambda x: x[0], obs)
                        scratch = pddpg.init_buffers(one_obs,
                                                     capacity=chunk)
                    chunk_stats = []
                    chunks = []
                    puts = []
                    adopts = []
                    for c in range(n_chunks):
                        # between-dispatch weight adoption: poll_once
                        # runs HERE, in the actor's own thread, so a
                        # swap can never land mid-batch (the fleet's
                        # flush-lock discipline, by construction)
                        if hub is not None:
                            hub.note_thread_phase(tname, "adopt")
                        try:
                            spec = (fault_plan.fire("watcher_stall", ep,
                                                    actor=aid)
                                    if fault_plan is not None else None)
                            if spec is not None:
                                if spec.arg:
                                    time.sleep(float(spec.arg))
                                raise FaultInjected(
                                    f"injected watcher stall: "
                                    f"watcher_stall@a{aid}:{ep}")
                            swapped = watcher.poll_once()
                        except Exception as e:
                            # a stalled/failing poll must not kill the
                            # actor: skip THIS adoption, keep acting on
                            # the current weights, adopt next chunk
                            swapped = False
                            recover(ep, site="watcher",
                                    action="skip_adopt",
                                    fault=type(e).__name__,
                                    detail=f"actor {aid}: {e}")
                        if swapped:
                            a_state = a_state.replace(
                                actor_params=policy.params)
                            if ledger is not None:
                                adopts.append([round(time.time(), 6),
                                               policy.policy_version])
                        start = jnp.int32(ep * episode_steps + c * chunk)
                        if hub is not None:
                            hub.note_thread_phase(tname, "dispatch")
                        t_roll = time.time()
                        with (timer.phase("actor_dispatch") if timer
                              else _noop()):
                            # R8 disabled below: the sharded binding's
                            # wrapper takes dispatch_lock itself
                            # (dp._bind_sharded_dispatch); the single-
                            # device path has no partition rendezvous
                            # to serialize
                            (a_state, scratch, env_states, obs,
                             stats) = pddpg.rollout_episodes(  # gsc-lint: disable=R8 -- wrapper holds dispatch_lock
                                a_state, scratch, env_states, obs,
                                topo, traffic, start, chunk)
                        if ledger is not None:
                            chunks.append([round(t_roll, 6),
                                           round(time.time(), 6),
                                           policy.policy_version])
                        chunk_stats.append(stats)
                        if hub is not None:
                            hub.note_thread_phase(tname, "blocked_put")
                        out_block = scratch.data
                        if fault_plan is not None and fault_plan.fire(
                                "ring_poison", ep) is not None:
                            # poison a COPY: scratch is this actor's
                            # live carry for the next rollout dispatch
                            out_block = poison_tree(scratch.data)
                        wait0 = actor_wait_s[aid]
                        seq = channel.put(out_block, B * chunk,
                                          policy.policy_version,
                                          shard=shard_of[aid],
                                          timer=timer, on_wait=on_wait)
                        if not seq:
                            return
                        if ledger is not None:
                            puts.append([
                                round(time.time(), 6),
                                round(actor_wait_s[aid] - wait0, 6),
                                B * chunk, policy.policy_version, seq])
                        if hub is not None:
                            hub.beat(tname)   # liveness = chunk cadence
                finally:
                    if lock is not None:
                        lock.release()
                        first = False
                if ledger is not None:
                    ledger.note_actor_episode({
                        "ep": ep, "actor": aid, "shard": shard_of[aid],
                        "chunks": chunks, "puts": puts, "adopts": adopts})
                with results_lock:
                    results.append({"episode": ep, "actor": aid,
                                    "policy_version":
                                        policy.policy_version,
                                    "chunk_stats": chunk_stats})
                supervisor.complete(aid, ep)
        except BaseException as e:   # supervised by the learner loop
            supervisor.report_failure(aid, ep, e)
            log.exception("actor %d died", aid)
        finally:
            watcher.stop()   # drops the publisher subscription; an
            # externally-owned publisher must not keep dead inboxes fed

    threads = [threading.Thread(target=actor_loop, args=(a,),
                                name=f"gsc-actor-{a}", daemon=True)
               for a in range(n_actors)]
    steps_per_burst = B * episode_steps   # the sync control's cadence
    bursts = publishes = last_ckpt = 0
    blocks_quarantined = steps_quarantined = 0
    drained: List[Dict] = []
    last_metrics = None
    guard = None
    pending_verify = None   # (burst_idx, device flag) awaiting its sync
    if rollback:
        guard = RollbackGuard()
        # seed with the (trivially finite) entry state so a poisoned
        # FIRST burst still has a restore target
        guard.init(start_episode - 1, state, buffers)
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    if hub is not None:
        # live idle-fraction probes: a mid-run /metrics scrape reads the
        # CURRENT fractions, not the last event-writer sample.  Replaced
        # by plain final gauges (and dropped) at run end.
        def _idle_probe(slot, acc):
            def probe():
                wall = time.perf_counter() - t_start
                return acc[slot] / wall if wall > 0 else 0.0
            return probe
        for a in range(n_actors):
            hub.live_gauge("actor_idle_frac",
                           _idle_probe(a, actor_wait_s), actor=a)
        hub.live_gauge("learner_idle_frac",
                       _idle_probe(0, learner_idle_acc))

    def allowance() -> int:
        return int(channel.ingested_steps * cfg.learn_ratio
                   // steps_per_burst)

    def maybe_publish(force: bool = False):
        nonlocal publishes
        if not force and (cfg.publish_bursts <= 0
                          or bursts % cfg.publish_bursts != 0):
            return
        if plan is not None:
            # ONE gather per publish: pull the (possibly resident-
            # sharded) actor params to host numpy here, once.  The
            # publisher's npz flatten is then a zero-copy pass-through
            # and every in-process subscriber (actor watchers) receives
            # the same host leaves the serving fleet's hot-swap reads
            # from disk — one publisher, two consumers, one gather.
            params = jax.tree_util.tree_map(
                lambda l: np.asarray(jax.device_get(l)),
                state.actor_params)
            finite = _finite_host(params)
        else:
            params = state.actor_params
            finite = _finite_host(params)
        if finite:
            # verified=True: the gate above already proved the leaves
            # finite, so the publisher skips its own (redundant) scan
            publisher.publish(params, meta={"burst": bursts,
                                            "episodes": len(drained)},
                              verified=True)
            latest_pub[0] = (publisher.version, params)
            publishes += 1
            if ledger is not None:
                ledger.note_publish(time.time(), publisher.version)
        else:
            log.warning("non-finite actor params at burst %d — publish "
                        "skipped so a poisoned state never reaches the "
                        "actors", bursts)
            if hub is not None:
                hub.counter("async_publish_skipped_total")

    def do_rollback(episode, detail):
        nonlocal state, buffers, pending_verify
        tag, s, b = guard.restore()
        state, buffers = s, b   # fresh copies — donation-safe carries
        pending_verify = None   # descendants of the poisoned state
        recover(episode, site="learner_state", action="rollback",
                fault="non_finite_state",
                detail=f"{detail}; restored last-verified snapshot "
                       f"(tag {tag})")
        if hub is not None:
            hub.counter("async_rollbacks_total")

    def verify_pending():
        """One-burst-deferred finite verdict: the LAST burst's
        ``state_finite`` flag syncs here (a single device scalar) right
        before the next burst dispatches — the flag's compute has had a
        full loop pass to finish, so the read rarely blocks the hot
        path.  Finite promotes the live carries to the guard's
        last-verified snapshot (blocks ingested since the burst are
        quarantine-checked, so the ring is still clean); non-finite
        restores that snapshot and the run continues."""
        nonlocal pending_verify
        if guard is None or pending_verify is None:
            return
        b_idx, flag = pending_verify
        pending_verify = None
        if bool(float(flag) > 0.0):
            guard.promote(b_idx, state, buffers, pending_empty=True)
        else:
            do_rollback(len(drained),
                        f"learn-burst {b_idx} landed non-finite")

    def check_stop():
        # polled at EVERY progress point, not just the outer loop top: a
        # fast actor fleet can finish the whole run inside one inner
        # ingest/drain pass, and a stop that only lands between passes
        # would never actually stop anything
        if should_stop is not None and not stop_event.is_set() \
                and should_stop():
            stop_event.set()   # actors stop at the next boundary; the
            # learner still DRAINS everything already produced

    def drain_results():
        while True:
            check_stop()
            with results_lock:
                if not results:
                    return
                rec = results.popleft()
            stats = rec.pop("chunk_stats")
            # device scalars, synced HERE (learner thread) so actors
            # never block on a host round-trip
            rec["episodic_return"] = sum(
                float(s["episodic_return"]) for s in stats)
            rec["mean_succ_ratio"] = (sum(
                float(s["mean_succ_ratio"]) for s in stats) / len(stats))
            rec["final_succ_ratio"] = float(
                stats[-1]["final_succ_ratio"])
            flags = [float(s["state_finite"]) for s in stats
                     if "state_finite" in s]
            rec["state_finite"] = bool(min(flags) > 0) if flags else None
            if guard is not None and rec["state_finite"] is False:
                # the actor acted on a non-finite state: same restore
                # path as a poisoned burst — the per-episode flag folds
                # into the guard instead of merely riding the record
                do_rollback(rec["episode"],
                            f"episode {rec['episode']} drained with a "
                            f"non-finite state flag")
            drained.append(rec)
            if on_episode is not None:
                on_episode(rec, buffers)

    actors_alive = lambda: any(t.is_alive() for t in threads)  # noqa: E731

    def spawn_actor(aid: int, suffix: str = ""):
        t = threading.Thread(target=actor_loop, args=(aid,),
                             name=f"gsc-actor-{aid}{suffix}", daemon=True)
        threads.append(t)
        t.start()

    def supervise():
        """Drain queued actor failures (learner thread only): restart
        within the per-actor budget, else degrade the fleet — reassign
        the dead actor's episodes to survivors and re-derive the default
        staleness cap for the smaller fleet."""
        while True:
            fail = supervisor.pop_failure()
            if fail is None:
                return
            aid, at_ep, exc = fail
            if stop_event.is_set():
                # stopping anyway: record the death, respawn nothing
                supervisor.degrade(aid, exc)
                continue
            if supervisor.restarts[aid] < supervisor.restart_budget:
                n = supervisor.note_restart(aid)
                recover(at_ep, site="actor", action="restart",
                        fault=type(exc).__name__, attempt=n,
                        detail=f"actor {aid} died at episode {at_ep}; "
                               f"restarting from its episode counter "
                               f"({n}/{supervisor.restart_budget})")
                if hub is not None:
                    hub.counter("actor_restarts_total")
                spawn_actor(aid, suffix=f"-r{n}")
            else:
                alive = supervisor.degrade(aid, exc)
                detail = (f"actor {aid} exhausted its restart budget "
                          f"({supervisor.restart_budget}); fleet "
                          f"degrades to {alive} actor(s)")
                if cfg.max_staleness <= 0 and alive > 0:
                    new_cap = 2 * alive * B * episode_steps
                    channel.set_max_outstanding(new_cap)
                    detail += f"; staleness cap re-derived to {new_cap}"
                recover(at_ep, site="actor", action="degrade",
                        fault=type(exc).__name__, detail=detail)
                if hub is not None:
                    hub.counter("actor_degraded_total")

    try:
        while True:
            supervise()
            check_stop()
            progressed = False
            # pop EVERYTHING queued before dispatching a single ingest:
            # the pop is what releases the staleness backpressure, and an
            # ingest dispatch can block on the ring's pending readers
            # (donating the ring while the in-flight learn_burst still
            # samples it makes the runtime wait for the burst) — popping
            # first keeps the actors dispatching through that wait
            # instead of stalling the whole fleet one pop per blocked
            # dispatch
            items = []
            item = channel.get_nowait()
            while item is not None:
                items.append(item)
                item = channel.get_nowait()
            for block, steps, version, seq, shard in items:
                if guarded:
                    # drain-boundary quarantine: ONE device reduction +
                    # one scalar host read per popped block.  A poisoned
                    # block is DROPPED with an evidence row — the ring
                    # never holds a NaN, and the drain accounting still
                    # balances (the pop already counted the steps as
                    # ingested; the quarantined tally rides info).
                    with dispatch_lock:
                        block_ok = bool(float(_block_finite(block)) > 0.0)
                    if not block_ok:
                        blocks_quarantined += 1
                        steps_quarantined += int(steps)
                        recover(len(drained), site="replay",
                                action="quarantine",
                                fault="non_finite_block",
                                detail=f"seq={seq} shard={shard} "
                                       f"steps={steps} version={version}")
                        if hub is not None:
                            hub.counter("replay_quarantined_total")
                            hub.event("replay_quarantine", seq=int(seq),
                                      shard=int(shard), steps=int(steps),
                                      policy_version=int(version))
                        progressed = True
                        check_stop()
                        continue
                if hub is not None:
                    hub.note_thread_phase("learner", "ingest")
                t_ing = time.time()
                with (timer.phase("replay_ingest") if timer
                      else _noop()):
                    # a multi-device ingest dispatch must not interleave
                    # its per-device enqueues with an actor's rollout
                    # dispatch (the XLA:CPU rendezvous deadlock — see
                    # ParallelDDPG.dispatch_lock); single-device runs
                    # hold no lock
                    with dispatch_lock:
                        buffers = replay_ingest(buffers, block)
                lag = publisher.version - version
                policy_lags.append(lag)
                outstanding = channel.outstanding()
                if ledger is not None:
                    ledger.note_ingest(t_ing, time.time(), steps, version,
                                  lag, seq, shard)
                if hub is not None and n_shards > 1:
                    # per-shard ingest heartbeat: a cold shard names a
                    # wedged actor (the stable assignment), without any
                    # device sync — counter + beat are host-side
                    hub.counter("replay_shard_ingest_total", shard=shard)
                    hub.gauge("replay_shard_ingest_seq", seq, shard=shard)
                    hub.beat(f"replay_shard{shard}")
                if hub is not None:
                    # gauges keep the PR 16 last-value semantics; the
                    # histograms add mid-run p50/p99/max to /metrics and
                    # the rings add history to /series — same samples,
                    # three read paths
                    hub.gauge("policy_lag", lag)
                    hub.gauge("replay_lag", outstanding)
                    hub.observe("policy_lag", lag)
                    hub.observe("replay_lag", outstanding)
                    hub.series("policy_lag", lag)
                    hub.series("replay_lag", outstanding)
                    hub.beat("learner")
                progressed = True
                check_stop()
            drain_results()
            if (checkpoint_every and checkpoint_fn is not None
                    and len(drained) - last_ckpt >= checkpoint_every):
                last_ckpt = len(drained)
                checkpoint_fn(state, buffers, len(drained))
            if bursts < allowance():
                verify_pending()   # may rollback + rebind the carries
                b_idx = bursts     # 0-based index of this burst
                if fault_plan is not None and fault_plan.fire(
                        "nan_grads", b_idx) is not None:
                    # async nan_grads is BURST-keyed: poison the state
                    # entering this burst; the deferred flag catches it
                    # one burst later and the guard restores
                    state = state.replace(
                        actor_params=poison_tree(state.actor_params))
                if hub is not None:
                    hub.note_thread_phase("learner", "learn_burst")
                t_burst = time.time()

                def dispatch_burst():
                    if fault_plan is not None and fault_plan.fire(
                            "learner_transient", b_idx) is not None:
                        raise TransientDispatchError(
                            f"injected transient at learn-burst {b_idx}")
                    with (timer.phase("learn_dispatch") if timer
                          else _noop()):
                        # R8 disabled below: same invariant as the
                        # actor's rollout dispatch — the sharded
                        # learn_burst wrapper takes dispatch_lock
                        # itself (dp.py)
                        return pddpg.learn_burst(state, buffers)  # gsc-lint: disable=R8 -- wrapper holds dispatch_lock

                if guarded:
                    # the transient class retries with backoff (the
                    # fault fires at entry, before anything dispatches,
                    # so a re-run consumes nothing)
                    state, last_metrics = call_with_retry(
                        dispatch_burst, retry_policy or RetryPolicy(),
                        on_retry=lambda attempt, exc, delay: recover(
                            len(drained), site="learner", action="retry",
                            fault=type(exc).__name__, attempt=attempt,
                            detail=f"learn-burst {b_idx}: {exc} "
                                   f"(backoff {delay:.2f}s)"))
                else:
                    state, last_metrics = dispatch_burst()
                bursts += 1
                if guard is not None and hasattr(last_metrics, "get"):
                    flag = last_metrics.get("state_finite")
                    if flag is not None:
                        pending_verify = (b_idx, flag)
                if ledger is not None:
                    ledger.note_burst(t_burst, time.time(), bursts)
                if hub is not None:
                    hub.beat("learner")
                if cfg.throttle_s:
                    time.sleep(cfg.throttle_s)
                if on_burst is not None:
                    on_burst(bursts, state, last_metrics)
                maybe_publish()
                progressed = True
            if not progressed:
                if not actors_alive() and channel.outstanding() == 0:
                    supervise()   # a just-queued failure may restart
                    if actors_alive() or channel.outstanding():
                        continue
                    if supervisor.unrun() and not stop_event.is_set():
                        # orphans with no live owner: respawn a cleanly-
                        # exited actor to drain them (degraded actors
                        # stay dead); with every actor past its budget,
                        # raise — never hang, never silently under-run
                        cand = [a for a in range(n_actors)
                                if a not in supervisor.dead]
                        if cand:
                            recover(len(drained), site="actor",
                                    action="restart", fault=None,
                                    detail=f"actor {cand[0]} respawned "
                                           f"to drain "
                                           f"{supervisor.unrun()} "
                                           f"orphaned episode(s)")
                            spawn_actor(cand[0], suffix="-orphans")
                            continue
                        raise RuntimeError(
                            f"async fleet exhausted: every actor is "
                            f"past its restart budget "
                            f"({supervisor.restart_budget}) with "
                            f"{supervisor.unrun()} episode(s) unrun"
                        ) from supervisor.errors[-1]
                    break
                if hub is not None:
                    hub.note_thread_phase("learner", "idle")
                    hub.beat("learner")   # an idle learner is not wedged
                t0 = time.perf_counter()
                channel.wait_for_data(cfg.idle_wait_s)
                waited = time.perf_counter() - t0
                learner_idle_acc[0] += waited
                if timer is not None:
                    timer.add("learner_idle", waited)
    finally:
        stop_event.set()
        channel.stop()
        for t in threads:
            t.join(timeout=30.0)
    drain_results()
    # final deferred verdict: with rollback on, the returned state is
    # ALWAYS verified — a burst poisoned at the very end restores here,
    # so preemption snapshots and final checkpoints never hold a NaN
    verify_pending()
    # graceful drain: nothing in flight, nothing lost, no future hung
    jax.block_until_ready((state, buffers))
    wall = time.perf_counter() - t_start
    idle_s = learner_idle_acc[0]
    if timer is not None:
        idle_s = (timer.summary().get("learner_idle")
                  or {}).get("total_s", idle_s)
    lag_sorted = sorted(policy_lags)
    pct = lambda q: (lag_sorted[min(int(q * len(lag_sorted)),  # noqa: E731
                                    len(lag_sorted) - 1)]
                     if lag_sorted else 0)
    actor_fracs = [round(w / wall, 4) if wall > 0 else 0.0
                   for w in actor_wait_s]
    info = {
        "actors": n_actors,
        "episodes_drained": len(drained),
        "produced_steps": channel.produced_steps,
        "ingested_steps": channel.ingested_steps,
        "transitions_lost": (channel.produced_steps
                             - channel.ingested_steps),
        "bursts": bursts,
        "publishes": publishes,
        "published_version": publisher.version,
        "max_staleness": max_stale,
        "max_replay_lag": channel.max_observed_lag,
        "policy_lag_max": max(policy_lags) if policy_lags else 0,
        "policy_lag_mean": (round(float(np.mean(policy_lags)), 4)
                            if policy_lags else 0.0),
        "policy_lag_p50": pct(0.50),
        "policy_lag_p99": pct(0.99),
        "wall_s": round(wall, 4),
        "learner_idle_s": round(idle_s, 4),
        "learner_idle_frac": round(idle_s / wall, 4) if wall > 0 else 0.0,
        "actor_idle_fracs": actor_fracs,
        "actor_idle_frac": max(actor_fracs) if actor_fracs else 0.0,
        "ring_shards": n_shards,
        "mesh": plan.describe() if plan is not None else None,
        # self-healing ledger (all zero on a clean run; the chaos stage
        # and bench_diff's informational keys read these)
        "actor_restarts": supervisor.total_restarts(),
        "actors_degraded": len(supervisor.dead),
        "blocks_quarantined": blocks_quarantined,
        "steps_quarantined": steps_quarantined,
        "rollbacks": guard.rollbacks if guard is not None else 0,
        # AOT-mined collective count on the ingest hot path; the prewarm
        # RAISES if it is ever nonzero, so a plan run always reports 0
        "ingest_collectives": ingest_collectives,
    }
    if hub is not None:
        # live probes made way for final plain gauges (a post-run scrape
        # must read the run's verdict, not a stale wall-clock fraction)
        hub.drop_live_gauge("learner_idle_frac")
        hub.gauge("learner_idle_frac", info["learner_idle_frac"])
        hub.series("learner_idle_frac", info["learner_idle_frac"])
        for a, frac in enumerate(actor_fracs):
            hub.drop_live_gauge("actor_idle_frac", actor=a)
            hub.gauge("actor_idle_frac", frac, actor=a)
            hub.series("actor_idle_frac", frac, actor=a)
        hub.gauge("actor_policy_version", publisher.version)
        # ring residency accounting: global bytes vs THIS host's
        # addressable-shard bytes (buffer_nbytes(local=True)) — under a
        # dp-sharded ring on a multi-host pod the local gauge is the
        # true per-host HBM spend; on one host they coincide.  Metadata
        # reads only, no device sync.
        hub.gauge("replay_ring_bytes", buffer_nbytes(buffers))
        hub.gauge("replay_ring_local_bytes",
                  buffer_nbytes(buffers, local=True))
        hub.gauge("replay_ring_shards", n_shards)
        if ledger is not None:
            ledger.flush_deferred(hub)
    return AsyncResult(state=state, buffers=buffers,
                       episodes=drained, info=info)


class _noop:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
