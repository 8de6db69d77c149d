"""Training driver — the host loop around the jitted rollout/learn kernels.

The analogue of SimpleDDPG.train + the experiment plumbing of
src/rlsp/agents/main.py: per episode it picks the scheduled topology,
samples traffic (host), dispatches the episode's device work, and logs
episode metrics (rewards.csv like result_writer.py:6-38, optional
TensorBoard like simple_ddpg.py:165-174).

The default ``pipeline=True`` path keeps the accelerator saturated between
episodes (Podracer-style, arXiv:2104.06272): a background thread PREFETCHES
episode k+1's topology/traffic (staged to device) while episode k runs, the
rollout scan and learn burst run as ONE fused jitted ``episode_step`` (no
host round-trip between them), per-episode metric syncs are DEFERRED one
episode so ``np.asarray`` never gates the next dispatch, and the replay
buffer / env-state carries are donated (updated in place in HBM instead of
copied every episode).  Results are bit-identical to the serial path —
per-episode PRNG streams are ``fold_in``-keyed by the episode index, so
look-ahead cannot perturb them and exact resume is preserved.
"""
from __future__ import annotations

import contextlib
import csv
import logging
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import DROP_REASONS, AgentConfig
from ..env.driver import EpisodeDriver
from ..env.env import ServiceCoordEnv
from ..obs.trace import emit_episode_spans, episode_span, phase_span
from ..resilience.faults import FaultInjected
from ..resilience.guard import RollbackGuard, all_finite, poison_tree
from ..resilience.retry import (RetryPolicy, TransientDispatchError,
                                call_with_retry)
from ..utils.debug import check_invariants
from ..utils.telemetry import PhaseTimer
from .buffer import buffer_nbytes, lockstep_cursor, pieced_leaves
from .ddpg import DDPG, DDPGState

log = logging.getLogger("gsc_tpu.agents.trainer")

# the replica path's boundary gates (Trainer._finite_device)
_all_finite_jit = jax.jit(all_finite)


class RewardsWriter:
    """rewards.csv with the live writer's schema (result_writer.py:23: field
    'r')."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self._file = None
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._file = open(path, "w", newline="")
            self._csv = csv.DictWriter(self._file, fieldnames=["r"])
            self._csv.writeheader()

    def write(self, reward: float):
        if self._file:
            self._csv.writerow({"r": reward})
            self._file.flush()

    def close(self):
        if self._file:
            self._file.close()


class Trainer:
    def __init__(self, env: ServiceCoordEnv, driver: EpisodeDriver,
                 agent_cfg: AgentConfig, seed: int = 0,
                 result_dir: Optional[str] = None,
                 tensorboard: bool = False, gnn_impl: str = None,
                 donate: bool = True, obs=None,
                 check_invariants: bool = False,
                 fault_plan=None, rollback: bool = True,
                 retry_policy: Optional[RetryPolicy] = None,
                 pipeline_fault_limit: int = 3):
        self.env = env
        self.driver = driver
        self.agent_cfg = agent_cfg
        self.seed = seed
        # --- resilience (gsc_tpu.resilience) -------------------------
        # fault_plan: deterministic injection schedule (FaultPlan) — None
        # in production; every recovery path below has a test through it
        self.fault_plan = fault_plan
        # rollback=True keeps a last-good in-memory snapshot of the
        # (state, replay) carries and restores it when the on-device
        # all-finite guard flags a poisoned learner state.  Costs two
        # device-side pytree copies per episode + ~2 retained replay
        # copies in HBM; with no violation the training math is
        # bit-identical either way (copies never enter the update path).
        self.rollback = rollback
        self.retry_policy = retry_policy or RetryPolicy()
        # pipeline faults (prefetcher death / watchdog-escalation
        # interrupts) beyond this limit degrade pipeline -> off for the
        # remainder of the run: serial host sampling + immediate drains
        # (the fused dispatch kernel itself is unaffected)
        self.pipeline_fault_limit = pipeline_fault_limit
        # set by train()/train_parallel(): episodes completed when the
        # loop exited (monotone resume counter) and whether a preemption
        # guard stopped it early — the CLI checkpoints off these
        self.completed_episodes = 0
        self.preempted = False
        self._last_drained = -1
        self._live_prefetch = None   # watchdog-escalation interrupt target
        # run observability (gsc_tpu.obs.RunObserver): events.jsonl +
        # metrics.json + device gauges + pipeline watchdog.  The trainer
        # only reports into it; lifecycle (start/close) belongs to the
        # caller (cli train wraps the whole run).
        self.obs = obs
        # opt-in per-episode simulator invariant check (utils.debug) —
        # violations surface as structured ``invariant_violation`` events
        # (and WARNs) instead of a silently-returned list
        self.check_invariants = check_invariants
        # learning-signal ledger (obs.learning): when the observer owns a
        # LearnLedger, thread its STATIC spec into the jitted agents so
        # the dispatched programs fold per-topology |TD| segments, Q
        # moments, layer norms and replay stats into their existing
        # outputs.  No observer / bare observer => spec None => the
        # historic traces, byte for byte.
        self.learn_obs = getattr(obs, "learn", None) \
            if obs is not None else None
        ledger_spec = None
        if self.learn_obs is not None:
            ledger_spec = self.learn_obs.spec(
                getattr(driver, "num_topo_ids", 1),
                getattr(driver, "topo_id_names", None))
        # donation is on by default: the training loops always rebind the
        # carries from the kernel returns, so in-place HBM updates of the
        # replay/env-state are safe; pass donate=False for comparison
        # drivers that re-call kernels on the same inputs
        self.ddpg = DDPG(env, agent_cfg, gnn_impl=gnn_impl, donate=donate,
                         learn_ledger=ledger_spec)
        if self.obs is not None:
            # param/compute/replay dtype gauges + one precision event so
            # run-to-run throughput comparisons can attribute speedups to
            # the dtype policy (bench rows carry the same field)
            self.obs.record_precision(agent_cfg.precision_policy)
        self.result_dir = result_dir
        # per-phase host wall timings of the last train() call
        # (utils.telemetry.PhaseTimer) — how much host time hid behind
        # device compute; populated by train(), logged at loop end
        self.phase_timer = None
        self.rewards_writer = RewardsWriter(
            os.path.join(result_dir, "rewards.csv") if result_dir else None)
        self.tb = None
        if tensorboard and result_dir:
            try:  # torch's TB writer, mirroring simple_ddpg.py:165
                from torch.utils.tensorboard import SummaryWriter
                self.tb = SummaryWriter(os.path.join(result_dir, "tb"))
            except ImportError:
                pass
        self.history: List[Dict[str, float]] = []

    def _log(self, episode: int, global_step: int, stats, learn_metrics,
             sps: float):
        row = {k: float(np.asarray(v)) for k, v in stats.items()}
        if learn_metrics is not None:
            row.update({k: float(np.asarray(v))
                        for k, v in learn_metrics.items()})
        row.update(episode=episode, sps=sps)
        self.history.append(row)
        self.rewards_writer.write(row["episodic_return"])
        if self.tb:
            self.tb.add_scalar("charts/episodic_return",
                               row["episodic_return"], global_step)
            self.tb.add_scalar("charts/SPS", sps, global_step)
            if learn_metrics is not None:
                self.tb.add_scalar("losses/qf1_loss", row["critic_loss"],
                                   global_step)
                self.tb.add_scalar("losses/actor_loss", row["actor_loss"],
                                   global_step)
                self.tb.add_scalar("losses/qf1_values", row["q_values"],
                                   global_step)

    def _drain(self, entry, start_time: float, start_episode: int,
               verbose: bool, timer) -> bool:
        """Sync one pending episode's device metrics to host and log it.
        On the pipelined path this runs one episode BEHIND the dispatch
        head, so the ``np.asarray`` syncs here wait on device work that has
        already been followed by the next episode's dispatch — the chip
        never idles on host-side logging.

        Returns the episode's all-finite verdict (the on-device guard
        flags computed inside ``episode_step``, drained here with the
        other deferred metrics): False means the learner state this
        episode saw or produced is poisoned and the caller should roll
        back."""
        ep, end_step, stats, learn_metrics, trunc_dev, sim, topo, \
            replay_bytes = entry
        hub = self.obs.hub if self.obs else None
        finite = True
        with phase_span("drain", timer, hub):
            # force the episode's device work complete BEFORE reading the
            # wall clock: sps must divide by time that includes the
            # episode's compute, not the async-dispatch return time
            jax.block_until_ready((stats, learn_metrics, trunc_dev))
            # learn-ledger extras are non-scalar (TD segment vectors,
            # layer-norm dicts): split them off before the scalar row
            # conversion below — already synced by the block above, so
            # the host-side emit later reads them for free
            replay = stats.pop("replay", None) \
                if isinstance(stats, dict) else None
            signal = learn_metrics.pop("learn_signal", None) \
                if isinstance(learn_metrics, dict) else None
            steps_per_ep = self.agent_cfg.episode_steps
            sps = ((ep - start_episode + 1) * steps_per_ep
                   / (time.time() - start_time))
            trunc = int(np.asarray(trunc_dev))
            if trunc > 0:
                # overload: the flow table (or per-substep arrival budget)
                # saturated, so some arrivals spawned late — generated-flow
                # timing no longer matches the reference's unbounded model
                log.warning(
                    "episode=%d: %d arrivals admitted late (flow-table "
                    "slot exhaustion) — raise SimConfig.max_flows to "
                    "restore exact arrival timing", ep, trunc)
            # divergence verdict: the rollout flag covers the state the
            # episode STARTED from, the learn flag the post-update state
            # — both already synced by the block above, so these asarray
            # reads are free
            if "state_finite" in stats:
                finite = bool(np.asarray(stats["state_finite"]) > 0)
            if learn_metrics is not None \
                    and "state_finite" in learn_metrics:
                finite = finite and bool(
                    np.asarray(learn_metrics["state_finite"]) > 0)
            self._log(ep, end_step, stats, learn_metrics, sps)
            if verbose:
                # per-episode progress line (the reference's tqdm + SPS
                # TensorBoard log, simple_ddpg.py:269-271) via the package
                # logger — setup_logging routes it to console + run.log
                log.info(
                    "episode=%d return=%.3f succ=%.3f sps=%.1f", ep,
                    float(np.asarray(stats["episodic_return"])),
                    float(np.asarray(stats["mean_succ_ratio"])), sps)
        # observability work sits OUTSIDE the drain span: the drain phase
        # measures time blocked on device→host metric syncs, not host-side
        # bookkeeping — and the emitted event then carries phase totals
        # that include the drain just finished
        with phase_span("episode_log", timer, hub):
            if self.check_invariants:
                # promoted from utils.debug: per drained episode, the final
                # sim state is checked host-side and violations become
                # structured events rather than a silently-returned list.
                # (check_invariants is a module-level import — a per-episode
                # lazy import here cost an import-system round-trip inside
                # the drain path, flagged by gsc-lint's hot-loop review.)
                errs = check_invariants(sim, topo, self.env.tables.chain_len)
                if errs:
                    log.warning("episode=%d simulator invariants violated: %s",
                                ep, "; ".join(errs))
                    if self.obs:
                        # routed through the sentinel event pathway (counter +
                        # structured event), same family as `compile` events
                        self.obs.invariant_violation(ep, errs)
            if self.obs:
                row = self.history[-1]
                # topology identity on the SERIAL path too: mixed batches get
                # per-replica names through the harness, but a single-replica
                # run's episodes must land in the same per-topology report
                # tables — stamp the scheduled network's name on the event
                # and gauge its return
                extra = self._topology_extra(ep, row["episodic_return"])
                self.obs.episode_end(
                    episode=ep, global_step=end_step,
                    metrics={k: v for k, v in row.items()
                             if k not in ("episode", "sps")},
                    sps=sps, phases=timer.summary(),
                    drop_reasons=dict(zip(
                        DROP_REASONS,
                        np.asarray(sim.metrics.drop_reasons).tolist())),
                    truncated_arrivals=trunc, replay_bytes=replay_bytes,
                    extra=extra)
                if self.learn_obs is not None and (signal is not None
                                                   or replay is not None):
                    # drained learning signal -> learn_signal event + gauges
                    # (values synced above; nothing here waits on the device)
                    self.learn_obs.episode(ep, signal=signal, replay=replay)
        return finite

    @staticmethod
    def _next_episode_span(root, timer, hub, ep: int, preempt) -> bool:
        """Top of a loop iteration: close the open root ``episode`` span
        (``root``, an ``ExitStack``) and open episode ``ep``'s — the root
        closes where the next opens, so everything an iteration does lies
        under it and what no child covers is its self time — then read
        the stop test under ``preempt_check``.  Returns whether to stop."""
        root.close()
        timer.episode = ep
        root.enter_context(phase_span("episode", timer, hub))
        with phase_span("preempt_check", timer, hub):
            return preempt is not None and preempt.triggered

    # ---------------------------------------------------------- resilience
    def _recover(self, episode: int, site: str, action: str,
                 fault: Optional[str] = None, attempt: Optional[int] = None,
                 detail: Optional[str] = None):
        """Log + emit one structured ``recovery`` event (obs.RunObserver)
        for a self-healing action — the recovery timeline every fault
        path below reports through."""
        log.warning("recovery: site=%s action=%s episode=%s fault=%s%s",
                    site, action, episode, fault,
                    f" ({detail})" if detail else "")
        if self.obs is not None:
            self.obs.recovery(episode=episode, site=site, action=action,
                              fault=fault, attempt=attempt, detail=detail)

    def _topology_extra(self, episode: int, episodic_return,
                        extra: Optional[Dict] = None) -> Optional[Dict]:
        """Topology identity for one drained episode (BOTH train paths):
        gauge ``topology_return{topology=<name>}`` and return the episode
        event's ``extra`` dict with the name stamped in — the one rule
        behind the serial drain and the homogeneous replica loop, so the
        per-topology tables obs_report merges can never diverge between
        them.  No-op (returns ``extra`` unchanged) without an observer or
        a nameable driver."""
        namer = getattr(self.driver, "topology_name_for", None)
        name = namer(episode) if namer is not None else None
        if not name or self.obs is None:
            return extra
        self.obs.hub.gauge("topology_return", float(episodic_return),
                           topology=name)
        return {**(extra or {}), "topology": name}

    @staticmethod
    def _finite_host(tree) -> bool:
        """Host-side all-finite scan over a (host-layout) pytree's
        inexact leaves: the async path's checkpoint gate, whose state is
        already gathered to the host for the save."""
        return all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree_util.tree_leaves(tree)
                   if np.issubdtype(np.asarray(leaf).dtype, np.inexact))

    @staticmethod
    def _finite_device(tree) -> bool:
        """The same verdict made where the state lives — the replica
        path's stand-in for the rollback guard: one jitted all-finite
        over the tree's inexact leaves (``resilience.guard.all_finite``)
        and ONE boolean read back, so no leaf crosses to the host for
        the check (a learner state of gigabytes would idle the chip for
        seconds at every boundary).  ONE definition shared by the chaos
        verify, the periodic-checkpoint gate and the hot-swap-publish
        gate in ``train_parallel``, so they can never diverge on what
        counts as a poisoned state."""
        return bool(_all_finite_jit(tree) > 0)

    # -------------------------------------------------------- cost ledger
    @staticmethod
    def _gauge_row_pieces(hub, buf) -> None:
        """How the learn burst fetches the ring's rows, for the program
        being built: ``replay_leaves_in_pieces`` ring leaves (and
        ``replay_row_pieces`` pieces in all) are too wide to gather in
        place and are fetched in column pieces (``buffer.take_rows``)."""
        pieces = pieced_leaves(buf)
        hub.gauge("replay_leaves_in_pieces", len(pieces))
        hub.gauge("replay_row_pieces", sum(pieces))

    @staticmethod
    def _ledger_fn(owner, name: str):
        """The dispatched-executable resolver (obs.perf.resolve_lowerable)
        — kept as a method so both train paths read the same way."""
        from ..obs.perf import resolve_lowerable
        return resolve_lowerable(owner, name)

    def _capture_costs(self, names_args: Dict[str, tuple]):
        """Feed the observer's device-cost ledger (obs.perf.CostLedger):
        AOT-lower each watched entry point ONCE, before the episode loop,
        so FLOPs/bytes/fusion counts are captured at compile time and the
        dispatch path itself stays sync-free.  ``names_args`` maps entry
        name -> (fn, args, kwargs); lowering never executes the program,
        so passing the live (donation-bound) carries is safe.  Best
        effort: a cost-model failure is a warning, never a dead run."""
        perf = getattr(self.obs, "perf", None) if self.obs else None
        if perf is None:
            return
        with phase_span("cost_capture", self.phase_timer, self.obs.hub):
            for name, (fn, args, kwargs) in names_args.items():
                perf.capture(name, fn, args, kwargs)

    def _note_cost_timings(self, timer, primary: Optional[str]):
        """Merge the run's measured host wall into the ledger AFTER the
        loop: the ``dispatch`` phase total attributes to the primary
        FUSED entry point (its calls are exactly what the phase wraps),
        and the full phase summary rides along as the device-vs-host
        split.  ``primary=None`` on the serial two-call path — there the
        dispatch phase covers rollout AND learn burst, and splitting it
        per entry would fabricate MFU numbers, so serial runs keep
        static costs + phases only."""
        perf = getattr(self.obs, "perf", None) if self.obs else None
        if perf is None or timer is None:
            return
        phases = timer.summary()
        disp = phases.get("dispatch")
        if primary is not None and disp:
            perf.note_timing(primary, disp["total_s"], disp["count"])
            if perf.has(f"{primary}_sharded"):
                # under a plan the dispatched program IS the partitioned
                # executable — the same dispatch wall attributes to its
                # capture too, so its MFU/roofline derive from the HLO
                # that actually ran (the plain entry keeps the
                # carving-comparable number)
                perf.note_timing(f"{primary}_sharded", disp["total_s"],
                                 disp["count"])
        perf.note_phases(phases)

    def _prefetch_fault_hook(self):
        """``before_episode`` hook for the prefetcher's producer thread —
        the injection point of the two producer-side fault sites."""
        plan = self.fault_plan
        if plan is None:
            return None

        def hook(ep: int, stop_event):
            spec = plan.fire("slow_episode", ep)
            if spec is not None:
                # interruptible: wakes the moment close() abandons this
                # producer (so an escalation-triggered restart is not
                # gated on the full injected delay)
                stop_event.wait(spec.arg if spec.arg is not None else 1.0)
            spec = plan.fire("prefetch_die", ep)
            if spec is not None:
                raise FaultInjected(
                    f"injected prefetcher death at episode {ep}")
        return hook

    def _on_watchdog_escalate(self, age: float):
        """Watchdog escalation callback (runs on the watchdog thread):
        interrupt the live prefetcher so the training loop — possibly
        blocked inside ``prefetch.get`` — wakes with a
        ``PrefetchInterrupted`` and restarts it from the episode counter
        (safe: the pipeline is bit-identical to serial sampling, so
        re-staging an episode reproduces it exactly)."""
        pf = self._live_prefetch
        if pf is not None:
            pf.interrupt(f"watchdog escalation: no completed episode in "
                         f"{age:.1f}s")

    def _dispatch_with_retry(self, ep, pipeline, state, buffer, env_state,
                             obs, topo, traffic, global_step, learn, timer,
                             hub):
        """One episode's device dispatch under the bounded-backoff retry
        policy.  Returns the 6-tuple (state, buffer, env_state, obs,
        stats, learn_metrics) on both dispatch shapes.

        The injected ``dispatch_transient`` fault raises at call entry —
        before the kernels consume any donated carry — so a retry
        re-dispatches untouched buffers; a REAL transient that aborted
        mid-program may have invalidated them, in which case the retry
        fails fast with XLA's donation error and propagates (see
        resilience.retry)."""
        plan = self.fault_plan

        # one donating call site per function scope: gsc-lint's R2
        # use-after-donation scan is linear and would read the serial
        # branch's rollout_episode(state, ...) as a use after the fused
        # branch's episode_step donated `state` — mutually exclusive
        # branches, but split closures make that obvious to the tool too
        def dispatch_fused():
            with phase_span("dispatch", timer, hub), episode_span(ep):
                return self.ddpg.episode_step(
                    state, buffer, env_state, obs, topo, traffic,
                    np.int32(global_step), learn=learn)

        def dispatch_serial():
            with phase_span("dispatch", timer, hub), episode_span(ep):
                st, buf, es, ob, stats = self.ddpg.rollout_episode(
                    state, buffer, env_state, obs, topo, traffic,
                    np.int32(global_step))
                metrics = None
                if learn:
                    st, metrics = self.ddpg.learn_burst(st, buf)
                return st, buf, es, ob, stats, metrics

        body = dispatch_fused if pipeline else dispatch_serial

        def dispatch():
            if plan is not None:
                spec = plan.fire("dispatch_transient", ep)
                if spec is not None:
                    raise TransientDispatchError(
                        "injected transient dispatch failure at episode "
                        f"{ep}")
            return body()

        return call_with_retry(
            dispatch, self.retry_policy,
            on_retry=lambda attempt, exc, delay: self._recover(
                ep, site="dispatch", action="retry", fault=repr(exc),
                attempt=attempt,
                detail=f"backing off {delay:.2f}s before re-dispatch"))

    def train(self, episodes: int, test_mode: bool = False,
              verbose: bool = False, profile: bool = False,
              init_state: Optional[DDPGState] = None,
              init_buffer=None, start_episode: int = 0,
              pipeline: bool = True, ckpt_manager=None,
              ckpt_interval: int = 0, preempt=None,
              publisher=None, publish_interval: int = 0):
        """Train through episode ``episodes - 1`` (train-at-episode-end
        schedule, simple_ddpg.py:280-329).  Returns (final learner state,
        replay buffer).  With ``profile`` a jax profiler trace of the run is
        written to <result_dir>/profile (SURVEY.md §5 tracing analogue).

        ``pipeline=True`` (default) runs the asynchronous episode pipeline:
        prefetched host traffic, one fused rollout+learn device call per
        episode, and metric draining deferred one episode behind dispatch.
        ``pipeline=False`` is the serial reference loop (two device calls
        per episode, synced logging) — results are bit-identical either
        way; the flag only changes host/device scheduling.

        Exact resume: pass a restored (``init_state``, ``init_buffer``,
        ``start_episode``) triple and the continuation reproduces an
        uninterrupted run bit-for-bit — per-episode keys derive from
        ``fold_in(seed, episode)`` rather than a sequential split chain, so
        the host-side stream needs no replay (the device-side stream lives
        in DDPGState.rng, which the checkpoint carries).  The reference
        cannot do this: it never saves optimizer or replay state
        (main.py:46-50, SURVEY.md §5).

        Self-healing (gsc_tpu.resilience), every action a structured
        ``recovery`` event:

        - transient dispatch failures retry with bounded exponential
          backoff (``Trainer(retry_policy=...)``);
        - a dead/interrupted prefetcher is restarted from the episode
          counter (bit-identical re-staging), and past
          ``pipeline_fault_limit`` faults the run degrades pipeline->off;
        - a non-finite learner state (on-device guard flags drained with
          the deferred metrics) rolls back to the last-good snapshot and
          skips the poisoned episode(s);
        - ``ckpt_manager`` + ``ckpt_interval`` write checksummed periodic
          checkpoints of the last VERIFIED state;
        - ``preempt`` (a resilience.PreemptionGuard) stops the loop at the
          next episode boundary after SIGTERM/SIGINT — the caller then
          snapshots ``(state, buffer)`` at ``self.completed_episodes``.

        Train-while-serve: ``publisher`` (a
        :class:`~gsc_tpu.serve.fleet.WeightPublisher`) + a positive
        ``publish_interval`` publish the actor params as a versioned
        hot-swap artifact every N drained-finite episodes — a
        concurrently running serving fleet's VersionWatchers pick each
        version up between dispatches.  With the rollback guard on
        (default), what ships is the guard's VERIFIED snapshot — the
        same state a periodic checkpoint saves — so a poisoned state is
        never published (the live carry is one dispatch ahead and
        unverified).  ``Trainer(rollback=False)`` has no verified
        snapshot and falls back to the live params.  Host gather at
        checkpoint-like cadence, never on the per-episode path."""
        if getattr(self.driver, "topo_mix", None):
            # the mix fills a replica axis this path does not have —
            # silently training one topology would fake mixture coverage
            raise ValueError(
                "topo_mix needs the replica-parallel path "
                "(train_parallel / --replicas > 1); the single-env loop "
                "has no batch axis to fill with the mixture")
        if profile and self.result_dir:
            from ..utils.debug import Profiler
            with Profiler(os.path.join(self.result_dir, "profile")):
                return self.train(episodes, test_mode, verbose,
                                  profile=False, init_state=init_state,
                                  init_buffer=init_buffer,
                                  start_episode=start_episode,
                                  pipeline=pipeline,
                                  ckpt_manager=ckpt_manager,
                                  ckpt_interval=ckpt_interval,
                                  preempt=preempt, publisher=publisher,
                                  publish_interval=publish_interval)
        self.phase_timer = timer = PhaseTimer()
        hub = self.obs.hub if self.obs else None
        base = jax.random.PRNGKey(self.seed)
        steps_per_ep = self.agent_cfg.episode_steps
        plan = self.fault_plan
        guard = RollbackGuard() if self.rollback else None
        self.preempted = False
        self._last_drained = start_episode - 1
        if ckpt_interval and ckpt_manager is not None and guard is None:
            log.warning("periodic checkpoints need the rollback guard's "
                        "verified snapshots (Trainer(rollback=True)) — "
                        "--ckpt-interval is ignored this run")

        if self.ddpg.donate:
            # restored carries (orbax checkpoints, caller-held pytrees) may
            # alias each other or host-owned storage; donation needs
            # exclusively-owned device buffers — donating a restored state
            # aborts the process on the CPU backend (pending_donation_
            # check).  Re-materialize once before the first donated
            # dispatch, mirroring init()'s target-aliasing break.
            if init_state is not None:
                init_state = jax.tree_util.tree_map(jnp.copy, init_state)
            if init_buffer is not None:
                init_buffer = jax.tree_util.tree_map(jnp.copy, init_buffer)

        def make_prefetcher(from_ep):
            # traffic staged to device FROM THE PREFETCH THREAD, so the
            # host→device transfer also overlaps the running episode; the
            # topology object passes through untouched (it is the driver's
            # cached pytree — id()-keyed caches downstream rely on that)
            # stop bound covers the unconditional initial sample even when
            # the episode range is empty (the serial loop's behavior)
            pf = self.driver.prefetcher(
                from_ep, max(episodes, start_episode + 1), test_mode,
                stage=lambda topo, traffic: (topo, jax.device_put(traffic)),
                heartbeat=(self.obs.prefetcher_heartbeat()
                           if self.obs else None),
                before_episode=self._prefetch_fault_hook())
            self._live_prefetch = pf
            if self.obs:
                self.obs.attach_prefetcher(pf)
            return pf

        prefetch = make_prefetcher(start_episode) if pipeline else None
        pipeline_faults = 0
        if self.obs:
            if self.obs.watchdog is not None:
                # escalation target for the duration of the episode loop:
                # the watchdog interrupts the live prefetcher; the loop's
                # recovery path below does the restart
                self.obs.watchdog.on_escalate = self._on_watchdog_escalate
            # arm the stall monitor only while the episode loop runs —
            # compile/eval/checkpoint time is not a pipeline stall
            self.obs.resume_watchdog()

        root = contextlib.ExitStack()   # the open root `episode` span
        pending = []  # dispatched episodes whose metrics are not yet synced
        # serial path drains immediately (the seed behavior); pipelined
        # drains lag one episode so the sync never gates the next dispatch
        max_pending = 1 if pipeline else 0

        def next_episode(ep):
            nonlocal prefetch, pipeline_faults, max_pending
            while prefetch is not None:
                try:
                    # blocks only when the producer thread is behind —
                    # i.e. host sampling is the true bottleneck, not the
                    # sync order
                    with phase_span("host_sample_wait", timer, hub):
                        return prefetch.get(ep)
                except RuntimeError as e:
                    # pipeline fault: producer death (surfaced error) or
                    # a watchdog-escalation interrupt.  Restart from the
                    # episode counter — staging is keyed purely by episode
                    # index, so the restarted sequence is bit-identical —
                    # or degrade pipeline->off past the fault limit.
                    pipeline_faults += 1
                    prefetch.close()
                    fault = f"{type(e).__name__}: {e}"
                    if pipeline_faults > self.pipeline_fault_limit:
                        prefetch = None
                        self._live_prefetch = None
                        max_pending = 0
                        self._recover(
                            ep, site="pipeline", action="pipeline_off",
                            fault=fault, attempt=pipeline_faults,
                            detail=f"{pipeline_faults} pipeline faults > "
                                   f"limit {self.pipeline_fault_limit}; "
                                   "serial sampling + immediate drains "
                                   "for the rest of the run")
                    else:
                        self._recover(
                            ep, site="prefetcher", action="restart",
                            fault=fault, attempt=pipeline_faults,
                            detail=f"re-staging from episode {ep}")
                        prefetch = make_prefetcher(ep)
            with phase_span("host_sample", timer, hub):
                return self.driver.episode(ep, test_mode)
        try:
            topo, traffic = next_episode(start_episode)
            env_state, obs = self.env.reset(
                jax.random.fold_in(base, 1000 + start_episode), topo,
                traffic)
            state = init_state if init_state is not None else \
                self.ddpg.init(jax.random.fold_in(base, 0), obs)
            buffer = init_buffer if init_buffer is not None else \
                self.ddpg.init_buffer(obs)
            # replay residency is static across the run (ring buffer):
            # computed once from shapes, streamed in every episode event
            replay_bytes = buffer_nbytes(buffer)
            if hub is not None:
                self._gauge_row_pieces(hub, buffer)
            if verbose:
                log.info(
                    "replay buffer: %.1f MiB resident%s",
                    replay_bytes / 2 ** 20,
                    " — donated, updated in place each episode"
                    if self.ddpg.donate else
                    " — copied each episode (donate=False)")

            # device-cost ledger capture (obs.perf): AOT-lower the watched
            # entry points ONCE, here at compile time — before any dispatch
            # and before donation can consume a carry (lowering never
            # executes the program; see _ledger_fn for which executable is
            # mined).  The steady-state variant (learn=True) is the one
            # the roofline table describes.
            gs0 = np.int32(start_episode * steps_per_ep)
            if pipeline:
                fn, pre = self._ledger_fn(self.ddpg, "episode_step")
                self._capture_costs({"episode_step": (
                    fn, (*pre, state, buffer, env_state, obs, topo,
                         traffic, gs0), {"learn": True})})
            else:
                r_fn, r_pre = self._ledger_fn(self.ddpg, "rollout_episode")
                l_fn, l_pre = self._ledger_fn(self.ddpg, "learn_burst")
                self._capture_costs({
                    "rollout_episode": (
                        r_fn, (*r_pre, state, buffer, env_state, obs,
                               topo, traffic, gs0), {}),
                    "learn_burst": (l_fn, (*l_pre, state, buffer), {}),
                })

            if guard is not None:
                # rollback target for a violation before any episode has
                # been verified (the fresh/restored state is finite)
                guard.init(start_episode - 1, state, buffer)

            start = time.time()

            def drain_one():
                """Drain the oldest pending episode; on a finite verdict
                promote snapshots + periodic-checkpoint, on a violation
                roll back and drop the in-flight descendants."""
                nonlocal state, buffer
                entry = pending.pop(0)
                k = entry[0]
                finite = self._drain(entry, start, start_episode, verbose,
                                     timer)
                if finite:
                    self._last_drained = max(self._last_drained, k)
                    if guard is not None:
                        guard.promote(k, state, buffer,
                                      pending_empty=not pending)
                        if (ckpt_manager is not None and ckpt_interval
                                and (k + 1 - start_episode) % ckpt_interval
                                == 0 and guard.last_good is not None
                                and guard.last_good[0] == k):
                            # the promoted snapshot IS the verified state
                            # after episode k — exactly what a resumable
                            # checkpoint must contain (the live carries
                            # may already be an episode ahead)
                            _, g_state, g_buffer = guard.last_good
                            with phase_span("ckpt", timer, hub):
                                ckpt_manager.save(g_state, g_buffer,
                                                  episode=k + 1)
                    if (publisher is not None and publish_interval
                            and (k + 1 - start_episode)
                            % publish_interval == 0):
                        # hot-swap publish: with the guard on, ship the
                        # VERIFIED snapshot the promote above just
                        # landed (state after episode k) — the live
                        # carry is up to one dispatch ahead and its
                        # finite flag has NOT drained yet, so publishing
                        # it could ship a poisoned state one episode
                        # before rollback catches it (the periodic
                        # checkpoint above refuses that for the same
                        # reason).  Rollback disabled = no verified
                        # snapshot exists; fall back to the live params
                        # (this drain's flag was finite, the next
                        # dispatch's is anyone's guess — documented).
                        src = None
                        if guard is not None:
                            if guard.last_good is not None \
                                    and guard.last_good[0] == k:
                                src = guard.last_good[1].actor_params
                        else:
                            src = state.actor_params
                        if src is not None:
                            # verified=True: both branches above ship a
                            # finite-verified state (promoted snapshot,
                            # or the live params whose flag just drained
                            # finite) — skip the publisher's own host
                            # scan
                            with phase_span("publish", timer, hub):
                                publisher.publish(jax.device_get(src),
                                                  meta={"episode": k + 1},
                                                  verified=True)
                    return
                if guard is None:
                    self._recover(
                        k, site="learner_state", action="detected",
                        fault="non_finite_state",
                        detail="rollback disabled (Trainer(rollback="
                               "False)) — continuing with the poisoned "
                               "state")
                    self._last_drained = max(self._last_drained, k)
                    return
                dropped = [e[0] for e in pending]
                pending.clear()
                tag, state, buffer = guard.restore()
                self._recover(
                    k, site="learner_state", action="rollback",
                    fault="non_finite_state",
                    detail=f"restored snapshot of episode {tag}; skipped "
                           f"poisoned episode {k}"
                           + (f"; dropped in-flight {dropped}"
                              if dropped else ""))

            for ep in range(start_episode, episodes):
                # under the pipeline a drain carries the iteration it ran
                # in, one past the episode it drains
                if self._next_episode_span(root, timer, hub, ep, preempt):
                    self.preempted = True
                    self._recover(
                        ep, site="run", action="preempt_snapshot",
                        fault=preempt.signame,
                        detail=f"stopping before episode {ep}; in-flight "
                               "episodes drain, then the caller "
                               "checkpoints")
                    break
                emit_episode_spans(hub, timer)
                if ep > start_episode:
                    topo, traffic = next_episode(ep)
                    env_state, obs = self.env.reset(
                        jax.random.fold_in(base, 1000 + ep), topo, traffic)
                global_step = ep * steps_per_ep
                end_step = global_step + steps_per_ep - 1
                learn = (end_step
                         >= self.agent_cfg.nb_steps_warmup_critic - 1)
                if guard is not None:
                    # candidate snapshot at the dispatch boundary: the
                    # state after episode ep-1, not yet verified (its
                    # finite flag drains one episode later under the
                    # pipeline) — promote() gates it.  Taken BEFORE the
                    # fault injection below so an injected poison can
                    # never be promoted, and copied so the dispatch's
                    # donation cannot invalidate it.
                    guard.stage(ep - 1, state, buffer)
                if plan is not None:
                    spec = plan.fire("nan_grads", ep)
                    if spec is not None:
                        # the effect of a NaN gradient update: the state
                        # entering this episode is poisoned; the
                        # on-device flag catches it at this episode's
                        # drain
                        state = state.replace(
                            actor_params=poison_tree(state.actor_params))
                (state, buffer, env_state, obs, stats,
                 learn_metrics) = self._dispatch_with_retry(
                    ep, pipeline, state, buffer, env_state, obs, topo,
                    traffic, global_step, learn, timer, hub)
                if self.obs:
                    self.obs.episode_dispatched(ep)
                # the retained arrays (stats, learn metrics, the truncation
                # scalar, and the episode-final sim state the obs/invariant
                # layer reads) are plain kernel outputs — never donated
                # (the NEXT episode's env_state comes from a fresh
                # env.reset, not this one), so deferring their sync is
                # safe under buffer donation
                pending.append((ep, end_step, stats, learn_metrics,
                                env_state.sim.truncated_arrivals,
                                env_state.sim, topo, replay_bytes))
                while len(pending) > max_pending:
                    drain_one()
            while pending:
                # happy-path tail drain stays INSIDE the try: an async
                # device fault surfacing at the final episode's sync must
                # raise like the serial loop would, not be downgraded
                drain_one()
        finally:
            root.close()
            emit_episode_spans(hub, timer)
            if self.obs:
                # disarm BEFORE the best-effort teardown drains — a fault
                # recovery path must not also spray stall events
                self.obs.pause_watchdog()
                if self.obs.watchdog is not None:
                    self.obs.watchdog.on_escalate = None
            self._live_prefetch = None
            # only nonempty when an exception is already propagating:
            # flush completed episodes' rows into rewards.csv exactly as
            # the serial loop would have written them before the fault.
            # Best effort — a drain that itself fails (device in a bad
            # state) must not mask the original exception.
            while pending:
                entry = pending.pop(0)
                try:
                    self._drain(entry, start, start_episode, verbose,
                                timer)
                except Exception:
                    log.warning("dropping metrics of episode %d: drain "
                                "failed after a faulted dispatch", entry[0])
                    break
            if prefetch is not None:
                prefetch.close()
        self.completed_episodes = self._last_drained + 1
        # measured wall -> ledger AFTER the loop (the deferred-drain
        # totals), so MFU/roofline derive from timings the dispatch path
        # already paid for — zero new host syncs
        self._note_cost_timings(
            timer, "episode_step" if pipeline else None)
        if plan is not None:
            # shared end-of-run check (FaultPlan.warn_unfired): a
            # mis-keyed plan must be loud on EVERY training path, with
            # the same structured event
            plan.warn_unfired(self.obs.hub if self.obs else None)
        if verbose:
            log.info("pipeline phase timings: %s", timer.summary())
        self.rewards_writer.close()
        if self.tb:
            self.tb.close()
        return state, buffer

    def train_parallel(self, episodes: int, num_replicas: int,
                       chunk: int = 50, verbose: bool = False,
                       device_traffic: bool = True, profile: bool = False,
                       init_state: Optional[DDPGState] = None,
                       init_buffers=None, start_episode: int = 0,
                       ckpt_manager=None, ckpt_interval: int = 0,
                       preempt=None, plan=None, publisher=None,
                       publish_interval: int = 0, curriculum=None):
        """Replica-parallel training: B vmapped env replicas per episode on
        the scheduled topology, chunked rollouts + end-of-episode learn
        burst (the bench/learning-curve path), logged through the same
        rewards.csv/history machinery as ``train``.  Per-episode traffic is
        sampled ON DEVICE by default (one DeviceTraffic sampler per
        distinct scheduled topology).  Returns (state, buffers).

        The reference has no analogue (one process, one env); evaluation
        and checkpointing consume the resulting learner state exactly like
        the single-env path's.

        ``plan`` (a ``parallel.ShardingPlan``, ``cli train --mesh``):
        replicas/replay/traffic shard over the plan's dp x mp device grid
        and the learner state lives in the plan's partition-rule layout
        between dispatches (ParallelDDPG's sharded dispatch owns the
        placement — this loop drives it unchanged).  Checkpoints are
        mesh-shape-AGNOSTIC: every save below gathers the carries to host
        layout through the plan's gather fns first (orbax 0.7.0 on this
        box cannot restore sharded layouts portably — host arrays are the
        format every future mesh can reshard from), and the returned
        (state, buffers) are host-gathered for the same reason, so the
        caller's final checkpoint + evaluation never see mesh residency.
        Elastic resume = restore those host arrays under a DIFFERENT
        plan: the first dispatch reshards them onto whatever mesh the
        resuming process built.  Under the ``tp`` book the state is
        RESIDENT-sharded through the compiled program (no entry/exit
        layout moves at all) — this loop still never touches mesh
        residency between dispatches: the ONLY host gathers are the
        save boundaries and the final return below, where
        ``gather_state`` assembles the sharded leaves directly.

        On-device scenario factory (``--topo-mix factory:...``): when
        the driver carries a :class:`~gsc_tpu.topology.factory.
        FactorySpec`, every episode SAMPLES a fresh per-replica
        (topology, traffic, fault plan) inside one jitted
        ``factory_sample`` call — the host-staged MixPlan products are
        replaced by device tensors, the ``scenario_regen`` phase
        collapses to dispatch-enqueue time, and nothing retraces (the
        bucket's shapes are static).  Batch composition is steered by
        the TD curriculum (:mod:`gsc_tpu.env.curriculum`, ``curriculum``
        = a ``CurriculumConfig``): each drained episode's per-family
        |TD| segment sums (the learn ledger's existing signal) update
        per-family EWMAs whose softmax — floored with a uniform mix —
        becomes the next episode's family-sampling weights
        (``curriculum_weight{family=}`` gauges + ``curriculum`` events).
        Without a learn ledger the weights stay uniform.

        Train-while-serve: ``publisher`` + a positive
        ``publish_interval`` publish the actor params every N episodes,
        exactly like :meth:`train` — except this path's carries are
        replica/mesh-sharded, so what ships is the HOST-GATHERED state
        (the plan's gather fns under ``--mesh``), finite-verified
        host-side first (this path has no rollback guard; a poisoned
        state skips the publish loudly instead of reaching the fleet).

        Resilience on this path: preemption stop + periodic checkpoints
        (finite-verified host-side).  Under a fault plan the harness
        additionally wires ``nan_grads`` (the state entering the keyed
        episode is poisoned) plus a host-side finite verify after EVERY
        episode, backed by a ``RollbackGuard`` last-verified snapshot
        when ``Trainer(rollback=True)`` — the replica loop drains
        synchronously, so the carries after an episode ARE the verified
        state and snapshots promote directly.  Without a plan none of
        this runs: the production path is byte-identical to before."""
        if profile and self.result_dir:
            from ..utils.debug import Profiler
            with Profiler(os.path.join(self.result_dir, "profile")):
                return self.train_parallel(episodes, num_replicas, chunk,
                                           verbose, device_traffic,
                                           profile=False,
                                           init_state=init_state,
                                           init_buffers=init_buffers,
                                           start_episode=start_episode,
                                           ckpt_manager=ckpt_manager,
                                           ckpt_interval=ckpt_interval,
                                           preempt=preempt, plan=plan,
                                           publisher=publisher,
                                           publish_interval=publish_interval,
                                           curriculum=curriculum)
        from ..parallel import ParallelDDPG
        from ..parallel.harness import run_chunked_episodes
        from ..sim.traffic_device import DeviceTraffic

        steps_per_ep = self.agent_cfg.episode_steps
        if steps_per_ep % chunk != 0:
            # never silently upgrade to a single full-episode scan — that
            # is exactly the call shape the chunking exists to avoid
            raise ValueError(
                f"chunk ({chunk}) must divide episode_steps "
                f"({steps_per_ep})")
        # mixed-topology batches (EpisodeDriver(topo_mix=...)): the B axis
        # carries a round-robin of the schedule's networks + registry
        # scenarios instead of one topology — per_replica_topology threads
        # the stacked [B] topology pytree through the vmapped dispatch, so
        # topology diversity fills the batch instead of costing wall-clock
        # episodes, and a "schedule switch" never recompiles (the switch
        # IS the per-replica topology tensor)
        # on-device scenario factory (topology.factory): the driver's
        # factory spec replaces the host MixPlan wholesale — scenarios
        # are device tensors sampled per episode, steered by the TD
        # curriculum below
        factory = (self.driver.scenario_factory
                   if getattr(self.driver, "factory_spec", None)
                   is not None else None)
        if factory is not None and not device_traffic:
            raise ValueError(
                "the scenario factory IS on-device sampling — "
                "device_traffic=False has no host path to fall back to "
                "(use a registry --topo-mix for host-generated traffic)")
        mix_plan = (self.driver.mix_plan(num_replicas)
                    if getattr(self.driver, "topo_mix", None)
                    and factory is None else None)
        if mix_plan is not None:
            from ..topology.scenarios import (mix_device_samplers,
                                              sample_mix_device)
        curr = None
        if factory is not None:
            from ..env.curriculum import Curriculum, CurriculumConfig
            curr = Curriculum(factory.family_names,
                              curriculum or CurriculumConfig())
        pddpg = ParallelDDPG(self.env, self.agent_cfg,
                             num_replicas=num_replicas, donate=True,
                             gnn_impl=self.ddpg.actor.gnn_impl, plan=plan,
                             per_replica_topology=(mix_plan is not None
                                                   or factory is not None),
                             learn_ledger=self.ddpg.learn_ledger)
        # learn-ledger segment names (topo_id -> name) for the harness's
        # per-episode learn_signal emit; None without a ledger
        seg_names = (self.learn_obs.segment_names
                     if self.learn_obs is not None else None)

        def to_host(state, buffers):
            """Carries in the mesh-shape-agnostic host layout checkpoints
            are written in (and the caller receives): the plan's per-leaf
            gather fns for the learner state, a plain device_get for the
            replica shards.  Without a plan this is the identity — the
            historic path hands orbax the live device arrays."""
            if plan is None:
                return state, buffers
            return plan.gather_state(state), jax.device_get(buffers)
        base = jax.random.PRNGKey(self.seed)
        # restored carries must be re-materialized before donation — see
        # train(): donating orbax-restored (host-owned / aliased) buffers
        # aborts the process
        if init_state is not None:
            init_state = jax.tree_util.tree_map(jnp.copy, init_state)
        if init_buffers is not None:
            # rings from outside: the rollout writes every replica's row
            # at ONE cursor, so cursors that differ are refused here
            lockstep_cursor(init_buffers)
            init_buffers = jax.tree_util.tree_map(jnp.copy, init_buffers)

        topo0, traffic0 = self.driver.episode(0, False)
        _, one_obs = self.env.reset(jax.random.fold_in(base, 1000), topo0,
                                    traffic0)
        state = init_state if init_state is not None else \
            pddpg.init(jax.random.fold_in(base, 0), one_obs)
        buffers = init_buffers if init_buffers is not None else \
            pddpg.init_buffers(one_obs)

        chaos = self.fault_plan
        guard = None
        if chaos is not None and self.rollback:
            # chaos-only rollback target (tree_copy'd snapshots — the
            # donating dispatch can never invalidate them)
            guard = RollbackGuard()
            guard.init(start_episode - 1, state, buffers)

        # one on-device sampler per scheduled topology (the scheduler
        # cycles training_network_files every `period` episodes); mixed
        # runs instead build one sampler per MIX ENTRY (each with its
        # scenario's traffic shape / fault tables) and interleave the
        # per-entry draws back into replica order
        samplers = {}
        mix_samplers = None

        def episode_traffic(ep, topo):
            nonlocal mix_samplers
            if mix_plan is not None:
                if not device_traffic:
                    return self.driver.mix_traffic(ep, mix_plan)
                if mix_samplers is None:
                    mix_samplers = mix_device_samplers(
                        mix_plan, self.env.sim_cfg, self.env.service,
                        steps_per_ep, default_trace=self.driver.trace)
                return sample_mix_device(
                    mix_plan, mix_samplers,
                    jax.random.fold_in(base, 2000 + ep))
            if not device_traffic:
                stacked = [self.driver.traffic_for(
                    ep, topo, seed=self.driver.base_seed + 1000 * ep + r)
                    for r in range(num_replicas)]
                return jax.tree_util.tree_map(
                    lambda *xs: jax.numpy.stack(xs), *stacked)
            # key by the topology OBJECT the episode actually uses — the
            # driver owns the schedule; re-deriving its index here would
            # duplicate that invariant.  One sampler per topology for the
            # run, so its jitted `traffic_sample` traces once per topology
            # the schedule visits, not once per episode
            if id(topo) not in samplers:
                samplers[id(topo)] = DeviceTraffic(
                    self.env.sim_cfg, self.env.service, topo, steps_per_ep,
                    trace=self.driver.trace, capacity=self.driver.capacity)
            return samplers[id(topo)].sample_batch(
                jax.random.fold_in(base, 2000 + ep), num_replicas)

        self.phase_timer = timer = PhaseTimer()
        hub = self.obs.hub if self.obs else None
        if hub is not None:
            self._gauge_row_pieces(hub, buffers)
        self.preempted = False
        self._last_drained = start_episode - 1
        if self.obs:
            self.obs.resume_watchdog()

        def _curriculum_hook(_i, _ret, _succ, metrics):
            """Harness ``on_episode`` callback (factory mode): fold the
            drained learn signal's per-family |TD| segments into the
            curriculum EWMAs.  The harness drain already synced these
            values — pure host arithmetic, never a device wait.  No
            ledger (``--no-learn-obs``) => no signal => the weights stay
            uniform (documented)."""
            sig = (metrics or {}).get("learn_signal") \
                if isinstance(metrics, dict) else None
            if sig is not None:
                curr.fold_td(np.asarray(sig["td_abs_sum"]),
                             np.asarray(sig["td_count"]))

        learn_row = {}

        def _on_episode(i, ret, succ, metrics):
            """Harness ``on_episode`` callback: keep the learn burst's
            losses for this episode's row (the serial path logs the same
            two through ``_log``) — values the drain already synced."""
            learn_row.clear()
            if isinstance(metrics, dict):
                learn_row.update(
                    {k: float(metrics[k])
                     for k in ("critic_loss", "actor_loss") if k in metrics})
            if curr is not None:
                _curriculum_hook(i, ret, succ, metrics)

        root = contextlib.ExitStack()   # the open root `episode` span
        try:
            # the scheduler may swap topologies mid-run, so drive the
            # harness one episode at a time with that episode's topology —
            # passing the GLOBAL step offset so the agent's warmup schedule
            # sees one continuous run (and a resumed run continues it
            # exactly)
            for ep in range(start_episode, episodes):
                ep_t0 = time.perf_counter()
                if self._next_episode_span(root, timer, hub, ep, preempt):
                    self.preempted = True
                    self._recover(
                        ep, site="run", action="preempt_snapshot",
                        fault=preempt.signame,
                        detail=f"stopping before episode {ep}; the caller "
                               "checkpoints the drained state")
                    break
                emit_episode_spans(hub, timer)
                # the scenario_regen phase measures what producing this
                # episode's (topology, traffic) costs the HOST: the full
                # Python regen wall on host-traffic paths; on device-
                # sampling paths one async dispatch of a memoised jit
                # (`traffic_sample` per (topology, B), `factory_sample`
                # per B — traced once, never waited on here, so the
                # sampler's device scan queues ahead of reset_all and
                # the first chunk_step).  The cost the factory deletes,
                # measured instead of asserted (SCEN_r01 banks the
                # before/after)
                with phase_span("scenario_regen", timer, hub):
                    if factory is not None:
                        # fresh per-replica scenarios, entirely on
                        # device: family weights from the curriculum
                        # (tiny [K] host vector — data, never a compile
                        # axis), keys by episode index like the device
                        # traffic samplers
                        probs = jax.numpy.asarray(curr.weights(),
                                                  jax.numpy.float32)
                        topo, traffic = factory.sample_batch(
                            jax.random.fold_in(base, 2000 + ep), probs,
                            num_replicas)
                    else:
                        # mixed mode: the stacked topology is the SAME
                        # pytree object every episode (driver memo), so
                        # the device placement memo and the compiled
                        # program both hit — the whole mixture trains
                        # with exactly one trace
                        topo = (mix_plan.topo if mix_plan is not None
                                else self.driver.topology_for(ep))
                        traffic = episode_traffic(ep, topo)
                if ep == start_episode and self.obs is not None \
                        and getattr(self.obs, "perf", None) is not None:
                    # cost-ledger capture for the replica path: shapes-only
                    # reset via eval_shape (no device work), then AOT-lower
                    # the fused chunk kernel's steady-state variant.  Under
                    # a sharding plan this lowers the PLAIN jit — no
                    # explicit in_/out_shardings, the carving-comparable
                    # number (the traced body still carries the plan's
                    # with_sharding_constraints, so under `tp` even this
                    # program partitions — the _sharded capture below is
                    # the one that mines the dispatched layout) — and
                    # because
                    # the sharded dispatch jits its own copy, that capture
                    # trace would read as a spurious chunk_step retrace in
                    # the sentinel stream: pause the monitor for exactly
                    # that case.  (Meshless the capture stays un-paused:
                    # on jax 0.9.0 it shows as one more chunk_step trace
                    # inside episode 0 — before the steady state any
                    # retrace check looks at — and its backend compile
                    # seeds the persistent cache the dispatch then hits.)
                    mon = self.obs.compile_monitor
                    paused = plan is not None and mon is not None
                    if paused:
                        mon.stop()
                    try:
                        pcls = type(pddpg)
                        es_s, obs_s = pcls.reset_all.eval_shape(
                            pddpg, jax.random.PRNGKey(0), topo, traffic)
                        c_fn, c_pre = self._ledger_fn(pddpg, "chunk_step")
                        # (no capture of `learn_burst` alone: this path
                        # never dispatches it — the burst runs inside
                        # chunk_step(learn=True), whose capture carries it
                        # under the `learn_burst` scope — and compiling the
                        # learner's whole program a second time is set-up
                        # nobody reads: 44 s with a 411 M-parameter torso)
                        self._capture_costs({
                            "chunk_step": (
                                c_fn,
                                (*c_pre, state, buffers, es_s, obs_s,
                                 topo, traffic,
                                 np.int32(ep * steps_per_ep)),
                                {"num_steps": chunk, "learn": True}),
                        })
                        if factory is not None:
                            # the factory-inclusive program: the jitted
                            # scenario sampler is episode device work
                            # too — mine its HLO next to chunk_step.
                            # The AOT lower shares the sampler jit's
                            # trace cache (same jit object, same
                            # shapes), so the capture never shows as a
                            # spurious factory_sample retrace.
                            self._capture_costs({
                                "factory_sample": (
                                    factory.lowerable(num_replicas),
                                    (jax.random.PRNGKey(0), probs), {}),
                            })
                        if plan is not None:
                            # ALSO capture the PARTITIONED executable the
                            # sharded dispatch actually runs: its HLO
                            # carries the collective ops (all-reduce
                            # count/bytes) the plain capture above cannot
                            # show — the machine-read half of the
                            # tp-vs-sharded interconnect claim.  One
                            # extra AOT compile at startup (--no-perf
                            # skips it).  The sharded jit takes statics
                            # positionally (in_shardings rejects kwargs).
                            s_fn = pddpg.sharded_lowerable("chunk_step",
                                                           state)
                            self._capture_costs({
                                "chunk_step_sharded": (
                                    s_fn,
                                    (state, buffers, es_s, obs_s,
                                     topo, traffic,
                                     np.int32(ep * steps_per_ep),
                                     chunk, True), {}),
                            })
                    except Exception as e:  # noqa: BLE001 - never fatal
                        log.warning("cost-ledger capture skipped on the "
                                    "replica path: %s", e)
                    finally:
                        if paused:
                            mon.start()
                if chaos is not None:
                    spec = chaos.fire("nan_grads", ep)
                    if spec is not None:
                        # the effect of a NaN gradient update: the state
                        # entering this episode is poisoned; the chaos
                        # verify below catches it at the episode's end
                        state = state.replace(
                            actor_params=poison_tree(state.actor_params))
                if self.obs:
                    self.obs.episode_dispatched(ep)
                state, buffers, rets, succ, final = run_chunked_episodes(
                    pddpg, topo, lambda _: traffic, state, buffers,
                    1, steps_per_ep, chunk, self.seed + ep,
                    step_offset=ep * steps_per_ep, hub=hub, timer=timer,
                    topo_names=(mix_plan.names if mix_plan is not None
                                else None),
                    learn_names=seg_names, on_episode=_on_episode)
                if curr is not None:
                    # next episode's family weights, from THIS episode's
                    # drained TD segments (the hook above updated the
                    # EWMAs) — gauges + one curriculum event per episode
                    curr.emit_weights(hub, ep)
                if chaos is not None:
                    # chaos-only episode-end verify (one host gather per
                    # episode, NEVER on the production path): the replica
                    # harness drains synchronously, so the carries here
                    # are exactly the state after episode ep
                    if self._finite_device(state):
                        if guard is not None:
                            guard.promote(ep, state, buffers,
                                          pending_empty=True)
                    elif guard is not None:
                        tag, state, buffers = guard.restore()
                        self._recover(
                            ep, site="learner_state", action="rollback",
                            fault="non_finite_state",
                            detail=f"restored snapshot of episode {tag}; "
                                   f"dropped poisoned episode {ep}")
                    else:
                        self._recover(
                            ep, site="learner_state", action="detected",
                            fault="non_finite_state",
                            detail="rollback disabled (Trainer(rollback="
                                   "False)) — continuing with the "
                                   "poisoned state")
                with phase_span("episode_log", timer, hub):
                    # this episode's steps over its root span so far (the
                    # publish and checkpoint cadences follow the row)
                    sps = (steps_per_ep * num_replicas
                           / (time.perf_counter() - ep_t0))
                    row = {"episodic_return": rets[0],
                           "mean_succ_ratio": succ[0],
                           "final_succ_ratio": final[0], **learn_row,
                           "episode": ep, "sps": sps}
                    self.history.append(row)
                    self.rewards_writer.write(rets[0])
                    if self.tb:
                        gs = (ep + 1) * steps_per_ep
                        self.tb.add_scalar("charts/episodic_return",
                                           rets[0], gs)
                        self.tb.add_scalar("charts/SPS", sps, gs)
                    if verbose:
                        log.info("episode=%d return=%.3f succ=%.3f sps=%.1f",
                                 ep, rets[0], succ[0], sps)
                    if self.obs:
                        extra = {"replicas": num_replicas}
                        if mix_plan is None and factory is None:
                            # homogeneous replica batches: one network per
                            # episode — same stamp as the serial drain (the
                            # harness's per-replica names cover mixes;
                            # factory episodes attribute per FAMILY through
                            # the learn ledger's topo_id segments, not a
                            # schedule name)
                            extra = self._topology_extra(ep, rets[0],
                                                         extra=extra)
                        self.obs.episode_end(
                            episode=ep,
                            global_step=(ep + 1) * steps_per_ep - 1,
                            metrics={k: v for k, v in row.items()
                                     if k not in ("episode", "sps")},
                            sps=sps, phases=timer.summary(),
                            replay_bytes=buffer_nbytes(buffers),
                            extra=extra)
                self._last_drained = ep
                if (publisher is not None and publish_interval
                        and (ep + 1 - start_episode) % publish_interval
                        == 0):
                    with phase_span("publish", timer, hub):
                        # hot-swap publish from the replica path (ROADMAP
                        # item 3's last leftover): only the ACTOR subtree
                        # ships, so gather exactly that — device_get
                        # assembles sharded leaves to host arrays (the same
                        # per-leaf move the plan's gather fns perform;
                        # pulling the whole state would move ~5x the bytes,
                        # and critic/targets/moments never serve).  With no
                        # rollback guard here, finite-verify (on the
                        # device) before anything reaches the fleet.  Host
                        # gather at publish cadence only, never per episode.
                        if self._finite_device(state.actor_params):
                            publisher.publish(
                                jax.device_get(state.actor_params),
                                meta={"episode": ep + 1}, verified=True)
                        else:
                            self._recover(
                                ep, site="learner_state", action="detected",
                                fault="non_finite_state",
                                detail="replica path has no rollback guard "
                                       "— hot-swap publish skipped so a "
                                       "poisoned state never reaches the "
                                       "serving fleet")
                if (ckpt_manager is not None and ckpt_interval
                        and (ep + 1 - start_episode) % ckpt_interval == 0):
                    with phase_span("ckpt", timer, hub):
                        # the replica harness drains synchronously, so the
                        # live carries ARE the state after episode ep — but
                        # with no rollback guard on this path the state must
                        # be verified HERE, or a NaN-poisoned run would
                        # checksum garbage into the last-good resume target.
                        # The verdict is made on the device and one boolean
                        # comes back; only a finite state is then put in
                        # the layout the manager is handed (under a plan
                        # the gather IS the mesh-agnostic checkpoint layout).
                        if self._finite_device(state):
                            h_state, h_buffers = to_host(state, buffers)
                            ckpt_manager.save(h_state, h_buffers,
                                              episode=ep + 1)
                        else:
                            self._recover(
                                ep, site="learner_state", action="detected",
                                fault="non_finite_state",
                                detail="replica path has no rollback guard "
                                       "— checkpoint skipped so the "
                                       "last-good pointer keeps the previous "
                                       "verified state")
        finally:
            root.close()
            emit_episode_spans(hub, timer)
            if self.obs:
                self.obs.pause_watchdog()
        self.completed_episodes = self._last_drained + 1
        if chaos is not None:
            chaos.warn_unfired(hub)
        self._note_cost_timings(timer, "chunk_step")
        self.rewards_writer.close()
        if self.tb:
            self.tb.close()
        # host layout on the way out (identity without a plan): the
        # caller's final checkpoint, the preemption snapshot and the
        # greedy evaluation must never depend on this run's mesh carving
        return to_host(state, buffers)

    def train_async(self, episodes: int, num_replicas: int,
                    chunk: int = 50, actor_threads: int = 2,
                    verbose: bool = False, device_traffic: bool = True,
                    profile: bool = False,
                    init_state: Optional[DDPGState] = None,
                    init_buffers=None, start_episode: int = 0,
                    ckpt_manager=None, ckpt_interval: int = 0,
                    preempt=None, plan=None, publisher=None,
                    publish_bursts: int = 1, curriculum=None,
                    max_staleness: int = 0, learn_ratio: float = 1.0,
                    throttle_s: float = 0.0):
        """Decoupled actor/learner training (``cli train --async``):
        ``actor_threads`` rollout threads run the jitted replica rollout
        continuously and ship device-resident transition blocks into the
        shared replay ring, while THIS thread — the learner — ingests
        them via one jitted ``replay_ingest`` per block, runs learn
        bursts back-to-back under its ``learn_ratio`` pacing, and
        publishes actor weights every ``publish_bursts`` bursts through
        a :class:`~gsc_tpu.serve.fleet.WeightPublisher` the actors
        subscribe to in-process (see :mod:`gsc_tpu.parallel.async_rl`
        for the full architecture + staleness-bounding contract).

        Scenario production (scheduled topology + DeviceTraffic,
        registry ``--topo-mix``, or the on-device factory with the TD
        curriculum) matches :meth:`train_parallel` episode for episode —
        scenarios are keyed by GLOBAL episode index, so what an episode
        trains on does not depend on which actor thread ran it.

        Mesh composition: ``plan`` (``--mesh``) now composes — the replay
        ring lives dp-sharded on the learner mesh (``plan.ring_sharding``)
        and ``run_async`` pre-builds the plan-bound dispatch plus the
        AOT-compiled per-shard donated ingest BEFORE any actor thread
        starts, under one run-wide compile-cache guard (the lazy-build
        race that used to force a refusal is dead code).  Learn-bursts
        run under the full pjit plan (tp rulebooks compose), and each
        publish gathers params to host once for both the actor watchers
        and the serving fleet.  Tp-only meshes (dp=1 with >1 devices)
        are still refused — the ring has no dp axis to shard over.

        Resilience: the fleet is SUPERVISED — a dead actor thread
        restarts from its episode counter within
        ``AsyncConfig.restart_budget``, then the fleet degrades to fewer
        actors (never hangs).  Under ``--fault-plan`` the async sites
        (``actor_die@a<N>:<ep>``, ``ring_poison``, ``publish_corrupt@
        v<N>``, ``watcher_stall``, ``learner_transient@<burst>``) fire
        inside :func:`~gsc_tpu.parallel.async_rl.run_async`, the learner
        finite-gates every popped block (poison quarantine) and keeps a
        ``RollbackGuard`` last-verified snapshot keyed by the burst-level
        ``state_finite`` flag; every recovery flows through
        ``RunObserver.recovery``.  Without a plan none of that costs
        anything — the fault-free path is byte-identical.

        One documented limit remains: bit-exact learning curves vs the
        sync control — actors act on K-burst-old weights by design;
        equivalence is BANDED (bench_diff curve bands at matched
        env-step + gradient-step budgets, tools/async_bench.py), never a
        digest.

        ``throttle_s`` artificially delays each burst (test/chaos knob
        for forcing backpressure); ``max_staleness`` bounds how many
        produced-but-uningested env steps the actors may run ahead
        (0 = one episode per actor).  Returns (state, buffers); the
        run's measured accounting (learner idle fraction, policy-lag
        extrema, produced==ingested proof) lands in
        ``self.async_info``."""
        if plan is not None:
            # dp-sharded replay needs a dp axis; tp-only grids refuse
            # with the recarve instructions (partition.py)
            plan.assert_async_capable()
        if profile and self.result_dir:
            from ..utils.debug import Profiler
            with Profiler(os.path.join(self.result_dir, "profile")):
                return self.train_async(
                    episodes, num_replicas, chunk,
                    actor_threads=actor_threads, verbose=verbose,
                    device_traffic=device_traffic, profile=False,
                    init_state=init_state, init_buffers=init_buffers,
                    start_episode=start_episode,
                    ckpt_manager=ckpt_manager,
                    ckpt_interval=ckpt_interval, preempt=preempt,
                    plan=plan, publisher=publisher,
                    publish_bursts=publish_bursts,
                    curriculum=curriculum, max_staleness=max_staleness,
                    learn_ratio=learn_ratio, throttle_s=throttle_s)
        from ..parallel import ParallelDDPG
        from ..parallel.async_rl import AsyncConfig, run_async
        from ..sim.traffic_device import DeviceTraffic
        from .buffer import buffer_fill_frac

        steps_per_ep = self.agent_cfg.episode_steps
        if steps_per_ep % chunk != 0:
            raise ValueError(
                f"chunk ({chunk}) must divide episode_steps "
                f"({steps_per_ep})")
        factory = (self.driver.scenario_factory
                   if getattr(self.driver, "factory_spec", None)
                   is not None else None)
        if factory is not None and not device_traffic:
            raise ValueError(
                "the scenario factory IS on-device sampling — "
                "device_traffic=False has no host path to fall back to "
                "(use a registry --topo-mix for host-generated traffic)")
        mix_plan = (self.driver.mix_plan(num_replicas)
                    if getattr(self.driver, "topo_mix", None)
                    and factory is None else None)
        if mix_plan is not None:
            from ..topology.scenarios import (mix_device_samplers,
                                              sample_mix_device)
        curr = None
        if factory is not None:
            from ..env.curriculum import Curriculum, CurriculumConfig
            curr = Curriculum(factory.family_names,
                              curriculum or CurriculumConfig())
        # donate=False is load-bearing: actors hand their scratch blocks
        # to the learner BY REFERENCE, so rollout outputs must be fresh
        # arrays, never donated-in-place ones another thread still reads.
        # The one donated call on this path is replay_ingest, whose ring
        # the learner thread owns exclusively (async_rl module docs).
        pddpg = ParallelDDPG(self.env, self.agent_cfg,
                             num_replicas=num_replicas, donate=False,
                             gnn_impl=self.ddpg.actor.gnn_impl,
                             per_replica_topology=(mix_plan is not None
                                                   or factory is not None),
                             plan=plan,
                             learn_ledger=self.ddpg.learn_ledger)
        seg_names = (self.learn_obs.segment_names
                     if self.learn_obs is not None else None)
        base = jax.random.PRNGKey(self.seed)
        # restored carries must be re-materialized before donation —
        # replay_ingest donates the ring, and donating orbax-restored
        # (host-owned / aliased) leaves aborts the process (see train())
        if init_state is not None:
            init_state = jax.tree_util.tree_map(jnp.copy, init_state)
        if init_buffers is not None:
            init_buffers = jax.tree_util.tree_map(jnp.copy, init_buffers)

        topo0, traffic0 = self.driver.episode(0, False)
        _, one_obs = self.env.reset(jax.random.fold_in(base, 1000), topo0,
                                    traffic0)
        state = init_state if init_state is not None else \
            pddpg.init(jax.random.fold_in(base, 0), one_obs)
        buffers = init_buffers if init_buffers is not None else \
            pddpg.init_buffers(one_obs)

        samplers = {}
        mix_samplers = None

        def episode_traffic(ep, topo):
            nonlocal mix_samplers
            if mix_plan is not None:
                if not device_traffic:
                    return self.driver.mix_traffic(ep, mix_plan)
                if mix_samplers is None:
                    mix_samplers = mix_device_samplers(
                        mix_plan, self.env.sim_cfg, self.env.service,
                        steps_per_ep, default_trace=self.driver.trace)
                return sample_mix_device(
                    mix_plan, mix_samplers,
                    jax.random.fold_in(base, 2000 + ep))
            if not device_traffic:
                stacked = [self.driver.traffic_for(
                    ep, topo, seed=self.driver.base_seed + 1000 * ep + r)
                    for r in range(num_replicas)]
                return jax.tree_util.tree_map(
                    lambda *xs: jax.numpy.stack(xs), *stacked)
            if id(topo) not in samplers:
                samplers[id(topo)] = DeviceTraffic(
                    self.env.sim_cfg, self.env.service, topo, steps_per_ep,
                    trace=self.driver.trace, capacity=self.driver.capacity)
            return samplers[id(topo)].sample_batch(
                jax.random.fold_in(base, 2000 + ep), num_replicas)

        def scenario_fn(ep):
            # called from actor threads under async_rl's scenario lock;
            # keyed by GLOBAL episode index exactly like train_parallel,
            # so the scenario stream is thread-schedule-independent
            with phase_span("scenario_regen", timer, hub):
                if factory is not None:
                    probs = jax.numpy.asarray(curr.weights(),
                                              jax.numpy.float32)
                    return factory.sample_batch(
                        jax.random.fold_in(base, 2000 + ep), probs,
                        num_replicas)
                topo = (mix_plan.topo if mix_plan is not None
                        else self.driver.topology_for(ep))
                return topo, episode_traffic(ep, topo)

        self.phase_timer = timer = PhaseTimer()
        hub = self.obs.hub if self.obs else None
        if hub is not None:
            self._gauge_row_pieces(hub, buffers)
        self.preempted = False
        self._last_drained = start_episode - 1
        if self.obs:
            self.obs.resume_watchdog()
            # fleet watchdog coverage: every actor thread + the learner
            # register their own heartbeats (run_async beats them per
            # chunk / per loop pass), so a stall event names the wedged
            # thread and the phase it is stuck in — blocked_put vs
            # dispatch vs adopt — instead of an anonymous quiet episode
            self.obs.watch_fleet(
                [f"actor{a}" for a in range(max(1, actor_threads))]
                + ["learner"])

        start = time.time()
        drained_n = [0]
        # episodes drain in COMPLETION order, so "max drained" could tag
        # a preemption checkpoint after an episode whose predecessors
        # never drained — the resume counter must advance only through
        # the contiguous drained prefix (the gap re-runs on resume)
        drained_set: set = set()
        prefix = [start_episode - 1]

        def on_episode(rec, ring):
            """Learner-thread drain of one actor episode: the same
            history/rewards/obs row discipline as train_parallel, in
            COMPLETION order (the episode index rides on every row and
            event, so analysis re-sorts; rewards.csv order is completion
            order — documented in README)."""
            ep = rec["episode"]
            drained_n[0] += 1
            sps = (drained_n[0] * steps_per_ep * num_replicas
                   / (time.time() - start))
            row = {"episodic_return": rec["episodic_return"],
                   "mean_succ_ratio": rec["mean_succ_ratio"],
                   "final_succ_ratio": rec["final_succ_ratio"],
                   "episode": ep, "sps": sps}
            self.history.append(row)
            self.rewards_writer.write(rec["episodic_return"])
            if self.tb:
                gs = (ep + 1) * steps_per_ep
                self.tb.add_scalar("charts/episodic_return",
                                   rec["episodic_return"], gs)
                self.tb.add_scalar("charts/SPS", sps, gs)
            if verbose:
                log.info("episode=%d actor=%d v=%d return=%.3f sps=%.1f",
                         ep, rec["actor"], rec["policy_version"],
                         rec["episodic_return"], sps)
            if curr is not None:
                curr.emit_weights(hub, ep)
            if self.obs:
                extra = {"replicas": num_replicas,
                         "actor": rec["actor"],
                         "policy_version": rec["policy_version"]}
                if mix_plan is None and factory is None:
                    extra = self._topology_extra(
                        ep, rec["episodic_return"], extra=extra)
                self.obs.episode_dispatched(ep)
                self.obs.episode_end(
                    episode=ep,
                    global_step=(ep + 1) * steps_per_ep - 1,
                    metrics={k: v for k, v in row.items()
                             if k not in ("episode", "sps")},
                    sps=sps, phases=timer.summary(),
                    replay_bytes=buffer_nbytes(ring), extra=extra)
            if hub is not None:
                # global ring fill (one [B]-vector sync per drained
                # episode — the satellite gauge that stays correct when
                # the ring lives sharded)
                hub.gauge("replay_fill_frac", buffer_fill_frac(ring))
                # this host's addressable share of the (possibly
                # dp-sharded) ring — metadata only, no sync; equals the
                # global gauge on a single host and the true per-host
                # HBM spend on a pod
                hub.gauge("replay_local_bytes",
                          buffer_nbytes(ring, local=True))
            drained_set.add(ep)
            while prefix[0] + 1 in drained_set:
                prefix[0] += 1
                drained_set.discard(prefix[0])
            self._last_drained = prefix[0]

        def on_burst(n, st, metrics):
            if curr is None:
                return
            sig = (metrics or {}).get("learn_signal") \
                if isinstance(metrics, dict) else None
            if sig is not None:
                # one [K]-vector sync per burst (K = family count):
                # the curriculum steers from LIVE burst TD here because
                # async bursts are not tied to any episode's drain
                curr.fold_td(np.asarray(sig["td_abs_sum"]),
                             np.asarray(sig["td_count"]))

        def checkpoint_fn(st, ring, n_drained):
            # same finite-verified host-layout save as train_parallel —
            # run_async's rollback guard (chaos runs) already keeps the
            # state verified, but this host scan is the last line for
            # guard-off runs; under a plan the state gathers through the
            # plan's fns so the checkpoint layout stays
            # mesh-shape-agnostic (elastic resume).  The episode tag is
            # the CONTIGUOUS drained prefix (on_episode above), so a
            # resume never skips an undrained episode.
            h_st = plan.gather_state(st) if plan is not None else st
            if self._finite_host(h_st):
                ckpt_manager.save(h_st, jax.device_get(ring),
                                  episode=self._last_drained + 1)
            else:
                self._recover(
                    self._last_drained, site="learner_state",
                    action="detected", fault="non_finite_state",
                    detail="async path has no rollback guard — "
                           "checkpoint skipped so the last-good pointer "
                           "keeps the previous verified state")

        cfg = AsyncConfig(actor_threads=actor_threads,
                          publish_bursts=publish_bursts,
                          max_staleness=max_staleness,
                          learn_ratio=learn_ratio, throttle_s=throttle_s)
        try:
            res = run_async(
                pddpg, scenario_fn, state, buffers, episodes,
                steps_per_ep, chunk, self.seed, cfg,
                publisher=publisher, hub=hub, timer=timer,
                on_episode=on_episode, on_burst=on_burst,
                should_stop=(
                    (lambda: preempt.triggered) if preempt is not None
                    else None),
                start_episode=start_episode,
                checkpoint_every=(ckpt_interval if ckpt_manager
                                  is not None else 0),
                checkpoint_fn=(checkpoint_fn if ckpt_manager is not None
                               else None),
                fault_plan=self.fault_plan,
                # the guard is chaos-scoped: a fault-free --async run
                # stays byte-identical to the guard-free stack (no
                # per-block finite dispatch, no snapshots);
                # --no-rollback still disables it under a plan
                rollback=(self.rollback and self.fault_plan is not None),
                on_recovery=self._recover,
                retry_policy=self.retry_policy)
        finally:
            if self.obs:
                # drop the per-thread watches BEFORE pausing: a paused
                # watchdog keeps its registry, and the next (sync) loop
                # must not inherit actor heartbeats nobody beats anymore
                self.obs.unwatch_fleet()
                self.obs.pause_watchdog()
        if preempt is not None and preempt.triggered:
            self.preempted = True
            self._recover(
                self._last_drained + 1, site="run",
                action="preempt_snapshot", fault=preempt.signame,
                detail="async run drained and stopped; the caller "
                       "checkpoints the drained state")
            if self.obs:
                # SIGTERM post-mortem (the PR 5 recovery path): the same
                # black-box dump a wedged fleet gets, tagged with the
                # signal — best effort, a failed dump must not block the
                # preemption snapshot itself
                try:
                    self.obs.write_blackbox(
                        reason=f"preempt:{preempt.signame}")
                except Exception:
                    log.warning("preempt black-box dump failed",
                                exc_info=True)
        self.completed_episodes = self._last_drained + 1
        self.async_info = res.info
        if self.fault_plan is not None:
            self.fault_plan.warn_unfired(hub)
        if hub is not None:
            hub.event("async_train", **res.info)
        # phases-only merge (primary=None): the async ledger splits the
        # wall per entry (actor_dispatch / learn_dispatch / replay_ingest)
        # and no single fused program owns a "dispatch" phase to attribute
        self._note_cost_timings(timer, None)
        self.rewards_writer.close()
        if self.tb:
            self.tb.close()
        return res.state, res.buffers

    def evaluate(self, state: DDPGState, episodes: int = 1,
                 test_mode: bool = True, telemetry: bool = False,
                 write_schedule: bool = False,
                 telemetry_flush_every: int = 1) -> Dict[str, float]:
        """Greedy rollout on the inference network (inference.py:17-40
        semantics: actor only, no noise, no learning).  With ``telemetry``
        the reference's test-mode CSV suite is written to
        <result_dir>/test (writer.py:16-110 schema);
        ``telemetry_flush_every`` batches the suite's per-interval file
        flushes for long sweeps (default 1 = reference behavior)."""
        writer = None
        if telemetry and self.result_dir:
            from ..utils.telemetry import TestModeWriter
            writer = TestModeWriter(
                os.path.join(self.result_dir, "test"),
                write_schedule=write_schedule,
                sf_names=self.env.service.sf_names,
                sfc_names=self.env.service.sfc_names,
                flush_every=telemetry_flush_every)
        totals = []
        succ = []
        # compile/warmup vs steady-state split: everything up to the first
        # completed control step of the first episode (env.reset + actor
        # trace + the first blocking env.step) is compile+warmup wall — on
        # a cold process it dominates the total, and hiding it inside one
        # aggregate number makes serving-path wins unmeasurable from here
        t_eval0 = time.time()
        warmup_s = None
        for ep in range(episodes):
            t_ep = time.time()
            topo, traffic = self.driver.episode(ep, test_mode)
            rng = jax.random.PRNGKey(self.seed + 10_000 + ep)
            env_state, obs = self.env.reset(rng, topo, traffic)
            ep_reward = 0.0
            infos = None
            for _ in range(self.agent_cfg.episode_steps):
                t0 = time.time()
                # the shared greedy policy fn (also the serving stack's AOT
                # target) — eager here, so the op sequence is unchanged
                action = self.ddpg.greedy_action(state.actor_params, obs)
                # algorithm runtime per control step (the adapter's
                # measurement between calls, siminterface/simulator.py:161-167);
                # block so async dispatch doesn't hide the compute time
                jax.block_until_ready(action)
                runtime = time.time() - t0
                env_state, obs, reward, done, infos = self.env.step(
                    env_state, topo, traffic, action)
                ep_reward += float(np.asarray(reward))
                if warmup_s is None:   # first step drained: compiles done
                    warmup_s = time.time() - t_eval0
                if writer:
                    # the schedule/placement the env actually applied,
                    # surfaced by env.step (no recomputation)
                    sched = infos["schedule"]
                    placement = infos["placement"]
                    t_steps = traffic.ingress_active.shape[0]
                    idx = min(int(env_state.sim.run_idx) - 1, t_steps - 1)
                    flat = (np.asarray(obs).tolist()
                            if not self.agent_cfg.graph_mode else
                            np.asarray(obs.nodes).T.reshape(-1).tolist())
                    writer.write_step(
                        episode=ep, time=float(env_state.sim.t),
                        metrics=env_state.sim.metrics, placement=placement,
                        node_cap=traffic.node_cap[max(idx, 0)],
                        schedule=sched, runtime=runtime, rl_state=flat,
                        truncated_arrivals=int(np.asarray(
                            env_state.sim.truncated_arrivals)))
            totals.append(ep_reward)
            succ.append(float(np.asarray(infos["succ_ratio"])))
            if self.obs:
                # greedy test rollouts stream through the same hub — a
                # long eval sweep is visible (and device memory sampled)
                # just like training episodes
                self.obs.eval_episode(ep, ep_reward, succ[-1],
                                      time.time() - t_ep)
        if writer:
            writer.close()
        total_s = time.time() - t_eval0
        warmup = warmup_s if warmup_s is not None else total_s
        return {"mean_return": float(np.mean(totals)),
                "final_succ_ratio": float(np.mean(succ)),
                # the split `cli infer` reports: first-step wall (compile +
                # warmup) vs everything after it — steady_s/total steps is
                # the per-request latency a serving deployment would see
                "compile_warmup_s": round(warmup, 3),
                "steady_s": round(total_s - warmup, 3),
                "total_s": round(total_s, 3)}
