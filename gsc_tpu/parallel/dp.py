"""Data-parallel DDPG over vmapped env replicas.

The scale-out path (BASELINE.json configs 2-5): B env replicas step in
lockstep under ``vmap`` (each with its own traffic sample and PRNG stream,
sharded across the ``dp`` mesh axis), feeding B per-replica replay shards;
the learner samples batches across all replicas and updates one replicated
parameter set — XLA turns the batch-mean gradient into a cross-chip psum
from the sharding annotations alone (no hand-written collectives).

Replica semantics mirror the single-env agent exactly (same warmup schedule,
noise, post-processing, episode-end learn burst); with B=1 this reduces to
``gsc_tpu.agents.DDPG``.

Precision: the replicated learner state stays f32 master state under every
policy (the inner DDPG owns that contract); ``init_buffers`` builds the
replica shards from ``DDPG.example_transition``, so a bf16 replay policy
halves EVERY shard and the cross-replica gathers of ``_sample_across`` /
``_sample_local`` move half the bytes per batch.  The batch-mean gradient
psum XLA inserts from the sharding annotations reduces f32 gradients — the
compute dtype never leaks into the cross-chip reduction.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..agents.buffer import (ReplayBuffer, buffer_advance,
                             buffer_write_lockstep, flatten_transition,
                             restore_batch, take_rows, transition_shapes)
from ..agents.ddpg import DDPG, DDPGState, donated_jit
from ..resilience.guard import all_finite
from ..config.schema import AgentConfig
from ..env.actions import action_mask
from ..env.env import ServiceCoordEnv


class ParallelDDPG:
    """B-replica data-parallel wrapper around the DDPG kernels."""

    def __init__(self, env: ServiceCoordEnv, agent: AgentConfig,
                 num_replicas: int, gnn_impl: str = None,
                 per_replica_topology: bool = False,
                 sample_mode: str = "across", donate: bool = False,
                 plan=None, learn_ledger=None):
        if sample_mode not in ("across", "local"):
            raise ValueError(f"unknown sample_mode {sample_mode!r}")
        self.env = env
        self.agent = agent
        self.B = num_replicas
        self.sample_mode = sample_mode
        self.donate = donate
        # ``plan`` (a partition.ShardingPlan): rebind the three dispatch
        # entry points with EXPLICIT in_shardings/out_shardings over the
        # plan's dp x mp mesh — replicas/replay over the whole grid,
        # learner state per the plan's partition rules.  plan=None is the
        # no-op fallback: the code path below is byte-identical to the
        # pre-partition stack.
        self.plan = plan
        if plan is not None and num_replicas % plan.n_devices != 0:
            raise ValueError(
                f"num_replicas ({num_replicas}) must be divisible by the "
                f"mesh device count ({plan.n_devices}, mesh "
                f"{plan.describe()}) for an even replica sharding")
        # the inner DDPG inherits ``donate`` so init() breaks the
        # target-params/params buffer aliasing that donation of the learner
        # state would otherwise trip over (double donation), and the
        # learn-ledger spec so the shared _learn_burst folds the
        # per-topology TD segments into the replica dispatch too
        self.ddpg = DDPG(env, agent, gnn_impl=gnn_impl, donate=donate,
                         learn_ledger=learn_ledger)
        # ``donate=True`` aliases the replay shards into the rollout call,
        # so XLA appends transitions to the multi-GB replay in place
        # instead of copying it every chunk call, and the learner state
        # into the learn burst / fused chunk step.  ``obs`` and env states
        # are never donated here: their leaves can legitimately share
        # device buffers, which XLA rejects as double donation.  Callers
        # must treat donated arguments as CONSUMED (always rebind from the
        # return) — the training loops do; comparison-style double-calls
        # on the same inputs must keep the default.
        # With per_replica_topology, ``topo`` arguments carry a leading [B]
        # axis (build with topology.stack_topologies) and every replica
        # trains on its own network — topology-generalization pressure in
        # ONE scan, beyond the reference's serial per-episode swapping
        # (gym_env.py:103-128).
        self.per_replica_topology = per_replica_topology
        self._t_ax = 0 if per_replica_topology else None
        if plan is not None:
            self._bind_sharded_dispatch()
        elif donate:
            cls = type(self)
            self.rollout_episodes = donated_jit(
                self, cls.rollout_episodes, static_argnums=(0, 8),
                donate_argnums=(2,))
            self.learn_burst = donated_jit(
                self, cls.learn_burst, static_argnums=(0, 3),
                donate_argnums=(1,))
            self.chunk_step = donated_jit(
                self, cls.chunk_step, static_argnums=(0, 8, 9),
                donate_argnums=(1, 2))

    def _bind_sharded_dispatch(self):
        """Rebind chunk_step / rollout_episodes / learn_burst as sharded
        jits: explicit ``in_shardings``/``out_shardings`` over the plan's
        mesh (donation folded in when ``donate=True``).

        The learner-state sharding tree needs the state's pytree
        structure, which only exists once a state does — so the jits are
        built LAZILY on the first dispatch and cached; later calls (and
        every shard/gather move, which is plain ``device_put``) reuse
        them without retracing.  ``jax.jit`` rejects kwargs when
        in_shardings is given, so the public wrappers keep the historic
        keyword signature and forward positionally."""
        from functools import partial as _partial

        from jax.sharding import NamedSharding

        cls = type(self)
        plan = self.plan
        data, rep = plan.data_sharding, plan.replicated
        topo_sh = data if self.per_replica_topology else rep
        # the tp book keeps the learner state RESIDENT-sharded through
        # the compiled program; the entry-placement counter below is the
        # no-layout-move witness tests assert on (exactly one placement
        # per caller-fresh state, zero on the steady-state dispatch path)
        tp = plan.resident_sharded
        self.entry_state_moves = 0
        fns = {}
        # the async path dispatches rollouts from MANY actor threads:
        # the build-once dict fill, the placement memo and the
        # entry-move counter are the binding's only shared mutable
        # state, so one lock makes every wrapper thread-safe (run_async
        # additionally pre-builds via sharded_lowerable before any
        # actor thread exists, so the lock is uncontended steady-state)
        import threading as _threading
        bind_lock = _threading.Lock()
        # XLA:CPU multi-device executions rendezvous their partitions at
        # every collective.  Enqueue order onto the per-device work
        # queues follows the Python call, and the GIL is released inside
        # it — so two threads dispatching multi-device programs
        # concurrently can interleave their per-device enqueue loops
        # inconsistently (device 0 sees program B first, devices 1..3
        # see program A first) and BOTH programs deadlock at their first
        # rendezvous, each holding the devices the other needs.
        # Observed live on the forced-device async x mesh path: two
        # actor threads' rollout dispatches stuck with complementary
        # arrival sets.  Serializing the dispatch CALL (not the
        # execution — dispatch is async; the call returns after
        # enqueue) makes the per-device queue order globally consistent,
        # which is deadlock-free by construction.  run_async shares
        # this lock for its AOT-compiled ingest dispatch.
        self.dispatch_lock = _threading.Lock()

        def build(state):
            # Two residency designs share this binding:
            #
            # replicated/sharded books (PR 8, ZeRO-style): the learner
            # state RESIDES sharded between dispatches (params + Adam
            # moments split over mp per the plan's rules — the
            # HBM-residency win), but the COMPILED PROGRAM only ever
            # sees it replicated: the wrappers below allgather it with
            # an eager ``device_put`` on the way in and slice it back to
            # shards on the way out (pure layout moves, never a
            # retrace).  With no mp annotation inside the program, the
            # partitioned executable is identical for every carving of
            # the same device count — which is exactly what makes the
            # final learner state BIT-identical across mesh shapes.
            #
            # tp book (true tensor-parallel compute): the state's
            # in_/out_shardings ARE the plan's partition layout, so it
            # stays sharded THROUGH the program — the entry-allgather /
            # exit-slice moves are deleted (the real HBM + interconnect
            # win) and GSPMD psums the partial products of the sharded
            # contractions.  The psum reduces shards in a
            # carving-dependent order (~1e-7 drift per mp size per
            # gradient step), so tp runs are accepted under the
            # bench_diff tolerance bands, never by digest.
            ss = plan.state_shardings(state)
            fns["_state_shardings"] = ss
            fns["_ss_leaves"] = jax.tree_util.tree_leaves(
                ss, is_leaf=lambda x: isinstance(x, NamedSharding))
            state_sh = ss if tp else rep
            # dynamic args of all three entry points, in order: state,
            # buffers, env_states, obs, topo, traffic, start (static
            # self/num_steps/learn are excluded from in_shardings).  A
            # per-replica topology carries the [B] replica axis, so it
            # shards like the other batch data; the historic single-
            # topology path keeps it replicated.
            arg_sh = (state_sh, data, data, data, topo_sh, data, rep)

            def shard_jit(method, static, donate_pos, n_in, out_sh):
                fn = getattr(method, "__wrapped__", method)
                return _partial(jax.jit(
                    fn, static_argnums=static,
                    donate_argnums=donate_pos if self.donate else (),
                    in_shardings=arg_sh[:n_in], out_shardings=out_sh),
                    self)

            fns["chunk_step"] = shard_jit(
                cls.chunk_step, (0, 8, 9), (1, 2), 7,
                (state_sh, data, data, data, rep, rep))
            fns["rollout_episodes"] = shard_jit(
                cls.rollout_episodes, (0, 8), (2,), 7,
                (state_sh, data, data, data, rep))
            fns["learn_burst"] = shard_jit(
                cls.learn_burst, (0, 3), (1,), 2, (state_sh, rep))
            return fns

        def state_in(state):
            if not tp:
                # entry allgather: ss -> replicated (no-op for a state
                # that is already replicated, e.g. the first dispatch)
                return jax.device_put(state, rep)
            # tp: the state is resident in the program's own layout —
            # a caller-fresh tree (init, restore) is placed exactly
            # once; every carry rebound from our outputs already
            # matches and passes through UNTOUCHED (no device_put, no
            # allgather — the contract tests assert via the counter).
            # All-leaf check, not first-leaf: a host-rebuilt leaf (e.g.
            # state.replace(rng=...)) must re-place, or the jit would
            # reject the mismatched committed leaf.
            ss_leaves = fns["_ss_leaves"]
            leaves = jax.tree_util.tree_leaves(state)
            if len(leaves) == len(ss_leaves) and all(
                    getattr(l, "sharding", None) == s
                    for l, s in zip(leaves, ss_leaves)):
                return state
            with bind_lock:
                self.entry_state_moves += 1
            return jax.device_put(state, fns["_state_shardings"])

        def state_out(state):
            if tp:
                # already in the plan's residency via out_shardings —
                # returning it unmoved IS the deleted exit slice
                return state
            # exit slice: replicated -> the plan's sharded residency
            return jax.device_put(state, fns["_state_shardings"])

        # entry placement for the data/replicated pytrees: this jax
        # version does NOT auto-reshard committed arguments that mismatch
        # in_shardings, and callers legitimately hand over single-device
        # pytrees (reset_all outputs, host-staged traffic, a restored
        # replay) — an eager device_put is a no-op for an already-placed
        # carry (same buffers back, so donation still consumes the
        # original) and a layout move exactly once otherwise.  This is
        # what lets Trainer/harness code drive the sharded path with ZERO
        # call-site changes.  Carries the caller rebinds from our outputs
        # (buffers/env_states/obs) are already placed, so their device_put
        # is free; topo/traffic arrive as the SAME host object every chunk
        # call of an episode — a small keep-alive memo makes their
        # placement once-per-object instead of once-per-call.
        from collections import OrderedDict
        memo = OrderedDict()

        def put_once(tree, sh):
            key = id(tree)
            with bind_lock:
                hit = memo.get(key)
                if hit is not None and hit[0] is tree and hit[1] is sh:
                    return hit[2]
            out = jax.device_put(tree, sh)
            # the retained `tree` ref keeps the id from being recycled;
            # the bound keeps a long run from accumulating every
            # episode's host traffic
            with bind_lock:
                memo[key] = (tree, sh, out)
                while len(memo) > 8:
                    memo.popitem(last=False)
            return out

        def put_data(tree):
            # rebound carries (buffers/env_states/obs): placed after the
            # first call, so no memo — memoizing DONATED trees would pin
            # consumed buffers alive
            return jax.device_put(tree, data)

        def built(name, state):
            # double-checked build: the lazy first-dispatch fill must not
            # race a second thread into a duplicate trace
            fn = fns.get(name)
            if fn is not None:
                return fn
            with bind_lock:
                if name not in fns:
                    build(state)
                return fns[name]

        def chunk_step(state, buffers, env_states, obs, topo, traffic,
                       episode_start_step, num_steps=None, learn=False):
            fn = built("chunk_step", state)
            with self.dispatch_lock:
                out = fn(state_in(state), put_data(buffers),
                         put_data(env_states), put_data(obs),
                         put_once(topo, topo_sh), put_once(traffic, data),
                         jax.device_put(episode_start_step, rep),
                         num_steps, learn)
            return (state_out(out[0]),) + out[1:]

        def rollout_episodes(state, buffers, env_states, obs, topo,
                             traffic, episode_start_step, num_steps=None):
            fn = built("rollout_episodes", state)
            with self.dispatch_lock:
                out = fn(state_in(state), put_data(buffers),
                         put_data(env_states), put_data(obs),
                         put_once(topo, topo_sh), put_once(traffic, data),
                         jax.device_put(episode_start_step, rep),
                         num_steps)
            return (state_out(out[0]),) + out[1:]

        def learn_burst(state, buffers):
            fn = built("learn_burst", state)
            with self.dispatch_lock:
                out = fn(state_in(state), put_data(buffers))
            return (state_out(out[0]),) + out[1:]

        self.chunk_step = chunk_step
        self.rollout_episodes = rollout_episodes
        self.learn_burst = learn_burst
        # the plan-bound jits themselves, for AOT capture (obs.perf mines
        # the SHARDED executable's HLO — collective counts/bytes — next
        # to the carving-comparable plain capture)
        self._sharded_fns = fns
        self._sharded_build = build

    def sharded_lowerable(self, name: str, state):
        """The plan-bound jit actually dispatched for ``name`` (a
        ``functools.partial`` over a jit with explicit shardings), built
        from ``state`` if the lazy binding has not happened yet; ``None``
        without a plan.  Callers lower it AOT (``obs.perf.CostLedger``)
        to mine the PARTITIONED program's HLO — fusions and collective
        ops — which the unsharded class jit cannot show."""
        if self.plan is None:
            return None
        if name not in self._sharded_fns:
            self._sharded_build(state)
        return self._sharded_fns[name]

    # ----------------------------------------------------------------- init
    def init(self, rng, sample_obs) -> DDPGState:
        """Replicated learner state (init from a single-replica obs)."""
        return self.ddpg.init(rng, sample_obs)

    def init_buffers(self, sample_obs, num_replicas: int = None,
                     capacity: int = None) -> ReplayBuffer:
        """Per-replica replay shards: leaves [B, capacity, ...]; capacity is
        mem_limit / B (floored at 1) so TOTAL memory matches the single-env
        agent's budget regardless of replica count — sampling is
        with-replacement, so small per-shard capacities stay valid.

        ``num_replicas`` overrides the leading axis for multi-PROCESS runs:
        each process allocates only its local shard (global B still sizes
        the per-replica capacity) and converts it with
        ``host_local_array_to_global_array`` — materializing the global
        buffer on one device first would transiently hold process_count
        times the per-chip replay budget.

        ``capacity`` overrides the per-replica slot count outright — the
        async actors allocate chunk-sized SCRATCH rings this way (one
        rollout dispatch fills the ring exactly, so the handed-off block
        is the chunk's transitions in step order)."""
        cap = (int(capacity) if capacity is not None
               else max(self.agent.mem_limit // self.B, 1))
        b = self.B if num_replicas is None else num_replicas
        example = self.ddpg.example_transition(sample_obs)
        data = jax.tree_util.tree_map(
            lambda x: jnp.zeros((b, cap) + jnp.shape(x),
                                jnp.asarray(x).dtype),
            flatten_transition(example))
        return ReplayBuffer(data=data, pos=jnp.zeros(b, jnp.int32),
                            size=jnp.zeros(b, jnp.int32),
                            shapes=transition_shapes(example))

    @partial(jax.jit, static_argnums=0)
    def reset_all(self, rng, topo, traffic):
        """vmap env.reset across replicas (traffic batched [B, ...])."""
        keys = jax.random.split(rng, self.B)
        return jax.vmap(self.env.reset, in_axes=(0, self._t_ax, 0))(
            keys, topo, traffic)

    # -------------------------------------------------------------- rollout
    def _rollout_body(self, state: DDPGState, buffers: ReplayBuffer,
                      env_states, obs, topo, traffic,
                      episode_start_step, num_steps: int = None) -> Tuple[
                          DDPGState, ReplayBuffer, Any, Any,
                          Dict[str, jnp.ndarray]]:
        """Replica rollout scan shared by ``rollout_episodes`` and the
        fused ``chunk_step`` (traced inside their jits)."""
        from ..env.permutation import ShuffleOps
        if (self.agent.shuffle_nodes and num_steps is not None
                and num_steps % self.agent.episode_steps != 0):
            raise ValueError(
                "chunked rollouts (num_steps < episode_steps) are "
                "incompatible with shuffle_nodes: each chunk call opens a "
                "fresh permutation frame, which is only correct at episode "
                "boundaries — disable shuffle_nodes or roll out whole "
                "episodes")
        rng, sub = jax.random.split(state.rng)
        shuffle = ShuffleOps(self.agent, self.env.limits)
        # per-replica node permutations, fresh each step, via the same
        # ShuffleOps protocol as the single-env agent
        sub, k0 = jax.random.split(sub)
        perms0 = jax.vmap(shuffle.init_perm)(jax.random.split(k0, self.B))
        obs = jax.vmap(shuffle.permute_obs)(obs, perms0)

        def one_step(es, ob, perm, tr, tp, key, i):
            mask = action_mask(tp.node_mask, self.env.limits.num_sfcs,
                               self.env.limits.max_sfs)
            step_mask = shuffle.step_mask(ob, mask, perm)
            with jax.named_scope("policy_forward"):
                action = self.ddpg.choose_action(
                    state.actor_params, ob, step_mask,
                    episode_start_step + i, key)
                action = self.env.process_action(action)
            es, next_ob, reward, done, info = self.env.step(
                es, tp, tr, shuffle.env_action(action, perm))
            next_ob, next_perm = shuffle.advance(
                jax.random.fold_in(key, 1), next_ob, perm)
            transition = {
                "obs": ob, "next_obs": next_ob, "action": action,
                "reward": reward, "done": done.astype(jnp.float32),
                # per-replica network attribution: in mixed-topology
                # batches tp is this replica's topology slice, so its
                # topo_id is the mix-entry index
                "topo_idx": tp.topo_id}
            stats = {"reward": reward, "succ_ratio": info["succ_ratio"],
                     "avg_e2e_delay": info["avg_e2e_delay"]}
            return es, next_ob, next_perm, transition, stats

        # The replay write sits OUTSIDE the vmap over replicas: the body
        # hands back the control step's transitions and step_fn writes
        # the [B]-stacked slab once per leaf at one scalar cursor.  A
        # buffer_add inside the vmap stores the same bits, but its
        # per-replica cursor is a [B]-index scatter that the TPU compiler
        # expands into a sequential loop over the replicas per leaf.  The
        # rings advance in lockstep (buffer_write_lockstep), so the cursor
        # is read once per dispatch, here, each step's slot follows from
        # the scan index, and pos/size advance once, after the scan —
        # only the ring's data rides the carry.
        capacity = jax.tree_util.tree_leaves(buffers.data)[0].shape[1]
        cursor0 = buffers.pos[0]

        def step_fn(carry, i):
            env_states, obs, perms, ring = carry
            keys = jax.random.split(jax.random.fold_in(sub, i), self.B)
            env_states, obs, perms, transitions, stats = jax.vmap(
                one_step, in_axes=(0, 0, 0, 0, self._t_ax, 0, None))(
                    env_states, obs, perms, traffic, topo, keys, i)
            ring = buffer_write_lockstep(ring, transitions,
                                         (cursor0 + i) % capacity)
            return (env_states, obs, perms, ring), stats

        T = self.agent.episode_steps if num_steps is None else num_steps
        with jax.named_scope("rollout_step"):   # what no inner layer claims
            (env_states, obs, _, ring), stats = jax.lax.scan(
                step_fn, (env_states, obs, perms0, buffers.data),
                jnp.arange(T))
            buffers = buffer_advance(buffers, ring, T)
        # stats leaves: [T, B]
        episode_stats = {
            "episodic_return": stats["reward"].sum(0).mean(),
            "mean_succ_ratio": stats["succ_ratio"].mean(),
            "mean_e2e_delay": stats["avg_e2e_delay"].mean(),
            "final_succ_ratio": stats["succ_ratio"][-1].mean(),
            # [B] per-replica returns ride along for telemetry: the obs
            # hub tags replica-resolved gauges from them (a collapsing
            # replica is invisible in the cross-replica mean)
            "per_replica_return": stats["reward"].sum(0),
            # divergence guardrail over the (replicated) learner state
            # entering the chunk — same contract as DDPG._rollout_body;
            # the post-update flag rides in the learn metrics via the
            # shared _learn_burst
            "state_finite": all_finite(state),
        }
        if self.ddpg.learn_ledger is not None:
            # per-replica replay fill/age ([B] leaves), on device — same
            # ledger contract as the single-agent rollout
            from ..obs.learning import replay_stats
            episode_stats["replay"] = replay_stats(buffers)
        return (state.replace(rng=rng), buffers, env_states, obs,
                episode_stats)

    @partial(jax.jit, static_argnums=(0, 8))
    def rollout_episodes(self, state: DDPGState, buffers: ReplayBuffer,
                         env_states, obs, topo, traffic,
                         episode_start_step, num_steps: int = None) -> Tuple[
                             DDPGState, ReplayBuffer, Any, Any,
                             Dict[str, jnp.ndarray]]:
        """One episode on every replica: scan over steps of a vmapped
        (action -> env.step) body and one replay write of the step's B
        transitions (the rings' cursors must be equal on entry:
        ``buffer.lockstep_cursor``).  Parameters are shared
        (replicated); env state, obs, buffers and traffic carry the leading
        [B] replica axis.

        ``num_steps`` (static) overrides the scan length so an episode can be
        split into several shorter device calls (carry env_states/obs/buffers
        across calls, pass the global step of the chunk start as
        ``episode_start_step``).  Long single-call scans (200 steps x 100
        engine substeps) exceed the TPU runtime's per-call limits; 25-50-step
        chunks are the validated operating range.  Chunked resumption assumes
        ``shuffle_nodes`` is off (default): with shuffling on, each call
        opens a fresh permutation frame, which is only correct at episode
        boundaries."""
        return self._rollout_body(state, buffers, env_states, obs, topo,
                                  traffic, episode_start_step, num_steps)

    @partial(jax.jit, static_argnums=(0, 8, 9))
    def chunk_step(self, state: DDPGState, buffers: ReplayBuffer,
                   env_states, obs, topo, traffic, episode_start_step,
                   num_steps: int = None, learn: bool = False) -> Tuple[
                       DDPGState, ReplayBuffer, Any, Any,
                       Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Fused chunk rollout + (optional) learn burst in ONE device
        program — the replica-parallel analogue of ``DDPG.episode_step``.
        Drive an episode as ``episode_steps/chunk`` calls with
        ``learn=False`` and pass ``learn=True`` on the FINAL chunk: the
        end-of-episode learn burst then runs in the same program as the
        last rollout chunk, eliminating the host round-trip between them
        and letting XLA overlap the scan tail with the first gradient
        steps.  The op sequence is identical to ``rollout_episodes`` +
        ``learn_burst``, so results are bit-identical to the two-call
        path.  Returns ``learn_metrics=None`` when ``learn=False``."""
        state, buffers, env_states, obs, stats = self._rollout_body(
            state, buffers, env_states, obs, topo, traffic,
            episode_start_step, num_steps)
        metrics = None
        if learn:
            sampler = (self._sample_local if self.sample_mode == "local"
                       else self._sample_across)
            state, metrics = self.ddpg._learn_burst(
                state, self._batch_sampler(sampler, buffers),
                constrain=self._state_constraint())
        return state, buffers, env_states, obs, stats, metrics

    # ------------------------------------------------------------- learning
    def _state_constraint(self):
        """Per-gradient-step learner-state re-pin for ``_learn_burst``:
        under a replicated/sharded plan the loop carry is
        constraint-gathered to replicated at the top of every step (see
        the sharded-dispatch ZeRO note), keeping every gradient step's
        math canonical.  Under the ``tp`` plan the pin is the PLAN'S OWN
        sharded layout instead — the constraint keeps GSPMD's fixpoint
        ON the tensor-parallel layout through steps 2..N and the
        back-edge, so every gradient step contracts sharded dims with
        psum accumulation (replacing the carry re-pin-to-replicated, not
        just dropping it: an unconstrained carry lets the fixpoint drift
        toward whatever layout minimizes the first step, changing the
        accepted numerics run to run).  None without a plan — the
        historic trace, byte for byte."""
        if self.plan is None:
            return None
        if self.plan.resident_sharded:
            plan = self.plan
            return lambda st: jax.lax.with_sharding_constraint(
                st, plan.state_shardings(st))
        rep = self.plan.replicated
        return lambda st: jax.lax.with_sharding_constraint(st, rep)

    def _batch_sampler(self, sampler, buffers: ReplayBuffer):
        """``sample_fn(key)`` for the learn burst.  Under a sharding plan
        the sampled batch is constraint-REPLICATED before any gradient
        math touches it: every batch contraction (loss mean, dW) then
        runs in canonical full-batch order identically on every device,
        so the learner state stays BIT-identical across mesh carvings —
        a batch left sharded would psum per-shard partial sums in a
        carving-dependent (dp-then-mp) order.  The gather this buys is
        one micro-batch per gradient step, orders of magnitude smaller
        than the replay shards that stay distributed.  The ``tp`` book
        keeps the SAME replicated-batch pin (the Megatron pattern:
        activations replicated/feature-sharded, weights sharded) — under
        tp it is the weight contractions, not the batch, that psum.
        Without a plan this is a no-op passthrough (the pre-partition
        stack verbatim)."""
        if self.plan is None:
            return lambda k: sampler(buffers, k)
        rep = self.plan.replicated
        return lambda k: jax.lax.with_sharding_constraint(
            sampler(buffers, k), rep)

    def _sample_across(self, buffers: ReplayBuffer, key):
        """Uniform batch over (replica, slot) pairs from all shards —
        exact single-agent semantics, but the gather touches every shard:
        on a real dp mesh each inner-loop batch is cross-device traffic."""
        kb, ks = jax.random.split(key)
        bidx = jax.random.randint(kb, (self.agent.batch_size,), 0, self.B)
        sidx = jax.random.randint(ks, (self.agent.batch_size,), 0,
                                  jnp.maximum(buffers.size[bidx], 1))
        raw = jax.tree_util.tree_map(lambda d: take_rows(d, bidx, sidx),
                                     buffers.data)
        return restore_batch(buffers.shapes, raw)

    def _sample_local(self, buffers: ReplayBuffer, key):
        """Shard-local stratified batch: batch_size/B (>=1) transitions from
        each replica's OWN shard, concatenated along the sharded axis — no
        cross-device gather; the batch-mean gradient reduces across shards
        through the psum XLA inserts from the sharding annotations.  Same
        uniform (replica, slot) marginal as _sample_across with the replica
        counts stratified; effective batch size rounds to B*max(batch//B,1)."""
        b_per = max(self.agent.batch_size // self.B, 1)
        keys = jax.random.split(key, self.B)

        def pick(shard, size, k):
            idx = jax.random.randint(k, (b_per,), 0, jnp.maximum(size, 1))
            return jax.tree_util.tree_map(lambda d: take_rows(d, idx), shard)

        batch = jax.vmap(pick)(buffers.data, buffers.size, keys)
        raw = jax.tree_util.tree_map(
            lambda d: d.reshape((self.B * b_per,) + d.shape[2:]), batch)
        return restore_batch(buffers.shapes, raw)

    @partial(jax.jit, static_argnums=(0, 3))
    def learn_burst(self, state: DDPGState, buffers: ReplayBuffer,
                    steps: int = None
                    ) -> Tuple[DDPGState, Dict[str, jnp.ndarray]]:
        """episode_steps gradient steps over the replica shards
        (simple_ddpg.py:307-325 schedule), sampling per ``sample_mode``.
        ``steps`` (static) overrides the burst length — the async
        learner's pacing knob over its externally-advancing ring."""
        sampler = (self._sample_local if self.sample_mode == "local"
                   else self._sample_across)
        return self.ddpg._learn_burst(
            state, self._batch_sampler(sampler, buffers),
            constrain=self._state_constraint(), steps=steps)
