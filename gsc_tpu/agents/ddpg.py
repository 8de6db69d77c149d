"""DDPG learner — jitted rollout + learn-burst (reference:
src/rlsp/agents/simple_ddpg.py:101-329).

CleanRL-style DDPG: one actor, one critic, Polyak-averaged targets, Adam.
The reference steps the env and nets one Python call at a time on CPU; here
a whole episode's rollout is one ``lax.scan`` (actions, env physics, replay
writes all on device) and the end-of-episode learning burst is one
``lax.fori_loop`` of ``episode_steps`` gradient steps (simple_ddpg.py:307-325).
The pipelined trainer fuses both into ONE device call per episode
(``episode_step``); the two-call path (``rollout_episode`` + ``learn_burst``)
remains for chunked/serial drivers and is bit-identical.

Faithful semantics:
- warmup (< nb_steps_warmup_critic global steps): uniform random action
  masked to valid entries (simple_ddpg.py:184-187)
- after warmup: actor output scaled to [-1,1], Gaussian noise
  N(rand_mu, rand_sigma) added, unscaled back and clipped to [0,1]
  (simple_ddpg.py:188-201; the reference's `.clip(-1,1)` on the scaled
  action is a no-op it discards — not reproduced)
- post-processing threshold+renormalize before the env sees the action
  (simple_ddpg.py:248-249)
- critic target: r + gamma * (1 - done) * Q_target(s', clamp(pi_target(s'), -1, 1))
  (simple_ddpg.py:207-214)
- actor loss: -Q(s, pi(s)).mean() (simple_ddpg.py:221-227)
- Polyak tau = target_model_update each gradient step (simple_ddpg.py:229-234)
- train once per episode end: episode_steps gradient steps on batches of
  batch_size (simple_ddpg.py:300-325)

Precision (AgentConfig.precision -> PrecisionPolicy): learner state —
params, Polyak targets, Adam moments, PRNG — is ALWAYS f32 master state;
the bf16 policy only changes the networks' internal compute dtype (casts
live inside actor/critic apply) and the replay STORAGE dtype of obs/action
leaves (``example_transition``; ``buffer_add``'s write-side ``astype``
then rounds rollout transitions once on insert).  Rewards, done flags,
exploration noise, TD targets and the soft-update arithmetic never leave
f32, so the reward scale and tau=1e-4 target updates are unaffected.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..config.schema import AgentConfig
from ..env.env import ServiceCoordEnv
from ..models.nets import Actor, QNetwork, scale_action, unscale_action
from ..models.torso import exit_entropy, exit_pass, take_pass
from ..obs.learning import (accumulate_signal, learn_signal, replay_stats,
                            zero_learn_signal)
from ..resilience.guard import all_finite
from .buffer import ReplayBuffer, buffer_add, buffer_init, buffer_sample


@struct.dataclass
class DDPGState:
    """Learner state (networks, targets, optimizers, PRNG)."""

    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt: Any
    critic_opt: Any
    rng: jnp.ndarray


def donated_jit(bound_self, method, static_argnums, donate_argnums):
    """Per-instance re-jit of a jitted method with buffer donation (the
    ParallelDDPG ``donate=True`` pattern, shared by both agent paths).
    Callers must treat the donated arguments as CONSUMED — always rebind
    from the return; comparison-style double-calls on the same inputs must
    construct the agent with the non-donating default."""
    fn = getattr(method, "__wrapped__", method)
    return partial(jax.jit(fn, static_argnums=static_argnums,
                           donate_argnums=donate_argnums), bound_self)


class DDPG:
    """Factory closing over static config; all methods are pure and jitted.

    ``donate=True`` aliases the large carried pytrees into their device
    calls so XLA updates them in place instead of copying every episode:
    the replay buffer (the largest HBM resident) and env-state carry are
    donated into the rollout, and the learner state into the learn burst /
    fused episode step.  ``obs`` is never donated (its leaves can alias
    env-state or topology buffers — double donation, which XLA rejects).
    """

    def __init__(self, env: ServiceCoordEnv, agent: AgentConfig,
                 gnn_impl: str = None, donate: bool = False,
                 learn_ledger=None):
        self.env = env
        self.agent = agent
        self.donate = donate
        # on-device learning-signal ledger (obs.learning.LearnLedgerSpec,
        # static — it rides on `self`): with a spec, the learn burst and
        # rollout fold per-topology |TD-error| segments, Q distribution
        # moments, per-layer param/grad norms and replay fill stats into
        # their EXISTING outputs (drained with the deferred drain, zero
        # new host syncs).  None (the default) traces the historic
        # programs byte for byte — the no-ledger path is the pre-ledger
        # stack.
        self.learn_ledger = learn_ledger
        self.action_dim = env.limits.action_dim
        gnn_impl = gnn_impl or agent.gnn_impl  # config-selected embedder
        sched_shape = env.limits.scheduling_shape
        self.actor = Actor(agent=agent, action_dim=self.action_dim,
                           gnn_impl=gnn_impl, sched_shape=sched_shape)
        self.critic = QNetwork(agent=agent, gnn_impl=gnn_impl,
                               action_dim=self.action_dim,
                               sched_shape=sched_shape)
        self.opt = optax.adam(agent.learning_rate)
        if donate:
            cls = type(self)
            self.rollout_episode = donated_jit(
                self, cls.rollout_episode, static_argnums=(0, 8),
                donate_argnums=(2, 3))
            self.learn_burst = donated_jit(
                self, cls.learn_burst, static_argnums=(0, 3),
                donate_argnums=(1,))
            self.episode_step = donated_jit(
                self, cls.episode_step, static_argnums=(0, 8, 9),
                donate_argnums=(1, 2, 3))

    # ---------------------------------------------------------------- init
    def init(self, rng, sample_obs) -> DDPGState:
        k1, k2, k3 = jax.random.split(rng, 3)
        actor_params = self.actor.init(k1, sample_obs)
        critic_params = self.critic.init(
            k2, sample_obs, jnp.zeros(self.action_dim))
        # fresh init shares the target trees' device buffers with the online
        # trees; under donation that is a double donation of the same buffer
        # (XLA rejects it), so break the aliasing with a one-time copy
        copy = (jax.tree_util.tree_map(jnp.copy, (actor_params,
                                                  critic_params))
                if self.donate else (actor_params, critic_params))
        return DDPGState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor_params=copy[0],
            target_critic_params=copy[1],
            actor_opt=self.opt.init(actor_params),
            critic_opt=self.opt.init(critic_params),
            rng=k3,
        )

    def example_transition(self, sample_obs):
        """Shape/dtype template of one replay transition.  Under a
        low-precision replay policy the float leaves of obs/next_obs and
        the action are stored in ``PrecisionPolicy.replay_dtype`` (halving
        the largest HBM resident); reward and done stay f32 so TD-target
        scale survives replay round-trips."""
        rd = self.agent.precision_policy.replay_cast_dtype
        obs, action = sample_obs, jnp.zeros(self.action_dim)
        if rd is not None:
            d = jnp.dtype(rd)
            obs = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x).astype(d)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                sample_obs)
            action = action.astype(d)
        return {
            "obs": obs,
            "next_obs": obs,
            "action": action,
            "reward": jnp.zeros(()),
            "done": jnp.zeros(()),
            # which network the transition was collected on (the
            # Topology's topo_id: schedule position, or mix-entry index
            # in mixed-topology batches) — 4 bytes/transition, lets
            # replay analysis attribute cross-topology experience
            "topo_idx": jnp.zeros((), jnp.int32),
        }

    def init_buffer(self, sample_obs) -> ReplayBuffer:
        return buffer_init(self.example_transition(sample_obs),
                           self.agent.mem_limit)

    # ------------------------------------------------------------- actions
    def greedy_action(self, actor_params, obs):
        """The greedy inference policy as a pure, loweable function of
        (actor_params, obs): actor forward pass, clip to [0, 1], agent-side
        post-processing (threshold + renormalize) — exactly the per-step op
        sequence of ``Trainer.evaluate`` (inference.py:17-40 semantics: no
        noise, no warmup branch, no learning).

        Deliberately NOT jit-decorated: ``Trainer.evaluate`` runs it eagerly
        (identical op-by-op to the historical inline code), while the
        serving stack (``gsc_tpu.serve``) vmaps it over request batches and
        AOT-lowers/exports the result per batch bucket."""
        a = self.actor.apply(actor_params, obs)
        a = jnp.clip(a, 0.0, 1.0)
        return self.env.process_action(a)

    def choose_action(self, actor_params, obs, mask, global_step, key):
        """Warmup random masked action, else actor + Gaussian noise in scaled
        space (simple_ddpg.py:182-201)."""
        k1, k2 = jax.random.split(key)
        random_action = jax.random.uniform(k1, (self.action_dim,)) * mask

        def policy_action():
            a = self.actor.apply(actor_params, obs)
            scaled = scale_action(a)
            noise = self.agent.rand_mu + self.agent.rand_sigma * \
                jax.random.normal(k2, (self.action_dim,))
            return jnp.clip(unscale_action(scaled + noise), 0.0, 1.0)

        warmup = global_step < self.agent.nb_steps_warmup_critic
        return jax.lax.cond(warmup, lambda: random_action, policy_action)

    # ------------------------------------------------------------- rollout
    def _rollout_body(self, state: DDPGState, buffer: ReplayBuffer,
                      env_state, obs, topo, traffic,
                      episode_start_step: jnp.ndarray,
                      num_steps: int = None
                      ) -> Tuple["DDPGState", ReplayBuffer, Any, Any,
                                 Dict[str, jnp.ndarray]]:
        """Rollout scan shared by ``rollout_episode`` and the fused
        ``episode_step`` (traced inside their jits, never called raw)."""
        from ..env.actions import action_mask
        from ..env.permutation import ShuffleOps
        mask = action_mask(topo.node_mask, self.env.limits.num_sfcs,
                           self.env.limits.max_sfs)
        rng, sub = jax.random.split(state.rng)
        shuffle = ShuffleOps(self.agent, self.env.limits)
        sub, k0 = jax.random.split(sub)
        perm0 = shuffle.init_perm(k0)
        # obs in the carry lives in the current permuted frame; the env gets
        # actions mapped back through the inverse (gym_env.py:193-206 flow)
        obs = shuffle.permute_obs(obs, perm0)

        def step_fn(carry, i):
            env_state, obs, perm, buffer = carry
            k = jax.random.fold_in(sub, i)
            step_mask = shuffle.step_mask(obs, mask, perm)
            with jax.named_scope("policy_forward"):
                action = self.choose_action(state.actor_params, obs,
                                            step_mask, episode_start_step + i,
                                            k)
                action = self.env.process_action(action)
            env_state, next_obs, reward, done, info = self.env.step(
                env_state, topo, traffic, shuffle.env_action(action, perm))
            next_obs, next_perm = shuffle.advance(
                jax.random.fold_in(k, 1), next_obs, perm)
            buffer = buffer_add(buffer, {
                "obs": obs, "next_obs": next_obs, "action": action,
                "reward": reward, "done": done.astype(jnp.float32),
                "topo_idx": topo.topo_id,
            })
            stats = {"reward": reward, "succ_ratio": info["succ_ratio"],
                     "avg_e2e_delay": info["avg_e2e_delay"]}
            return (env_state, next_obs, next_perm, buffer), stats

        T = self.agent.episode_steps if num_steps is None else num_steps
        with jax.named_scope("rollout_step"):   # what no inner layer claims
            (env_state, obs, _, buffer), stats = jax.lax.scan(
                step_fn, (env_state, obs, perm0, buffer), jnp.arange(T))
        episode_stats = {
            "episodic_return": stats["reward"].sum(),
            "mean_succ_ratio": stats["succ_ratio"].mean(),
            "mean_e2e_delay": stats["avg_e2e_delay"].mean(),
            "final_succ_ratio": stats["succ_ratio"][-1],
            # divergence guardrail (resilience.guard): all-finite flag over
            # the learner state ENTERING this episode, computed on device
            # and drained with the deferred metrics — catches a poisoned
            # state even during warmup, when no learn burst runs (the
            # post-update flag lives in the learn metrics)
            "state_finite": all_finite(state),
        }
        if self.learn_ledger is not None:
            # replay fill/age computed ON DEVICE from the post-rollout
            # buffer (reading buffer.size host-side would sync the
            # dispatch head); drained with the other deferred stats
            episode_stats["replay"] = replay_stats(buffer)
        return state.replace(rng=rng), buffer, env_state, obs, episode_stats

    @partial(jax.jit, static_argnums=(0, 8))
    def rollout_episode(self, state: DDPGState, buffer: ReplayBuffer,
                        env_state, obs, topo, traffic,
                        episode_start_step: jnp.ndarray,
                        num_steps: int = None
                        ) -> Tuple["DDPGState", ReplayBuffer, Any, Any,
                                   Dict[str, jnp.ndarray]]:
        """One full episode as a lax.scan: action -> env.step -> buffer.add.
        Returns (state w/ fresh rng, buffer, final_env_state, final_obs,
        episode stats).  ``num_steps`` (static) overrides the scan length so
        an episode can run as several shorter device calls (see
        ParallelDDPG.rollout_episodes for the chunking contract)."""
        return self._rollout_body(state, buffer, env_state, obs, topo,
                                  traffic, episode_start_step, num_steps)

    @partial(jax.jit, static_argnums=(0, 8, 9))
    def episode_step(self, state: DDPGState, buffer: ReplayBuffer,
                     env_state, obs, topo, traffic,
                     episode_start_step: jnp.ndarray,
                     num_steps: int = None, learn: bool = False
                     ) -> Tuple["DDPGState", ReplayBuffer, Any, Any,
                                Dict[str, jnp.ndarray],
                                Dict[str, jnp.ndarray]]:
        """Fused rollout + learn: one device program per episode.

        Runs the chunked rollout scan and — when ``learn`` (static; the
        host decides it from the warmup schedule, which depends only on the
        episode index) — the end-of-episode learn burst in the SAME jitted
        call, eliminating the host round-trip between the two dispatches
        and letting XLA overlap the tail of the scan with the first
        gradient steps.  Returns (state, buffer, env_state, obs, stats,
        learn_metrics) with ``learn_metrics=None`` during warmup.  The op
        sequence is identical to ``rollout_episode`` followed by
        ``learn_burst``, so results are bit-identical to the two-call
        path."""
        state, buffer, env_state, obs, stats = self._rollout_body(
            state, buffer, env_state, obs, topo, traffic,
            episode_start_step, num_steps)
        metrics = None
        if learn:
            state, metrics = self._learn_burst(
                state,
                lambda k: buffer_sample(buffer, k, self.agent.batch_size))
        return state, buffer, env_state, obs, stats, metrics

    # ------------------------------------------------------------ learning
    def _td_target(self, state: DDPGState, batch):
        next_a = jnp.clip(
            self.actor.apply(state.target_actor_params, batch["next_obs"]),
            -1.0, 1.0)  # clamp(-1,1), simple_ddpg.py:208
        q_next = self.critic.apply(state.target_critic_params,
                                   batch["next_obs"], next_a)[..., 0]
        return batch["reward"] + (1.0 - batch["done"]) * self.agent.gamma * q_next

    def _critic_loss(self, critic_params, state: DDPGState, batch):
        if self.agent.torso is not None:
            return self._critic_exit_loss(critic_params, state, batch)
        target = self._td_target(state, batch)
        q = self.critic.apply(critic_params, batch["obs"], batch["action"])[..., 0]
        # the residual IS the loss argument — naming it changes no op.
        # With the learn ledger the aux also carries it, so the burst can
        # segment |TD| per topology without recomputing the targets;
        # without a ledger the aux stays the historic single-tensor `q`.
        td = q - jax.lax.stop_gradient(target)
        aux = (q, td) if self.learn_ledger is not None else q
        return jnp.mean(td ** 2), aux

    def _actor_loss(self, actor_params, critic_params, batch):
        a = self.actor.apply(actor_params, batch["obs"])
        return -jnp.mean(self.critic.apply(critic_params, batch["obs"], a))

    # the exit objective (arXiv 2510.25741) with DDPG's as the task loss:
    # a network with a looped torso answers once per pass, and its loss
    # is the exit distribution's expectation of the per-pass task loss
    # less beta times that distribution's entropy.  Targets and the Q
    # inside the actor's loss come from the pass the exit threshold picks
    # (the networks' default answer).
    def _critic_exit_loss(self, critic_params, state: DDPGState, batch):
        torso = self.agent.torso
        target = jax.lax.stop_gradient(self._td_target(state, batch))
        q, p = self.critic.apply(critic_params, batch["obs"],
                                 batch["action"], passes=True)
        td = q[..., 0] - target                             # [T, batch]
        loss = jnp.mean(jnp.sum(p * td ** 2, axis=0)) \
            - torso.exit_entropy_beta * jnp.mean(exit_entropy(p))
        idx = exit_pass(p, torso.early_exit_threshold)
        return loss, (take_pass(q[..., 0], idx), take_pass(td, idx), p)

    def _actor_exit_loss(self, actor_params, critic_params, batch):
        a, p = self.actor.apply(actor_params, batch["obs"], passes=True)
        # the T candidate actions share one pass of the critic's torso:
        # the action enters after it
        q = self.critic.apply(critic_params, batch["obs"], a)[..., 0]
        loss = jnp.mean(jnp.sum(p * -q, axis=0)) \
            - self.agent.torso.exit_entropy_beta * jnp.mean(exit_entropy(p))
        return loss, p

    def gradient_step(self, state: DDPGState, buffer: ReplayBuffer, key
                      ) -> Tuple[DDPGState, Dict[str, jnp.ndarray]]:
        """One (critic, actor, Polyak) update on a sampled batch
        (simple_ddpg.py:204-234, 307-325)."""
        batch = buffer_sample(buffer, key, self.agent.batch_size)
        return self.gradient_step_on_batch(state, batch)

    def gradient_step_on_batch(self, state: DDPGState, batch
                               ) -> Tuple[DDPGState, Dict[str, jnp.ndarray]]:
        with jax.named_scope("critic_update"):
            (critic_loss, aux), cgrad = jax.value_and_grad(
                self._critic_loss, has_aux=True)(state.critic_params, state,
                                                 batch)
            exits = None
            if self.agent.torso is not None:
                q_vals, td, exits = aux[0], aux[1], {"critic": aux[2]}
            else:
                q_vals, td = aux if self.learn_ledger is not None \
                    else (aux, None)
            cupd, critic_opt = self.opt.update(cgrad, state.critic_opt)
            critic_params = optax.apply_updates(state.critic_params, cupd)

        with jax.named_scope("actor_update"):
            if exits is not None:
                (actor_loss, exits["actor"]), agrad = jax.value_and_grad(
                    self._actor_exit_loss, has_aux=True)(
                        state.actor_params, critic_params, batch)
            else:
                actor_loss, agrad = jax.value_and_grad(self._actor_loss)(
                    state.actor_params, critic_params, batch)
            aupd, actor_opt = self.opt.update(agrad, state.actor_opt)
            actor_params = optax.apply_updates(state.actor_params, aupd)

        tau = self.agent.target_model_update
        polyak = lambda t, p: jax.tree_util.tree_map(
            lambda tl, pl: tau * pl + (1 - tau) * tl, t, p)
        with jax.named_scope("target_update"):
            state = DDPGState(
                actor_params=actor_params, critic_params=critic_params,
                target_actor_params=polyak(state.target_actor_params,
                                           actor_params),
                target_critic_params=polyak(state.target_critic_params,
                                            critic_params),
                actor_opt=actor_opt, critic_opt=critic_opt, rng=state.rng)
        # grad norms ride along for run telemetry (events.jsonl) — computed
        # from the already-materialized grads, so the update path is
        # untouched and pipeline/serial bit-identity holds
        metrics = {"critic_loss": critic_loss, "actor_loss": actor_loss,
                   "q_values": q_vals.mean(),
                   "critic_grad_norm": optax.global_norm(cgrad),
                   "actor_grad_norm": optax.global_norm(agrad)}
        if self.learn_ledger is not None:
            # learning-signal ledger (obs.learning): consumes tensors the
            # update already materialized (td, grads, post-update params),
            # so the update math is untouched either way
            metrics["learn_signal"] = learn_signal(
                self.learn_ledger, batch["topo_idx"], td, q_vals,
                params={"actor": actor_params, "critic": critic_params},
                grads={"actor": agrad, "critic": cgrad}, exits=exits)
        return state, metrics

    def _learn_burst(self, state: DDPGState, sample_fn, constrain=None,
                     steps: Optional[int] = None
                     ) -> Tuple[DDPGState, Dict[str, jnp.ndarray]]:
        """End-of-episode training: episode_steps gradient steps
        (simple_ddpg.py:307-325) as one fori_loop.  ``sample_fn(key)``
        yields a batch — single-buffer and cross-replica samplers both
        plug in here.

        ``steps`` overrides the per-burst gradient-step count (static —
        each distinct value is its own trace).  The async learner runs
        bursts against an EXTERNALLY-advancing replay (actors keep
        ingesting between bursts), where burst length is a pacing knob
        decoupled from the episode length the sync default encodes.

        ``constrain`` (optional; the sharded multi-chip path) re-pins the
        carried learner state — top of every gradient step AND the
        back-edge — to the layout the caller's plan intends.  The
        replicated/sharded books pin to REPLICATED: without it, GSPMD's
        fixpoint solve pulls the caller's sharded state layout INTO the
        loop carry and steps 2..N compute tensor-parallel with
        carving-dependent reduction order, breaking their bit-equality
        contract.  The ``tp`` book pins to its OWN sharded layout: there
        tensor-parallel compute is the point, and the constraint keeps
        the fixpoint ON that layout so every step's contractions psum
        the same way (acceptance is banded, see
        ``parallel.partition.tp_rules``).  ``None`` (the default, every
        single-agent path) traces the historic body verbatim."""
        rng, sub = jax.random.split(state.rng)
        state = state.replace(rng=sub)

        def body(i, carry):
            st, acc = carry
            if constrain is not None:
                st = constrain(st)
            with jax.named_scope("replay_sample"):
                batch = sample_fn(jax.random.fold_in(sub, i))
            st, metrics = self.gradient_step_on_batch(st, batch)
            if self.learn_ledger is not None:
                # TD segments ACCUMULATE across the burst (per-topology
                # learning pressure over all sampled batches); moments
                # and norms keep the last step's values — the same
                # last-write carry semantics as the loss metrics
                metrics = {**metrics, "learn_signal": accumulate_signal(
                    acc["learn_signal"], metrics["learn_signal"])}
            if constrain is not None:
                # pin the RETURNED carry too: the constraint on entry
                # alone leaves the loop's back-edge free for GSPMD to
                # settle on whatever layout minimizes the first step,
                # which then back-propagates through the Adam/Polyak
                # updates into the gradient dots — the update math must
                # stay on the INTENDED layout end to end (replicated for
                # the bit-exact books, the plan's sharded layout for tp)
                st = constrain(st)
            return st, metrics

        zero = {"critic_loss": jnp.zeros(()), "actor_loss": jnp.zeros(()),
                "q_values": jnp.zeros(()),
                "critic_grad_norm": jnp.zeros(()),
                "actor_grad_norm": jnp.zeros(())}
        if self.learn_ledger is not None:
            zero["learn_signal"] = zero_learn_signal(
                self.learn_ledger, state,
                exits=self.agent.torso is not None)
        # `steps` is a STATIC jit arg (dp.py marks it static_argnums) —
        # int() here normalizes a Python int, never syncs a tracer
        n_steps = (int(steps) if steps is not None  # gsc-lint: disable=R1
                   else self.agent.learn_steps
                   if self.agent.learn_steps is not None
                   else self.agent.episode_steps)
        with jax.named_scope("learn_burst"):   # the `while` carries the name
            state, metrics = jax.lax.fori_loop(0, n_steps, body,
                                               (state, zero))
        # divergence guardrail: flag the POST-update learner state in the
        # same device program (no extra host sync — the trainer reads it
        # from the deferred metric drain and rolls back on violation)
        metrics = {**metrics, "state_finite": all_finite(state)}
        return state.replace(rng=rng), metrics

    @partial(jax.jit, static_argnums=(0, 3))
    def learn_burst(self, state: DDPGState, buffer: ReplayBuffer,
                    steps: Optional[int] = None
                    ) -> Tuple[DDPGState, Dict[str, jnp.ndarray]]:
        return self._learn_burst(
            state, lambda k: buffer_sample(buffer, k, self.agent.batch_size),
            steps=steps)
