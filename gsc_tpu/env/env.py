"""The RL environment: pure functional reset/step over the batched simulator.

The TPU-native replacement for the reference's GymEnv + SimulatorWrapper stack
(src/rlsp/envs/gym_env.py:24-211, src/rlsp/envs/simulator_wrapper.py:22-176):
instead of a stateful gym.Env mutating a SimPy simulator, ``ServiceCoordEnv``
is a factory of pure ``reset``/``step`` functions over ``EnvState`` pytrees —
they jit, vmap over env replicas, and shard over device meshes.  Episode
control (topology scheduling, per-episode traffic generation) lives in the
host-side ``EpisodeDriver``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..config.schema import AgentConfig, EnvLimits, ServiceConfig, SimConfig
from ..sim.engine import SimEngine
from ..sim.state import SimState, TrafficSchedule
from ..topology.compiler import Topology
from .actions import action_mask, action_to_schedule, derive_placement, post_process_action
from .observations import GraphObs, flat_obs, graph_obs
from .rewards import compute_reward, reward_constants


@struct.dataclass
class EnvState:
    """Per-replica environment state (the analogue of GymEnv's mutable
    attributes: run_count, ewma_flows — gym_env.py:47-51, 80-82)."""

    sim: SimState
    step: jnp.ndarray        # [] i32 steps taken this episode
    ewma_flows: jnp.ndarray  # [] f32 EWMA of flow success (gym_env.py:80-91)


class ServiceCoordEnv:
    """Factory closing over static configuration.

    ``reset(rng, topo, traffic)``  -> (EnvState, obs)
    ``step(state, topo, traffic, action)`` -> (EnvState, obs, reward, done, info)

    ``action`` is the flat [A] scheduling tensor in [0, 1] *after* agent-side
    post-processing (``process_action``), matching the reference's split where
    SimpleDDPG post-processes and GymEnv.step consumes
    (simple_ddpg.py:248-249, gym_env.py:171-211).
    """

    def __init__(self, service: ServiceConfig, sim_cfg: SimConfig,
                 agent: AgentConfig, limits: EnvLimits,
                 engine: Optional[SimEngine] = None):
        self.service = service
        self.sim_cfg = sim_cfg
        self.agent = agent
        self.limits = limits
        # injectable engine: pass sim.dummy.DummyEngine to exercise the RL
        # stack without the simulator (the reference's dummy_env pattern)
        self.engine = engine if engine is not None else SimEngine(
            service, sim_cfg, limits)
        self.tables = self.engine.tables
        self.min_delay, self.diameter = reward_constants(
            agent, [service.sf_list[n].processing_delay_mean
                    for n in service.sf_names])

    # ------------------------------------------------------------- helpers
    def process_action(self, action: jnp.ndarray) -> jnp.ndarray:
        """Agent-side action post-processing (simple_ddpg.py:374-395)."""
        return post_process_action(action, self.limits.max_nodes,
                                   self.agent.schedule_threshold)

    def _masked_schedule(self, action: jnp.ndarray, topo: Topology) -> jnp.ndarray:
        """Flat action -> [N,C,S,N] schedule with padded src/dst entries
        zeroed (the wrapper's mask selection, simulator_wrapper.py:139-146:
        padded destinations never receive weight, so WRR ignores them)."""
        sched = action_to_schedule(action, self.limits.scheduling_shape)
        m = topo.node_mask.astype(sched.dtype)
        return sched * m[:, None, None, None] * m[None, None, None, :]

    def _obs(self, state: SimState, topo: Topology, traffic: TrafficSchedule):
        t_steps = traffic.node_cap.shape[0]
        cap_now = traffic.node_cap[jnp.clip(state.run_idx, 0, t_steps - 1)]
        override = None
        if self.sim_cfg.prediction:
            # show upcoming ingress traffic instead of observed (the traffic
            # predictor subsystem, traffic_predictor.py:22-56)
            from ..sim.predictor import predict_ingress_traffic
            override = predict_ingress_traffic(
                traffic, state.run_idx, self.sim_cfg.run_duration,
                self.limits.max_nodes)
        if self.agent.graph_mode:
            return graph_obs(state.metrics, topo, cap_now, self.tables.chain_sf,
                             self.agent.observation_space,
                             self.limits.num_sfcs, self.limits.max_sfs,
                             ingress_override=override)
        return flat_obs(state.metrics, topo, cap_now, self.tables.chain_sf,
                        self.agent.observation_space,
                        ingress_override=override)

    def obs_dim(self) -> int:
        """Flat observation length (len(observation_space) stacked node
        vectors, padded to MAX_NODES)."""
        return self.limits.max_nodes * len(self.agent.observation_space)

    # --------------------------------------------------------------- reset
    @partial(jax.jit, static_argnums=0)
    def reset(self, rng, topo: Topology, traffic: TrafficSchedule):
        """New episode: fresh simulator state, observation of the empty
        network (the reference's wrapper.init runs only the t=0 bookkeeping
        event before producing the first obs, duration_controller.py:20-33)."""
        sim = self.engine.init(rng, topo)
        state = EnvState(sim=sim, step=jnp.zeros((), jnp.int32),
                         ewma_flows=jnp.ones((), jnp.float32))  # gym_env.py:81
        return state, self._obs(sim, topo, traffic)

    # ---------------------------------------------------------------- step
    @partial(jax.jit, static_argnums=0)
    def step(self, state: EnvState, topo: Topology, traffic: TrafficSchedule,
             action: jnp.ndarray):
        schedule = self._masked_schedule(action, topo)
        t_steps = traffic.ingress_active.shape[0]
        active_ing = (topo.is_ingress & topo.node_mask
                      & traffic.ingress_active[
                          jnp.clip(state.sim.run_idx, 0, t_steps - 1)])
        placement = derive_placement(
            schedule, self.tables.chain_sf, self.tables.chain_len,
            active_ing, self.limits.sf_pool)
        sim, metrics = self.engine.apply(state.sim, topo, traffic, schedule,
                                         placement)
        with jax.named_scope("env_observe"):
            reward, ewma, info = compute_reward(
                self.agent, metrics, placement, topo.node_mask,
                self.limits.sf_pool, self.min_delay, self.diameter,
                state.ewma_flows)
        step = state.step + 1
        done = step >= self.agent.episode_steps
        info["run_generated"] = metrics.run_generated
        info["run_processed"] = metrics.run_processed
        info["run_dropped"] = metrics.run_dropped
        # surface what was actually applied so telemetry doesn't recompute it
        info["placement"] = placement
        info["schedule"] = schedule
        state = EnvState(sim=sim, step=step, ewma_flows=ewma)
        with jax.named_scope("env_observe"):
            next_obs = self._obs(sim, topo, traffic)
        return state, next_obs, reward, done, info
