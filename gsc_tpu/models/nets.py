"""Actor and critic networks (reference: src/rlsp/agents/models.py:55-153).

Graph mode: GNN embedding of the padded network graph, concatenated with the
flattened action mask (and the action for the critic), through an MLP; the
actor's output is multiplied by the mask so padded (src, dst) entries are
exactly zero (models.py:146-153).  Flat mode: plain MLPs over the
concatenated observation vectors.  (The reference's flat-mode layer sizing is
internally inconsistent — models.py:80 declares mask-sized inputs its forward
never builds; we size flat inputs correctly instead.)

MLP semantics follow torch_geometric.nn.MLP with norm=None, plain_last=True:
Linear -> ReLU between layers, no activation after the last (so the actor's
output is unbounded; the agent clips to the action box after adding noise,
simple_ddpg.py:195-201).

Mixed precision (AgentConfig.precision -> config.schema.PrecisionPolicy):
the GNN embedder and the Dense stacks compute in the policy's compute
dtype (params stay f32 masters, cast at use; matmuls accumulate f32 via
``preferred_element_type``), and BOTH network outputs — actions and
Q-values — are cast to f32 at the module boundary so exploration noise,
TD targets and Polyak updates always run at full precision.  The "f32"
policy takes the original code paths verbatim (bit-identical).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schema import AgentConfig
from ..env.observations import GraphObs
from .gnn import GNNEmbedder, masked_mean_pool
from .torso import LoopedTorso, exit_pass, take_pass


def _accum_f32_dot_general(lhs, rhs, dimension_numbers, precision=None,
                           preferred_element_type=None):
    """Low-precision operands, f32 MXU accumulation, activation settled
    back to the operand dtype (nn.Dense ``dot_general`` hook)."""
    return jax.lax.dot_general(
        lhs, rhs, dimension_numbers, precision=precision,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def _dense_kw(dtype: str | None) -> dict:
    """nn.Dense kwargs for a compute dtype; {} = the exact legacy layer."""
    if dtype is None:
        return {}
    return dict(dtype=jnp.dtype(dtype), dot_general=_accum_f32_dot_general)


class MLP(nn.Module):
    """Linear/ReLU stack, plain last layer (torch_geometric MLP, norm=None).
    ``dtype`` is the compute dtype (PrecisionPolicy.mlp_compute); params
    are stored f32 and cast at use, dots accumulate f32."""

    features: Tuple[int, ...]
    dtype: str = None

    @nn.compact
    def __call__(self, x):
        kw = _dense_kw(self.dtype)
        for i, f in enumerate(self.features):
            x = nn.Dense(f, **kw)(x)
            if i < len(self.features) - 1:
                x = nn.relu(x)
        return x


def _embedder(agent: AgentConfig, impl: str) -> GNNEmbedder:
    return GNNEmbedder(hidden=agent.gnn_features,
                       num_layers=agent.gnn_num_layers,
                       num_iter=agent.gnn_num_iter,
                       mean_aggr=agent.gnn_aggr == "mean",
                       impl=impl,
                       compute_dtype=agent.precision_policy.gnn_dtype)


def _torso_features(agent: AgentConfig, impl: str, obs, passes: bool):
    """The embedder's per-node output through the configured torso:
    per-node states, their masked mean and the exit distribution.  With
    ``passes`` every pass keeps its leading ``[T]`` axis; without, the
    pass the exit threshold picks is taken and ``p`` is dropped."""
    x = _node_embedder(agent, impl)(obs.nodes, obs.edge_index,
                                    obs.edge_mask, obs.node_mask)
    h, z, p = LoopedTorso(agent.torso)(x, obs.node_mask)
    if passes:
        return h, z, p
    idx = exit_pass(p, agent.torso.early_exit_threshold)
    return take_pass(h, idx), take_pass(z, idx), None


def _node_embedder(agent: AgentConfig, impl: str) -> GNNEmbedder:
    return GNNEmbedder(hidden=agent.gnn_features,
                       num_layers=agent.gnn_num_layers,
                       num_iter=agent.gnn_num_iter,
                       mean_aggr=agent.gnn_aggr == "mean",
                       impl=impl, pool=False,
                       compute_dtype=agent.precision_policy.gnn_dtype)


# action dims (N * C * S * N') above which the monolithic Dense output
# layer stops fitting one chip (a 256-hidden head on the rung-5 393k-dim
# action is a ~100M-param matrix, measured RESOURCE_EXHAUSTED even at B=4
# — BENCH_NOTES r3) and the factored decoder takes over by default
FACTORED_HEAD_THRESHOLD = 16384


def use_factored_head(agent: AgentConfig, action_dim: int) -> bool:
    if agent.factored_head is not None:
        return agent.factored_head and agent.graph_mode
    return agent.graph_mode and action_dim >= FACTORED_HEAD_THRESHOLD


def _check_sched_shape(sched_shape, action_dim: int) -> Tuple[int, ...]:
    if sched_shape is None:
        raise ValueError(
            "factored action head needs sched_shape=(N, C, S, N') "
            "(see EnvLimits.scheduling_shape)")
    n, c, s, n2 = sched_shape
    if n * c * s * n2 != action_dim:
        raise ValueError(f"sched_shape {sched_shape} does not factor "
                         f"action dim {action_dim}")
    return n, c, s, n2


class Actor(nn.Module):
    """Policy network (models.py:97-153).

    Two heads over the shared GNN trunk:

    - monolithic (the reference's shape): graph embedding ++ mask -> MLP ->
      Dense(action_dim).  Exact reference semantics, but the output matrix
      scales as hidden x (N*C*S*N) — ~100M params at rung-5 padding.
    - factored (``use_factored_head``): the schedule is structured
      [src, sfc, sf, dst], so score it as a bilinear form between per-node
      embeddings: h_src -> per-(sfc, sf) query vectors, h_dst -> key
      vectors, logits[n,c,s,m] = <q[n,c,s], k[m]>.  Parameters scale with
      C*S*hidden*key_dim instead of N^2*C*S*hidden (~2000x fewer at
      rung 5), and every op is an einsum on the MXU.

    Both heads multiply by ``obs.mask`` so padded (src, dst) entries are
    exactly zero (models.py:146-153)."""

    agent: AgentConfig
    action_dim: int
    gnn_impl: str = "dense"
    # (N, C, S, N') of the scheduling tensor; required for the factored head
    sched_shape: Tuple[int, int, int, int] = None

    @nn.compact
    def __call__(self, obs, passes: bool = False):
        """``passes`` (torso only): return ``(answers [T, ..., A],
        p [T, ...])``, one answer per pass with the exit distribution,
        where the default returns the answer of the pass the exit
        threshold picks."""
        mdt = self.agent.precision_policy.mlp_dtype
        if not self.agent.graph_mode:
            out = MLP(tuple(self.agent.actor_hidden_layer_nodes)
                      + (self.action_dim,), dtype=mdt)(obs)
            return out.astype(jnp.float32)
        assert isinstance(obs, GraphObs)
        torso, p = self.agent.torso is not None, None
        if torso:
            # the heads below read the torso's per-node states and their
            # masked mean where they read the embedder's
            feats, pooled, p = _torso_features(self.agent, self.gnn_impl,
                                               obs, passes)
        if use_factored_head(self.agent, self.action_dim):
            n, c, s, n2 = _check_sched_shape(self.sched_shape,
                                             self.action_dim)
            if not torso:
                feats = _node_embedder(self.agent, self.gnn_impl)(
                    obs.nodes, obs.edge_index, obs.edge_mask, obs.node_mask)
                pooled = masked_mean_pool(feats, obs.node_mask)
            # per-src hidden through the configured actor stack (global
            # context broadcast onto every node)
            h = jnp.concatenate(
                [feats, jnp.broadcast_to(
                    pooled.astype(feats.dtype)[..., None, :],
                    feats.shape[:-1] + pooled.shape[-1:])],
                axis=-1)
            h = MLP(tuple(self.agent.actor_hidden_layer_nodes),
                    dtype=mdt)(h)
            h = nn.relu(h)
            g = self.agent.factored_key_dim
            q = nn.Dense(c * s * g, name="query",
                         **_dense_kw(mdt))(h)             # [.., N, C*S*G]
            k = nn.Dense(g, name="key", **_dense_kw(mdt))(feats)  # [.., N', G]
            q = q.reshape(q.shape[:-2] + (n, c, s, g))
            if mdt is None:
                out = jnp.einsum("...ncsg,...mg->...ncsm", q, k)
            else:  # bilinear logits accumulate f32
                out = jnp.einsum("...ncsg,...mg->...ncsm", q, k,
                                 preferred_element_type=jnp.float32)
            out = out.reshape(out.shape[:-4] + (self.action_dim,))
        else:
            emb = pooled if torso else _embedder(self.agent, self.gnn_impl)(
                obs.nodes, obs.edge_index, obs.edge_mask, obs.node_mask)
            mask = obs.mask
            if torso:      # one mask per pass
                mask = jnp.broadcast_to(mask,
                                        emb.shape[:-1] + mask.shape[-1:])
            h = jnp.concatenate([emb, mask.astype(emb.dtype)], axis=-1)
            out = MLP(tuple(self.agent.actor_hidden_layer_nodes)
                      + (self.action_dim,), dtype=mdt)(h)
        # actions leave the network in f32 regardless of compute dtype:
        # noise, clipping and replay post-processing stay full precision
        out = (out * obs.mask).astype(jnp.float32)
        return (out, p) if passes and torso else out


class QNetwork(nn.Module):
    """Critic Q(s, a) (models.py:55-95).

    Factored mode mirrors the actor: the [src, sfc, sf, dst] action is
    contracted against per-node key vectors over the dst axis, giving
    per-src action features that join the node embeddings; a per-node
    Dense + masked mean-pool reduces to a fixed-size vector regardless of
    N, and the configured critic MLP scores it.  (The monolithic head's
    explicit mask input is dropped here: the mask is derived purely from
    node_mask — actions.py action_mask — and node validity already enters
    through the GNN.  Replayed actions DO carry mass on masked entries
    after exploration noise / renormalization; the critic simply reads it
    through the same contraction.)

    The factoring decision keys on ``action.shape[-1]`` at call time, so a
    construction site cannot accidentally pick the monolithic head by
    omitting a field."""

    agent: AgentConfig
    gnn_impl: str = "dense"
    action_dim: int = 0       # informational; the call uses action.shape[-1]
    sched_shape: Tuple[int, int, int, int] = None

    @nn.compact
    def __call__(self, obs, action, passes: bool = False):
        """With a torso the action enters after it, so ``action`` may
        carry leading axes the observation lacks (several candidate
        actions for one state share one pass of the torso); ``passes``
        returns ``(q [T, ..., 1], p [T, ...])``, one answer per pass with
        the exit distribution, where the default answers from the pass
        the exit threshold picks."""
        mdt = self.agent.precision_policy.mlp_dtype
        if not self.agent.graph_mode:
            out = MLP(tuple(self.agent.critic_hidden_layer_nodes) + (1,),
                      dtype=mdt)(
                jnp.concatenate([obs, action.astype(obs.dtype)], axis=-1))
            return out.astype(jnp.float32)
        assert isinstance(obs, GraphObs)
        torso, p = self.agent.torso is not None, None
        node_mask = obs.node_mask
        if torso:
            assert not passes or action.ndim == obs.mask.ndim, \
                "one action per state when every pass answers"
            feats, pooled, p = _torso_features(self.agent, self.gnn_impl,
                                               obs, passes)
            # states and action meet on their common leading axes
            lead = jnp.broadcast_shapes(pooled.shape[:-1], action.shape[:-1])
            feats = jnp.broadcast_to(feats, lead + feats.shape[-2:])
            pooled = jnp.broadcast_to(pooled, lead + pooled.shape[-1:])
            action = jnp.broadcast_to(action, lead + action.shape[-1:])
            node_mask = jnp.broadcast_to(node_mask,
                                         lead + node_mask.shape[-1:])
        if use_factored_head(self.agent, action.shape[-1]):
            n, c, s, n2 = _check_sched_shape(self.sched_shape,
                                             action.shape[-1])
            if not torso:
                feats = _node_embedder(self.agent, self.gnn_impl)(
                    obs.nodes, obs.edge_index, obs.edge_mask, obs.node_mask)
                pooled = masked_mean_pool(feats, obs.node_mask)
            g = self.agent.factored_key_dim
            a4 = action.reshape(action.shape[:-1] + (n, c, s, n2))
            k = nn.Dense(g, name="key", **_dense_kw(mdt))(feats)  # [.., N', G]
            if mdt is None:
                a_enc = jnp.einsum("...ncsm,...mg->...ncsg", a4, k)
            else:  # action contraction accumulates f32
                a_enc = jnp.einsum("...ncsm,...mg->...ncsg",
                                   a4.astype(jnp.dtype(mdt)), k,
                                   preferred_element_type=jnp.float32)
            z = jnp.concatenate(
                [feats, a_enc.reshape(a_enc.shape[:-3]
                                      + (c * s * g,)).astype(feats.dtype)],
                axis=-1)
            z = nn.relu(nn.Dense(self.agent.gnn_features, name="src",
                                 **_dense_kw(mdt))(z))
            z = masked_mean_pool(z, node_mask)
            h = jnp.concatenate([pooled, z], axis=-1)
        else:
            emb = pooled if torso else _embedder(self.agent, self.gnn_impl)(
                obs.nodes, obs.edge_index, obs.edge_mask, obs.node_mask)
            mask = obs.mask
            if torso:      # one mask per pass and candidate action
                mask = jnp.broadcast_to(mask,
                                        emb.shape[:-1] + mask.shape[-1:])
            h = jnp.concatenate([emb, mask.astype(emb.dtype),
                                 action.astype(emb.dtype)], axis=-1)
        # Q-values leave in f32: TD targets and losses stay full precision
        q = MLP(tuple(self.agent.critic_hidden_layer_nodes) + (1,),
                dtype=mdt)(h).astype(jnp.float32)
        return (q, p) if passes and torso else q


def scale_action(action: jnp.ndarray, low: float = 0.0,
                 high: float = 1.0) -> jnp.ndarray:
    """[low, high] -> [-1, 1] (models.py:127-135)."""
    return 2.0 * (action - low) / (high - low) - 1.0


def unscale_action(scaled: jnp.ndarray, low: float = 0.0,
                   high: float = 1.0) -> jnp.ndarray:
    """[-1, 1] -> [low, high] (models.py:137-144)."""
    return low + 0.5 * (scaled + 1.0) * (high - low)
