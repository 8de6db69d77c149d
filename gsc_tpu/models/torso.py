"""Looped decoder stack as the torso of actor and critic
(``AgentConfig.torso`` -> :class:`~gsc_tpu.config.schema.TorsoConfig`).

The architecture is the looped language model of "Scaling Latent Reasoning
via Looped Language Models" (arXiv 2510.25741; ByteDance/Ouro): a stack of
``L`` decoder layers applied ``T`` times **on the same weights**, the
normed output of one pass feeding the next, with a learned exit gate that
turns the passes into a distribution over where to stop.  Here its tokens
are the network's nodes (the GNN embedder's per-node output stands where
the token embedding stood), so attention is not causal and masks padded
slots, and there is one exit distribution per graph.

One layer, sandwich-normed (four RMSNorm scales)::

    a  = h + N2(Attn(N1(h)))            Attn: q, k, v = u Wq, u Wk, u Wv per
    h' = a + N4(MLP(N3(a)))             head, rotary embedding on the whole
    MLP(u) = (silu(u Wg) * u Wu) Wd     head with the node's slot index as
                                        position, softmax(q k^T / sqrt(hd)
                                        + mask) v, then Wo; no biases

One pass: the L layers in order, then the final norm.  Exit gate:
``z_t`` = masked mean over real nodes of ``h_t``, ``lambda_t =
sigmoid(w_g . z_t + b_g)``, ``p_t = lambda_t prod_{j<t}(1 - lambda_j)``
for t < T and ``p_T`` the remainder.

Every layer's weights are ONE stacked leaf ``[L, ...]`` run by a
``lax.scan`` over layers inside a ``lax.scan`` over passes, so the program
text and its compile time do not grow with L x T, and reverse mode sums a
shared leaf's gradient over the passes by construction.  Leaf names avoid
the partition rulebooks' ``kernel`` / ``w_l`` / ``w_r`` patterns on
purpose: those rules were written for 2-D leaves and would put ``mp`` on
the contraction axis of a stacked ``[L, in, out]`` leaf, so the torso's
leaves stay replicated under every book.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.schema import TorsoConfig

NEG_INF = -1e30


def dot(x, w):
    """``x @ w`` with float32 accumulation, whatever the operands."""
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary_tables(n: int, head_dim: int, theta: float):
    """cos / sin ``[n, head_dim]`` for positions 0..n-1, the half-split
    layout (the first half of a head pairs with the second)."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """``x``: [..., N, heads, head_dim]; tables [N, head_dim]."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def exit_distribution(lam):
    """``lam`` [T, ...] gate outputs -> ``p`` [T, ...]: the probability of
    stopping after pass t; the last pass takes what is left, so the T-th
    gate output is not read and ``p`` sums to one."""
    if lam.shape[0] == 1:
        return jnp.ones_like(lam)
    surv = jnp.cumprod(1.0 - lam[:-1], axis=0)      # prod_{j<=t}(1-lam_j)
    return jnp.concatenate(
        [lam[:1], lam[1:-1] * surv[:-1], surv[-1:]], axis=0)


def exit_entropy(p):
    """H(p) over the pass axis (axis 0)."""
    return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)


def exit_pass(p, threshold: float) -> Optional[jnp.ndarray]:
    """The pass acting, targets and the actor's Q read: the first t whose
    cumulative ``p`` reaches ``threshold`` (0-based index, [...]), the
    last where none does.  ``None`` stands for "the last pass, always":
    below the last pass the cumulative mass is ``1 - prod(1 - lambda)``
    with every ``lambda`` < 1, so a threshold of one is only ever reached
    by the remainder — decided here, statically, not by whether a sigmoid
    rounded to one."""
    if threshold >= 1.0:
        return None
    reached = jnp.cumsum(p, axis=0) >= threshold
    last = p.shape[0] - 1
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), last)


def take_pass(x, idx: Optional[jnp.ndarray]):
    """``x`` [T, *b, ...] at pass ``idx`` ([*b] or None = last) ->
    [*b, ...]."""
    if idx is None:
        return x[-1]
    idx = idx.reshape((1,) + idx.shape + (1,) * (x.ndim - 1 - idx.ndim))
    return jnp.take_along_axis(x, idx, axis=0)[0]


class LoopedTorso(nn.Module):
    """``(x [..., N, F], node_mask [..., N]) -> (h [T, ..., N, d],
    z [T, ..., d], p [T, ...])``: per-node states, their masked mean and
    the exit distribution, one entry per pass."""

    cfg: TorsoConfig

    @nn.compact
    def __call__(self, x, node_mask) -> Tuple[jnp.ndarray, jnp.ndarray,
                                              jnp.ndarray]:
        c = self.cfg
        d, hd, inter = c.hidden_size, c.head_dim, c.intermediate_size
        nh, nkv, L = (c.num_attention_heads, c.num_key_value_heads,
                      c.num_hidden_layers)
        n = x.shape[-2]
        glorot = nn.initializers.glorot_uniform(in_axis=-2, out_axis=-1,
                                                batch_axis=(0,))
        ones = nn.initializers.ones
        w_in = self.param("w_in", nn.initializers.glorot_uniform(),
                          (x.shape[-1], d))
        layers = {
            "wq": self.param("wq", glorot, (L, d, nh * hd)),
            "wk": self.param("wk", glorot, (L, d, nkv * hd)),
            "wv": self.param("wv", glorot, (L, d, nkv * hd)),
            "wo": self.param("wo", glorot, (L, nh * hd, d)),
            "w_gate": self.param("w_gate", glorot, (L, d, inter)),
            "w_up": self.param("w_up", glorot, (L, d, inter)),
            "w_down": self.param("w_down", glorot, (L, inter, d)),
            "norm_attn_in": self.param("norm_attn_in", ones, (L, d)),
            "norm_attn_out": self.param("norm_attn_out", ones, (L, d)),
            "norm_mlp_in": self.param("norm_mlp_in", ones, (L, d)),
            "norm_mlp_out": self.param("norm_mlp_out", ones, (L, d)),
        }
        final_norm = self.param("final_norm", ones, (d,))
        gate_w = self.param("gate_w", nn.initializers.glorot_uniform(),
                            (d, 1))
        gate_b = self.param("gate_b", nn.initializers.zeros, (1,))

        eps = c.rms_norm_eps
        cos, sin = rotary_tables(n, hd, c.rope_theta)
        key_bias = jnp.where(node_mask, 0.0, NEG_INF)[..., None, None, :]
        real = node_mask.astype(jnp.float32)[..., None]
        count = jnp.maximum(real.sum(axis=-2), 1.0)

        def attention(u, w):
            lead = u.shape[:-1]
            q = apply_rotary(dot(u, w["wq"]).reshape(lead + (nh, hd)),
                             cos, sin)
            k = apply_rotary(dot(u, w["wk"]).reshape(lead + (nkv, hd)),
                             cos, sin)
            v = dot(u, w["wv"]).reshape(lead + (nkv, hd))
            if nkv != nh:
                k = jnp.repeat(k, nh // nkv, axis=-2)
                v = jnp.repeat(v, nh // nkv, axis=-2)
            logits = jnp.einsum("...qhd,...khd->...hqk", q, k,
                                preferred_element_type=jnp.float32) \
                * (hd ** -0.5) + key_bias
            out = jnp.einsum("...hqk,...khd->...qhd",
                             jax.nn.softmax(logits, axis=-1), v,
                             preferred_element_type=jnp.float32)
            return dot(out.reshape(lead + (nh * hd,)), w["wo"])

        def mlp(u, w):
            return dot(jax.nn.silu(dot(u, w["w_gate"])) * dot(u, w["w_up"]),
                       w["w_down"])

        def layer(h, w):
            with jax.named_scope("torso_attention"):
                a = h + rms_norm(
                    attention(rms_norm(h, w["norm_attn_in"], eps), w),
                    w["norm_attn_out"], eps)
            with jax.named_scope("torso_mlp"):
                h = a + rms_norm(mlp(rms_norm(a, w["norm_mlp_in"], eps), w),
                                 w["norm_mlp_out"], eps)
            return h, None

        def one_pass(h, _):
            with jax.named_scope("torso_pass"):
                h, _ = jax.lax.scan(jax.checkpoint(layer), h, layers)
                h = rms_norm(h, final_norm, eps)
            return h, h

        h0 = dot(x.astype(jnp.float32), w_in)
        _, h = jax.lax.scan(one_pass, h0, None, length=c.total_ut_steps)
        with jax.named_scope("exit_gate"):
            z = (h * real).sum(axis=-2) / count               # [T, ..., d]
            lam = jax.nn.sigmoid(dot(z, gate_w)[..., 0] + gate_b[0])
            p = exit_distribution(lam)
        return h, z, p
