"""Model FLOPs from shapes: what the algorithm needs, whatever implements
it.  A multiply-add counts two; a backward pass counts twice its forward
(weight and input gradients), an input-gradient-only backward once.

Per environment step the policy runs one actor forward per replica.  Per
gradient step and batch row the learner runs the target actor and target
critic forward, the critic forward and backward, the actor forward and
backward, and the critic forward with the backward to its action input.
The simulator's work is not model FLOPs and is not counted.
"""
from __future__ import annotations

from typing import Dict


def gatv2_flops(n: int, f_in: int, f: int) -> int:
    """One dense GATv2 layer on n nodes: two projections, the pairwise
    sum with its LeakyReLU, the logits contraction, the aggregation."""
    return 2 * (2 * n * f_in * f) + 2 * n * n * f + 2 * n * n * f \
        + 2 * n * n * f


def embedder_flops(n: int, f_in: int, f: int, layers: int, iters: int) -> int:
    total = gatv2_flops(n, f_in, f)
    if layers > 1:
        total += iters * (layers - 1) * gatv2_flops(n, f, f)
    return total


def mlp_flops(sizes) -> int:
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def model_flops(cfg: dict) -> Dict[str, float]:
    """``actor_fwd``, ``critic_fwd`` (one row), ``env_step`` (policy work
    per environment step) and ``grad_step`` (learner work per gradient
    step, the whole batch)."""
    n = int(cfg["max_nodes"])
    sfcs = len(cfg["service"]["sfc_list"])
    sfs = max(len(c) for c in cfg["service"]["sfc_list"].values())
    a = n * sfcs * sfs * n
    f = int(cfg["GNN_features"])
    f_in = len(cfg["observation_space"])
    emb = embedder_flops(n, f_in, f, int(cfg["GNN_num_layers"]),
                         int(cfg["GNN_num_iter"]))
    ah = list(cfg["actor_hidden_layer_nodes"])
    ch = list(cfg["critic_hidden_layer_nodes"])
    if a >= int(cfg["factored_head_threshold"]):
        g = int(cfg["factored_key_dim"])
        csg = sfcs * sfs * g
        actor = emb + n * mlp_flops([2 * f] + ah) \
            + n * 2 * ah[-1] * csg + n * 2 * f * g + 2 * n * csg * n
        critic = emb + n * 2 * f * g + 2 * n * csg * n \
            + n * 2 * (f + csg) * f + mlp_flops([2 * f] + ch + [1])
    else:
        actor = emb + mlp_flops([f + a] + ah + [a])
        critic = emb + mlp_flops([f + 2 * a] + ch + [1])
    batch = int(cfg["batch_size"])
    grad = batch * ((actor + critic) + 3 * critic + 3 * actor + 2 * critic)
    return {"actor_fwd": float(actor), "critic_fwd": float(critic),
            "env_step": float(actor), "grad_step": float(grad)}
