"""GATv2 attention math — pure-function XLA implementations.

These are the kernel-level primitives behind ``gsc_tpu.models.gnn``: dense
masked attention (the default XLA path) and the edge-list segment-sum
formulation (numerically identical to torch-geometric's sparse computation,
used for parity tests).  The fused Pallas TPU kernel lives in
``gsc_tpu.ops.pallas_gat`` and is parity-tested against ``gatv2_dense``.

GATv2 math per directed edge j->i (torch_geometric GATv2Conv semantics,
reference usage at src/rlsp/agents/models.py:22-27):
    e_ij   = a^T LeakyReLU_0.2(W_l x_j + W_r x_i)
    alpha  = softmax_j(e_ij) over in-neighbors (self-loop included)
    out_i  = aggr_j(alpha_ij * W_l x_j) + b      (aggr: sum or mean)

Mixed precision (config.schema.PrecisionPolicy): every entry point takes a
``compute_dtype`` — ``None`` runs the original float32 code VERBATIM
(bit-identical to the dtype-unaware stack); ``"bfloat16"`` keeps the big
pairwise [.., N, N, F] intermediate and the matmul operands in bf16 while
the attention logits, softmax and all contraction ACCUMULATORS stay f32
(``preferred_element_type``).  ``attention_dense`` keys the branch on its
input dtype so the Pallas kernel's custom VJP (which differentiates through
it) follows the forward's precision automatically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
LEAKY_SLOPE = 0.2


def project(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
            compute_dtype: str | None = None) -> jnp.ndarray:
    """``x @ w + b`` under the precision policy.  ``None``: the original
    f32 expression, bit-identical.  Low precision: operands cast to the
    compute dtype, the matmul accumulates f32 on the MXU
    (``preferred_element_type``), and the activation settles back to the
    compute dtype."""
    if compute_dtype is None:
        return x @ w + b
    cd = jnp.dtype(compute_dtype)
    xc = x.astype(cd)
    y = jax.lax.dot_general(
        xc, w.astype(cd), (((xc.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (y + b).astype(cd)


def dense_adj(edge_index: jnp.ndarray, edge_mask: jnp.ndarray,
              node_mask: jnp.ndarray) -> jnp.ndarray:
    """Directed edge list -> dense [N, N] bool adjacency ``adj[i, j]`` = "j is
    an in-neighbor of i", with self-loops on real nodes (GATv2Conv's
    add_self_loops default).  Leading batch dims supported via vmap."""
    def one(ei, em, nm):
        n = nm.shape[0]
        adj = jnp.zeros((n, n), bool)
        src, dst = ei[0], ei[1]
        adj = adj.at[jnp.where(em, dst, n), jnp.where(em, src, n)].set(
            True, mode="drop")
        return adj | (jnp.eye(n, dtype=bool) & nm[:, None])

    for _ in range(edge_index.ndim - 2):
        one = jax.vmap(one)
    return one(edge_index, edge_mask, node_mask)


def attention_dense(xl: jnp.ndarray, xr: jnp.ndarray, att: jnp.ndarray,
                    bias: jnp.ndarray, adj: jnp.ndarray,
                    mean_aggr: bool) -> jnp.ndarray:
    """The attention STAGE on already-projected features (xl/xr:
    [..., N, F]) — the math the Pallas kernel fuses, and the backward pass
    it borrows (pallas_gat.py defines the kernel's custom VJP through this
    function).

    Precision follows ``xl.dtype``: float32 inputs take the original code
    path verbatim; low-precision inputs (bf16) keep the [.., i, j, F]
    pairwise tensor and both matmul operand sets in that dtype with f32
    logits/softmax/accumulators, and return in the input dtype — the same
    op sequence the bf16 Pallas kernel fuses, so interpret-mode parity
    holds bit-for-bit."""
    if xl.dtype == jnp.float32:
        e = xl[..., None, :, :] + xr[..., :, None, :]   # [..., i, j, F]
        e = jnp.where(e >= 0, e, LEAKY_SLOPE * e)
        logits = jnp.einsum("...ijf,f->...ij", e, att)
        logits = jnp.where(adj, logits, NEG_INF)
        mx = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        ex = jnp.where(adj, jnp.exp(logits - mx), 0.0)
        denom = ex.sum(axis=-1, keepdims=True)
        alpha = ex / jnp.maximum(denom, 1e-30)
        out = jnp.einsum("...ij,...jf->...if", alpha, xl)
        if mean_aggr:
            deg = adj.sum(axis=-1, keepdims=True)
            out = out / jnp.maximum(deg, 1)
        has_nbr = adj.any(axis=-1, keepdims=True)
        return jnp.where(has_nbr, out + bias, 0.0)
    cd = xl.dtype
    e = xl[..., None, :, :] + xr[..., :, None, :]       # [..., i, j, F] bf16
    e = jnp.where(e >= 0, e, LEAKY_SLOPE * e)
    logits = jnp.einsum("...ijf,f->...ij", e, att.astype(cd),
                        preferred_element_type=jnp.float32)
    logits = jnp.where(adj, logits, NEG_INF)            # f32 logits
    mx = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    ex = jnp.where(adj, jnp.exp(logits - mx), 0.0)      # f32 softmax
    denom = ex.sum(axis=-1, keepdims=True)
    alpha = (ex / jnp.maximum(denom, 1e-30)).astype(cd)
    out = jnp.einsum("...ij,...jf->...if", alpha, xl,
                     preferred_element_type=jnp.float32)
    if mean_aggr:
        deg = adj.sum(axis=-1, keepdims=True)
        out = out / jnp.maximum(deg, 1)
    has_nbr = adj.any(axis=-1, keepdims=True)
    return jnp.where(has_nbr, out + bias, 0.0).astype(cd)


def gatv2_dense(x: jnp.ndarray, adj: jnp.ndarray, w_l: jnp.ndarray,
                b_l: jnp.ndarray, w_r: jnp.ndarray, b_r: jnp.ndarray,
                att: jnp.ndarray, bias: jnp.ndarray,
                mean_aggr: bool,
                compute_dtype: str | None = None) -> jnp.ndarray:
    """Dense masked GATv2 layer.  x: [..., N, F_in], adj: [..., N, N] bool.
    ``compute_dtype`` (PrecisionPolicy.gnn_compute) selects the attention
    precision; None is the exact f32 path."""
    with jax.named_scope("gat_layer"):
        xl = project(x, w_l, b_l, compute_dtype)  # [..., N, F] source proj.
        xr = project(x, w_r, b_r, compute_dtype)  # [..., N, F] target proj.
        return attention_dense(xl, xr, att, bias, adj, mean_aggr)


def gatv2_segment(x: jnp.ndarray, edge_index: jnp.ndarray,
                  edge_mask: jnp.ndarray, node_mask: jnp.ndarray,
                  w_l: jnp.ndarray, b_l: jnp.ndarray, w_r: jnp.ndarray,
                  b_r: jnp.ndarray, att: jnp.ndarray, bias: jnp.ndarray,
                  mean_aggr: bool,
                  compute_dtype: str | None = None) -> jnp.ndarray:
    """Edge-list segment-sum GATv2 (torch-geometric's sparse formulation),
    single graph: x [N, F_in], edge_index [2, E].  Self-loops appended for
    real nodes.  With ``compute_dtype`` the per-edge features stay in the
    compute dtype while logits, softmax and the segment-sum aggregation
    accumulate f32 (segment sums of a bf16*f32 product promote to f32)."""
    n = x.shape[0]
    xl = project(x, w_l, b_l, compute_dtype)
    xr = project(x, w_r, b_r, compute_dtype)
    loops = jnp.arange(n)
    # drop any self-loops already present, then append exactly one per real
    # node (torch-geometric removes and re-adds; the dense path dedups via
    # the bool adjacency)
    src = jnp.concatenate([edge_index[0], loops])
    dst = jnp.concatenate([edge_index[1], loops])
    em = jnp.concatenate([edge_mask & (edge_index[0] != edge_index[1]),
                          node_mask])
    e = xl[src] + xr[dst]
    e = jnp.where(e >= 0, e, LEAKY_SLOPE * e)
    if compute_dtype is None:
        logits = jnp.where(em, e @ att, NEG_INF)
    else:
        logits = jnp.where(
            em, jnp.einsum("ef,f->e", e, att.astype(e.dtype),
                           preferred_element_type=jnp.float32), NEG_INF)
    seg_max = jax.ops.segment_max(logits, dst, num_segments=n)
    seg_max = jax.lax.stop_gradient(
        jnp.where(jnp.isfinite(seg_max), seg_max, 0.0))
    ex = jnp.where(em, jnp.exp(logits - seg_max[dst]), 0.0)
    denom = jax.ops.segment_sum(ex, dst, num_segments=n)
    alpha = ex / jnp.maximum(denom[dst], 1e-30)
    out = jax.ops.segment_sum(alpha[:, None] * xl[src], dst, num_segments=n)
    if mean_aggr:
        deg = jax.ops.segment_sum(em.astype(out.dtype), dst, num_segments=n)
        out = out / jnp.maximum(deg[:, None], 1)
    has_nbr = jax.ops.segment_max(em.astype(jnp.int32), dst, num_segments=n) > 0
    out = jnp.where(has_nbr[:, None], out + bias, 0.0)
    return out if compute_dtype is None else out.astype(compute_dtype)
