"""GATv2 graph modules (flax) — TPU-native.

The reference embeds ≤24-node network graphs with torch-geometric
``GATv2Conv`` layers (src/rlsp/agents/models.py:10-53): an encoder conv, then
``num_layers-1`` process convs applied ``num_iter`` times with *shared
weights* (weight-tied message passing), ReLU between, masked mean-pool
readout.  Single attention head, configurable neighborhood aggregation
(``mean`` in sample_agent.yaml:32), self-loops included.

The graph here is dense and padded (MAX_NODES fixed), so attention is a
masked [N, N] softmax — batches of graphs map straight onto the MXU as
batched matmuls, with no gather/scatter in the hot path.  The attention math
lives in ``gsc_tpu.ops`` with three parity-tested implementations (dense XLA,
edge-list segment-sum, fused Pallas kernel) selected by ``impl``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.gat import dense_adj, gatv2_dense, gatv2_segment, project


class GATv2Conv(nn.Module):
    """One GATv2 layer (reference: torch_geometric GATv2Conv as used at
    models.py:22-27).  ``impl``: 'dense' (default), 'segment' or 'pallas'.

    ``compute_dtype`` (PrecisionPolicy.gnn_compute, e.g. "bfloat16") sets
    the attention compute precision; parameters are always stored f32
    (master copies) and cast at use, and ``None`` keeps the exact legacy
    f32 path."""

    features: int
    mean_aggr: bool = True
    impl: str = "dense"
    compute_dtype: str = None

    @nn.compact
    def __call__(self, x, adj=None, edge_index=None, edge_mask=None,
                 node_mask=None):
        f_in = x.shape[-1]
        cd = self.compute_dtype
        glorot = nn.initializers.glorot_uniform()
        w_l = self.param("w_l", glorot, (f_in, self.features))
        b_l = self.param("b_l", nn.initializers.zeros, (self.features,))
        w_r = self.param("w_r", glorot, (f_in, self.features))
        b_r = self.param("b_r", nn.initializers.zeros, (self.features,))
        att = self.param("att", glorot, (self.features, 1))[:, 0]
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        if self.impl == "segment":
            fn = lambda xi, ei, em, nm: gatv2_segment(
                xi, ei, em, nm, w_l, b_l, w_r, b_r, att, bias,
                self.mean_aggr, compute_dtype=cd)
            for _ in range(x.ndim - 2):
                fn = jax.vmap(fn)
            return fn(x, edge_index, edge_mask, node_mask)
        if self.impl == "pallas":
            from ..ops.pallas_gat import gatv2_pallas
            with jax.named_scope("gat_layer"):
                xl = project(x, w_l, b_l, cd)
                xr = project(x, w_r, b_r, cd)
                return gatv2_pallas(xl, xr, att, bias, adj, self.mean_aggr)
        return gatv2_dense(x, adj, w_l, b_l, w_r, b_r, att, bias,
                           self.mean_aggr, compute_dtype=cd)


def masked_mean_pool(x: jnp.ndarray, node_mask: jnp.ndarray) -> jnp.ndarray:
    """global_mean_pool over real nodes (models.py:44, 53).  The readout
    reduction always ACCUMULATES in f32 (PrecisionPolicy accum contract) —
    a no-op for f32 inputs, a widening cast for bf16 activations."""
    xf = x.astype(jnp.float32)
    m = node_mask.astype(xf.dtype)[..., None]
    return (xf * m).sum(axis=-2) / jnp.maximum(m.sum(axis=-2), 1.0)


class GNNEmbedder(nn.Module):
    """Encoder conv + weight-tied process convs iterated ``num_iter`` times,
    ReLU between convs, masked mean-pool readout (models.py:10-53).  Defaults
    follow sample_agent.yaml:29-32 (22 features, 2 layers, 2 iters, mean)."""

    hidden: int = 22
    num_layers: int = 2
    num_iter: int = 2
    mean_aggr: bool = True
    impl: str = "dense"
    pool: bool = True   # False: return per-node features at the readout
                        # point (factored action heads read node embeddings)
    compute_dtype: str = None  # PrecisionPolicy.gnn_compute; None = f32

    @nn.compact
    def __call__(self, nodes, edge_index, edge_mask, node_mask):
        adj = None
        if self.impl != "segment":
            adj = dense_adj(edge_index, edge_mask, node_mask)
        kw = dict(adj=adj, edge_index=edge_index, edge_mask=edge_mask,
                  node_mask=node_mask)
        conv_args = dict(features=self.hidden, mean_aggr=self.mean_aggr,
                         impl=self.impl, compute_dtype=self.compute_dtype)

        def readout(x):
            return masked_mean_pool(x, node_mask) if self.pool else x

        x = GATv2Conv(**conv_args, name="encoder")(nodes, **kw)
        x = nn.relu(x)
        if self.num_layers == 1:
            return readout(x)
        # instantiating each process conv once and calling it num_iter times
        # shares its parameters — the reference's weight tying (models.py:44-53)
        process = [GATv2Conv(**conv_args, name=f"process_{i}")
                   for i in range(self.num_layers - 1)]
        for it in range(self.num_iter):
            for i, conv in enumerate(process):
                x = conv(x, **kw)
                if i == self.num_layers - 2 and it == self.num_iter - 1:
                    return readout(x)
                x = nn.relu(x)
