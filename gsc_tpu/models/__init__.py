"""Neural network models (reference: src/rlsp/agents/models.py)."""
from ..ops.gat import dense_adj, gatv2_dense, gatv2_segment
from .gnn import GATv2Conv, GNNEmbedder, masked_mean_pool
from .nets import MLP, Actor, QNetwork, scale_action, unscale_action
from .torso import LoopedTorso

__all__ = [
    "GATv2Conv", "GNNEmbedder", "dense_adj", "gatv2_dense", "gatv2_segment",
    "masked_mean_pool", "MLP", "Actor", "QNetwork", "LoopedTorso",
    "scale_action",
    "unscale_action",
]
