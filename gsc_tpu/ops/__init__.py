"""TPU kernel-level ops: XLA reference implementations + Pallas kernels."""
from .gat import (LEAKY_SLOPE, NEG_INF, dense_adj, gatv2_dense,
                  gatv2_segment, project)
from .pallas_gat import gatv2_pallas

__all__ = ["LEAKY_SLOPE", "NEG_INF", "dense_adj", "gatv2_dense",
           "gatv2_segment", "gatv2_pallas", "project"]
