"""Device operations of the policy's forward pass in the compiled
``chunk_step``: the ``policy_forward`` scope with the ``gat_layer``
operations met under it."""
from benchmarks.metrics._spans import scope_ops


def read(record):
    return scope_ops(record, "policy_forward")
