"""Device operations of one simulator substep in the compiled
``chunk_step``: the ``sim_substep`` scope with ``traffic_arrivals``
inside it."""
from benchmarks.metrics._spans import scope_ops


def read(record):
    return scope_ops(record, "sim_substep")
