"""Device operations of one replay write in the compiled ``chunk_step``:
the ``replay_write`` scope."""
from benchmarks.metrics._spans import scope_ops


def read(record):
    return scope_ops(record, "replay_write")
