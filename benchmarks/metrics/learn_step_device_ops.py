"""Device operations of one gradient step in the compiled ``chunk_step``:
the ``learn_burst`` scope with its children (``replay_sample``,
``critic_update``, ``actor_update``, ``target_update`` and the
``gat_layer`` operations under them), less what the compiler stages at the
entry for the burst's loop."""
from benchmarks.metrics._spans import scope_ops


def read(record):
    return scope_ops(record, "learn_burst", less_own_moves=True)
