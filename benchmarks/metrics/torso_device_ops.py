"""Device operations of one pass of a looped torso in the compiled
``chunk_step``: the ``torso_pass`` scope with the ``torso_attention`` and
``torso_mlp`` operations met under it, every occurrence in the program text
(the policy's forward, the learner's forwards and their transposes).
Nothing to read where the program has no such scope."""
from benchmarks.metrics._spans import scope_ops


def read(record):
    return scope_ops(record, "torso_pass")
