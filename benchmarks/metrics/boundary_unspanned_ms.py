"""Host milliseconds per window episode of the root ``episode`` span that
none of its children covers."""
from benchmarks.metrics._spans import root_self_ms


def read(record):
    return root_self_ms(record)
