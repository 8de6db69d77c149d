"""Host milliseconds per window episode under ``ckpt`` (the checkpoint
cadence's finite check and save) and ``publish``."""
from benchmarks.metrics._spans import mean_ms


def read(record):
    return mean_ms(record, ("ckpt", "publish"))
