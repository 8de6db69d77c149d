"""Host milliseconds per window episode under ``scenario_regen`` (the
episode's topology and traffic) and ``reset_enqueue`` (``reset_all``)."""
from benchmarks.metrics._spans import mean_ms


def read(record):
    return mean_ms(record, ("scenario_regen", "reset_enqueue"))
