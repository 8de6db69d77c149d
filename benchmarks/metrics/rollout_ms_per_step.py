"""Device time of one control step of the rollout, milliseconds: the
untraced episode's device span less the traced learn burst, over the
episode's control steps (``_common.rollout_seconds_per_step``)."""
from benchmarks.metrics._common import rollout_seconds_per_step


def read(record):
    seconds = rollout_seconds_per_step(record)
    return None if seconds is None else 1e3 * seconds
