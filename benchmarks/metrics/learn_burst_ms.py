"""Device time of the end-of-episode learn burst's loop in the traced
slice (``_common.learn_burst_seconds``), milliseconds."""
from benchmarks.metrics._common import learn_burst_seconds


def read(record):
    seconds = learn_burst_seconds(record)
    return None if seconds is None else 1e3 * seconds
