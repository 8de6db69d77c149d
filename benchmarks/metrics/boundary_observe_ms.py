"""Host milliseconds per window episode under ``harness_observe`` (the
harness's gauges, ``harness_episode``, the learn signal, the caller's
hook) and ``episode_log`` (history row, rewards writer, TensorBoard,
``obs.episode_end``)."""
from benchmarks.metrics._spans import mean_ms


def read(record):
    return mean_ms(record, ("harness_observe", "episode_log"))
