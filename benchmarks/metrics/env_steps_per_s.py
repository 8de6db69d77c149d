"""All environment steps of the window over all of the window's time:
``replicas x episode_steps x whole episodes / (close - open)`` on the
host's clock, boundaries stamped after each episode's synchronous drain."""
from benchmarks.metrics._common import env_steps, window_seconds


def read(record):
    span = window_seconds(record)
    return None if not span else env_steps(record) / span
