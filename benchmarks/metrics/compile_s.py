"""Seconds the compile monitor counted (tracing and XLA compilation of the
watched entry points) before the window opened."""
from benchmarks.metrics._common import events


def read(record):
    if record.get("opened") is None:
        return None
    return sum(float(e.get("duration_s", 0.0))
               for e in events(record, "compile")
               if e.get("ts", 0.0) < record["opened"])
