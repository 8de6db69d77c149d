"""Traces and compilations of watched entry points that the compile
monitor counted inside the window (expected 0)."""
from benchmarks.metrics._common import events


def read(record):
    if record.get("opened") is None or record.get("closed_at") is None:
        return None
    return float(sum(
        1 for e in events(record, "compile")
        if record["opened"] <= e.get("ts", 0.0) <= record["closed_at"]))
