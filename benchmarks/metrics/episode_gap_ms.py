"""Per window episode, boundary-to-boundary wall less the loop's own
``dispatch`` and ``drain`` phase walls and less what the harness's own
boundary hook took (what is left: scenario regeneration, the ``reset_all``
enqueue, logging, observers, the checkpoint cadence's finite check), mean
over the window, in milliseconds."""
from benchmarks.metrics._common import events


def read(record):
    warm, n = record["warm_episodes"], record.get("window_episodes", 0)
    stamps = record.get("stamps") or []
    if not n or len(stamps) < warm + n + 1:
        return None
    totals = {}
    for e in events(record, "episode"):
        ph = e.get("phases") or {}
        totals[e["episode"]] = sum(
            ph.get(p, {}).get("total_s", 0.0) for p in ("dispatch", "drain"))
    gaps = []
    for k in range(warm, warm + n):
        if k not in totals or (k - 1 not in totals and k > 0):
            return None
        in_phases = totals[k] - totals.get(k - 1, 0.0)
        hook = (record.get("hook_s") or [0.0] * (n + 1))[k - warm]
        gaps.append(stamps[k + 1] - stamps[k] - in_phases - hook)
    return 1e3 * sum(gaps) / len(gaps)
