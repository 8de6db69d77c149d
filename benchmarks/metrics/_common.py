"""What several readers share: the window's span and its episodes."""
from __future__ import annotations

from typing import List, Optional


def window_seconds(record: dict) -> Optional[float]:
    if not record.get("window_episodes") or record.get("opened") is None \
            or record.get("closed_at") is None:
        return None
    return record["closed_at"] - record["opened"]


def env_steps(record: dict) -> int:
    return (record["replicas"] * record["episode_steps"]
            * record["window_episodes"])


def events(record: dict, kind: str) -> List[dict]:
    return [e for e in record.get("events", []) if e.get("event") == kind]


def learn_burst_seconds(record: dict) -> Optional[float]:
    """Device seconds of the learn burst's loop, found by where it stands
    and not by how long it is: the last ``while`` that no recorded
    operation encloses in the traced slice.  The slice is the last
    seconds of an episode (the cell's ``trace_slice_s``); the episode's
    last program runs its rollout scan and then the burst's
    ``fori_loop``, after which no loop follows.  The rollout scan began
    before the slice and is not held, so the 100-substep scans of its
    last control steps stand un-enclosed too, before the burst, each as
    long as a control step (on the flagship 115 ms against the burst's
    138 ms, PERF.md section 5): a reader that took the longest loop would
    report a substep scan once the burst got a fifth faster."""
    loops = (record.get("trace") or {}).get("top_level_loops") or []
    return loops[-1][1] if loops else None


def rollout_seconds_per_step(record: dict) -> Optional[float]:
    """Device seconds of one control step of the rollout: the window's
    first (untraced) episode's device span on the host clock —
    ``dispatch`` + ``drain`` phase walls, first enqueue to last completion
    — less the traced learn burst, over the episode's control steps
    (``reset_all``'s milliseconds are shared among them)."""
    burst = learn_burst_seconds(record)
    span = device_span(record, record["warm_episodes"])
    if burst is None or span is None:
        return None
    return (span - burst) / record["episode_steps"]


def device_span(record: dict, episode: int) -> Optional[float]:
    """Host wall of an episode's ``dispatch`` and ``drain`` phases: from
    the first enqueue to the last program's completion."""
    totals = {}
    for e in events(record, "episode"):
        ph = e.get("phases") or {}
        totals[e["episode"]] = sum(
            ph.get(p, {}).get("total_s", 0.0) for p in ("dispatch", "drain"))
    if episode not in totals or (episode > 0 and episode - 1 not in totals):
        return None
    return totals[episode] - totals.get(episode - 1, 0.0)
