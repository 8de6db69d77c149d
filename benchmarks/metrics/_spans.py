"""What the span and scope readers share: the window episodes' recorded
host spans (``episode_spans`` events) and the dispatched ``chunk_step``'s
operations by named scope (its ``compile_cost`` event).  A record from a
program that emits neither gives ``None`` everywhere."""
from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks.metrics._common import events


def window_spans(record: dict) -> Optional[Dict[int, List[dict]]]:
    """``{episode: its spans}`` for the window's episodes, or None where
    one of them has no root ``episode`` span."""
    warm, n = record.get("warm_episodes"), record.get("window_episodes") or 0
    if warm is None or not n:
        return None
    by_episode = {k: [] for k in range(warm, warm + n)}
    for e in events(record, "episode_spans"):
        for span in e.get("spans") or []:
            if span.get("episode") in by_episode:
                by_episode[span["episode"]].append(span)
    if not all(any(s["name"] == "episode" and s.get("parent") is None
                   for s in spans) for spans in by_episode.values()):
        return None
    return by_episode


def mean_ms(record: dict, names) -> Optional[float]:
    """Milliseconds under the named spans, mean per window episode."""
    by_episode = window_spans(record)
    if by_episode is None:
        return None
    totals = [sum(s["dur_s"] for s in spans if s["name"] in names)
              for spans in by_episode.values()]
    return 1e3 * sum(totals) / len(totals)


def root_self_ms(record: dict) -> Optional[float]:
    """The root ``episode`` span less its children, mean per window
    episode: what the loop does under no span of its own."""
    by_episode = window_spans(record)
    if by_episode is None:
        return None
    left = [sum(s["dur_s"] for s in spans
                if s["name"] == "episode" and s.get("parent") is None)
            - sum(s["dur_s"] for s in spans if s.get("parent") == "episode")
            for spans in by_episode.values()]
    return 1e3 * sum(left) / len(left)


def chunk_step_scopes(record: dict) -> Optional[dict]:
    """``scopes`` of the ``chunk_step`` cost capture, or None."""
    for e in events(record, "compile_cost"):
        if e.get("fn") == "chunk_step" and e.get("scopes"):
            return e["scopes"]
    return None


def scope_ops(record: dict, scope: str, less_own_moves: bool = False
              ) -> Optional[float]:
    """Operations of the compiled ``chunk_step`` whose path passes through
    ``scope`` (nested scopes included), each counted once per occurrence in
    the program text.  ``less_own_moves`` leaves out what the compiler
    inserted for the scope's own instruction (a loop's operands staged at
    the entry), so a loop's scope reads as one iteration.  None where the
    program carries no such scope."""
    rec = (chunk_step_scopes(record) or {}).get(scope)
    if not rec or not rec.get("ops_incl"):
        return None
    ops = rec["ops_incl"] - (rec.get("inherited", 0) if less_own_moves else 0)
    return float(ops)
