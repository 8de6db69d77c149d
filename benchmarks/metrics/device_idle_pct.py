"""Share of the traced slice's device span (first to last recorded
operation: the rollout's last control steps and the learn burst) during
which no leaf operation ran: the gaps inside the compiled program.  (The
host's gap at the episode boundary is ``episode_gap_ms``; the result
line's ``busy_s``/``window_s`` cover the whole slice, the drain and the
loop's bookkeeping included.)"""


def read(record):
    trace = record.get("trace") or {}
    span, busy = trace.get("ops_span_s"), trace.get("busy_s")
    if not span or not busy:
        return None
    return 100.0 * (1.0 - busy / span)
