"""Bounded exponential-backoff retry for transient dispatch failures.

A single runtime error at dispatch used to kill an entire training run;
production systems retry such failures with backoff before escalating
(MindSpeed RL, arXiv:2507.19017).  The retried set is
``jax.errors.JaxRuntimeError`` — EVERY error the XLA runtime raises, a
compile-time out-of-memory included, because the runtime does not type
its errors by cause — plus the fault injector's own class.  A
deterministic failure therefore costs ``attempts`` tries and their
backoff before it propagates unchanged; Python-level errors (shape
mismatches, bad arguments) are never retried.

Donation caveat: the trainer's dispatch closures re-run end-to-end on
retry.  A failure raised at call entry (the common transient shape, and
where the fault injector raises) leaves the donated carries untouched; a
fault that aborted mid-program may have invalidated them, in which case
the retry itself fails fast with XLA's donation error and propagates after
the bounded attempts — retry never hides a genuinely broken carry.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Optional, Tuple

log = logging.getLogger("gsc_tpu.resilience.retry")


class TransientDispatchError(RuntimeError):
    """An injected runtime-error-like transient dispatch failure
    (``FaultPlan`` site ``dispatch_transient``)."""


def transient_error_types() -> Tuple[type, ...]:
    """Error types retried: the injected transient class and the XLA
    runtime's error type."""
    import jax

    return (TransientDispatchError, jax.errors.JaxRuntimeError)


@dataclasses.dataclass
class RetryPolicy:
    """``attempts`` TOTAL tries; sleep ``min(cap_s, base_s * 2**k)`` before
    retry k (k >= 1)."""

    attempts: int = 3
    base_s: float = 0.05
    cap_s: float = 2.0

    def backoff_s(self, attempt: int) -> float:
        return min(self.cap_s, self.base_s * (2.0 ** max(attempt - 1, 0)))


def call_with_retry(fn: Callable, policy: Optional[RetryPolicy] = None,
                    on_retry: Optional[Callable[[int, BaseException, float],
                                                None]] = None):
    """Run ``fn()`` with bounded exponential backoff on transient errors.

    ``on_retry(attempt, exc, backoff_s)`` fires before each re-attempt
    (attempt numbering starts at 1 for the first RETRY) — the trainer
    hangs its structured ``recovery`` event off it.  The final failure
    propagates unchanged."""
    policy = policy or RetryPolicy()
    transient = transient_error_types()
    for attempt in range(1, max(policy.attempts, 1) + 1):
        try:
            return fn()
        except transient as e:
            if attempt >= policy.attempts:
                log.error("transient dispatch failure persisted through "
                          "%d attempts: %r", attempt, e)
                raise
            delay = policy.backoff_s(attempt)
            if on_retry is not None:
                on_retry(attempt, e, delay)
            log.warning("transient dispatch failure (attempt %d/%d): %r — "
                        "backing off %.2fs", attempt, policy.attempts, e,
                        delay)
            time.sleep(delay)
