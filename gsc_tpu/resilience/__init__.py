"""Self-healing training: fault injection, guards, retry, preemption.

PR 2's watchdog and PR 4's sentinels made trouble *visible*; this package
makes the stack *survive* it, and proves each path with injected faults:

- :mod:`~gsc_tpu.resilience.faults` — ``FaultPlan``: deterministic named
  faults at named sites keyed by episode index
  (``--fault-plan`` / ``GSC_FAULT_PLAN``).
- :mod:`~gsc_tpu.resilience.guard` — on-device all-finite flags folded
  into the fused episode programs + the trainer's last-good rollback
  snapshot.
- :mod:`~gsc_tpu.resilience.retry` — bounded exponential backoff around
  episode dispatch for transient runtime-error-like failures.
- :mod:`~gsc_tpu.resilience.preempt` — SIGTERM/SIGINT ->
  snapshot-and-exit-cleanly.
- :mod:`~gsc_tpu.resilience.ckpt` — checksummed periodic checkpoints with
  a rotating last-good pointer and ``--resume auto`` discovery.  (Import
  the submodule directly: it pulls in the checkpoint/agent stack, which
  would make this package's import circular for ``agents.ddpg``'s use of
  :func:`~gsc_tpu.resilience.guard.all_finite`.)

The degradation ladder, every rung reported as a structured ``recovery``
event in ``events.jsonl``:

    retry (dispatch) -> prefetcher restart -> pipeline off -> rollback
"""
from .faults import ENV_VAR, SITES, FaultInjected, FaultPlan, FaultSpec
from .guard import RollbackGuard, all_finite, poison_tree, tree_copy
from .preempt import PreemptionGuard
from .retry import (RetryPolicy, TransientDispatchError, call_with_retry,
                    transient_error_types)

__all__ = [
    "ENV_VAR", "SITES", "FaultInjected", "FaultPlan", "FaultSpec",
    "RollbackGuard", "all_finite", "poison_tree", "tree_copy",
    "PreemptionGuard", "RetryPolicy", "TransientDispatchError",
    "call_with_retry", "transient_error_types",
]
