"""Static analysis + runtime sentinels for the jit discipline.

Three generations of hand-won invariants — donation safety (PR 1), the
telemetry contracts (PR 2), the precision-policy dtype discipline (PR 3)
— are enforced here mechanically:

- :mod:`~gsc_tpu.analysis.astlint` — the AST linter behind
  ``tools/gsc_lint.py`` (rules R1–R5: host syncs in traced code,
  use-after-donation, impure trace-time state, missing
  ``preferred_element_type`` in bf16-policy modules, weak-type scalar
  args at jitted entry points).
- :mod:`~gsc_tpu.analysis.concur` — the concurrency-discipline rules
  (R6–R10: lock-order cycles, ``# guarded-by:`` field discipline,
  multi-device dispatch outside ``dispatch_lock`` — the PR 18 deadlock
  class — blocking calls while holding a lock, and unnamed/non-daemon
  thread constructors), run through the same driver and baseline.
- :mod:`~gsc_tpu.analysis.baseline` — the suppression baseline that
  encodes accepted pre-existing cases (each with a written reason), so
  CI fails only on NEW findings.
- :mod:`~gsc_tpu.analysis.hlo` — compiled-HLO structure metrics:
  ``count_fusions`` (the op-count perf proxy that gates substep changes
  — the rejected bit-exact-but-281->294-fusions scatter-merge is the
  case it encodes), read by the cost ledger (``obs/perf.py``) and the
  tier-1 fusion-budget test (``tests/test_engine.py``).
- :mod:`~gsc_tpu.analysis.sentinels` — the runtime side:
  :class:`CompileMonitor` (per-entry-point trace/compile counting, wired
  into ``events.jsonl`` as ``compile`` events), ``assert_no_retrace``
  and ``no_host_sync`` guards used by ``pytest -m analysis`` tests to
  prove the pipelined episode loop compiles once and performs zero
  unplanned device->host syncs in steady state.

The linter is stdlib-only (``ast``); jax is imported lazily by the
sentinels so ``tools/gsc_lint.py`` runs on a login node without device
init.
"""
from .astlint import DONATED_SIGS, lint_files, lint_paths
from .baseline import (apply_baseline, inline_suppression, load_baseline,
                       save_baseline)
from .concur import DISPATCH_NAMES, check_concurrency
from .findings import RULE_IDS, RULE_TITLES, Finding, LintResult
from .hlo import count_fusions, count_ops, hlo_text
from .sentinels import (DEFAULT_WATCH, CompileMonitor, HostSyncError,
                        RetraceError, assert_no_retrace, no_host_sync)

__all__ = [
    "DONATED_SIGS", "lint_files", "lint_paths",
    "DISPATCH_NAMES", "check_concurrency",
    "apply_baseline", "inline_suppression", "load_baseline",
    "save_baseline",
    "RULE_IDS", "RULE_TITLES", "Finding", "LintResult",
    "count_fusions", "count_ops", "hlo_text",
    "DEFAULT_WATCH", "CompileMonitor", "HostSyncError", "RetraceError",
    "assert_no_retrace", "no_host_sync",
]
