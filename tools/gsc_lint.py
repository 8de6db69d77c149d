#!/usr/bin/env python
"""gsc-lint CLI — JAX-aware static analysis for this repo.

Usage:
    python tools/gsc_lint.py [paths...]            # default: gsc_tpu/ tools/
    python tools/gsc_lint.py --json [paths...]
    python tools/gsc_lint.py --rules R1,R4 [paths...]
    python tools/gsc_lint.py --changed [REF]       # only files in git diff REF
    python tools/gsc_lint.py --write-baseline      # accept current findings
    python tools/gsc_lint.py --prune-stale         # drop baseline entries
                                                   # that match nothing
    python tools/gsc_lint.py --no-baseline         # raw findings, no suppressions

Rules (gsc_tpu/analysis/astlint.py + concur.py):
    R1  host-sync calls (.item(), float()/int() on arrays, np.asarray,
        block_until_ready, device_get) reachable from jitted/scanned code
    R2  use of a variable after it was passed as a donated argument
    R3  time.time()/Python RNG/global mutation inside traced code
    R4  dot/einsum in bf16-policy modules (ops/, models/) missing
        preferred_element_type
    R5  bare Python scalars passed to jitted entry points (weak-type
        retrace risk)
    R6  lock-order cycle: two functions nest the same locks in opposite
        orders (ABBA deadlock)
    R7  field annotated ``# guarded-by: <lock>`` read/written without
        holding that lock (``# requires-lock:`` on a def asserts callers
        hold it)
    R8  multi-device dispatch (chunk_step / rollout_episodes /
        learn_burst / replay_ingest) in a thread-spawning module outside
        ``with dispatch_lock:`` — the PR 18 partition-rendezvous deadlock
    R9  blocking call (untimed get/wait/join/result, nested acquire,
        device call) while holding a lock
    R10 threading.Thread(...) without name=/daemon= (unnamed threads
        break watchdog stall events and black-box post-mortems)

Exit status: 0 when every finding is suppressed (baseline or inline
``gsc-lint: disable=R<k>`` marker), 1 when new findings exist, 2 on usage
errors.  The baseline lives at tools/gsc_lint_baseline.json; every entry
carries a one-line reason.  ``--write-baseline`` rewrites it from the
current findings, preserving existing reasons; entries it has to stamp
with a TODO reason make the write exit 1 until a human replaces them —
an unreviewed suppression must not pass the gate.

Fingerprints hash (rule, path, function, source-line text), not line
numbers, so code motion does not invalidate suppressions; two identical
lines in one function share a fingerprint (suppressing one suppresses
both).  Stale baseline entries (matching nothing) are reported but never
fatal.  Stdlib-only: runs without jax / device init.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from gsc_tpu.analysis import (  # noqa: E402
    RULE_IDS, RULE_TITLES, load_baseline, save_baseline)
from gsc_tpu.analysis.astlint import _iter_py_files, lint_files  # noqa: E402
from gsc_tpu.analysis.baseline import build_result  # noqa: E402

DEFAULT_PATHS = ("gsc_tpu/", "tools/")
DEFAULT_BASELINE = os.path.join(REPO_ROOT, "tools",
                                "gsc_lint_baseline.json")


def _rel(path: str) -> str:
    return os.path.relpath(os.path.abspath(path),
                           REPO_ROOT).replace(os.sep, "/")


def _git_changed_files(ref: str) -> Optional[List[str]]:
    """Repo-relative paths changed vs ``ref`` (staged + unstaged), or
    None when git is unavailable / this is not a work tree — the caller
    falls back to a full scan rather than silently linting nothing."""
    import subprocess
    try:
        proc = subprocess.run(
            ["git", "diff", "--name-only", ref],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode != 0:
        return None
    return [ln.strip().replace(os.sep, "/")
            for ln in proc.stdout.splitlines() if ln.strip()]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="\n".join(f"  {r}  {RULE_TITLES[r]}" for r in RULE_IDS))
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to lint [default: {DEFAULT_PATHS}]")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="suppression baseline JSON "
                         "[default: tools/gsc_lint_baseline.json]")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report every finding)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(existing reasons preserved; new entries get a "
                         "TODO reason)")
    ap.add_argument("--prune-stale", action="store_true",
                    help="rewrite the baseline with stale entries "
                         "(matching nothing in the linted scope) removed")
    ap.add_argument("--changed", nargs="?", const="HEAD", default=None,
                    metavar="REF",
                    help="lint only .py files in `git diff --name-only "
                         "REF` [REF default: HEAD]; falls back to a full "
                         "scan when git is unavailable")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset, e.g. R1,R4")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="findings only, no summary lines")
    args = ap.parse_args(argv)

    rules = None
    if args.rules:
        rules = {r.strip().upper() for r in args.rules.split(",")}
        bad = rules - set(RULE_IDS)
        if bad:
            ap.error(f"unknown rule(s): {sorted(bad)}")

    paths = args.paths or [os.path.join(REPO_ROOT, p)
                           for p in DEFAULT_PATHS]
    for p in paths:
        if not os.path.exists(p):
            ap.error(f"no such path: {p}")
        if os.path.isfile(p) and not p.endswith(".py"):
            # _iter_py_files would silently drop it and report a clean
            # "0 files" run — an explicit unlintable file is a usage error
            ap.error(f"not a Python file: {p}")

    if args.write_baseline:
        from gsc_tpu.analysis import inline_suppression

        files = _iter_py_files(paths)
        raw, _ = lint_files(files, rules=rules, root=REPO_ROOT)
        # inline-marked findings are already suppressed at their source
        # line; a baseline entry for one would match nothing on the next
        # run and report as stale
        raw = [f for f in raw
               if not inline_suppression(f.line_text, f.rule)]
        existing = (load_baseline(args.baseline)
                    if os.path.exists(args.baseline) else [])
        # a scoped rewrite (--rules subset / explicit path subset) only
        # re-checked part of the tree: entries outside that scope are
        # preserved verbatim, never silently dropped
        linted_rel = {
            os.path.relpath(os.path.abspath(f),
                            REPO_ROOT).replace(os.sep, "/")
            for f in files}
        preserved = [
            e for e in existing
            if (rules is not None and e.get("rule") not in rules)
            or e.get("path") not in linted_rel]
        n = save_baseline(args.baseline, raw, existing=existing,
                          preserve=preserved)
        print(f"gsc-lint: baseline rewritten with {n} suppression(s) -> "
              f"{args.baseline}")
        todo = sum(1 for e in load_baseline(args.baseline)
                   if e["reason"].startswith("TODO"))
        if todo:
            # exit non-zero: an unreviewed TODO reason must not slip
            # through the CI gate as an accepted suppression
            print(f"gsc-lint: {todo} entries need a written reason "
                  "(search for TODO) before the baseline is reviewable")
            return 1
        return 0

    files = _iter_py_files(paths)
    if args.changed is not None:
        changed = _git_changed_files(args.changed)
        if changed is None:
            if not args.quiet:
                print("gsc-lint: --changed: git unavailable, falling "
                      "back to a full scan", file=sys.stderr)
        else:
            changed_set = set(changed)
            files = [f for f in files if _rel(f) in changed_set]
            if not files:
                if args.as_json:
                    json.dump({"files": 0, "findings": [],
                               "suppressed": [], "stale_suppressions": [],
                               "by_rule": {}, "ok": True},
                              sys.stdout, indent=1)
                    sys.stdout.write("\n")
                elif not args.quiet:
                    print("gsc-lint: no lintable files changed vs "
                          f"{args.changed}")
                return 0

    all_entries = [] if args.no_baseline else load_baseline(args.baseline)
    entries = all_entries
    if rules:
        entries = [e for e in entries
                   if e.get("rule") in rules or not e.get("rule")]
    raw, nfiles = lint_files(files, rules=rules, root=REPO_ROOT)
    result = build_result(raw, entries, nfiles)

    # an entry can only be called stale if this run actually re-checked
    # its file — a scoped run (--changed, an explicit path subset) must
    # not report (or prune) suppressions it never looked at
    linted_rel = {_rel(f) for f in files}
    stale = [e for e in result.stale_suppressions
             if e.get("path") in linted_rel]

    if args.prune_stale:
        if args.no_baseline:
            ap.error("--prune-stale needs the baseline "
                     "(drop --no-baseline)")
        prune = {e["fingerprint"] for e in stale}
        if prune:
            keep = [e for e in all_entries
                    if e["fingerprint"] not in prune]
            save_baseline(args.baseline, [], preserve=keep)
        print(f"gsc-lint: pruned {len(prune)} stale suppression(s) -> "
              f"{args.baseline}")
        stale = []

    if args.as_json:
        json.dump({
            "files": result.files,
            "findings": [f.to_json() for f in result.findings],
            "suppressed": [f.to_json() for f in result.suppressed],
            "stale_suppressions": stale,
            "by_rule": result.by_rule(),
            "ok": result.ok,
        }, sys.stdout, indent=1)
        sys.stdout.write("\n")
        return 0 if result.ok else 1

    for f in result.findings:
        print(f.format())
    if not args.quiet:
        by_rule = result.by_rule()
        detail = ", ".join(f"{r}={n}" for r, n in sorted(by_rule.items()))
        print(f"gsc-lint: {result.files} files, "
              f"{len(result.findings)} finding(s)"
              + (f" ({detail})" if detail else "")
              + f", {len(result.suppressed)} suppressed"
              + (f", {len(stale)} stale" if stale else ""))
        for e in stale:
            print(f"gsc-lint: stale suppression (matched nothing): "
                  f"{e['fingerprint']} {e.get('path', '?')} — run "
                  "--prune-stale to drop it")
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
