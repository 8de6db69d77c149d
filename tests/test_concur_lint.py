"""Concurrency-discipline lint tests (``pytest -m lint``).

Rules R6-R10 (gsc_tpu/analysis/concur.py) against seeded-violation
fixtures and their clean counterparts:

- R6 lock-order cycle fires on an ABBA inversion and stays quiet when
  the same locks nest in one global order;
- R7 guarded-by fires on a bare read of an annotated field and honors
  both ``with``-held locks and ``# requires-lock:`` method annotations;
- R8 re-detects the PR 18 dispatch deadlock shape — including on a
  variant of the CLEAN fixture with its ``with dispatch_lock:`` line
  deleted, the acceptance property for this rule;
- R9 blocking-under-lock fires on untimed get / nested acquire / device
  call and accepts the timed/ordered/unlocked forms;
- R10 thread-ctor discipline requires ``name=`` and ``daemon=``.

Plus the CLI satellites (``--changed`` git scoping with its full-scan
fallback, ``--prune-stale`` baseline hygiene) and the whole-tree gate:
the live tree must carry ZERO unsuppressed findings with R6-R10 active.

Stdlib-only — no jax import, runs anywhere gsc-lint does.
"""
import json
import os
import subprocess
import sys

import pytest

from gsc_tpu.analysis import lint_paths, load_baseline, save_baseline
from gsc_tpu.analysis.astlint import lint_files

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "assets", "lint_fixtures")
GSC_LINT = os.path.join(REPO, "tools", "gsc_lint.py")


def _fixture(name):
    return os.path.join(FIXTURES, name)


def _run(paths, **kw):
    return lint_paths([_fixture(p) if not os.path.isabs(p) else p
                       for p in paths], root=REPO, **kw)


def _cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, GSC_LINT, *args],
                          capture_output=True, text=True, cwd=cwd)


# ------------------------------------------------------- rules on fixtures
@pytest.mark.parametrize("fixture,rule,count,symbols", [
    ("concur_r6_cycle.py", "R6", 2,
     {"InvertedOrders.writer", "InvertedOrders.swapper"}),
    ("concur_r7_guarded.py", "R7", 1, {"GuardedCounter.peek"}),
    ("concur_r8_dispatch.py", "R8", 1, {"Fleet._actor_loop"}),
    ("concur_r9_blocking.py", "R9", 3,
     {"BlocksUnderLock.drain", "BlocksUnderLock.double",
      "BlocksUnderLock.flush"}),
    ("concur_r10_thread.py", "R10", 2,
     {"spawn_anonymous", "spawn_named_not_daemon"}),
])
def test_rule_fires_on_seeded_fixture(fixture, rule, count, symbols):
    """Each rule fires on its seed file — exact rule id, count AND the
    offending function(s), nothing else."""
    result = _run([fixture])
    assert not result.ok
    assert result.by_rule() == {rule: count}, \
        [f.format() for f in result.findings]
    assert {f.symbol for f in result.findings} == symbols


@pytest.mark.parametrize("fixture", [
    "concur_r6_clean.py", "concur_r7_clean.py", "concur_r8_locked.py",
    "concur_r9_clean.py",
])
def test_rules_quiet_on_clean_variant(fixture):
    result = _run([fixture])
    assert result.ok, [f.format() for f in result.findings]
    assert result.findings == [] and result.suppressed == []


def test_r8_redetects_pr18_shape_when_lock_deleted(tmp_path):
    """The acceptance property: take the CLEAN locked fixture, delete its
    ``with self.dispatch_lock:`` line (dedenting the guarded call), and
    the linter must produce exactly the R8 dispatch-deadlock finding."""
    src = open(_fixture("concur_r8_locked.py")).read()
    lines = src.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if ln.strip() == "with self.dispatch_lock:")
    indent = len(lines[start]) - len(lines[start].lstrip())
    body_end = start + 1
    while body_end < len(lines) and (
            not lines[body_end].strip()
            or len(lines[body_end]) - len(lines[body_end].lstrip())
            > indent):
        body_end += 1
    unlocked = lines[:start] + [
        ln[4:] if ln.strip() else ln
        for ln in lines[start + 1:body_end]] + lines[body_end:]
    mod = tmp_path / "fleet_unlocked.py"
    mod.write_text("\n".join(unlocked) + "\n")

    raw, _ = lint_files([str(mod)], root=str(tmp_path))
    assert [f.rule for f in raw] == ["R8"], [f.format() for f in raw]
    assert raw[0].symbol == "Fleet._actor_loop"
    assert "PR 18" in raw[0].message
    assert "rollout_episodes" in raw[0].message


def test_r7_requires_lock_annotation_is_honored():
    """The clean fixture's `_bump_locked` touches the guarded field with
    no `with` in sight — only the `# requires-lock:` header keeps it
    quiet, so scoping the lint to R7 must still return nothing."""
    result = _run(["concur_r7_clean.py"], rules={"R7"})
    assert result.ok and result.findings == []


def test_r6_quiet_on_distinct_classes_same_field_names(tmp_path):
    """Two classes' unrelated `self._lock`/`self.flush_lock` pairs must
    not alias into one graph: opposite nesting ACROSS classes is fine."""
    mod = tmp_path / "two.py"
    mod.write_text(
        "import threading\n\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.a_lock = threading.Lock()\n"
        "        self.b_lock = threading.Lock()\n"
        "    def f(self):\n"
        "        with self.a_lock:\n"
        "            with self.b_lock:\n"
        "                pass\n\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self.a_lock = threading.Lock()\n"
        "        self.b_lock = threading.Lock()\n"
        "    def g(self):\n"
        "        with self.b_lock:\n"
        "            with self.a_lock:\n"
        "                pass\n")
    raw, _ = lint_files([str(mod)], root=str(tmp_path))
    assert raw == [], [f.format() for f in raw]


def test_inline_disable_silences_concurrency_finding(tmp_path):
    """`# gsc-lint: disable=R9 -- reason` on the offending line moves the
    finding to `suppressed` — the mechanism the live tree's documented
    flush-lock-across-device-call case relies on."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import threading\n\n"
        "class S:\n"
        "    def __init__(self, run_batch):\n"
        "        self.flush_lock = threading.Lock()\n"
        "        self.run_batch = run_batch\n"
        "    def flush(self, b):\n"
        "        with self.flush_lock:\n"
        "            return self.run_batch(b)  "
        "# gsc-lint: disable=R9 -- hot-swap contract\n")
    result = lint_paths([str(mod)], root=str(tmp_path))
    assert result.ok
    assert [f.rule for f in result.suppressed] == ["R9"]
    assert result.suppressed[0].suppressed_by == "inline"


# --------------------------------------------------------- whole-tree gate
def test_whole_tree_zero_unsuppressed_with_concurrency_rules():
    """The live tree under the committed baseline: 0 unsuppressed
    findings with R6-R10 active, and the concurrency rules are genuinely
    exercised (the documented R7/R8/R9 cases land in `suppressed`)."""
    result = lint_paths(
        [os.path.join(REPO, "gsc_tpu"), os.path.join(REPO, "tools")],
        baseline_path=os.path.join(REPO, "tools",
                                   "gsc_lint_baseline.json"),
        root=REPO)
    assert result.ok, [f.format() for f in result.findings]
    quiet_rules = {f.rule for f in result.suppressed}
    assert {"R7", "R8", "R9"} <= quiet_rules, quiet_rules


def test_cli_exit_codes_on_concurrency_fixtures():
    for name in ("concur_r6_cycle.py", "concur_r7_guarded.py",
                 "concur_r8_dispatch.py", "concur_r9_blocking.py",
                 "concur_r10_thread.py"):
        p = _cli("--no-baseline", "-q", _fixture(name))
        assert p.returncode == 1, (name, p.stdout, p.stderr)
    p = _cli("--no-baseline", "-q", _fixture("concur_r8_locked.py"))
    assert p.returncode == 0, (p.stdout, p.stderr)


# ---------------------------------------------------------- CLI satellites
def test_changed_falls_back_to_full_scan_on_bad_ref():
    p = _cli("--changed", "this-ref-does-not-exist")
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "falling back to a full scan" in p.stderr
    assert "files, 0 finding(s)" in p.stdout


def test_changed_scopes_to_git_diff():
    """--changed REF lints at most the diff'd files; against HEAD the run
    must stay clean (whatever is in flight is held to the same gate)."""
    p = _cli("--changed", "HEAD", "--json")
    assert p.returncode == 0, (p.stdout, p.stderr)
    doc = json.loads(p.stdout)
    assert doc["ok"] and doc["findings"] == []
    full = json.loads(_cli("--json").stdout)
    assert doc["files"] <= full["files"]


def test_prune_stale_drops_only_in_scope_entries(tmp_path):
    """--prune-stale removes entries that matched nothing IN THE LINTED
    SCOPE and preserves both live entries and out-of-scope ones."""
    fixture = _fixture("concur_r9_blocking.py")
    raw, _ = lint_files([fixture], root=REPO)
    assert len(raw) == 3
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), raw)
    entries = load_baseline(str(bl))
    rel = os.path.relpath(fixture, REPO).replace(os.sep, "/")
    entries.append({"fingerprint": "feedfacefeedface", "rule": "R9",
                    "path": rel, "line_text": "gone()",
                    "reason": "stale: in linted scope"})
    entries.append({"fingerprint": "cafebabecafebabe", "rule": "R1",
                    "path": "gsc_tpu/never_linted_here.py",
                    "line_text": "x.item()",
                    "reason": "out of scope: must survive"})
    bl.write_text(json.dumps({"version": 1, "suppressions": entries}))

    p = _cli("--baseline", str(bl), "--prune-stale", fixture)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "pruned 1 stale suppression(s)" in p.stdout
    after = {e["fingerprint"] for e in load_baseline(str(bl))}
    assert "feedfacefeedface" not in after
    assert "cafebabecafebabe" in after
    assert {f.fingerprint for f in raw} <= after


def test_prune_stale_with_nothing_stale_leaves_baseline_untouched(
        tmp_path):
    fixture = _fixture("concur_r9_blocking.py")
    raw, _ = lint_files([fixture], root=REPO)
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), raw)
    before = bl.read_bytes()
    mtime = bl.stat().st_mtime_ns
    p = _cli("--baseline", str(bl), "--prune-stale", fixture)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "pruned 0 stale suppression(s)" in p.stdout
    assert bl.read_bytes() == before
    assert bl.stat().st_mtime_ns == mtime


def test_stale_count_lands_in_summary_line(tmp_path):
    fixture = _fixture("concur_r9_blocking.py")
    raw, _ = lint_files([fixture], root=REPO)
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), raw)
    entries = load_baseline(str(bl))
    rel = os.path.relpath(fixture, REPO).replace(os.sep, "/")
    entries.append({"fingerprint": "feedfacefeedface", "rule": "R9",
                    "path": rel, "line_text": "gone()",
                    "reason": "stale"})
    bl.write_text(json.dumps({"version": 1, "suppressions": entries}))
    p = _cli("--baseline", str(bl), fixture)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "1 stale" in p.stdout and "--prune-stale" in p.stdout
