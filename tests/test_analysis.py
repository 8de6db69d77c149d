"""Static-analysis + sentinel tests (``pytest -m analysis``).

Three layers:

- gsc-lint rules R1-R5 against seeded-violation fixtures
  (tests/assets/lint_fixtures): every rule must FIRE on its fixture and
  stay QUIET on clean code, and the CLI must exit non-zero on fixtures /
  zero on the real tree.
- the suppression baseline: fingerprint round-trip, line-move stability,
  stale-entry reporting, inline ``gsc-lint: disable`` markers.
- the runtime sentinels: CompileMonitor trace counting, the
  assert-no-retrace guard, the pipelined trainer compiling
  ``episode_step`` exactly once in steady state (with ``compile`` events
  landing in events.jsonl), and the host-sync sentinel proving the
  steady-state dispatch region performs zero unplanned device->host
  syncs.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.analysis import (
    CompileMonitor,
    HostSyncError,
    RetraceError,
    assert_no_retrace,
    lint_paths,
    load_baseline,
    no_host_sync,
    save_baseline,
)
from gsc_tpu.analysis.astlint import _iter_py_files, lint_files
from tests.test_agent import make_driver, make_stack

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "assets", "lint_fixtures")


def _fixture(name):
    return os.path.join(FIXTURES, name)


def _run(paths, **kw):
    return lint_paths([_fixture(p) if not os.path.isabs(p) else p
                       for p in paths], root=REPO, **kw)


# ------------------------------------------------------------ rules on
# fixtures: each rule fires on its seed file and is quiet on clean code
@pytest.mark.parametrize("fixture,rule,count", [
    ("r1_host_sync.py", "R1", 3),
    ("r2_donated_reuse.py", "R2", 3),
    ("r3_impure.py", "R3", 4),
    (os.path.join("ops", "r4_accum.py"), "R4", 2),
    ("r5_weak_scalar.py", "R5", 2),
])
def test_rule_fires_on_seeded_fixture(fixture, rule, count):
    result = _run([fixture])
    assert not result.ok
    assert result.by_rule() == {rule: count}, \
        [f.format() for f in result.findings]


def test_rules_quiet_on_clean_fixture():
    result = _run(["clean.py"])
    assert result.ok, [f.format() for f in result.findings]
    # the seeded inline marker lands in `suppressed`, not `findings`
    assert [f.suppressed_by for f in result.suppressed] == ["inline"]


def test_r2_reports_donor_call_site():
    result = _run(["r2_donated_reuse.py"])
    msg = result.findings[0].message
    assert "donated to episode_step()" in msg and "rebind" in msg


def test_r4_f32_gates_are_exempt():
    """Only the two seeded contractions fire: the `is None` gate, the
    dtype==float32 gate and the preferred_element_type call are clean."""
    result = _run([os.path.join("ops", "r4_accum.py")])
    lines = sorted(f.line for f in result.findings)
    texts = [f.line_text for f in result.findings]
    assert len(lines) == 2
    assert any("einsum" in t for t in texts)
    assert any("@" in t for t in texts)


def test_whole_tree_is_lint_clean_under_baseline():
    """The acceptance gate: gsc_tpu/ tools/ with the committed
    baseline has zero unsuppressed findings, and every baseline entry
    still matches something (no stale suppressions)."""
    result = lint_paths(
        [os.path.join(REPO, "gsc_tpu"), os.path.join(REPO, "tools")],
        baseline_path=os.path.join(REPO, "tools",
                                   "gsc_lint_baseline.json"),
        root=REPO)
    assert result.ok, [f.format() for f in result.findings]
    assert result.stale_suppressions == [], result.stale_suppressions
    assert result.suppressed, "baseline should be exercised"


def test_cli_exit_codes():
    """tools/gsc_lint.py: non-zero on every seeded fixture, zero on the
    final tree (the driver's acceptance criterion, via the same command)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("r1_host_sync.py", "r2_donated_reuse.py",
                 "r3_impure.py", os.path.join("ops", "r4_accum.py"),
                 "r5_weak_scalar.py"):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "gsc_lint.py"),
             "--no-baseline", "-q", _fixture(name)],
            capture_output=True, text=True, env=env, cwd=REPO)
        assert p.returncode == 1, (name, p.stdout, p.stderr)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gsc_lint.py"),
         "gsc_tpu/", "tools/"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert p.returncode == 0, (p.stdout, p.stderr)


# ------------------------------------------------------- baseline plumbing
def test_suppression_roundtrip(tmp_path):
    """findings -> save_baseline -> lint again == all suppressed; a
    hand-edited reason survives a rewrite; unmatched entries surface as
    stale."""
    raw, _ = lint_files([_fixture("r1_host_sync.py")], root=REPO)
    assert raw
    bl = tmp_path / "baseline.json"
    save_baseline(str(bl), raw)
    entries = load_baseline(str(bl))
    assert all(e["reason"].startswith("TODO") for e in entries)
    # write a real reason; it must survive a second rewrite
    entries[0]["reason"] = "accepted: fixture"
    bl.write_text(json.dumps({"version": 1, "suppressions": entries}))
    save_baseline(str(bl), raw, existing=load_baseline(str(bl)))
    assert load_baseline(str(bl))[0]["reason"] == "accepted: fixture"

    result = _run(["r1_host_sync.py"], baseline_path=str(bl))
    assert result.ok and len(result.suppressed) == len(raw)
    assert result.stale_suppressions == []

    # stale: an entry whose fingerprint matches nothing is reported
    entries.append({"fingerprint": "deadbeefdeadbeef", "rule": "R1",
                    "path": "gone.py", "reason": "obsolete"})
    bl.write_text(json.dumps({"version": 1, "suppressions": entries}))
    result = _run(["r1_host_sync.py"], baseline_path=str(bl))
    assert result.ok
    assert [e["fingerprint"] for e in result.stale_suppressions] == \
        ["deadbeefdeadbeef"]


def test_donated_sigs_match_real_donated_jit_sites():
    """Drift guard: DONATED_SIGS hand-mirrors the donated_jit call sites
    in agents/ddpg.py and parallel/dp.py.  If a PR changes
    donate_argnums/static_argnums there without updating the table, R2/R5
    would silently check the wrong positions — fail here instead."""
    import ast

    from gsc_tpu.analysis.astlint import DONATED_SIGS

    found = {}
    for rel in ("gsc_tpu/agents/ddpg.py", "gsc_tpu/parallel/dp.py"):
        tree = ast.parse(open(os.path.join(REPO, rel)).read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "donated_jit"):
                continue
            # donated_jit(self, cls.<name>, static_argnums=.., donate_argnums=..)
            name = node.args[1].attr
            kw = {k.arg: k.value for k in node.keywords}

            def positions(val):
                if isinstance(val, ast.Tuple):
                    return tuple(e.value for e in val.elts)
                return (val.value,)

            # jit argnums count `self`; call sites bind it — shift by 1
            donated = tuple(p - 1 for p in positions(kw["donate_argnums"]))
            static = tuple(p - 1 for p in positions(kw["static_argnums"])
                           if p != 0)
            found.setdefault(name, set()).add((donated, static))
    assert set(found) == set(DONATED_SIGS), (found.keys(),
                                             DONATED_SIGS.keys())
    for name, variants in found.items():
        table_donated = DONATED_SIGS[name][0]
        table_static = DONATED_SIGS[name][2]
        for donated, static in variants:
            assert donated == table_donated, (name, donated, table_donated)
            assert static == table_static, (name, static, table_static)


def test_save_baseline_dedups_shared_fingerprints(tmp_path):
    """Two identical flagged lines in one function share a fingerprint;
    the written baseline must carry ONE entry (one reason covers both)."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n"
        "    x[0].item()\n"
        "    x[0].item()\n"
        "    return x\n")
    raw, _ = lint_files([str(mod)], root=str(tmp_path))
    assert len(raw) == 2
    assert raw[0].fingerprint == raw[1].fingerprint
    bl = tmp_path / "bl.json"
    n = save_baseline(str(bl), raw)
    assert n == 1
    assert len(load_baseline(str(bl))) == 1


def test_baseline_requires_reasons(tmp_path):
    bl = tmp_path / "bad.json"
    bl.write_text(json.dumps({"version": 1, "suppressions": [
        {"fingerprint": "abc123", "rule": "R1"}]}))
    with pytest.raises(ValueError, match="no reason"):
        load_baseline(str(bl))


def test_fingerprint_survives_line_moves(tmp_path):
    """Identity hashes (rule, path, symbol, line text) — prepending code
    must not invalidate a suppression."""
    body = ("import jax\n\n@jax.jit\ndef f(x):\n"
            "    return x[0].item()\n")
    a = tmp_path / "mod.py"
    a.write_text(body)
    raw1, _ = lint_files([str(a)], root=str(tmp_path))
    a.write_text("# comment\n# another\n\n" + body)
    raw2, _ = lint_files([str(a)], root=str(tmp_path))
    assert [f.fingerprint for f in raw1] == [f.fingerprint for f in raw2]
    assert raw1[0].line != raw2[0].line


def test_iter_py_files_skips_caches(tmp_path):
    (tmp_path / "__pycache__").mkdir()
    (tmp_path / "__pycache__" / "x.py").write_text("")
    (tmp_path / "a.py").write_text("")
    assert [os.path.basename(p)
            for p in _iter_py_files([str(tmp_path)])] == ["a.py"]


# -------------------------------------------------------- retrace sentinel
def test_compile_monitor_counts_traces_and_detects_retrace():
    prev_log_compiles = jax.config.jax_log_compiles
    mon = CompileMonitor(watch=None)
    with mon:
        @jax.jit
        def sentinel_probe(x):
            return x * 3

        sentinel_probe(jnp.ones(3))
        sentinel_probe(jnp.ones(3))          # cache hit: no new trace
        assert mon.traces("sentinel_probe") == 1
        with pytest.raises(RetraceError, match="sentinel_probe"):
            with mon.assert_no_retrace("sentinel_probe"):
                sentinel_probe(jnp.ones(5))  # new shape -> retrace
    # monitor restores whatever log_compiles value it found
    assert jax.config.jax_log_compiles is prev_log_compiles


def test_stacked_monitors_both_count():
    """A suppressing observer-owned monitor must not blind a later
    standalone assert_no_retrace: the shared log tap fans records out to
    every active monitor instead of short-circuiting the filter chain."""
    prev_log_compiles = jax.config.jax_log_compiles
    outer = CompileMonitor(watch=None, suppress_logs=True)
    with outer:
        @jax.jit
        def stacked_probe(x):
            return x - 1

        stacked_probe(jnp.ones(2))
        with pytest.raises(RetraceError, match="stacked_probe"):
            with assert_no_retrace("stacked_probe"):
                stacked_probe(jnp.ones(6))   # retrace under BOTH monitors
        assert outer.traces("stacked_probe") == 2
    assert jax.config.jax_log_compiles is prev_log_compiles


def test_r1_catches_module_form_block_until_ready(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n"
        "    jax.block_until_ready(x)\n    return x\n")
    raw, _ = lint_files([str(mod)], root=str(tmp_path))
    assert [f.rule for f in raw] == ["R1"], raw
    assert "block_until_ready" in raw[0].message


def test_r1_sees_inside_lambdas(tmp_path):
    """Lambdas passed to cond/scan have no FunctionInfo of their own —
    their bodies belong to the enclosing traced function."""
    mod = tmp_path / "m.py"
    mod.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n"
        "    return jax.lax.cond(x.sum() > 0,\n"
        "                        lambda v: v[0].item(),\n"
        "                        lambda v: 0.0, x)\n")
    raw, _ = lint_files([str(mod)], root=str(tmp_path))
    assert [f.rule for f in raw] == ["R1"], raw
    assert ".item()" in raw[0].message


def test_write_baseline_scoped_rewrite_preserves_out_of_scope(tmp_path):
    """--write-baseline with a --rules/path subset must keep suppressions
    it never re-checked (their hand-written reasons included)."""
    bl = tmp_path / "baseline.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    gsc_lint = os.path.join(REPO, "tools", "gsc_lint.py")
    # full-scope write over two fixtures, then hand-write a reason
    p = subprocess.run(
        [sys.executable, gsc_lint, "--write-baseline",
         "--baseline", str(bl),
         _fixture("r1_host_sync.py"), _fixture("r5_weak_scalar.py")],
        capture_output=True, text=True, env=env, cwd=REPO)
    # the baseline IS written, but TODO reasons make the write exit 1 so
    # an unreviewed suppression can't slide through CI
    assert p.returncode == 1, (p.stdout, p.stderr)
    assert "need a written reason" in p.stdout
    entries = load_baseline(str(bl))
    assert {e["rule"] for e in entries} == {"R1", "R5"}
    for e in entries:
        if e["rule"] == "R5":
            e["reason"] = "accepted: hand-written R5 reason"
    bl.write_text(json.dumps({"version": 1, "suppressions": entries}))
    # scoped rewrite: R1 only, one file only — R5 entries must survive
    p = subprocess.run(
        [sys.executable, gsc_lint, "--write-baseline", "--rules", "R1",
         "--baseline", str(bl), _fixture("r1_host_sync.py")],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert p.returncode == 1, (p.stdout, p.stderr)   # R1 reasons still TODO
    after = load_baseline(str(bl))
    r5 = [e for e in after if e["rule"] == "R5"]
    assert len(r5) == 2 and all(
        e["reason"] == "accepted: hand-written R5 reason" for e in r5), after


def test_write_baseline_skips_inline_suppressed_findings(tmp_path):
    """An inline-marked line is suppressed at source; baselining it too
    would create an entry that matches nothing (stale) on the next run."""
    bl = tmp_path / "baseline.json"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gsc_lint.py"),
         "--write-baseline", "--baseline", str(bl), _fixture("clean.py")],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert load_baseline(str(bl)) == []


def test_standalone_assert_no_retrace_passes_in_steady_state():
    @jax.jit
    def steady_probe(x):
        return x + 1

    steady_probe(jnp.ones(4))                # compile outside the guard
    with assert_no_retrace("steady_probe"):
        for _ in range(3):
            steady_probe(jnp.ones(4))


def test_pipelined_trainer_compiles_episode_step_exactly_once(tmp_path):
    """The acceptance property: across N steady-state pipelined episodes
    the fused episode kernel traces ONCE, and a further training loop on
    the same agent runs under assert_no_retrace without tripping."""
    from gsc_tpu.agents import Trainer

    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    t = Trainer(env, driver, agent, seed=0)
    mon = CompileMonitor(watch=None)
    with mon:
        t.train(episodes=4, pipeline=True)
        assert mon.traces("episode_step") == 1, mon.snapshot()
        # steady state: re-running the loop (same shapes, same static
        # args) dispatches from cache — zero new traces allowed
        with mon.assert_no_retrace("episode_step"):
            t.train(episodes=3, pipeline=True)


def test_compile_events_land_in_events_jsonl_and_report(tmp_path):
    """RunObserver's monitor emits `compile` events for watched entry
    points into events.jsonl; tools/obs_report.py surfaces them."""
    from gsc_tpu.obs import RunObserver

    obs = RunObserver(str(tmp_path), run_id="compile-test")
    obs.start()
    try:
        @jax.jit
        def episode_step(x):      # name is in the sentinel watch set
            return x * 2

        episode_step(jnp.ones(3))
    finally:
        obs.close()
    events = [json.loads(l)
              for l in open(tmp_path / "events.jsonl")]
    compiles = [e for e in events if e["event"] == "compile"]
    assert any(e["fn"] == "episode_step" and e["stage"] == "trace"
               for e in compiles), events
    assert all({"fn", "stage", "duration_s", "count"} <= set(e)
               for e in compiles)

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obs_report
    summary = obs_report.summarize(obs_report.load_events(str(tmp_path)))
    assert summary["compiles"]["per_fn"]["episode_step"]["traces"] >= 1


# ------------------------------------------------------ host-sync sentinel
def test_no_host_sync_trips_on_materialization():
    x = jnp.arange(4.0)
    with pytest.raises(HostSyncError, match="np.asarray"):
        with no_host_sync("test region"):
            np.asarray(x)
    with pytest.raises(HostSyncError, match="block_until_ready"):
        with no_host_sync("test region"):
            jax.block_until_ready(x)
    # tripwires restored after the region
    assert np.asarray(x).shape == (4,)


def test_no_host_sync_trips_on_containers_of_arrays():
    """np.asarray over a LIST of jax arrays syncs every leaf — the
    tripwire must look inside containers, not just at the argument."""
    x = jnp.arange(4.0)
    with pytest.raises(HostSyncError, match="np.asarray"):
        with no_host_sync("drain check"):
            np.asarray([x[0], x[1]])
    with pytest.raises(HostSyncError, match="np.array"):
        with no_host_sync("drain check"):
            np.array({"a": x}["a"])


def test_no_host_sync_allows_dispatch_and_host_numpy():
    x = jnp.arange(4.0)
    with no_host_sync():
        y = jax.jit(lambda a: a + 1)(x)
        np.asarray([1.0, 2.0])        # host-side numpy stays legal
    assert float(y[0]) == 1.0


def test_steady_state_dispatch_performs_zero_host_syncs():
    """The episode loop's dispatch region — env.reset + fused
    episode_step with np.int32-pinned scalars — runs under the host-sync
    sentinel; the deferred drain (np.asarray on stats) correctly trips it
    when moved inside."""
    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    from gsc_tpu.agents import DDPG

    ddpg = DDPG(env, agent)
    base = jax.random.PRNGKey(0)
    # pre-sample host traffic (the prefetcher's job, outside the guard)
    episodes = [driver.episode(ep, False) for ep in range(3)]
    env_state, obs0 = env.reset(jax.random.fold_in(base, 1000),
                                *episodes[0])
    state = ddpg.init(jax.random.fold_in(base, 0), obs0)
    buf = ddpg.init_buffer(obs0)
    # episode 0 compiles everything outside the guard
    out = ddpg.episode_step(state, buf, env_state, obs0, *episodes[0],
                            np.int32(0), learn=True)
    state, buf = out[0], out[1]
    steps = agent.episode_steps

    with no_host_sync("steady-state episode dispatch"):
        for ep in (1, 2):
            topo_e, traffic_e = episodes[ep]
            env_state, obs = env.reset(
                jax.random.fold_in(base, 1000 + ep), topo_e, traffic_e)
            out = ddpg.episode_step(state, buf, env_state, obs, topo_e,
                                    traffic_e, np.int32(ep * steps),
                                    learn=True)
            state, buf, stats = out[0], out[1], out[4]

    # the drain belongs OUTSIDE the dispatch region; inside it the
    # sentinel catches exactly the PR 1 regression class
    with pytest.raises(HostSyncError):
        with no_host_sync("dispatch region"):
            np.asarray(stats["episodic_return"])
    assert np.isfinite(float(np.asarray(stats["episodic_return"])))
