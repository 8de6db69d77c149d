"""Device time by layer (``obs.trace``'s join of a profiler trace to the
cost ledger's ``op_map``): on synthetic event lists — nested scopes,
executions cut at either end of the trace, a program with no map, two
programs of one name, idle gaps under nested host spans, a partial join —
and on a small trace recorded on a TPU v5e
(``benchmarks/tests/record_scope_fixture.py``) with its compiled text."""
import io
import json
import os

import pytest

from gsc_tpu.analysis.hlo import instruction_head
from gsc_tpu.obs import trace
from gsc_tpu.obs.perf import scope_ledger

pytestmark = pytest.mark.perf_obs

ASSETS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "assets")
FIXTURE = os.path.join(ASSETS, "scope_fixture")


def op(name, start, dur, kind="fusion", shape="f32[8]{0}"):
    """An ``XLA Ops`` event as a chip's trace names it."""
    return (f"%{name} = {shape} {kind}(f32[8]{{0}} %p)", start, dur)


def program(module, paths, anchors, shape="f32[8]{0}", kind="fusion"):
    """An ``op_map`` whose operations all have one result type."""
    sig = instruction_head(f"%x = {shape} {kind}(%p)")[1]
    return {"module": module, "paths": paths, "anchors": anchors,
            "signatures": {n: sig for names in paths.values()
                           for n in names}}


# a control step (anchor `s0`) of two substeps (anchor `a`) and a policy
# forward, three control steps, the first and the last cut by the trace
STEP = program("jit_chunk_step",
               {"rollout_step": ["s0", "s1"],
                "rollout_step/sim_substep": ["a", "b"],
                "rollout_step/policy_forward": ["p"],
                "unscoped": ["u"]},
               {"rollout_step": ["s0"], "rollout_step/sim_substep": ["a"],
                "rollout_step/policy_forward": ["p"]})


def control_step(t):
    """One control step from ``t``: 100 ns of own work, two substeps of
    30 ns, a 20 ns policy forward, 10 ns unscoped; 200 ns long."""
    return [op("s0", t, 10), op("a", t + 10, 20), op("b", t + 30, 10),
            op("a", t + 40, 20), op("b", t + 60, 10), op("p", t + 70, 20),
            op("s1", t + 90, 90), op("u", t + 180, 10)]


def device(ops, modules):
    return {"ops": ops, "modules": modules}


@pytest.fixture
def three_steps():
    # the trace starts inside the first step (after its `s0` and first
    # substep) and stops inside the third (after its first substep);
    # a `while` encloses the steps: busy only in its leaves
    ops = control_step(0)[2:] + control_step(200) + control_step(400)[:3]
    ops.append(("%while.1 = (s32[]) while((s32[]) %t), body=%b", 30, 410))
    return device(ops, [("jit_chunk_step(77)", 30, 410)])


def test_nested_scopes_innermost_and_inclusive(three_steps):
    got = trace.scope_seconds(trace.join_scopes(three_steps, [STEP]))
    ns = 1e-9
    # every traced leaf counts, the cut steps' too: substeps 40 + 60 + 30
    assert got["innermost"]["sim_substep"] == pytest.approx(130 * ns)
    assert got["innermost"]["policy_forward"] == pytest.approx(40 * ns)
    assert got["innermost"]["rollout_step"] == pytest.approx(200 * ns)
    # nested scopes count in their parent, unscoped stays apart
    assert got["inclusive"] == {"rollout_step": pytest.approx(370 * ns),
                                "sim_substep": pytest.approx(130 * ns),
                                "policy_forward": pytest.approx(40 * ns)}
    assert got["unscoped"] == pytest.approx(20 * ns)
    assert got["unmatched"] == {} and got["unmapped"] == {}
    # the `while` spans its body's operations and is no leaf: 390 ns of
    # leaves in its 410 ns
    assert sum(got["innermost"].values()) + got["unscoped"] == \
        pytest.approx(390 * ns)


def test_executions_cut_at_either_end_are_not_counted(three_steps):
    joined = trace.join_scopes(three_steps, [STEP])
    got = trace.scope_executions(joined)
    step = got["rollout_step"]
    # `s0` shows at 200 and 400 only: one whole control step between,
    # 180 ns under the scope (its unscoped 10 ns apart)
    assert step["executions"] == 1
    assert step["per_execution_s"] == pytest.approx(180e-9)
    sub = got["rollout_step/sim_substep"]
    # `a` at 40, 210, 240, 410: three whole substeps, 30 ns each
    assert sub["executions"] == 3
    assert sub["per_execution_s"] == pytest.approx(30e-9)
    fwd = got["rollout_step/policy_forward"]
    assert fwd["executions"] == 1
    assert fwd["per_execution_s"] == pytest.approx(20e-9)
    # one occurrence is no whole execution
    short = device(control_step(0), [("jit_chunk_step(77)", 0, 200)])
    assert "rollout_step" not in trace.scope_executions(
        trace.join_scopes(short, [STEP]))
    # the paths asked for alone
    assert set(trace.scope_executions(joined, ["rollout_step"])) == {
        "rollout_step"}


def test_a_program_without_a_map_is_reported_by_name(three_steps):
    three_steps["ops"] += [op("is-finite", 500, 5), op("s0", 600, 5)]
    three_steps["modules"] += [("jit_all_finite(9)", 500, 5)]
    got = trace.scope_seconds(trace.join_scopes(three_steps, [STEP]))
    assert got["unmapped"] == {"jit_all_finite": pytest.approx(5e-9),
                               trace.NO_MODULE: pytest.approx(5e-9)}
    # the `s0` outside every execution is never guessed into a scope
    assert got["inclusive"]["rollout_step"] == pytest.approx(370e-9)


def test_programs_of_one_name_are_told_apart_by_their_signatures():
    learn = program("jit_chunk_step", {"learn_burst": ["s0", "x"]},
                    {"learn_burst": ["x"]}, shape="f32[4,4]{1,0}")
    ops = control_step(0) + [op("s0", 300, 10, shape="f32[4,4]{1,0}"),
                             op("x", 310, 10, shape="f32[4,4]{1,0}")]
    dev = device(ops, [("jit_chunk_step(1)", 0, 200),
                       ("jit_chunk_step(2)", 300, 20)])
    joined = trace.join_scopes(dev, [STEP, learn])
    assert [e[3] for e in joined["executions"]] == [STEP, learn]
    got = trace.scope_seconds(joined)
    assert got["inclusive"]["learn_burst"] == pytest.approx(20e-9)
    assert got["inclusive"]["rollout_step"] == pytest.approx(180e-9)
    # two maps that both pass leave the program unmapped: no guess
    twin = dict(STEP)
    joined = trace.join_scopes(dev, [STEP, twin, learn])
    assert joined["executions"][0][3] is None
    assert trace.scope_seconds(joined)["unmapped"] == {
        "jit_chunk_step": pytest.approx(190e-9)}


def test_coverage_and_a_partial_join():
    ops = control_step(0) + [op("zz", 200, 990)]      # not in the map
    dev = device(ops, [("jit_chunk_step(77)", 0, 1190)])
    joined = trace.join_scopes(dev, [STEP])
    assert trace.join_coverage(joined) == {
        "jit_chunk_step": pytest.approx(190 / 1180)}
    assert trace.scope_seconds(joined)["unmatched"] == {
        "jit_chunk_step": pytest.approx(990e-9)}
    # under MIN_COVERAGE no layer is read off the program at all
    got = trace.layer_times({"devices": {"d": dev}, "spans": []}, [STEP])
    assert got["coverage"]["jit_chunk_step"] < trace.MIN_COVERAGE
    assert got["executions"] == {} and got["scopes"]["inclusive"] == {}
    assert got["scopes"]["unmapped"]["jit_chunk_step"] == \
        pytest.approx(1180e-9)
    full = trace.layer_times({"devices": {"d": device(
        control_step(0) + control_step(200),
        [("jit_chunk_step(77)", 0, 400)])}, "spans": []}, [STEP])
    assert full["coverage"] == {"jit_chunk_step": 1.0}
    assert full["executions"]["rollout_step"]["per_execution_s"] == \
        pytest.approx(180e-9)
    # the execution's span ends with the trace's last leaf; the 10 ns
    # between the two steps is idle inside the program
    assert full["programs"]["jit_chunk_step"] == {
        "executions": 1, "span_s": pytest.approx(390e-9),
        "busy_s": pytest.approx(380e-9)}


def test_a_gap_goes_to_the_innermost_host_span():
    leaves = [(0, 10), (100, 110), (300, 310), (500, 510), (530, 540)]
    spans = [("episode", 5, 600),               # the root, 5..605
             ("harness_observe", 20, 70),       # inside it: 20..90
             ("ckpt", 95, 10)]                  # 95..105
    got = trace.idle_spans(leaves, spans, top=4)
    # 10..100: the inner span covers 70 of 90 -> it, not the root (nor
    # `ckpt`, which covers 5); 110..300, 310..500, 510..530: the root
    assert [g[0] for g in got] == ["episode", "episode", "harness_observe",
                                   "episode"]
    assert [g[1] for g in got] == pytest.approx([190e-9, 190e-9, 90e-9,
                                                 20e-9])
    # a child whose tail reaches into the gap does not take it from the
    # root that holds the rest
    assert trace.idle_spans([(0, 10), (100, 110)],
                            [("episode", 0, 1000), ("ckpt", 5, 10)]) == \
        [["episode", pytest.approx(90e-9)]]
    # no span overlaps, or more of the gap lies under none than under
    # any span
    assert trace.idle_spans([(0, 10), (50, 60)], spans[2:])[0][0] == "none"
    got = trace.idle_spans([(0, 10), (500, 510)], [("ckpt", 20, 5)])
    assert got[0][0] == "none" and got[0][1] == pytest.approx(490e-9)


def test_event_names_are_parsed_once(monkeypatch):
    from gsc_tpu.analysis import hlo

    calls = []
    real = hlo.instruction_head

    def counting(name):
        calls.append(name)
        return real(name)

    monkeypatch.setattr(hlo, "instruction_head", counting)
    ops = control_step(0) * 1 + control_step(200) + control_step(400)
    trace.join_scopes(device(ops, [("jit_chunk_step(77)", 0, 600)]),
                      [STEP])
    assert len(calls) == len({o[0] for o in ops}) == 6


# ------------------------------------------------ a trace from the chip
@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE + ".hlo.txt") as f:
        text = f.read()
    with open(FIXTURE + ".json") as f:
        meta = json.load(f)
    stats, op_map = scope_ledger(text)
    loaded = trace.load_profile(FIXTURE + ".xplane.pb")
    return stats, op_map, loaded, meta


def test_recorded_map_is_the_programs_and_joins_whole(recorded):
    stats, op_map, loaded, meta = recorded
    assert op_map["module"] == "jit_chunk_step"
    assert sorted(meta["modules"]) == sorted(
        {n for d in loaded["devices"].values() for n, _, _ in d["modules"]})
    (dev,) = loaded["devices"].values()
    joined = trace.join_scopes(dev, [op_map])
    # every executed operation of chunk_step is in its map, with its type
    assert trace.join_coverage(joined) == {"jit_chunk_step": 1.0}
    got = trace.scope_seconds(joined)
    assert set(got["unmapped"]) == {"jit_all_finite"}
    assert got["unmatched"] == {}
    assert got["inclusive"]["rollout_step"] == pytest.approx(
        got["innermost"]["rollout_step"] + got["innermost"]["sim_substep"]
        + got["innermost"]["policy_forward"])


def test_recorded_executions(recorded):
    from benchmarks.tests.record_scope_fixture import STEPS, SUBSTEPS
    _, op_map, loaded, _ = recorded
    got = trace.layer_times(loaded, [op_map])
    ex = got["executions"]
    step = ex["rollout_step"]
    programs = got["programs"]["jit_chunk_step"]
    # the first and the last execution are cut by the trace: fewer whole
    # control steps than the executions' steps, and at least all but two
    # executions' worth
    assert (programs["executions"] - 2) * STEPS <= step["executions"] \
        < programs["executions"] * STEPS
    assert ex["rollout_step/policy_forward"]["executions"] == \
        step["executions"]
    assert abs(ex["rollout_step/sim_substep"]["executions"]
               - SUBSTEPS * step["executions"]) <= SUBSTEPS
    # a control step is its substeps, its policy forward and a little
    parts = SUBSTEPS * ex["rollout_step/sim_substep"]["per_execution_s"] \
        + ex["rollout_step/policy_forward"]["per_execution_s"]
    assert parts < step["per_execution_s"] < 1.02 * parts
    # the program keeps the chip busy between its own operations
    assert programs["busy_s"] / programs["span_s"] > 0.99


def test_recorded_gaps_name_the_host_spans(recorded):
    _, op_map, loaded, _ = recorded
    gaps = trace.layer_times(loaded, [op_map])["idle_spans"]
    # the 20 ms sleep under harness_observe (inside episode), then the
    # 10 ms under the root alone and the 10 ms under no span
    assert gaps[0][0] == "harness_observe" and gaps[0][1] > 0.02
    assert sorted(g[0] for g in gaps[1:3]) == ["episode", "none"]
    assert all(0.01 < g[1] < 0.02 for g in gaps[1:3])


def test_obs_report_prints_device_time_by_layer(tmp_path, capsys):
    """``tools/obs_report.py <result_dir>`` of a ``--profile`` run: the
    trace under ``profile/`` read through ``perf.json``'s maps."""
    import shutil
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(ASSETS), "..", "tools"))
    import obs_report

    with open(FIXTURE + ".hlo.txt") as f:
        _, op_map = scope_ledger(f.read())
    obs_report._synthetic_events(str(tmp_path / "events.jsonl"))
    with open(tmp_path / "perf.json", "w") as f:
        json.dump({"schema_version": 1, "entries": {
            "chunk_step": {"available": True, "op_map": op_map}}}, f)
    # no trace yet: no section
    assert obs_report.layer_summary(str(tmp_path), obs_report.load_perf(
        str(tmp_path))) is None
    prof = tmp_path / "profile" / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    shutil.copyfile(FIXTURE + ".xplane.pb", prof / "host.xplane.pb")
    assert obs_report.main([str(tmp_path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    layers = summary["layers"]
    assert layers["trace"] == os.path.join(
        "profile", "plugins", "profile", "run", "host.xplane.pb")
    assert layers["coverage"] == {"jit_chunk_step": 1.0}
    buf = io.StringIO()
    obs_report.render_text(summary, out=buf)
    text = buf.getvalue()
    section = text[text.index("device time by layer"):]
    assert "jit_chunk_step: join coverage 100.00%" in section
    for line in ("rollout_step/sim_substep", "unmapped jit_all_finite",
                 "longest device-idle gaps (host span open): "
                 "harness_observe"):
        assert line in section


def test_a_cpu_trace_holds_no_chip_plane(tmp_path):
    """A ``--profile`` run on the CPU: the trace has host spans and no
    chip plane, and the report says so instead of an empty table."""
    import sys

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(os.path.dirname(ASSETS), "..", "tools"))
    import obs_report

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path / "profile"))
    with jax.profiler.TraceAnnotation("dispatch"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    loaded = trace.load_profile(trace.find_profile(str(tmp_path / "profile")))
    assert loaded["devices"] == {}
    assert [s[0] for s in loaded["spans"]] == ["dispatch"]
    with open(FIXTURE + ".hlo.txt") as fh:
        _, op_map = scope_ledger(fh.read())
    perf = {"entries": {"chunk_step": {"available": True, "op_map": op_map}}}
    assert obs_report.layer_summary(str(tmp_path), perf) == {
        "error": "the trace holds no chip plane (a CPU run)"}
