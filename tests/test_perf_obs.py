"""Performance-observability tests (device-cost ledger, trace export,
bench_diff, rotation) — the PR-10 layer every campaign reports through.

Covers: cost-ledger fields present and arithmetically consistent
(intensity = flops/bytes, MFU = achieved/peak, wall mean = total/count),
capture through the donated_jit partial shape, failure non-fatality, the
no-host-sync dispatch contract, perf.json end-to-end from a tiny train
run, strict trace-event validation (monotone ts, matched B/E, pid/tid)
on both synthetic and real streams, bench_diff regression/ok/
missing-baseline verdicts on synthetic artifacts, and the events.jsonl
rotation roundtrip through every reader.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.obs import (CostLedger, JsonlSink, ListSink, MetricsHub,
                         PERF_SCHEMA_VERSION, RunObserver,
                         device_memory_snapshot, rotated_paths)
from gsc_tpu.obs.perf import DEVICE_PEAKS
from gsc_tpu.obs.trace import build_trace, read_events, validate_trace

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

import bench_diff
import obs_report

pytestmark = pytest.mark.perf_obs


def _matmul_jit():
    @jax.jit
    def f(a, b):
        return jnp.tanh(a @ b).sum()
    return f


# ------------------------------------------------------------- cost ledger
def test_cost_ledger_fields_arithmetically_consistent():
    hub = MetricsHub(tags={"run": "ledger"})
    sink = ListSink()
    hub.add_sink(sink)
    # the v5e row of the ONE peaks table, pinned by device_kind string
    led = CostLedger(hub=hub, device_kind="TPU v5 lite")
    a = jnp.ones((64, 64), jnp.float32)
    entry = led.capture("mm", _matmul_jit(), (a, a))
    assert entry["available"] is True
    assert entry["flops"] > 0 and entry["bytes_accessed"] > 0
    assert isinstance(entry["fusions"], int) and entry["fusions"] >= 0
    assert set(entry["ops"]) == {"while", "dot", "scatter", "gather"}
    assert entry["ops"]["dot"] >= 1
    assert entry["arithmetic_intensity"] == pytest.approx(
        entry["flops"] / entry["bytes_accessed"], rel=1e-3)
    # one structured compile_cost event per capture
    (ev,) = sink.of_kind("compile_cost")
    assert ev["fn"] == "mm" and ev["flops"] == entry["flops"]
    assert hub.get_gauge("compile_fusions", fn="mm") == entry["fusions"]

    # timing merge: MFU/roofline derive exactly from flops x wall x peak
    led.note_timing("mm", total_s=0.0005, count=100)
    full = led.entry("mm")
    assert full["dispatches"] == 100
    assert full["wall_s_mean"] == pytest.approx(5e-6)
    peak = DEVICE_PEAKS["TPU v5 lite"]
    assert (peak["flops_per_s"], peak["bytes_per_s"]) == (197e12, 819e9)
    assert peak["source"]
    assert full["achieved_flops_per_s"] == pytest.approx(
        entry["flops"] / 5e-6, rel=1e-3)
    assert full["mfu"] == pytest.approx(
        (entry["flops"] / 5e-6) / peak["flops_per_s"], rel=1e-2)
    roof = full["roofline"]
    ridge = peak["flops_per_s"] / peak["bytes_per_s"]
    assert roof["ridge"] == pytest.approx(ridge, rel=1e-3)
    assert roof["regime"] == ("memory_bound"
                              if roof["intensity"] < ridge
                              else "compute_bound")
    assert roof["roof_multiple"] >= 1.0

    # schema-versioned document roundtrip
    doc = led.summary()
    assert doc["schema_version"] == PERF_SCHEMA_VERSION
    assert doc["backend"] == jax.default_backend()
    assert doc["device_kind"] == "TPU v5 lite"
    assert doc["device_count"] == len(jax.devices())
    assert doc["peaks"] == peak and "peaks_note" not in doc
    assert doc["run"] == "ledger"
    assert json.loads(json.dumps(doc))["entries"]["mm"]["mfu"] \
        == full["mfu"]


def test_unknown_device_gets_no_mfu_and_says_so():
    """A device that is not in DEVICE_PEAKS (here: the CPU the suite runs
    on) keeps its achieved rates but carries no mfu / bw_util / roofline
    field, and the document names the device and says why."""
    led = CostLedger()
    a = jnp.ones((64, 64), jnp.float32)
    led.capture("mm", _matmul_jit(), (a, a))
    led.note_timing("mm", total_s=0.5, count=100)
    full = led.entry("mm")
    assert full["achieved_flops_per_s"] > 0
    assert full["achieved_bytes_per_s"] > 0
    assert not {"mfu", "bw_util", "roofline"} & set(full)
    doc = led.summary()
    assert doc["device_kind"] == jax.devices()[0].device_kind
    assert doc["peaks"] is None
    assert doc["device_kind"] in doc["peaks_note"]


def test_cost_ledger_unwraps_donated_jit_partial():
    """The trainer's donated entry points are ``partial(jit(fn), self)``
    — capture must peel the partial and fold its bound args in."""
    import functools

    fn = functools.partial(
        jax.jit(lambda s, x: x * s, static_argnums=0), 3)
    led = CostLedger()
    entry = led.capture("scaled", fn, (jnp.ones(8),))
    assert entry["available"] is True and entry["flops"] > 0


def test_cost_ledger_capture_failure_is_nonfatal():
    led = CostLedger()
    entry = led.capture("broken", lambda x: x, (1,))   # not a jit object
    assert entry["available"] is False and "error" in entry
    # an unavailable entry serializes without derived fields
    doc = led.summary()
    assert doc["entries"]["broken"]["available"] is False


def test_ledger_adds_no_host_sync_to_dispatch():
    """The acceptance contract: with a ledger captured, dispatching the
    same entry point performs ZERO device->host syncs — cost analysis
    happened at compile time, timings come from the deferred drains."""
    from gsc_tpu.analysis.sentinels import no_host_sync

    f = _matmul_jit()
    a = jnp.ones((32, 32), jnp.float32)
    led = CostLedger()
    led.capture("mm", f, (a, a))
    with no_host_sync("perf-instrumented dispatch"):
        out = f(a, a)          # async dispatch only — no sync tripwire
    assert np.isfinite(np.asarray(out))   # sync OUTSIDE the guard


def test_device_memory_records_carry_backend():
    """CPU: memory_stats() is None — the record must still appear, with
    available=False and the backend named (never silently skipped)."""
    recs = device_memory_snapshot()
    assert recs, "no device records at all"
    for rec in recs:
        assert "available" in rec and rec["backend"] == "cpu"
        if not rec["available"]:
            assert "bytes_in_use" not in rec


# ------------------------------------------------------------- end-to-end
def test_tiny_run_writes_perf_json_and_valid_trace(tmp_path):
    """A tiny pipelined train run under RunObserver(perf=True) produces a
    complete cost ledger (flops/bytes/fusions/MFU for episode_step, with
    dispatch counts matching the episodes run) and an events stream the
    trace exporter renders into a VALID trace."""
    from gsc_tpu.agents import Trainer
    from tests.test_agent import make_driver, make_stack

    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    obs = RunObserver(str(tmp_path / "obs"), run_id="perfrun", perf=True)
    obs.start(meta={"episodes": 2})
    trainer = Trainer(env, driver, agent, seed=0,
                      result_dir=str(tmp_path), obs=obs)
    trainer.train(episodes=2)
    obs.close()

    perf = json.load(open(tmp_path / "obs" / "perf.json"))
    assert perf["schema_version"] == PERF_SCHEMA_VERSION
    e = perf["entries"]["episode_step"]
    assert e["available"] and e["flops"] > 0 and e["bytes_accessed"] > 0
    assert e["fusions"] > 0
    assert e["dispatches"] == 2 and e["wall_s_total"] > 0
    # the suite's CPU is not in the peaks table: achieved rates only
    assert e["achieved_flops_per_s"] > 0 and "mfu" not in e
    assert perf["peaks"] is None and perf["backend"] == "cpu"
    assert e["arithmetic_intensity"] == pytest.approx(
        e["flops"] / e["bytes_accessed"], rel=1e-3)
    assert "dispatch" in perf["phases"]

    events = [json.loads(line)
              for line in open(tmp_path / "obs" / "events.jsonl")]
    costs = [ev for ev in events if ev["event"] == "compile_cost"]
    assert [ev["fn"] for ev in costs] == ["episode_step"]
    assert costs[0]["flops"] == e["flops"]

    # obs_report renders the ledger without error
    summary = obs_report.summarize(
        obs_report.load_events(str(tmp_path / "obs")),
        perf=obs_report.load_perf(str(tmp_path / "obs")))
    assert summary["perf"]["entries"]["episode_step"]["fusions"] \
        == e["fusions"]
    assert summary["memory_unavailable_backends"] == ["cpu"]
    obs_report.render_text(summary, out=open(os.devnull, "w"))

    # trace export: strict validation on a REAL stream
    trace = build_trace(read_events(str(tmp_path / "obs")))
    assert validate_trace(trace) == []
    names = {ev.get("name") for ev in trace["traceEvents"]}
    assert "episode 0" in names and "episode 1" in names
    assert "dispatch" in names


# ----------------------------------------------------------- trace export
def test_trace_export_synthetic_stream_valid(tmp_path):
    """The selftest stream exercises every track: episodes with phases,
    a stall + escalation, a recovery ladder (flow arrows), compiles and
    serve stats — the built trace must pass the strict validator."""
    p = tmp_path / "events.jsonl"
    obs_report._synthetic_events(str(p))
    trace = build_trace(read_events(str(p)))
    assert validate_trace(trace) == []
    evs = trace["traceEvents"]
    assert all("pid" in e and "tid" in e and "ph" in e for e in evs)
    stalls = [e for e in evs if e["name"] == "stall"]
    assert stalls and stalls[0]["ph"] == "i"
    # recovery ladder: one flow start + matching finish
    assert [e["ph"] for e in evs if e.get("name") == "ladder"] \
        == ["s", "f"]
    # per-tid B/E pairs balance (the validator proved it; double-check
    # the episode track specifically)
    ep_tid = [e for e in evs
              if e["tid"] == 1 and e["ph"] in ("B", "E")]
    assert sum(1 for e in ep_tid if e["ph"] == "B") \
        == sum(1 for e in ep_tid if e["ph"] == "E")
    # non-metadata timestamps are monotone
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_validator_rejects_malformed(tmp_path):
    p = tmp_path / "events.jsonl"
    obs_report._synthetic_events(str(p))
    trace = build_trace(read_events(str(p)))

    # dropped E -> unclosed B
    broken = {"traceEvents": [e for e in trace["traceEvents"]
                              if not (e["ph"] == "E"
                                      and e["name"] == "drain")]}
    assert any("unclosed" in err or "stack" in err
               for err in validate_trace(broken))

    # shuffled ts -> monotonicity violation
    evs = [dict(e) for e in trace["traceEvents"]]
    non_meta = [i for i, e in enumerate(evs) if e["ph"] != "M"]
    evs[non_meta[1]]["ts"] = evs[non_meta[-1]]["ts"] + 100.0
    assert any("monotone" in err for err in validate_trace(
        {"traceEvents": evs}))

    # missing tid
    evs2 = [dict(e) for e in trace["traceEvents"]]
    del evs2[non_meta[0]]["tid"]
    assert any("'tid'" in err for err in validate_trace(
        {"traceEvents": evs2}))

    assert validate_trace({}) == ["traceEvents missing or not a list"]


def test_trace_export_cli_roundtrip(tmp_path):
    import subprocess

    p = tmp_path / "events.jsonl"
    obs_report._synthetic_events(str(p))
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_export.py")
    out = tmp_path / "trace.json"
    r = subprocess.run([sys.executable, tool, str(tmp_path),
                        "-o", str(out)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    trace = json.load(open(out))
    assert validate_trace(trace) == []


# -------------------------------------------------------------- bench_diff
def _bench_artifact(tmp_path, name, value, fusions, traces=1):
    p = tmp_path / f"{name}.json"
    p.write_text(json.dumps({
        "metric": "env_steps_per_sec_per_chip", "status": "ok",
        "value": value, "unit": "env-steps/s",
        "jit_traces": {"chunk_step": traces},
        "cost": {"chunk_step": {"available": True, "fusions": fusions,
                                "flops": 1e9}}}))
    return str(p)


def test_bench_diff_verdicts(tmp_path):
    good = _bench_artifact(tmp_path, "BENCH_rA", 2000.0, 280)
    bad = _bench_artifact(tmp_path, "BENCH_rB", 1500.0, 310, traces=2)
    traj = str(tmp_path / "BENCH_TRAJECTORY.json")
    doc = bench_diff.ingest([good, bad], traj)
    assert set(doc["rows"]) == {"BENCH_rA", "BENCH_rB"}
    assert doc["schema_version"] == bench_diff.TRAJECTORY_SCHEMA_VERSION

    # self-compare: clean
    assert bench_diff.main(["diff", "BENCH_rA", "--baseline", "BENCH_rA",
                            "--trajectory", traj]) == 0
    # regression beyond band: nonzero, names the axes
    d = bench_diff.diff_rows({**doc["rows"]["BENCH_rB"], "name": "B"},
                             {**doc["rows"]["BENCH_rA"], "name": "A"})
    assert d["verdict"] == "regression"
    assert {"env_steps_per_sec", "chunk_step_fusions",
            "chunk_step_jit_traces"} <= set(d["regressions"])
    assert d["metrics"]["chunk_step_flops"]["verdict"] == "informational"
    assert bench_diff.main(["diff", "BENCH_rB", "--baseline", "BENCH_rA",
                            "--trajectory", traj]) == 1
    # the reverse is an improvement
    d2 = bench_diff.diff_rows({**doc["rows"]["BENCH_rA"], "name": "A"},
                              {**doc["rows"]["BENCH_rB"], "name": "B"})
    assert d2["verdict"] == "ok" \
        and d2["metrics"]["env_steps_per_sec"]["verdict"] == "improved"
    # missing baseline: distinct verdict + exit code
    assert bench_diff.main(["diff", "BENCH_rA", "--baseline", "BENCH_rZ",
                            "--trajectory", traj]) == 3
    # tolerance override declassifies
    d3 = bench_diff.diff_rows(
        {"name": "a", "metrics": {"x_mfu": 0.9}},
        {"name": "b", "metrics": {"x_mfu": 1.0}},
        tolerances={"x_mfu": 0.5})
    assert d3["verdict"] == "ok"


def test_bench_diff_ingests_perf_ledger(tmp_path):
    led = CostLedger(hub=MetricsHub(tags={"run": "ingme"}),
                     device_kind="TPU v5 lite")
    a = jnp.ones((64, 64), jnp.float32)
    led.capture("mm", _matmul_jit(), (a, a))
    led.note_timing("mm", 1e-4, 10)
    perf_path = str(tmp_path / "perf.json")
    led.write_json(perf_path)
    traj = str(tmp_path / "traj.json")
    doc = bench_diff.ingest([perf_path], traj)
    row = doc["rows"]["perf_ingme"]
    assert row["kind"] == "perf_ledger"
    assert row["metrics"]["mm_fusions"] >= 0
    assert row["metrics"]["mm_mfu"] > 0
    # a perf row self-compares clean through the CLI
    assert bench_diff.main(["diff", "perf_ingme", "--baseline",
                            "perf_ingme", "--trajectory", traj]) == 0


# --------------------------------------------------------------- rotation
def test_rotation_roundtrip_through_every_reader(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path, rotate_mb=0.001)   # ~1 KiB segments
    sink.emit({"event": "run_start", "ts": 1.0, "run": "rot"})
    for i in range(60):
        sink.emit({"event": "episode", "ts": 2.0 + i, "episode": i,
                   "pad": "x" * 64})
    sink.emit({"event": "run_end", "ts": 99.0, "status": "ok"})
    sink.close()
    segments = rotated_paths(path)
    assert len(segments) > 2, "stream never rotated"
    assert segments[-1] == path

    # obs_report walks the segments transparently
    events = obs_report.load_events(path)
    assert [e["event"] for e in events][0] == "run_start"
    assert [e.get("episode") for e in events
            if e["event"] == "episode"] == list(range(60))

    # the trace reader sees the same stream and builds a valid trace
    assert read_events(path) == events
    assert validate_trace(build_trace(events)) == []
