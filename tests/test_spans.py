"""Host spans and device scopes (PR 25): ``PhaseTimer`` spans with a start,
a parent and an episode; the replica loop's ``episode_spans`` events; the
trace exporter placing slices at their recorded starts; and the two name
tuples (``obs.trace.SPAN_NAMES``, ``DEVICE_SCOPES``) covering every
``phase_span`` and ``jax.named_scope`` in the package."""
import os
import re
import threading

import pytest

from gsc_tpu.agents.trainer import Trainer
from gsc_tpu.obs import ListSink, RunObserver
from gsc_tpu.obs.trace import (DEVICE_SCOPES, SPAN_NAMES, TRACE_TRACKS,
                               build_trace, emit_episode_spans, phase_span,
                               validate_trace)
from gsc_tpu.utils.telemetry import PhaseTimer

from tests.test_agent import make_driver, make_stack

pytestmark = pytest.mark.perf_obs

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "gsc_tpu")
# what the replica loop runs: every span name but the serial loop's two
REPLICA_SPANS = set(SPAN_NAMES) - {"host_sample", "host_sample_wait"}


class FakeClock:
    """One counter for both clocks: wall = 1000 + t, monotonic = t."""

    def __init__(self):
        self.t = 0.0

    def wall(self):
        return 1000.0 + self.t

    def perf(self):
        return self.t


def fake_timer():
    clock = FakeClock()
    return clock, PhaseTimer(wall=clock.wall, perf=clock.perf)


# ------------------------------------------------------------- PhaseTimer
def test_spans_nest_with_parent_and_episode_on_a_fake_clock():
    clock, timer = fake_timer()
    timer.episode = 7
    with phase_span("episode", timer):
        clock.t += 1.0
        with phase_span("dispatch", timer):
            clock.t += 2.0
            with phase_span("drain", timer):
                clock.t += 0.5
        timer.episode = 8           # a span keeps the episode it opened in
        clock.t += 0.25
    spans = {s["name"]: s for s in timer.take_spans()}
    assert list(spans) == ["drain", "dispatch", "episode"]   # closing order
    assert spans["episode"] == {"name": "episode", "parent": None,
                                "episode": 7, "t0": 1000.0, "dur_s": 3.75}
    assert spans["dispatch"]["parent"] == "episode"
    assert spans["dispatch"]["t0"] == 1001.0
    assert spans["dispatch"]["dur_s"] == 2.5
    assert spans["drain"] == {"name": "drain", "parent": "dispatch",
                              "episode": 7, "t0": 1003.0, "dur_s": 0.5}
    # root self time: its duration less its direct children
    children = sum(s["dur_s"] for s in spans.values()
                   if s["parent"] == "episode")
    assert spans["episode"]["dur_s"] - children == 1.25


def test_totals_keep_their_meaning_beside_the_spans():
    clock, timer = fake_timer()
    for dur in (1.0, 2.0):
        with phase_span("dispatch", timer):
            clock.t += dur
    with timer.phase("blocked_put"):        # the async fleet's ledger
        clock.t += 4.0
    timer.add("adopt", 0.5)
    summary = timer.summary()
    assert summary["dispatch"] == {"total_s": 3.0, "count": 2,
                                   "mean_ms": 1500.0}
    assert summary["blocked_put"]["total_s"] == 4.0
    spans = timer.take_spans()
    # phase()/add() stay totals-only; phase_span records the span too
    assert [s["name"] for s in spans] == ["dispatch", "dispatch"]
    assert sum(s["dur_s"] for s in spans) == summary["dispatch"]["total_s"]
    assert timer.take_spans() == []         # taken once


def test_span_parent_is_per_thread():
    clock, timer = fake_timer()
    seen = []

    def other():
        with phase_span("host_sample", timer):
            pass
        seen.append(True)

    with phase_span("episode", timer):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    spans = {s["name"]: s for s in timer.take_spans()}
    assert seen and spans["host_sample"]["parent"] is None
    assert spans["episode"]["parent"] is None


def test_untaken_spans_are_a_bounded_ring():
    _, timer = fake_timer()
    for _ in range(PhaseTimer.MAX_SPANS + 10):
        with phase_span("dispatch", timer):
            pass
    assert len(timer.take_spans()) == PhaseTimer.MAX_SPANS
    assert timer.summary()["dispatch"]["count"] == PhaseTimer.MAX_SPANS + 10


def test_emit_episode_spans_needs_a_hub_and_spans():
    _, timer = fake_timer()
    sink = ListSink()

    class Hub:
        def event(self, kind, **fields):
            sink.emit({"event": kind, **fields})

    emit_episode_spans(Hub(), timer)            # nothing closed: no event
    assert sink.records == []
    with phase_span("drain", timer):
        pass
    emit_episode_spans(None, timer)             # no hub: taken, dropped
    assert timer.take_spans() == []
    with phase_span("drain", timer):
        pass
    emit_episode_spans(Hub(), timer)
    assert [e["event"] for e in sink.records] == ["episode_spans"]
    assert sink.records[0]["spans"][0]["name"] == "drain"


# --------------------------------------------------------- the two tuples
def _literals(pattern):
    found = {}
    for d, _, files in os.walk(PACKAGE):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    for name in re.findall(pattern, fh.read()):
                        found.setdefault(name, []).append(f)
    return found


@pytest.mark.parametrize("pattern,names", [
    (r'phase_span\(\s*"([^"]+)"', SPAN_NAMES),
    (r'named_scope\(\s*"([^"]+)"', DEVICE_SCOPES)])
def test_every_name_in_the_package_is_in_its_tuple(pattern, names):
    found = _literals(pattern)
    assert found, pattern
    assert set(found) <= set(names), {k: v for k, v in found.items()
                                      if k not in names}
    # and nothing is promised that the package does not open
    assert set(names) <= set(found), set(names) - set(found)
    assert len(set(names)) == len(names)


# ------------------------------------------------------- the replica loop
class StopAfter:
    """``preempt`` that stops the loop at its (n+1)-th boundary."""
    signame = "test_stop"

    def __init__(self, n):
        self.n, self.reads = n, 0

    @property
    def triggered(self):
        self.reads += 1
        return self.reads > self.n


class Keep:
    def __init__(self):
        self.calls = []

    def save(self, state, buffers, episode, **_):
        self.calls.append(("save", episode))

    def publish(self, params, meta=None, verified=False):
        self.calls.append(("publish", meta["episode"]))


@pytest.fixture(scope="module")
def replica_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    obs = RunObserver(str(tmp / "obs"), run_id="spans", learn=True,
                      perf=True)
    sink = ListSink()
    obs.hub.add_sink(sink)
    obs.start(meta={"episodes": 2})
    trainer = Trainer(env, driver, agent, seed=0, result_dir=str(tmp),
                      obs=obs)
    keep = Keep()
    trainer.train_parallel(episodes=10, num_replicas=2, chunk=2,
                           preempt=StopAfter(2), ckpt_manager=keep,
                           ckpt_interval=1, publisher=keep,
                           publish_interval=1)
    obs.close()
    return {"events": list(sink.records), "trainer": trainer, "keep": keep,
            "agent": agent}


def _spans(run):
    return [s for e in run["events"] if e["event"] == "episode_spans"
            for s in e["spans"]]


def test_one_episode_spans_event_per_episode_plus_the_tail(replica_run):
    assert replica_run["trainer"].preempted
    events = [e for e in replica_run["events"]
              if e["event"] == "episode_spans"]
    assert len(events) == 3            # top of episodes 0 and 1, loop exit
    # the first holds only what ran before it: episode 0's stop test
    assert [(s["name"], s["episode"]) for s in events[0]["spans"]] == \
        [("preempt_check", 0)]
    # the tail holds the last episode's whole root and the stopping
    # iteration's own (its stop test and nothing else)
    tail = [(s["name"], s["episode"]) for s in events[2]["spans"]]
    assert ("episode", 1) in tail and tail[-2:] == [("preempt_check", 2),
                                                    ("episode", 2)]


def test_every_replica_span_name_is_emitted(replica_run):
    names = {s["name"] for s in _spans(replica_run)}
    assert names == REPLICA_SPANS
    # cost capture belongs to the first episode alone
    assert {s["episode"] for s in _spans(replica_run)
            if s["name"] == "cost_capture"} == {0}
    assert replica_run["keep"].calls == [("publish", 1), ("save", 1),
                                         ("publish", 2), ("save", 2)]


@pytest.mark.parametrize("episode", [0, 1])
def test_children_lie_inside_their_root(replica_run, episode):
    spans = [s for s in _spans(replica_run) if s["episode"] == episode]
    root = [s for s in spans if s["name"] == "episode"]
    assert len(root) == 1 and root[0]["parent"] is None
    root = root[0]
    children = [s for s in spans if s["name"] != "episode"]
    assert children and all(s["parent"] == "episode" for s in children)
    slack = 5e-3        # t0 is wall clock, dur_s monotonic
    for s in children:
        assert s["t0"] >= root["t0"] - slack, s
        assert s["t0"] + s["dur_s"] <= root["t0"] + root["dur_s"] + slack, s
    self_s = root["dur_s"] - sum(s["dur_s"] for s in children)
    assert 0 <= self_s < 0.25 * root["dur_s"]
    # the loop's order within an episode
    order = [s["name"] for s in sorted(children, key=lambda s: s["t0"])
             if s["name"] != "cost_capture"]
    assert order == ["preempt_check", "scenario_regen", "reset_enqueue",
                     "dispatch", "drain", "harness_observe", "episode_log",
                     "publish", "ckpt"]


def test_cumulative_phases_equal_the_sums_of_their_spans(replica_run):
    episodes = [e for e in replica_run["events"] if e["event"] == "episode"]
    assert [e["episode"] for e in episodes] == [0, 1]
    spans = _spans(replica_run)
    for e in episodes:
        for name in ("dispatch", "drain"):
            upto = sum(s["dur_s"] for s in spans
                       if s["name"] == name and s["episode"] <= e["episode"])
            assert e["phases"][name]["total_s"] == pytest.approx(
                upto, abs=1e-3)
            assert e["phases"][name]["count"] == e["episode"] + 1


def test_sps_is_this_episode_alone(replica_run):
    agent = replica_run["agent"]
    spans = _spans(replica_run)
    rows = replica_run["trainer"].history
    events = [e for e in replica_run["events"] if e["event"] == "episode"]
    assert len(rows) == 2
    for row, event in zip(rows, events):
        assert row["sps"] > 0
        assert event["sps"] == pytest.approx(row["sps"], abs=1e-3)
        steps = 2 * agent.episode_steps
        root = [s for s in spans if s["name"] == "episode"
                and s["episode"] == row["episode"]][0]
        device = sum(s["dur_s"] for s in spans
                     if s["episode"] == row["episode"]
                     and s["name"] in ("dispatch", "drain"))
        # over the root span up to the row: no faster than the device
        # phases allow, no slower than the whole root
        assert steps / root["dur_s"] <= row["sps"] <= steps / device
    # episode 0 compiles; a cumulative rate would hold episode 1 under it
    assert rows[1]["sps"] > 2 * rows[0]["sps"]


# ------------------------------------------------------- the trace export
def _stream(with_spans):
    base = 1_000_000.0
    events = [{"event": "run_start", "ts": base, "run": "r"}]
    phases = {}
    for ep in range(2):
        t0 = base + 1 + 10 * ep
        phases = {"dispatch": {"total_s": 4.0 * (ep + 1)},
                  "drain": {"total_s": 3.0 * (ep + 1)}}
        events.append({"event": "episode", "ts": t0 + 9.5, "run": "r",
                       "episode": ep, "sps": 5.0, "episodic_return": 1.0,
                       "phases": phases})
        if with_spans:
            events.append({"event": "episode_spans", "ts": t0 + 10.0,
                           "run": "r", "spans": [
                {"name": "scenario_regen", "parent": "episode",
                 "episode": ep, "t0": t0 + 0.5, "dur_s": 1.0},
                {"name": "dispatch", "parent": "episode", "episode": ep,
                 "t0": t0 + 2.0, "dur_s": 4.0},
                {"name": "drain", "parent": "episode", "episode": ep,
                 "t0": t0 + 6.0, "dur_s": 3.0},
                {"name": "episode", "parent": None, "episode": ep,
                 "t0": t0, "dur_s": 10.0}]})
    return events


def test_trace_places_slices_at_their_recorded_start():
    trace = build_trace(_stream(with_spans=True))
    assert validate_trace(trace) == []
    tid = TRACE_TRACKS["episode"]
    slices = {(e["name"], e["ts"]): e for e in trace["traceEvents"]
              if e.get("tid") == tid and e["ph"] == "X"}
    # episode 1 began 11 s after run_start; its dispatch 2 s into it
    assert slices[("episode 1", 11e6)]["dur"] == 10e6
    assert slices[("episode 1", 11e6)]["args"]["sps"] == 5.0
    assert slices[("dispatch", 13e6)]["dur"] == 4e6
    assert slices[("drain", 17e6)]["dur"] == 3e6
    assert slices[("scenario_regen", 1.5e6)]["args"] == {
        "episode": 0, "parent": "episode"}
    # nothing reconstructed beside them
    assert not [e for e in trace["traceEvents"]
                if e.get("tid") == tid and e["ph"] in ("B", "E")]


def test_trace_reconstructs_only_for_streams_without_spans():
    trace = build_trace(_stream(with_spans=False))
    assert validate_trace(trace) == []
    tid = TRACE_TRACKS["episode"]
    begins = [e for e in trace["traceEvents"]
              if e.get("tid") == tid and e["ph"] == "B"]
    assert [e["name"] for e in begins] == [
        "episode 0", "dispatch", "drain", "episode 1", "dispatch", "drain"]
    # laid back-to-back from the episode's start: not the recorded place
    assert begins[1]["ts"] == begins[0]["ts"]
    assert not [e for e in trace["traceEvents"]
                if e.get("tid") == tid and e["ph"] == "X"]


def test_real_stream_exports_clean(replica_run):
    trace = build_trace(replica_run["events"])
    assert validate_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"episode 0", "episode 1", "dispatch", "harness_observe",
            "ckpt"} <= names
