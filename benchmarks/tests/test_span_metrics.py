"""The eight readers of PR 25 (``boundary_*_ms`` from the program's
``episode_spans`` events, ``*_device_ops`` from its ``compile_cost`` event):
on a synthetic record, on records that lack the events (the parent's), and,
on the CPU rehearsal's record, the identity with ``episode_gap_ms``, which
gets the same milliseconds from outside."""
import copy

import pytest

from benchmarks import harness, rehearse

BOUNDARY = {"boundary_regen_ms": 1410.0, "boundary_observe_ms": 250.0,
            "boundary_ckpt_ms": 65.0, "boundary_unspanned_ms": 4.0}
DEVICE = {"substep_device_ops": 545.0, "policy_device_ops": 86.0,
          "replay_write_device_ops": 196.0, "learn_step_device_ops": 660.0}
NEW = {**BOUNDARY, **DEVICE}


def spans_of(episode, t0, scale=1.0):
    """One episode's spans: children that add up, with ``dispatch`` and
    ``drain``, to the root less 4 ms; times in seconds."""
    ms = {"preempt_check": 1.0, "scenario_regen": 1400.0 * scale,
          "reset_enqueue": 10.0 * scale, "dispatch": 3.0, "drain": 20000.0,
          "harness_observe": 150.0 * scale, "episode_log": 100.0 * scale,
          "publish": 5.0 * scale, "ckpt": 60.0 * scale}
    out, t = [], t0
    for name, dur in ms.items():
        out.append({"name": name, "parent": "episode", "episode": episode,
                    "t0": t, "dur_s": dur / 1e3})
        t += dur / 1e3
    out.append({"name": "episode", "parent": None, "episode": episode,
                "t0": t0, "dur_s": (sum(ms.values()) + 4.0 * scale) / 1e3})
    return out


def scopes():
    rec = lambda ops, incl, inh=0: {"ops": ops, "fusions": 0, "copies": 0,
                                    "out_bytes": 0, "inherited": inh,
                                    "ops_incl": incl}
    return {"rollout_step": rec(811, 1684, 650),
            "sim_substep": rec(436, 545), "traffic_arrivals": rec(109, 109),
            "policy_forward": rec(50, 86), "replay_write": rec(196, 196),
            "learn_burst": rec(577, 1127, 467),
            "replay_sample": rec(179, 179), "gat_layer": rec(278, 278),
            "unscoped": rec(42, 42)}


def synthetic():
    """Three episodes, the first set-up's (twice as slow at the boundary:
    it must not be read), the next two the window's."""
    events = [{"event": "compile_cost", "fn": "learn_burst",
               "scopes": {"learn_burst": {"ops": 1, "ops_incl": 1,
                                          "inherited": 0}}},
              {"event": "compile_cost", "fn": "chunk_step",
               "scopes": scopes()}]
    for ep in range(3):
        events.append({"event": "episode_spans", "ts": 100.0 * ep + 99.0,
                       "spans": spans_of(ep, 100.0 * ep,
                                         scale=2.0 if ep == 0 else 1.0)})
    return {"warm_episodes": 1, "window_episodes": 2, "events": events}


def read(name, record):
    return harness.load_module("metrics", name).read(record)


@pytest.mark.parametrize("name,value", sorted(NEW.items()))
def test_reader_on_a_synthetic_record(name, value):
    assert read(name, synthetic()) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(NEW))
def test_record_without_the_events_reads_nothing(name):
    """The parent's program emits neither event: nothing, and no raise."""
    for record in ({"warm_episodes": 1, "window_episodes": 2, "events": []},
                   {"events": [{"event": "compile_cost", "fn": "chunk_step",
                                "fusions": 3}]},
                   {}):
        assert read(name, record) is None
    # a window of no episode has no boundary; the program's counters
    # do not depend on the window
    empty = {"warm_episodes": 1, "window_episodes": 0,
             "events": synthetic()["events"]}
    assert (read(name, empty) is None) == (name in BOUNDARY)


def test_a_window_episode_without_its_root_reads_nothing():
    record = synthetic()
    record["events"] = [e for e in record["events"]
                        if not (e["event"] == "episode_spans"
                                and e["spans"][0]["episode"] == 2)]
    for name in BOUNDARY:
        assert read(name, record) is None
    assert read("substep_device_ops", record) == 545.0


def test_a_program_whose_names_were_lost_reads_nothing():
    """Scopes present and all zero (a cached executable of an older
    source): nothing to read, not a count of zero."""
    record = synthetic()
    for e in record["events"]:
        if e.get("fn") == "chunk_step":
            e["scopes"] = {k: dict(v, ops=0, ops_incl=0, inherited=0)
                           for k, v in e["scopes"].items()}
    for name in DEVICE:
        assert read(name, record) is None


def test_new_metrics_are_listed_and_read_through_the_harness():
    bench = harness.manifest()
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(listed)
    assert set(NEW) <= set(harness.list_names("metrics", ".py"))
    for name in NEW:
        m = listed[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert m["better"] == "lower" and m["moves"] == "env_steps_per_s"
        assert m["source"] == ("program_span" if name in BOUNDARY
                               else "program_counter")
    names = harness.metric_names(bench, "flagship-b256", traced=True)
    got = harness.read_metrics(names, {**synthetic(), "trace": None},
                               harness.units_of(bench))
    assert {k: v["value"] for k, v in got.items() if k in NEW} == \
        pytest.approx(NEW)
    # the accepted entries stand first and as they were
    assert [m["name"] for m in bench["per_layer"]][:8] == [
        "compile_s", "window_compiles", "episode_gap_ms",
        "rollout_ms_per_step", "learn_burst_ms", "step_mfu_pct",
        "device_idle_pct", "peak_hbm_gb"]


def test_boundary_metrics_add_up_to_episode_gap_on_the_rehearsal():
    """From inside (the spans) and from outside (boundary stamps less the
    ``dispatch``/``drain`` totals and the hook): the same milliseconds."""
    cell = rehearse.tiny_cell()
    line = rehearse.run_once(copy.deepcopy(cell), seed=5, seconds=0.5,
                             traced=True)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(m), sorted(m)
    inside = sum(m[k] for k in BOUNDARY)
    assert inside == pytest.approx(m["episode_gap_ms"], abs=5.0)
    assert 0 <= m["boundary_unspanned_ms"] < 10.0
    for k in DEVICE:
        assert m[k] >= 1.0
    record = line["record"]
    spans = [s for e in record["events"] if e["event"] == "episode_spans"
             for s in e["spans"]]
    window = range(record["warm_episodes"],
                   record["warm_episodes"] + record["window_episodes"])
    # the benchmark's own hook (the tracer) lies in preempt_check, which
    # neither side counts
    assert {s["episode"] for s in spans if s["name"] == "preempt_check"} \
        >= set(window)
