"""The reduction on a small trace recorded on the chip (TPU v5 lite, PR 24,
``record_fixture.py``): two rounds of a sleeping ``scenario_regen`` span,
three ``chunk_step`` and one ``learn_step`` execution under ``dispatch``,
and a ``drain``.  (Per-program device time is not reduced: no metric reads
it, since a whole ``chunk_step`` execution does not fit the traced slice.)"""
import os

import pytest

from benchmarks import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture.xplane.pb")
WINDOW_S = 0.04586148262023926       # host clock around the recording


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_file(FIXTURE, WINDOW_S)


def test_device_plane_and_events(reduced):
    assert reduced["n_devices"] == 1 and reduced["n_events"] == 132


def test_idle_share(reduced):
    # leaf operations ran for 26.8 microseconds of a 45.9 ms window
    assert reduced["busy_s"] == pytest.approx(2.6832e-05, rel=1e-6)
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.99941, abs=1e-5)


def test_nested_while_is_not_busy_time(reduced):
    ops = dict(map(tuple, reduced["breakdown"]["device_ops"]))
    whiles = [s for n, s in ops.items() if " while " in n]
    fusions = [s for n, s in ops.items() if " fusion " in n]
    # the scan's while spans its body: its self time is what the body
    # leaves over, far under the fusion it runs
    assert whiles and fusions and max(whiles) < max(fusions)
    assert len(reduced["breakdown"]["device_ops"]) == 10
    assert all(len(n) <= 96 for n in ops)


def test_gap_attribution(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert len(gaps) == 5
    # the longest device-idle gap lies under the sleeping host span
    assert gaps[0][0] == "scenario_regen"
    assert gaps[0][1] == pytest.approx(0.022128307, rel=1e-6)


def test_top_level_loops_in_order(reduced):
    # each chunk_step execution is one 8-step scan: six top-level loops
    loops = reduced["top_level_loops"]
    assert len(loops) == 6
    assert all(2e-6 < d < 4e-6 for _, d in loops)
    starts = [t for t, _ in loops]
    assert starts == sorted(starts) and starts[0] >= 0
    assert 0 < reduced["busy_s"] <= reduced["ops_span_s"] < WINDOW_S
