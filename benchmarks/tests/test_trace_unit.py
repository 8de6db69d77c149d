"""The reduction's arithmetic on hand-made events."""
import pytest

from benchmarks import trace


def test_self_time_and_leaves_with_nesting():
    ops = [("while", 0, 100), ("fusion.1", 10, 20), ("fusion.2", 50, 30),
           ("copy", 120, 10)]
    self_ns, leaves = trace.self_times(ops)
    assert self_ns == {"while": 50.0, "fusion.1": 20.0, "fusion.2": 30.0,
                       "copy": 10.0}
    assert sorted(leaves) == [(10, 30), (50, 80), (120, 130)]


def test_union_and_busy():
    assert trace.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]


def test_gap_attribution():
    busy = [(0, 10), (110, 120), (125, 130)]
    spans = [("scenario_regen", 5, 100), ("dispatch", 105, 30)]
    gaps = trace.gaps_by_span(busy, spans, top=5)
    assert [g[0] for g in gaps] == ["scenario_regen", "dispatch"]
    assert gaps[0][1] == pytest.approx(100e-9)
    assert gaps[1][1] == pytest.approx(5e-9)
    none = trace.gaps_by_span([(0, 1), (50, 60)], [], 5)
    assert none[0][0] == "none" and none[0][1] == pytest.approx(49e-9)


def test_reduce_counts_only_leaf_time_as_busy():
    loaded = {"devices": {"/device:TPU:0": {
        "ops": [("while", 0, 1_000_000_000), ("f", 0, 250_000_000),
                ("g", 500_000_000, 250_000_000)]}},
        "spans": [("drain", 250_000_000, 250_000_000)]}
    red = trace.reduce(loaded, window_s=2.0)
    assert red["busy_s"] == 0.5 and red["window_s"] == 2.0
    assert red["breakdown"]["idle_gaps"][0] == ["drain", 0.25]
    assert dict(map(tuple, red["breakdown"]["device_ops"]))["while"] == 0.5


def test_device_plane_names():
    assert trace.is_device_plane("/device:TPU:0")
    assert not trace.is_device_plane("/device:TPU:0 SparseCore 1")
    assert not trace.is_device_plane("/host:CPU")


def test_top_level_loops_are_the_unenclosed_whiles():
    ops = [("%while.9 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
            100, 50),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 110, 5),
           ("%while.2 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %u), "
            "condition=%c2, body=%b2", 200, 400),
           ("%while.7 = (s32[]) while((s32[]) %v), condition=%c3, body=%b3",
            250, 20),
           ("%copy.1 = f32[8]{0} copy(f32[8]{0} %q)", 700, 10)]
    loaded = {"devices": {"/device:TPU:0": {"ops": ops}}, "spans": []}
    red = trace.reduce(loaded, window_s=1.0)
    # in the order they ran; the nested %while.7 is enclosed by %while.2
    assert red["top_level_loops"] == [
        [0.0, pytest.approx(50e-9)],
        [pytest.approx(100e-9), pytest.approx(400e-9)]]
    assert trace.opcode(ops[0][0]) == "while"
    assert trace.opcode(ops[1][0]) == "fusion"
    assert red["ops_span_s"] == pytest.approx((710 - 110) * 1e-9)


def test_learn_burst_is_the_last_loop_not_the_longest():
    from benchmarks.metrics import _common
    # three substep scans of the rollout's last steps, each longer than
    # the burst that follows them
    record = {"trace": {"top_level_loops": [[0.0, 0.115], [0.12, 0.115],
                                            [0.24, 0.115], [0.36, 0.09]]}}
    assert _common.learn_burst_seconds(record) == 0.09
    assert _common.learn_burst_seconds({"trace": {}}) is None
    assert _common.learn_burst_seconds({"trace": None}) is None
