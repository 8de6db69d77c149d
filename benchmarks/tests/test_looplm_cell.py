"""The cell ``flagship-ouro-b32`` rehearsed on the CPU with its torso cut
to d = 64 (4 heads of 16, MLP 176, 2 layers run 4 times): the same files,
driver, reference (``reference/looplm.py``) and readers as the chip run,
and the parent's way of failing when the program drops the ``torso``."""
import json

import pytest

from benchmarks import harness, rehearse
from benchmarks.metrics import burst_mfu_pct, torso_device_ops

CELL = "flagship-ouro-b32"
TINY_TORSO = dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, head_dim=16, intermediate_size=176,
                  num_hidden_layers=2, total_ut_steps=4,
                  early_exit_threshold=1.0, exit_entropy_beta=0.05)


@pytest.fixture(scope="module")
def traced():
    cell = rehearse.tiny_cell(CELL, torso=TINY_TORSO)
    return rehearse.run_once(cell, seed=2**31 + 5, seconds=0.5, traced=True,
                             limits=dict(rehearse.LIMITS))


def test_rehearsal_is_correct_against_the_looped_reference(traced):
    assert traced["correct"] is True, traced["compared"]
    assert set(traced["compared"]) == set(rehearse.LIMITS)
    events = traced["record"]["events"]
    signal = [e for e in events if e.get("event") == "learn_signal"
              and "exit_step_mean_actor" in e]
    assert signal, "no learn_signal carries the exit distribution"
    for net in ("actor", "critic"):
        assert 1.0 <= signal[-1][f"exit_step_mean_{net}"] <= 4.0
        assert signal[-1][f"exit_entropy_{net}"] > 0.0


def test_the_cells_own_metrics_are_listed_for_it_alone(traced):
    bench = harness.manifest()
    names = harness.metric_names(bench, CELL, traced=True)
    assert {"torso_device_ops", "burst_mfu_pct"} <= set(names)
    for other in ("flagship-b256", "interroute-b32"):
        assert not {"torso_device_ops", "burst_mfu_pct"} & set(
            harness.metric_names(bench, other, traced=True))
    record = traced["record"]
    assert traced["metrics"]["torso_device_ops"]["value"] > 0
    # no device loops in a CPU trace: nothing to read, never 0
    assert "burst_mfu_pct" not in traced["metrics"]
    assert burst_mfu_pct.read(record) is None
    record = dict(record, trace={"top_level_loops": [["while.1", 2.0]]},
                  peaks={"bf16_flops": 1e12})
    want = 100.0 * record["flops"]["grad_step"] * record["episode_steps"] \
        / 2.0 / 1e12
    assert burst_mfu_pct.read(record) == pytest.approx(want)
    # a program without the scope (the parent): nothing, and no raise
    assert torso_device_ops.read({"events": []}) is None
    assert burst_mfu_pct.read({"events": [], "trace": None}) is None


def test_configuration_file_carries_the_published_numbers():
    cfg = harness.load_json("configs", "flagship-ouro")
    published = {"head_dim": 128, "hidden_size": 2048,
                 "intermediate_size": 5632, "num_attention_heads": 16,
                 "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
                 "rope_theta": 1000000, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "max_position_embeddings": 65536,
                 "max_window_layers": 48}
    for key, value in published.items():
        assert cfg[key] == value, key
        if key in cfg["torso"]:
            assert cfg["torso"][key] == value, key
    assert cfg["num_hidden_layers"] == cfg["torso"]["num_hidden_layers"] == 4
    entry = next(c for c in harness.manifest()["configs"]
                 if c["name"] == "flagship-ouro")
    assert entry["reduced"] == cfg["reduced"]
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert len(json.dumps(entry["source"])) <= 202


def test_a_program_that_drops_the_torso_is_refused_before_it_runs():
    """The parent's loader drops the agent file's unknown ``torso`` key and
    builds the 63 k-parameter policy; here the driver leaves the key out
    of the agent file, to the same effect."""
    import time

    cell = rehearse.tiny_cell(CELL, torso=TINY_TORSO)
    driver = harness.load_driver(cell)
    driver.RESERVED = driver.RESERVED + ("torso",)
    driver.prepare(cell)
    with pytest.raises(SystemExit, match="LoopedTorso_0/w_in"):
        driver.run(cell, seed=3, seconds=0.5, traced=False,
                   t_start=time.time(), peaks=rehearse.FAKE_PEAKS,
                   log=lambda *a: None)


def test_one_side_control_is_judged_by_the_check_itself():
    """``control_one_side.probe`` at rehearsal size: the reference proper
    put in the program's place is ``correct`` with a learner gap of zero,
    and a batch half left out is not."""
    import time

    from benchmarks import control_one_side

    cell = rehearse.tiny_cell(CELL, torso=TINY_TORSO)
    limits = cell["cell"]["limits"] = dict(rehearse.LIMITS)
    driver = harness.load_driver(cell)
    driver.prepare(cell)
    controls = (("sound", {}), ("fault_half_batch", {"half_batch": True}))
    rec = driver.run(
        cell, seed=5, seconds=0.5, traced=False, t_start=time.time(),
        peaks=rehearse.FAKE_PEAKS, log=lambda *a: None,
        probe=lambda r, **kw: control_one_side.probe(
            r, limits, controls=controls, **kw))
    assert rec["correct"] is True, rec["compared"]
    sound, half = rec["probe"]["sound"], rec["probe"]["fault_half_batch"]
    assert sound["correct"] is True, sound["compared"]
    assert sound["compared"]["td_gap"]["value"] == 0.0
    assert sound["compared"]["moment_mid_gap"]["value"] == 0.0
    assert half["correct"] is False
    assert "td_gap" in half["fails"]
    assert set(half["fails"]) <= {"td_gap", "moment_gap", "moment_mid_gap",
                                  "change_gap", "moment2_mid_gap"}
    assert rec["probe"]["control_policy_bfloat16"]["limit"] == \
        limits["policy_action_gap"]
