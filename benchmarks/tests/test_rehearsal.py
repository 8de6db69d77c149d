"""The command end to end on the CPU at tiny sizes (``rehearse.py``), the
control, and the timed path broken underneath: ``correct`` has to come out
false for each fault the cell can have.

Skips the harness's look for a chip and drives the rest of a run.  Each
run compiles the tiny cell's programs: the module takes a few minutes."""
import jax
import pytest

from benchmarks import check, control, rehearse

LIMITS = rehearse.LIMITS
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def tiny_run(traced=False, probe=None, seed=3):
    cell = rehearse.tiny_cell()
    cell["cell"]["limits"] = dict(LIMITS)
    if probe is None:
        return rehearse.run_once(cell, seed=seed, seconds=0.5, traced=traced)
    driver = rehearse.harness.load_driver(cell)
    driver.prepare(cell)
    import time
    return driver.run(cell, seed=seed, seconds=0.5, traced=False,
                      t_start=time.time(), peaks=rehearse.FAKE_PEAKS,
                      log=lambda *a: None, probe=probe)


@pytest.fixture(scope="module")
def sound():
    return tiny_run(traced=True)


def test_result_line_has_the_contract_keys(sound):
    keys = [k for k in sound if k != "record"]
    assert keys[:5] == KEYS and keys[-1] == "compared"
    assert "breakdown" in keys
    assert sound["correct"] is True, sound["compared"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert set(sound["compared"]) == set(check.NUMBERS)
    for v in sound["compared"].values():
        assert v["limit"] is not None and v["value"] <= v["limit"]
    assert sound["device"]["platform"] == jax.devices()[0].platform
    assert sound["device"]["busy_s"] > 0 and sound["device"]["window_s"] > 0
    assert 1 <= len(sound["breakdown"]["device_ops"]) <= 10
    assert len(sound["breakdown"]["idle_gaps"]) <= 10


def test_traced_run_reports_per_layer_metrics(sound):
    m = sound["metrics"]
    for name in ("compile_s", "window_compiles", "episode_gap_ms",
                 "step_mfu_pct", "device_idle_pct"):
        assert name in m, name
    assert m["window_compiles"]["value"] == 0
    assert m["compile_s"]["value"] > 0
    assert 0 < m["device_idle_pct"]["value"] < 100
    # no chip here, so no memory statistics and no device loops in the
    # trace: the readers that need them return nothing, never 0
    for name in ("peak_hbm_gb", "rollout_ms_per_step", "learn_burst_ms"):
        assert name not in m, name


def test_window_is_whole_episodes(sound):
    rec = sound["record"]
    assert rec["window_episodes"] == len(rec["stamps"]) - rec["warm_episodes"] - 1
    assert rec["closed_at"] - rec["opened"] >= 0.5
    eps = [e["episode"] for e in rec["events"] if e["event"] == "episode"]
    assert eps == list(range(rec["warm_episodes"] + rec["window_episodes"]))


def test_untraced_run_reports_end_to_end_metrics():
    line = tiny_run(traced=False, seed=2**31 + 77)
    assert set(line["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert line["correct"] is True, line["compared"]
    assert "breakdown" not in line


def test_control_and_planted_faults_come_out_not_correct():
    rec = tiny_run(probe=control.probe)
    assert rec["correct"] is True, rec["compared"]
    readings = rec["probe"]

    def fails(numbers):
        return [k for k, v in numbers.items()
                if k in LIMITS and not v <= LIMITS[k]]

    for number in ("policy_action_gap", "td_gap", "moment2_mid_gap"):
        assert number in fails(readings["control_bfloat16"])
    assert fails(readings["fault_half_batch"])
    assert "action_gap" in fails(readings["fault_answer_altered"])
    assert "policy_action_gap" in fails(
        readings["fault_policy_action_altered"])
    assert "return_gap" in fails(readings["fault_answer_altered"])
    assert "reward_gap" in fails(readings["fault_sim_half_rate"])


def _state_unchanged(monkeypatch):
    from gsc_tpu.agents.ddpg import DDPG
    orig = DDPG._learn_burst

    def burst(self, state, sample_fn, constrain=None, steps=None):
        _, metrics = orig(self, state, sample_fn, constrain, steps)
        return state, metrics
    monkeypatch.setattr(DDPG, "_learn_burst", burst)


def _half_batch(monkeypatch):
    from gsc_tpu.parallel.dp import ParallelDDPG
    orig = ParallelDDPG._sample_across

    def sample(self, buffers, key):
        batch = orig(self, buffers, key)
        return jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
    monkeypatch.setattr(ParallelDDPG, "_sample_across", sample)


def _answer_altered(monkeypatch):
    from gsc_tpu.env.env import ServiceCoordEnv
    orig = ServiceCoordEnv.process_action
    monkeypatch.setattr(ServiceCoordEnv, "process_action",
                        lambda self, a: orig(self, a) * 1.001)


def _policy_action_altered(monkeypatch):
    # the policy branch alone: the warm-up's random actions stay as they are
    from gsc_tpu.agents import ddpg
    orig = ddpg.unscale_action
    monkeypatch.setattr(ddpg, "unscale_action",
                        lambda x, *a, **k: orig(x, *a, **k) * 1.01)


def _half_of_the_replicas_left_out(monkeypatch):
    # the one replay write per control step keeps the old rows of the
    # upper half of the replicas: their transitions never reach the ring
    from gsc_tpu.parallel import dp
    orig = dp.buffer_write_lockstep

    def write(data, items, cursor):
        def keep_half(new, old):
            upper = jax.numpy.arange(new.shape[0]) >= new.shape[0] // 2
            return jax.numpy.where(
                upper.reshape((-1,) + (1,) * (new.ndim - 1)), old, new)
        return jax.tree_util.tree_map(keep_half, orig(data, items, cursor),
                                      data)
    monkeypatch.setattr(dp, "buffer_write_lockstep", write)


def _ring_cursors_not_advanced(monkeypatch):
    # the rings' size and cursor stand still for the upper half
    from gsc_tpu.parallel import dp
    orig = dp.buffer_advance

    def advance(buf, data, n):
        new = orig(buf, data, n)
        upper = jax.numpy.arange(new.pos.shape[0]) >= new.pos.shape[0] // 2
        return new.replace(pos=jax.numpy.where(upper, buf.pos, new.pos),
                           size=jax.numpy.where(upper, buf.size, new.size))
    monkeypatch.setattr(dp, "buffer_advance", advance)


def _half_the_substeps(monkeypatch):
    from gsc_tpu.config.schema import SimConfig
    monkeypatch.setattr(SimConfig, "substeps_per_run",
                        property(lambda self: 50))


def _reward_altered(monkeypatch):
    from gsc_tpu.env import env as env_mod
    orig = env_mod.compute_reward

    def reward(*a, **k):
        r, ewma, info = orig(*a, **k)
        return r + 0.01, ewma, info
    monkeypatch.setattr(env_mod, "compute_reward", reward)


@pytest.mark.parametrize("plant,caught_by", [
    (_half_the_substeps, "reward_gap"),
    (_reward_altered, "reward_gap"),
    (_state_unchanged, "change_gap"),
    (_half_batch, "td_gap"),
    (_answer_altered, "action_gap"),
    (_policy_action_altered, "policy_action_gap"),
    (_half_of_the_replicas_left_out, "return_gap"),
    (_ring_cursors_not_advanced, "ring_rows_off"),
])
def test_broken_timed_path_is_not_correct(monkeypatch, plant, caught_by):
    plant(monkeypatch)
    line = tiny_run()
    assert line["correct"] is False
    c = line["compared"][caught_by]
    assert not c["value"] <= c["limit"], line["compared"]
