"""The driver hands its seeded learner state to the trainer and keeps no
device copy of it (ISSUE 37): at the recorder's first save no device
array of the seeded weights is alive, the live device bytes are the
trainer's, ``final`` keeps the ring's accounting alone, and the output
check reads what it read when the driver handed device arrays over.

Each run compiles the tiny cell's programs: the module takes a minute."""
import time
import weakref

import jax
import pytest

from benchmarks import rehearse


def nbytes(tree):
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def tiny_run(patch):
    """The tiny cell through the driver's ``run``, with ``patch(driver,
    seen)`` applied to the driver module first; returns (record, seen)."""
    cell = rehearse.tiny_cell()
    cell["cell"]["limits"] = dict(rehearse.LIMITS)
    driver = rehearse.harness.load_driver(cell)
    driver.prepare(cell)
    seen = {}
    patch(driver, seen)
    record = driver.run(cell, seed=11, seconds=0.5, traced=False,
                        t_start=time.time(), peaks=rehearse.FAKE_PEAKS,
                        log=lambda *a: None,
                        probe=lambda record, final, **_: sorted(final))
    return record, seen


def watch_handoff(driver, seen):
    """Weak references to the reference's device weights as the driver
    makes them, and at the recorder's first save which are still alive,
    the live device bytes and what the trainer holds."""
    made = driver.host_init_state

    def host_init_state(seed, state_shape, init_weights):
        def init(*a):
            weights = init_weights(*a)
            seen["refs"] = [weakref.ref(w) for w in weights.values()]
            return weights
        return made(seed, state_shape, init)

    class Recorder(driver.Recorder):
        def save(self, state, buffers, episode, **kw):
            if "alive" not in seen:
                seen["alive"] = [r() for r in seen["refs"]
                                 if r() is not None]
                seen["live"] = driver.device_live_bytes()
                seen["trainer"] = nbytes(state) + nbytes(buffers)
                seen["on_device"] = all(
                    isinstance(x, jax.Array)
                    for x in jax.tree_util.tree_leaves(state))
            return super().save(state, buffers, episode, **kw)

    driver.host_init_state = host_init_state
    driver.Recorder = Recorder


def device_handoff(driver, seen):
    """The parent's path: the seeded state handed over as device arrays."""
    driver.host_init_state = driver.make_init_state


@pytest.fixture(scope="module")
def runs():
    return tiny_run(watch_handoff), tiny_run(device_handoff)


def test_no_device_copy_of_the_seeded_state_at_the_first_save(runs):
    (record, seen), _ = runs
    assert seen["refs"], "the reference made no weights"
    assert seen["alive"] == [], "the driver's device weights outlive set-up"
    assert record["correct"] is True, record["compared"]
    # every live device byte beyond the trainer's learner state and ring
    # is the environment's and the traffic's: at most 0.2 GB
    assert seen["live"] <= seen["trainer"] + 0.2e9
    # the trainer's learner state is device arrays of its own
    assert seen["on_device"]


def test_final_keeps_the_ring_accounting_alone(runs):
    (record, _), _ = runs
    assert record["probe"] == ["capacity", "pos", "size"]


def test_check_reads_what_the_device_handoff_gives(runs):
    (host, _), (device, _) = runs
    assert host["correct"] is device["correct"] is True
    assert host["compared"] == device["compared"]
