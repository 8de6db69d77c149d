"""Window arithmetic on a fake loop that polls ``triggered`` the way
``Trainer.train_parallel`` does: at the top of every episode, after the
previous episode's drain."""
import pytest

from benchmarks.harness import Window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def fake_loop(window, clock, episode_s, max_episodes=50):
    """for ep in range(...): if preempt.triggered: break; run; drain."""
    ran = 0
    for _ in range(max_episodes):
        if window.triggered:
            break
        clock.t += episode_s      # dispatch + synchronous drain
        ran += 1
    return ran


@pytest.mark.parametrize("seconds,episode_s,warm,want", [
    (30.0, 24.9, 1, 2),     # the flagship: closes at the second boundary
    (26.0, 24.9, 1, 2),
    (49.0, 24.9, 1, 2),
    (50.0, 24.9, 1, 3),
    (10.0, 4.0, 4, 3),      # cut episodes: four warm-up episodes first
    (0.0, 5.0, 1, 0),       # opens and closes at the same boundary
])
def test_whole_episodes_only(seconds, episode_s, warm, want):
    clock = FakeClock()
    w = Window(seconds, warm, clock=clock)
    ran = fake_loop(w, clock, episode_s)
    assert ran == warm + want
    assert w.episodes == want
    assert w.closed and w.triggered          # stays stopped once closed
    span = w.closed_at - w.opened
    assert span == pytest.approx(want * episode_s)
    # the episode in flight always finishes: overshoot under one episode
    assert seconds <= span or want == 0
    assert span - seconds < episode_s or want == 0
    # stamps are the boundaries, one per poll, set-up's included
    assert len(w.stamps) == warm + want + 1
    assert w.boundaries() == w.stamps[warm:]


def test_boundary_hook_runs_after_the_stamp():
    clock = FakeClock()
    seen = []

    def hook(k, now):
        seen.append((k, now))
        clock.t += 1.5          # what stopping a trace costs

    w = Window(6.0, 1, clock=clock, on_boundary=hook)
    fake_loop(w, clock, 5.0)
    # the hook's cost falls into the episode that follows the boundary
    assert [k for k, _ in seen] == [0, 1]
    assert w.stamps[1] == seen[0][1] and w.stamps[2] == seen[1][1]
    assert w.stamps[2] - w.stamps[1] == 6.5 and w.episodes == 1


def test_traced_window_is_a_fixed_number_of_episodes():
    clock = FakeClock()
    w = Window(1000.0, 1, clock=clock, episodes=2)
    assert fake_loop(w, clock, 5.0) == 3 and w.episodes == 2
    clock = FakeClock()
    w = Window(0.0, 2, clock=clock, episodes=2)
    assert fake_loop(w, clock, 5.0) == 4 and w.episodes == 2
