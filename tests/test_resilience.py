"""Resilience subsystem tests: fault-plan grammar, no-fault bit-identity
of the divergence guard, every rung of the degradation ladder under
injected faults (retry -> prefetcher restart -> pipeline off -> rollback),
watchdog escalation, checksummed checkpoint rotation with resume-auto
fallback, and the SIGTERM -> snapshot -> --resume auto roundtrip.

All marked ``resilience`` — `pytest -m resilience -q` is the standalone
smoke group.
"""
import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from gsc_tpu.agents import Trainer
from gsc_tpu.resilience import (
    FaultPlan,
    PreemptionGuard,
    RetryPolicy,
    TransientDispatchError,
    call_with_retry,
)
from tests.test_agent import make_driver, make_stack

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def _train(episodes=4, fault_plan=None, obs=None, seed=7, **trainer_kw):
    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    t = Trainer(env, driver, agent, seed=seed, obs=obs,
                fault_plan=fault_plan, **trainer_kw)
    state, buffer = t.train(episodes=episodes)
    return t, state, buffer


@pytest.fixture(scope="module")
def reference_run():
    """One faultless default-config run the fault tests compare against —
    retry and prefetcher-restart recoveries must be BIT-invisible in the
    training results."""
    t, state, buffer = _train()
    return state, buffer, t.history


def _assert_matches_reference(reference_run, state, buffer, history):
    s_ref, b_ref, h_ref = reference_run
    _assert_trees_equal(
        (s_ref.actor_params, s_ref.critic_params, s_ref.rng,
         b_ref.data, b_ref.pos, b_ref.size),
        (state.actor_params, state.critic_params, state.rng,
         buffer.data, buffer.pos, buffer.size))
    assert len(history) == len(h_ref)
    for ra, rb in zip(h_ref, history):
        for k in ra:
            if k != "sps":
                assert ra[k] == rb[k], (k, ra[k], rb[k])


# -------------------------------------------------------------- fault plan
def test_fault_plan_grammar_and_fire_once(monkeypatch):
    plan = FaultPlan.parse("prefetch_die@1;nan_grads@3 , slow_episode@2:1.5")
    assert [(s.site, s.episode, s.arg) for s in plan.specs] == [
        ("prefetch_die", 1, None), ("nan_grads", 3, None),
        ("slow_episode", 2, 1.5)]
    # exact-match fire, exactly once
    assert plan.fire("prefetch_die", 0) is None
    spec = plan.fire("prefetch_die", 1)
    assert spec is not None and spec.fired
    assert plan.fire("prefetch_die", 1) is None
    # at_or_after (the checkpoint-site semantics: saves only happen every
    # interval, so an exact key could never land)
    assert plan.fire("nan_grads", 5, at_or_after=True).episode == 3
    assert [s.site for s in plan.unfired()] == ["slow_episode"]

    for bad in ("bogus@1", "nan_grads@x", "nan_grads", "nan_grads@-1",
                "nan_grads@1:z", ""):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)

    monkeypatch.setenv("GSC_FAULT_PLAN", "dispatch_transient@0")
    env_plan = FaultPlan.from_env()
    assert env_plan.specs[0].site == "dispatch_transient"
    # an explicit flag value overrides the env var...
    assert FaultPlan.from_env("nan_grads@2").specs[0].site == "nan_grads"
    # ...and an EXPLICIT empty flag disables injection even under an
    # exported env plan (the clean control leg of a chaos comparison)
    assert FaultPlan.from_env("") is None
    monkeypatch.delenv("GSC_FAULT_PLAN")
    assert FaultPlan.from_env() is None


def test_fault_plan_async_grammar():
    """The fleet sites' key forms: a<actor>:<episode> (actor-keyed),
    v<version> (version-keyed), plain ints for burst-keyed — with
    actor-aware matching and per-site validation errors."""
    plan = FaultPlan.parse("actor_die@a0:3;watcher_stall@a1:4:0.5;"
                           "publish_corrupt@v2;ring_poison@5;"
                           "learner_transient@7")
    assert [s.key for s in plan.specs] == ["a0:3", "a1:4", "v2", "5", "7"]
    assert plan.specs[1].arg == 0.5
    # actor-keyed specs never fire on the wrong actor, even at the right
    # episode — chaos runs must not be racy on thread scheduling
    assert plan.fire("actor_die", 3, actor=1) is None
    spec = plan.fire("actor_die", 3, actor=0)
    assert spec is not None and spec.fired
    assert plan.fire("actor_die", 3, actor=0) is None   # exactly once
    assert plan.fire("publish_corrupt", 2).key == "v2"

    for bad, msg in [("actor_die@3", "actor-keyed"),
                     ("actor_die@a0", "missing episode"),
                     ("actor_die@ax:3", "not an integer"),
                     ("actor_die@a-1:3", ">= 0"),
                     ("watcher_stall@v1", "actor-keyed"),
                     ("publish_corrupt@2", "version"),
                     ("publish_corrupt@vx", "not an integer"),
                     ("learner_transient@x", "burst")]:
        with pytest.raises(ValueError, match=msg):
            FaultPlan.parse(bad)

    # the shared end-of-run check: one structured event per run listing
    # every entry that never fired (serial + replica + async paths all
    # call this same method)
    class Hub:
        def __init__(self):
            self.events = []

        def event(self, name, **kw):
            self.events.append((name, kw))

    hub = Hub()
    un = plan.warn_unfired(hub)
    assert {f"{s.site}@{s.key}" for s in un} == \
        {"watcher_stall@a1:4", "ring_poison@5", "learner_transient@7"}
    assert hub.events[0][0] == "fault_plan_unfired"
    assert hub.events[0][1]["count"] == 3


def test_nan_grads_rolls_back_on_replica_path(tmp_path):
    """train_parallel now wires nan_grads: the poisoned episode is caught
    by the chaos-only host verify, the RollbackGuard restores the last
    verified snapshot, and the run finishes with a finite state."""
    from gsc_tpu.obs import RunObserver

    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    obs = RunObserver(str(tmp_path), run_id="repnan").start()
    t = Trainer(env, driver, agent, seed=0, obs=obs,
                fault_plan=FaultPlan.parse("nan_grads@1"))
    state, buffers = t.train_parallel(episodes=3, num_replicas=2, chunk=2)
    obs.close()
    assert t.completed_episodes == 3
    assert all(np.isfinite(np.asarray(l)).all() for l in
               jax.tree_util.tree_leaves((state.actor_params,
                                          state.critic_params)))
    events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
    recs = [(e["site"], e["action"]) for e in events
            if e["event"] == "recovery"]
    assert ("learner_state", "rollback") in recs
    assert not any(e["event"] == "fault_plan_unfired" for e in events)


def test_call_with_retry_semantics():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientDispatchError("flaky")
        return "ok"

    retries = []
    policy = RetryPolicy(attempts=3, base_s=0.0, cap_s=0.0)
    assert call_with_retry(flaky, policy,
                           on_retry=lambda a, e, d: retries.append(a)) \
        == "ok"
    assert len(calls) == 3 and retries == [1, 2]
    # bounded: persistent transient propagates after `attempts` tries
    calls.clear()
    with pytest.raises(TransientDispatchError):
        call_with_retry(lambda: flaky() if len(calls) < 99 else None,
                        RetryPolicy(attempts=2, base_s=0.0))
    # non-transient errors are never retried
    boom = []

    def hard():
        boom.append(1)
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        call_with_retry(hard, policy)
    assert len(boom) == 1


# ----------------------------------------------------- guard / bit-identity
def test_no_fault_guard_is_bit_identical_and_never_triggers(reference_run):
    """Acceptance bar: with no fault plan the guardrail flag is computed
    (1.0 on every episode) but training output is bit-identical with the
    rollback snapshots disabled entirely — the guard never perturbs the
    math, it only watches it."""
    s_ref, b_ref, h_ref = reference_run
    assert all(row["state_finite"] == 1.0 for row in h_ref)
    t, state, buffer = _train(rollback=False)
    _assert_matches_reference(reference_run, state, buffer, t.history)


def test_nan_poison_rolls_back_and_recovers(tmp_path, reference_run):
    """The nan_grads fault: the poisoned episode drains with a zero
    finite-flag, the trainer restores the last-good snapshot, emits a
    structured recovery event, and the final learner state is finite."""
    from gsc_tpu.obs import RunObserver

    obs = RunObserver(str(tmp_path), run_id="nan").start()
    t, state, buffer = _train(episodes=5,
                              fault_plan=FaultPlan.parse("nan_grads@2"),
                              obs=obs)
    obs.close()
    assert all(np.isfinite(np.asarray(l)).all() for l in
               jax.tree_util.tree_leaves((state.actor_params,
                                          state.critic_params,
                                          state.actor_opt)))
    events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
    recs = [e for e in events if e["event"] == "recovery"]
    assert [(r["site"], r["action"]) for r in recs] == \
        [("learner_state", "rollback")]
    assert recs[0]["episode"] == 2 and recs[0]["fault"] == \
        "non_finite_state"
    # the poisoned episode's event carries the evidence...
    by_ep = {e["episode"]: e for e in events if e["event"] == "episode"}
    assert by_ep[2]["state_finite"] == 0.0
    # ...and the post-rollback episode ran on a finite state again
    assert max(by_ep) == 4 and by_ep[4]["state_finite"] == 1.0
    assert events[-1]["event"] == "run_end"
    assert events[-1]["recoveries"] == 1.0


def test_dispatch_transient_retries_bit_identical(tmp_path, reference_run):
    """An injected transient dispatch failure is retried with backoff and
    leaves NO trace in the training results — only in the recovery
    timeline."""
    from gsc_tpu.obs import RunObserver

    obs = RunObserver(str(tmp_path), run_id="retry").start()
    t, state, buffer = _train(
        fault_plan=FaultPlan.parse("dispatch_transient@1"), obs=obs,
        retry_policy=RetryPolicy(attempts=3, base_s=0.01))
    obs.close()
    _assert_matches_reference(reference_run, state, buffer, t.history)
    events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
    recs = [e for e in events if e["event"] == "recovery"]
    assert [(r["site"], r["action"], r["attempt"]) for r in recs] == \
        [("dispatch", "retry", 1)]


def test_prefetcher_death_restarts_bit_identical(reference_run):
    """A dead producer thread surfaces on the consumer's get; the trainer
    restarts the prefetcher from the episode counter and the re-staged
    sequence is bit-identical to an undisturbed run."""
    t, state, buffer = _train(fault_plan=FaultPlan.parse("prefetch_die@2"))
    _assert_matches_reference(reference_run, state, buffer, t.history)


def test_repeated_pipeline_faults_degrade_to_pipeline_off(tmp_path,
                                                          reference_run):
    """Past pipeline_fault_limit faults the run degrades pipeline->off
    (serial sampling, immediate drains) instead of thrashing restarts —
    and still finishes bit-identical (the pipeline is pure scheduling)."""
    from gsc_tpu.obs import RunObserver

    obs = RunObserver(str(tmp_path), run_id="degrade").start()
    t, state, buffer = _train(
        fault_plan=FaultPlan.parse("prefetch_die@1;prefetch_die@2"),
        obs=obs, pipeline_fault_limit=1)
    obs.close()
    _assert_matches_reference(reference_run, state, buffer, t.history)
    events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
    actions = [(e["site"], e["action"]) for e in events
               if e["event"] == "recovery"]
    assert actions == [("prefetcher", "restart"),
                       ("pipeline", "pipeline_off")]


def test_watchdog_escalation_interrupts_and_restarts(tmp_path,
                                                     reference_run):
    """An artificially slow episode staging trips the watchdog; after the
    escalation budget the watchdog interrupts the prefetcher, the trainer
    restarts it, and the run completes bit-identical."""
    from gsc_tpu.obs import RunObserver

    obs = RunObserver(str(tmp_path), run_id="esc", watchdog_budget_s=0.25,
                      watchdog_escalate=1).start()
    t, state, buffer = _train(
        fault_plan=FaultPlan.parse("slow_episode@2:30"), obs=obs)
    obs.close()
    _assert_matches_reference(reference_run, state, buffer, t.history)
    events = [json.loads(l) for l in open(tmp_path / "events.jsonl")]
    assert [e for e in events if e["event"] == "stall"], \
        "slow staging never tripped the watchdog"
    assert [e for e in events if e["event"] == "escalation"], \
        "watchdog never escalated"
    restarts = [e for e in events if e["event"] == "recovery"
                and e["site"] == "prefetcher"]
    assert restarts and "escalation" in restarts[0]["fault"]


# ------------------------------------------------------- async fleet battery
@pytest.fixture(scope="module")
def astack():
    """One compiled noise-free async stack for the fleet battery (see
    tests/test_async_rl._setup: rings come from a factory because
    replay_ingest donates them; pddpg/state are safely reusable).
    Noise-free (rand_sigma=rand_mu=0) so actor restarts are
    bit-reproducible: scenario and env-reset keys are GLOBAL-episode-
    keyed, and without exploration noise the actor's thread-local rng
    stream is inert."""
    from tests.test_async_rl import _setup
    return _setup(episode_steps=4, rand_sigma=0.0, rand_mu=0.0)


def _collecting(events):
    def on_recovery(episode, site=None, action=None, fault=None,
                    attempt=None, detail=None):
        events.append({"episode": episode, "site": site, "action": action,
                       "fault": fault, "attempt": attempt,
                       "detail": detail})
    return on_recovery


def _ring_finite(buffers):
    return all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(buffers.data)
               if np.issubdtype(np.asarray(l).dtype, np.inexact))


def test_async_actor_restart_bit_identical(astack):
    """actor_die at episode entry: the supervisor restarts the actor from
    its episode counter and the re-staged ring is BIT-identical to an
    undisturbed run (publishing frozen, noise-free, death at the FIRST
    episode so the restarted actor's fresh scratch matches the control's
    — later-episode blocks carry dead padding lanes from the previous
    chunk, a masked-out residue a re-staged scratch can't replay)."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    cfg = AsyncConfig(actor_threads=1, publish_bursts=10**6)

    ref = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                    episode_steps=4, chunk=2, seed=0, cfg=cfg)
    evts = []
    res = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                    episode_steps=4, chunk=2, seed=0, cfg=cfg,
                    fault_plan=FaultPlan.parse("actor_die@a0:0"),
                    on_recovery=_collecting(evts))
    assert res.info["actor_restarts"] == 1
    assert res.info["actors_degraded"] == 0
    assert [(e["site"], e["action"]) for e in evts] == \
        [("actor", "restart")]
    assert evts[0]["fault"] == "FaultInjected" and evts[0]["attempt"] == 1
    assert sorted(r["episode"] for r in res.episodes) == [0, 1, 2]
    _assert_trees_equal(ref.buffers.data, res.buffers.data)
    _assert_trees_equal((ref.buffers.pos, ref.buffers.size),
                        (res.buffers.pos, res.buffers.size))


def test_async_ring_poison_quarantined(astack):
    """A NaN-poisoned block is dropped at the learner's drain boundary
    with an evidence row: the ring never holds a NaN, drain accounting
    still balances, and the run completes."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    evts = []
    res = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                    episode_steps=4, chunk=2, seed=0,
                    cfg=AsyncConfig(actor_threads=1),
                    fault_plan=FaultPlan.parse("ring_poison@1"),
                    on_recovery=_collecting(evts))
    info = res.info
    assert info["blocks_quarantined"] == 1
    assert info["steps_quarantined"] == 2 * 2   # one [B=2, chunk=2] block
    assert info["produced_steps"] == info["ingested_steps"]
    assert info["transitions_lost"] == 0
    assert info["episodes_drained"] == 3
    assert _ring_finite(res.buffers), "a poisoned block reached the ring"
    quar = [e for e in evts if e["site"] == "replay"]
    assert [(e["action"], e["fault"]) for e in quar] == \
        [("quarantine", "non_finite_block")]


def test_async_rollback_then_continue(astack):
    """Burst-keyed nan_grads poisons the learner state; the deferred
    state_finite verdict restores the RollbackGuard's last-verified
    snapshot and the run CONTINUES to a finite final state (and the
    publish gate never let the poisoned version out)."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    evts = []
    res = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=4,
                    episode_steps=4, chunk=2, seed=0,
                    cfg=AsyncConfig(actor_threads=1), rollback=True,
                    fault_plan=FaultPlan.parse("nan_grads@1"),
                    on_recovery=_collecting(evts))
    assert res.info["rollbacks"] == 1
    rb = [e for e in evts if e["site"] == "learner_state"]
    assert [(e["action"], e["fault"]) for e in rb] == \
        [("rollback", "non_finite_state")]
    assert all(np.isfinite(np.asarray(l)).all() for l in
               jax.tree_util.tree_leaves((res.state.actor_params,
                                          res.state.critic_params)))
    assert _ring_finite(res.buffers)
    assert res.info["episodes_drained"] == 4


def test_async_learner_transient_retried(astack):
    """learner_transient raises the retryable class at learn-burst entry;
    the retry layer backs off, re-dispatches, and the run is otherwise
    undisturbed."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    evts = []
    res = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                    episode_steps=4, chunk=2, seed=0,
                    cfg=AsyncConfig(actor_threads=1),
                    fault_plan=FaultPlan.parse("learner_transient@1"),
                    retry_policy=RetryPolicy(attempts=3, base_s=0.01),
                    on_recovery=_collecting(evts))
    retries = [e for e in evts if e["site"] == "learner"]
    assert [(e["action"], e["attempt"]) for e in retries] == \
        [("retry", 1)]
    assert res.info["episodes_drained"] == 3
    assert res.info["transitions_lost"] == 0


def test_async_watcher_stall_skips_adoption(astack):
    """A stalled/failing version poll never kills the actor: the adoption
    is skipped with a recovery row and the episode completes on the
    current weights."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    evts = []
    res = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                    episode_steps=4, chunk=2, seed=0,
                    cfg=AsyncConfig(actor_threads=1),
                    fault_plan=FaultPlan.parse("watcher_stall@a0:1"),
                    on_recovery=_collecting(evts))
    stalls = [e for e in evts if e["site"] == "watcher"]
    assert [(e["action"], e["fault"]) for e in stalls] == \
        [("skip_adopt", "FaultInjected")]
    assert res.info["episodes_drained"] == 3
    assert res.info["actor_restarts"] == 0


def test_async_restart_budget_exhaustion_degrades(astack):
    """Past the per-actor restart budget the fleet degrades to fewer
    actors: the dead actor's episodes are reassigned (episode data is
    GLOBAL-index-keyed, so WHO runs them never changes WHAT they train
    on), the staleness cap is re-derived, and every episode still
    drains."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    evts = []
    # two actors, zero budget: actor 0 dies at its episode 2 and is
    # degraded immediately; actor 1 absorbs the orphans
    res = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=4,
                    episode_steps=4, chunk=2, seed=0,
                    cfg=AsyncConfig(actor_threads=2, restart_budget=0),
                    fault_plan=FaultPlan.parse("actor_die@a0:2"),
                    on_recovery=_collecting(evts))
    assert res.info["actors_degraded"] == 1
    assert res.info["actor_restarts"] == 0
    deg = [e for e in evts if e["action"] == "degrade"]
    assert len(deg) == 1 and "degrades to 1 actor" in deg[0]["detail"]
    assert "staleness cap re-derived" in deg[0]["detail"]
    assert sorted(r["episode"] for r in res.episodes) == [0, 1, 2, 3]
    assert res.info["transitions_lost"] == 0


def test_async_whole_fleet_exhausted_raises(astack):
    """Every actor past its budget with episodes unrun: the run RAISES
    (chained to the actor's error) instead of hanging or silently
    under-running."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    with pytest.raises(RuntimeError, match="exhausted"):
        run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                  episode_steps=4, chunk=2, seed=0,
                  cfg=AsyncConfig(actor_threads=1, restart_budget=0),
                  fault_plan=FaultPlan.parse("actor_die@a0:1"))


def test_async_fault_free_guarded_run_bit_identical(astack):
    """Satellite acceptance: with no fault fired, the guarded stack
    (rollback snapshots + per-block quarantine checks) is BIT-identical
    to the guard-free stack — the guards watch the math, never perturb
    it."""
    from gsc_tpu.parallel.async_rl import AsyncConfig, run_async

    pddpg, state, make_buffers, scenario_fn = astack
    cfg = AsyncConfig(actor_threads=1, publish_bursts=10**6)
    off = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                    episode_steps=4, chunk=2, seed=0, cfg=cfg)
    on = run_async(pddpg, scenario_fn, state, make_buffers(), episodes=3,
                   episode_steps=4, chunk=2, seed=0, cfg=cfg,
                   rollback=True)
    assert on.info["rollbacks"] == 0
    assert on.info["blocks_quarantined"] == 0
    # the ring is the deterministic artifact (the learner STATE depends
    # on how ingests interleave with bursts, same as any two fault-free
    # runs — see test_async_rl.test_async_deterministic_replay)
    _assert_trees_equal(off.buffers.data, on.buffers.data)
    _assert_trees_equal((off.buffers.pos, off.buffers.size),
                        (on.buffers.pos, on.buffers.size))


def test_publisher_finite_gate_and_corrupt_publish(tmp_path):
    """Satellite: the in-process zero-copy publish path is finite-gated
    exactly like the file path — an unverified non-finite publish is
    skipped (no version bump, no delivery), and a publish_corrupt'd
    version is parked by the watcher-side gates on BOTH paths."""
    import jax.numpy as jnp
    from gsc_tpu.serve.fleet import VersionWatcher, WeightPublisher

    class Server:
        policy_version = -1

        def apply_weights(self, leaves, version, fingerprint, meta=None):
            self.leaves, self.policy_version = leaves, version

    # 1) unverified non-finite params never publish
    got = []
    pub = WeightPublisher(subscribers=[lambda rec, p: got.append(rec)])
    assert pub.publish({"w": jnp.asarray([1.0, float("nan")])}) is None
    assert pub.version == 0 and not got
    assert pub.publish({"w": jnp.ones(2)})["version"] == 1
    assert got and got[0]["version"] == 1

    # 2) in-process publish_corrupt: the delivered leaves are poisoned,
    # the watcher's finite gate refuses the version (parked, version
    # unchanged) and a later clean publish is adopted normally
    pub2 = WeightPublisher(
        fault_plan=FaultPlan.parse("publish_corrupt@v1"))
    srv = Server()
    w = VersionWatcher(None, srv, publisher=pub2)
    assert pub2.publish({"w": jnp.ones(2)}, verified=True)["version"] == 1
    assert not w.poll_once()           # gate parks the poisoned version
    assert srv.policy_version == -1
    assert pub2.publish({"w": jnp.full(2, 2.0)},
                        verified=True)["version"] == 2
    assert w.poll_once() and srv.policy_version == 2
    np.testing.assert_array_equal(np.asarray(srv.leaves[0]),
                                  np.full(2, 2.0))
    w.stop()

    # 3) file-path publish_corrupt: the blob's flipped byte fails the
    # manifest fingerprint and the directory watcher parks the version
    pub3 = WeightPublisher(str(tmp_path),
                           fault_plan=FaultPlan.parse("publish_corrupt@v1"))
    srv3 = Server()
    w3 = VersionWatcher(str(tmp_path), srv3)
    assert pub3.publish({"w": np.ones(4, np.float32)},
                        verified=True)["version"] == 1
    assert not w3.poll_once()
    assert srv3.policy_version == -1
    w3.stop()


@pytest.mark.slow
@pytest.mark.skipif(not hasattr(signal, "SIGTERM"), reason="POSIX only")
def test_async_sigterm_resume_auto_roundtrip(tmp_path):
    """Tentpole (d): SIGTERM a live `cli train --async` subprocess — the
    fleet stops its actors, drains fully (the exit JSON carries the
    produced==ingested proof), snapshots, exits 0 — then
    `--async --resume auto` continues with a monotone episode counter."""
    from click.testing import CliRunner

    from gsc_tpu.cli import cli as cli_group
    from gsc_tpu.utils.checkpoint import verify_checkpoint
    from tests.test_agent import write_tiny_configs

    args = write_tiny_configs(tmp_path)
    res = str(tmp_path / "res")
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "gsc_tpu.cli", "train", *args,
         "--episodes", "500", "--replicas", "2", "--async",
         "--async-actors", "2", "--chunk", "3", "--ckpt-interval", "50",
         "--result-dir", res],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 300
        events_path = None
        while time.time() < deadline:
            for root, _, files in os.walk(res):
                if "events.jsonl" in files:
                    p = os.path.join(root, "events.jsonl")
                    if any('"event": "episode"' in l for l in open(p)):
                        events_path = p
                        break
            if events_path or proc.poll() is not None:
                break
            time.sleep(0.25)
        assert proc.poll() is None, proc.communicate()
        assert events_path, "no episode event before deadline"
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, (out, err)
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["status"] == "preempted" and tail["signal"] == "SIGTERM"
    done = tail["episodes_completed"]
    assert done >= 1
    assert verify_checkpoint(tail["checkpoint"]), tail
    # the drain proof rides the exit line: nothing produced was lost
    assert tail["drain"]["produced_steps"] == \
        tail["drain"]["ingested_steps"]
    assert tail["drain"]["transitions_lost"] == 0
    events = [json.loads(l) for l in open(events_path)]
    assert any(e["event"] == "recovery" and e["action"] ==
               "preempt_snapshot" for e in events)

    r = CliRunner().invoke(cli_group, ["train", *args,
                                       "--episodes", str(done + 2),
                                       "--replicas", "2", "--async",
                                       "--async-actors", "2",
                                       "--chunk", "3",
                                       "--resume", "auto",
                                       "--result-dir", res])
    assert r.exit_code == 0, (r.output, r.exception)
    out2 = json.loads(r.output.strip().splitlines()[-1])
    events2 = [json.loads(l) for l in
               open(os.path.join(out2["result_dir"], "events.jsonl"))]
    eps = sorted(e["episode"] for e in events2 if e["event"] == "episode")
    # monotone continuation: exactly the gap episodes, nothing re-run
    # below the snapshot's contiguous drained prefix
    assert eps == [done, done + 1]


# ------------------------------------------------------------- checkpoints
def test_ckpt_meta_tolerates_corrupt_sidecar(tmp_path, caplog):
    """Satellite: a truncated/garbage/non-object .meta.json degrades to {}
    with a warning instead of raising — a half-written sidecar must not
    brick --resume."""
    import logging

    from gsc_tpu.utils.checkpoint import read_checkpoint_meta

    ckpt = str(tmp_path / "ckpt")
    sidecar = ckpt + ".meta.json"
    cases = [b'{"precision": "bf16', b"\xff\xfe\x00garbage", b'"a-string"',
             b"[1, 2]", b""]
    for raw in cases:
        with open(sidecar, "wb") as f:
            f.write(raw)
        with caplog.at_level(logging.WARNING, "gsc_tpu.utils.checkpoint"):
            caplog.clear()
            assert read_checkpoint_meta(ckpt) == {}, raw
        assert any("sidecar" in r.message for r in caplog.records), raw
    os.unlink(sidecar)
    assert read_checkpoint_meta(ckpt) == {}   # absent: silent pre-meta


def test_ckpt_manager_checksum_rotation_and_fallback(tmp_path):
    from gsc_tpu.agents import DDPG
    from gsc_tpu.resilience.ckpt import (CheckpointManager,
                                         corrupt_checkpoint, find_resumable)
    from gsc_tpu.utils.checkpoint import read_checkpoint_meta, \
        verify_checkpoint

    env, agent, topo, traffic = make_stack()
    _, obs0 = env.reset(jax.random.PRNGKey(0), topo, traffic)
    ddpg = DDPG(env, agent)
    state = ddpg.init(jax.random.PRNGKey(1), obs0)
    buf = ddpg.init_buffer(obs0)

    m = CheckpointManager(str(tmp_path / "ckpts"), retain=2,
                          meta={"precision": "f32"})
    for ep in (2, 4, 6):
        path = m.save(state, buf, episode=ep)
        assert path and verify_checkpoint(path)
        assert read_checkpoint_meta(path)["episode"] == ep
    names = {n for n in os.listdir(tmp_path / "ckpts")
             if n.startswith("ep") and not n.endswith(".json")}
    assert names == {"ep00000004", "ep00000006"}   # retention pruned ep2
    pointer = json.load(open(m.pointer_path))
    assert pointer["episode"] == 6

    newest = find_resumable(str(tmp_path))
    assert newest.endswith("ep00000006")
    # resume-auto fallback: a corrupted newest checkpoint fails its
    # checksum and the previous good one wins
    corrupt_checkpoint(newest)
    assert not verify_checkpoint(newest)
    assert find_resumable(str(tmp_path)).endswith("ep00000004")

    # the injected ckpt_corrupt fault is caught by validation and
    # re-saved, with a structured recovery event
    from gsc_tpu.obs import RunObserver
    obs = RunObserver(str(tmp_path / "obs"), run_id="ck").start()
    m2 = CheckpointManager(str(tmp_path / "ckpts2"), retain=2,
                           fault_plan=FaultPlan.parse("ckpt_corrupt@8"),
                           obs=obs)
    path = m2.save(state, buf, episode=8)
    obs.close()
    assert path and verify_checkpoint(path)
    events = [json.loads(l) for l in open(tmp_path / "obs" /
                                          "events.jsonl")]
    recs = [e for e in events if e["event"] == "recovery"]
    assert [(r["site"], r["action"]) for r in recs] == \
        [("checkpoint", "resave")]


def test_cli_periodic_ckpt_and_resume_auto(tmp_path):
    """cli train --ckpt-interval writes checksummed rotating checkpoints;
    a follow-up --resume auto picks the newest valid one and continues
    with a monotone episode counter."""
    from click.testing import CliRunner

    from gsc_tpu.cli import cli as cli_group
    from tests.test_agent import write_tiny_configs

    args = write_tiny_configs(tmp_path)
    res = str(tmp_path / "res")
    r1 = CliRunner().invoke(cli_group, ["train", *args, "--episodes", "4",
                                        "--ckpt-interval", "2",
                                        "--result-dir", res])
    assert r1.exit_code == 0, (r1.output, r1.exception)
    out1 = json.loads(r1.output.strip().splitlines()[-1])
    ckpts = os.path.join(out1["result_dir"], "ckpts")
    assert os.path.exists(os.path.join(ckpts, "last_good.json"))
    assert any(n.startswith("ep") for n in os.listdir(ckpts))

    r2 = CliRunner().invoke(cli_group, ["train", *args, "--episodes", "6",
                                        "--resume", "auto",
                                        "--result-dir", res])
    assert r2.exit_code == 0, (r2.output, r2.exception)
    out2 = json.loads(r2.output.strip().splitlines()[-1])
    events = [json.loads(l) for l in
              open(os.path.join(out2["result_dir"], "events.jsonl"))]
    eps = [e["episode"] for e in events if e["event"] == "episode"]
    # the resumed run continues where the newest valid checkpoint stopped
    assert eps == [4, 5]

    # resume auto with nothing restorable is a clean parameter error
    r3 = CliRunner().invoke(cli_group, ["train", *args, "--episodes", "2",
                                        "--resume", "auto", "--result-dir",
                                        str(tmp_path / "empty")])
    assert r3.exit_code != 0
    assert "resume auto" in r3.output


@pytest.mark.skipif(not hasattr(signal, "SIGTERM"), reason="POSIX only")
def test_sigterm_snapshot_and_resume_auto_roundtrip(tmp_path):
    """Satellite acceptance: SIGTERM a live `cli train` subprocess
    mid-training — the handler drains, writes a checksummed checkpoint,
    exits 0 — then --resume auto continues to completion with the episode
    counter monotone."""
    from click.testing import CliRunner

    from gsc_tpu.cli import cli as cli_group
    from gsc_tpu.utils.checkpoint import verify_checkpoint
    from tests.test_agent import write_tiny_configs

    args = write_tiny_configs(tmp_path)
    res = str(tmp_path / "res")
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               # share the repo compile cache so the subprocess's
               # episode_step compile is a disk hit, not a minute of XLA
               JAX_COMPILATION_CACHE_DIR=os.path.join(REPO, ".jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="1",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    proc = subprocess.Popen(
        [sys.executable, "-m", "gsc_tpu.cli", "train", *args,
         "--episodes", "500", "--ckpt-interval", "50",
         "--result-dir", res],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        # wait until training demonstrably progresses (first episode
        # event drained), then preempt
        deadline = time.time() + 240
        events_path = None
        while time.time() < deadline:
            for root, _, files in os.walk(res):
                if "events.jsonl" in files:
                    p = os.path.join(root, "events.jsonl")
                    if any('"event": "episode"' in l for l in open(p)):
                        events_path = p
                        break
            if events_path or proc.poll() is not None:
                break
            time.sleep(0.25)
        assert proc.poll() is None, proc.communicate()
        assert events_path, "no episode event before deadline"
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, (out, err)
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["status"] == "preempted" and tail["signal"] == "SIGTERM"
    done = tail["episodes_completed"]
    assert done >= 1
    assert verify_checkpoint(tail["checkpoint"]), tail
    # events stream of the killed run records the preemption recovery
    events = [json.loads(l) for l in open(events_path)]
    assert any(e["event"] == "recovery" and e["action"] ==
               "preempt_snapshot" for e in events)

    r = CliRunner().invoke(cli_group, ["train", *args,
                                       "--episodes", str(done + 2),
                                       "--resume", "auto",
                                       "--result-dir", res])
    assert r.exit_code == 0, (r.output, r.exception)
    out2 = json.loads(r.output.strip().splitlines()[-1])
    events2 = [json.loads(l) for l in
               open(os.path.join(out2["result_dir"], "events.jsonl"))]
    eps = [e["episode"] for e in events2 if e["event"] == "episode"]
    # monotone continuation: picks up exactly where the snapshot stopped
    assert eps == [done, done + 1]


# -------------------------------------------------------------- preemption
def test_preemption_guard_flag_and_trainer_stop():
    with PreemptionGuard() as g:
        assert not g.triggered
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not g.triggered and time.time() < deadline:
            time.sleep(0.01)
        assert g.triggered and g.signame == "SIGTERM"
        env, agent, topo, traffic = make_stack()
        driver = make_driver(env, agent, topo, traffic)
        t = Trainer(env, driver, agent, seed=0)
        t.train(episodes=3, preempt=g)
        assert t.preempted and t.completed_episodes == 0
    # handlers restored on exit
    assert signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL,
                                                signal.default_int_handler,
                                                signal.Handlers.SIG_DFL)


def test_prefetcher_interrupt_api():
    env, agent, topo, traffic = make_stack()
    driver = make_driver(env, agent, topo, traffic)
    from gsc_tpu.env.driver import PrefetchInterrupted

    pf = driver.prefetcher(0, 5, False)
    try:
        pf.get(0)
        pf.interrupt("test escalation")
        with pytest.raises(PrefetchInterrupted, match="test escalation"):
            pf.get(1)
    finally:
        pf.close()
