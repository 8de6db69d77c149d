"""Serving-fleet tests (gsc_tpu.serve.fleet + the continuous batcher
mode): continuous-vs-deadline bit-identity for a serial client, backlog
folding, the completion-stamp-before-event contract, weight publish/
watch/hot-swap roundtrips (including corrupt-artifact rejection), swap
atomicity against per-version single-shot servers, ArtifactCache.prune
retention, and FleetDispatcher routing/brownout.

Most tests drive numpy-backed batchers (no jax compile); the learned-tier
hot-swap tests share one compiled module fixture."""
import json
import os
import threading
import time

import numpy as np
import pytest

from gsc_tpu.obs.hub import MetricsHub
from gsc_tpu.obs.sinks import ListSink
from gsc_tpu.serve import (ArtifactCache, FleetDispatcher, MicroBatcher,
                           ObsTemplate, PolicyServer, SPRFallbackPolicy,
                           ServeError, ServeFuture, VersionWatcher,
                           WeightPublisher, params_fingerprint)
from gsc_tpu.serve.batcher import _STOP  # noqa: F401 - sanity import
from gsc_tpu.serve.fleet import load_version, read_latest

pytestmark = pytest.mark.fleet


def _obs(value=0.0, dim=3):
    return np.full(dim, value, np.float32)


def _echo_run(leaves, k, bucket):
    """Answer = 2x the request's first leaf — input-dependent, so
    bit-identity comparisons across modes are meaningful."""
    return np.asarray(leaves[0], np.float32) * 2.0


# --------------------------------------------------- completion-stamp race
def test_completion_stamp_written_before_event_set():
    """Regression for the ServeFuture race: ``t_completed`` (and the
    policy version) must be readable the instant ``done()`` flips — a
    waiter or a racing tracer-record build must never observe a done
    future with ``t_completed=None``."""
    t = ObsTemplate(_obs())
    mb = MicroBatcher(_echo_run, t, buckets=(1,),
                      version_provider=lambda: 7)
    fut = ServeFuture()
    fut.t_admitted = time.perf_counter()
    seen = {}
    orig_set = fut._event.set

    def checked_set():
        seen["t_completed"] = fut.t_completed
        seen["policy_version"] = fut.policy_version
        orig_set()

    fut._event.set = checked_set
    mb._flush([(fut, t.flatten(_obs(1.5)))])
    np.testing.assert_array_equal(fut.result(5), _obs(3.0))
    assert seen["t_completed"] is not None, \
        "t_completed stamped AFTER the event was set"
    assert seen["policy_version"] == 7
    # the error path honors the same contract: version AND completion
    # stamp readable before the event fires
    def boom(leaves, k, bucket):
        raise RuntimeError("device on fire")
    mb2 = MicroBatcher(boom, t, buckets=(1,), version_provider=lambda: 9)
    fut2 = ServeFuture()
    fut2.t_admitted = time.perf_counter()
    seen2 = {}
    orig_set2 = fut2._event.set

    def checked_set2():
        seen2["t_completed"] = fut2.t_completed
        orig_set2()

    fut2._event.set = checked_set2
    mb2._flush([(fut2, t.flatten(_obs()))])
    with pytest.raises(ServeError):
        fut2.result(5)
    assert fut2.policy_version == 9
    assert seen2["t_completed"] is not None, \
        "errored future exposed t_completed=None after done()"


# ------------------------------------------------------ continuous batching
def test_continuous_serial_client_bit_identical_to_deadline():
    """One serial client: continuous mode must produce the same device
    calls (bucket-1, one per request) and bit-identical answers as the
    deadline batcher — the disciplines differ only in scheduling."""
    t = ObsTemplate(_obs())
    results = {}
    for mode in ("deadline", "continuous"):
        calls = []

        def run(leaves, k, bucket, _calls=calls):
            _calls.append((k, bucket))
            return _echo_run(leaves, k, bucket)

        mb = MicroBatcher(run, t, buckets=(1, 4), deadline_ms=5.0,
                          mode=mode).start()
        try:
            outs = [np.asarray(mb.submit(_obs(float(i))).result(30))
                    for i in range(6)]
        finally:
            mb.stop()
        results[mode] = (calls, outs)
    assert results["deadline"][0] == results["continuous"][0] \
        == [(1, 1)] * 6
    for a, b in zip(results["deadline"][1], results["continuous"][1]):
        np.testing.assert_array_equal(a, b)


def test_continuous_backlog_folds_while_in_flight():
    """Requests arriving during an in-flight device call become the next
    batch: 1 + 8 requests against a slow backend must fold into a few
    large flushes, never nine bucket-1 calls — and a lone request
    dispatches immediately instead of waiting any deadline out."""
    t = ObsTemplate(_obs())
    calls = []

    def slow_run(leaves, k, bucket):
        calls.append((k, bucket))
        time.sleep(0.02)
        return np.zeros((bucket, 3), np.float32)

    # deadline_ms huge: if continuous mode consulted it, this test would
    # take 9 x 5s; it must finish in a few device calls' wall
    mb = MicroBatcher(slow_run, t, buckets=(1, 8), deadline_ms=5000.0,
                      mode="continuous").start()
    try:
        t0 = time.perf_counter()
        futs = [mb.submit(_obs()) for _ in range(9)]
        for f in futs:
            f.result(30)
        wall = time.perf_counter() - t0
    finally:
        mb.stop()
    assert sum(k for k, _ in calls) == 9
    assert len(calls) <= 4, f"backlog served as too many flushes: {calls}"
    assert wall < 2.0, f"continuous mode waited a deadline out: {wall}s"


def test_continuous_stop_drains_then_rejects():
    t = ObsTemplate(_obs())

    def slow_run(leaves, k, bucket):
        time.sleep(0.01)
        return np.zeros((bucket, 3), np.float32)

    mb = MicroBatcher(slow_run, t, buckets=(1, 4),
                      mode="continuous").start()
    futs = [mb.submit(_obs()) for _ in range(5)]
    mb.stop()
    for f in futs:           # queued-before-stop requests are answered
        assert f.result(5).shape == (3,)
    with pytest.raises(ServeError, match="stopping"):
        mb.submit(_obs())


def test_continuous_overload_never_wedges():
    """Deadlock regression: a tiny bounded queue under more clients than
    capacity exercises the dispatcher-publishes-_FREE-into-a-full-queue
    window — every accepted request must still complete (backpressure
    rejections are fine; a hang is not)."""
    t = ObsTemplate(_obs())

    def slow(leaves, k, bucket):
        time.sleep(0.002)
        return np.zeros((bucket, 3), np.float32)

    mb = MicroBatcher(slow, t, buckets=(1, 2), deadline_ms=1.0,
                      mode="continuous", max_queue=4).start()
    failures = []
    served = []

    def client(n):
        for _ in range(n):
            try:
                fut = mb.submit(_obs())
            except ServeError:
                continue          # queue-full backpressure: acceptable
            try:
                fut.result(15)
                served.append(1)
            except Exception as e:  # noqa: BLE001 - recorded for assert
                failures.append(e)

    threads = [threading.Thread(target=client, args=(25,))
               for _ in range(6)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    alive = [th for th in threads if th.is_alive()]
    try:
        assert not alive, "clients wedged — continuous mode deadlocked"
        assert not failures, failures[:3]
        assert served, "every request rejected — no backpressure test"
    finally:
        mb.stop()


def test_continuous_backpressure_and_honest_depth():
    """max_queue must keep biting in continuous mode: the consumer
    drains the raw queue into its pending list, so the cap is enforced
    on accepted-not-yet-dispatched requests — and queue_depth reports
    that same backlog (the routing/brownout signal), not the drained
    queue's ~0."""
    t = ObsTemplate(_obs())
    release = threading.Event()

    def gated(leaves, k, bucket):
        release.wait(20)
        return np.zeros((bucket, 3), np.float32)

    mb = MicroBatcher(gated, t, buckets=(1, 2), deadline_ms=1.0,
                      mode="continuous", max_queue=6).start()
    try:
        futs = [mb.submit(_obs()) for _ in range(6)]
        # 1-2 requests are dispatching (stuck in the gated call), the
        # rest are backlog — depth must report them even though the
        # consumer has drained the raw queue
        time.sleep(0.05)
        assert mb.queue_depth >= 3, mb.queue_depth
        with pytest.raises(ServeError, match="queue full"):
            for _ in range(8):   # cap = accepted-not-dispatched
                mb.submit(_obs())
    finally:
        release.set()
        for f in futs:
            f.result(30)
        mb.stop()
    assert mb.queue_depth == 0


def test_batcher_rejects_unknown_mode():
    with pytest.raises(ValueError, match="mode"):
        MicroBatcher(_echo_run, ObsTemplate(_obs()), mode="sometimes")


def test_worker_tagged_metrics_and_version_stamp():
    """With a worker id, the queue-depth gauge and per-worker counters
    land tagged (N workers share one hub without colliding), and every
    flush stamps the provider's current version on its futures."""
    hub = MetricsHub()
    t = ObsTemplate(_obs())
    version = {"v": 3}
    mb = MicroBatcher(_echo_run, t, buckets=(1,), deadline_ms=1.0,
                      hub=hub, worker="w7",
                      version_provider=lambda: version["v"]).start()
    try:
        f1 = mb.submit(_obs())
        f1.result(30)
        version["v"] = 4
        f2 = mb.submit(_obs())
        f2.result(30)
    finally:
        mb.stop()
    assert (f1.policy_version, f2.policy_version) == (3, 4)
    assert hub.get_counter("serve_requests_total", worker="w7") == 2
    assert hub.get_counter("serve_batches_total", worker="w7") == 2
    assert hub.get_counter("serve_requests_total") == 2   # fleet aggregate
    assert hub.get_gauge("serve_queue_depth", worker="w7") == 0
    assert hub.get_gauge("serve_queue_depth") is None     # never untagged


# ------------------------------------------------------- publisher / watcher
def _params(scale=1.0):
    return {"dense": {"kernel": np.full((4, 2), scale, np.float32),
                      "bias": np.arange(2, dtype=np.float32) * scale}}


def test_publisher_versions_fingerprints_and_retention(tmp_path):
    pub = WeightPublisher(str(tmp_path), keep_versions=2)
    recs = [pub.publish(_params(float(i))) for i in range(1, 6)]
    assert [r["version"] for r in recs] == [1, 2, 3, 4, 5]
    # identical content republished -> same fingerprint, new version
    again = pub.publish(_params(5.0))
    assert again["version"] == 6
    assert again["fingerprint"] == recs[-1]["fingerprint"]
    assert len({r["fingerprint"] for r in recs}) == 5
    # retention: only the newest keep_versions survive on disk
    names = sorted(os.listdir(str(tmp_path)))
    assert names == ["latest.json", "v00005.json", "v00005.npz",
                     "v00006.json", "v00006.npz"]
    latest = read_latest(str(tmp_path))
    assert latest["version"] == 6
    leaves = load_version(str(tmp_path), latest)
    assert params_fingerprint(leaves) == again["fingerprint"]
    # a new publisher over the same dir continues the numbering
    pub2 = WeightPublisher(str(tmp_path), keep_versions=2)
    assert pub2.publish(_params())["version"] == 7


def test_read_latest_tolerates_missing_and_torn(tmp_path):
    assert read_latest(str(tmp_path)) is None
    with open(os.path.join(str(tmp_path), "latest.json"), "w") as f:
        f.write('{"version": ')
    assert read_latest(str(tmp_path)) is None
    with open(os.path.join(str(tmp_path), "latest.json"), "w") as f:
        json.dump({"not": "a weights record"}, f)
    assert read_latest(str(tmp_path)) is None


def test_load_version_rejects_corrupt_and_mismatched(tmp_path):
    pub = WeightPublisher(str(tmp_path))
    rec = pub.publish(_params())
    # truncated blob
    blob = os.path.join(str(tmp_path), rec["blob"])
    with open(blob, "wb") as f:
        f.write(b"\x00not-an-npz")
    with pytest.raises(ValueError, match="unreadable"):
        load_version(str(tmp_path), rec)
    # content swapped under the manifest: fingerprint must catch it
    rec2 = pub.publish(_params(2.0))
    import shutil
    shutil.copy(os.path.join(str(tmp_path), rec2["blob"]), blob)
    with pytest.raises(ValueError, match="fingerprint|signature"):
        load_version(str(tmp_path), rec)


class _SwapServer:
    """Duck-typed server for watcher tests: records applied swaps."""

    def __init__(self):
        self.policy_version = 0
        self.applied = []

    def apply_weights(self, leaves, version, fingerprint, meta=None):
        self.applied.append((version, fingerprint))
        self.policy_version = version


def test_version_watcher_applies_once_retries_bounded(tmp_path):
    pub = WeightPublisher(str(tmp_path))
    srv = _SwapServer()
    watcher = VersionWatcher(str(tmp_path), srv, hub=MetricsHub(),
                             max_retries=2)
    assert watcher.poll_once() is False          # nothing published
    rec = pub.publish(_params())
    assert watcher.poll_once() is True
    assert watcher.poll_once() is False          # same version: no re-swap
    assert srv.applied == [(1, rec["fingerprint"])]
    # corrupt the next version's blob: skipped loudly with a BOUNDED
    # retry budget (a transient NFS read must get another chance; a
    # genuinely bad artifact must not be re-logged every poll forever)
    rec2 = pub.publish(_params(2.0))
    blob2 = os.path.join(str(tmp_path), rec2["blob"])
    good_bytes = open(blob2, "rb").read()
    with open(blob2, "wb") as f:
        f.write(b"garbage")
    hub = watcher.hub
    for _ in range(4):
        assert watcher.poll_once() is False
    assert hub.get_counter("serve_swap_failed_total") == 2  # parked at max
    assert srv.policy_version == 1
    # a good NEWER version recovers
    rec3 = pub.publish(_params(3.0))
    assert watcher.poll_once() is True
    assert srv.policy_version == 3 and srv.applied[-1][0] == 3
    # transient failure recovers WITHIN the retry budget: corrupt blob
    # fixed between polls swaps on the retry
    rec4 = pub.publish(_params(4.0))
    blob4 = os.path.join(str(tmp_path), rec4["blob"])
    real = open(blob4, "rb").read()
    with open(blob4, "wb") as f:
        f.write(b"half-written")
    assert watcher.poll_once() is False
    with open(blob4, "wb") as f:
        f.write(real)
    assert watcher.poll_once() is True
    assert srv.policy_version == 4
    assert isinstance(good_bytes, bytes)


def test_train_parallel_publisher_feeds_version_watcher(tmp_path):
    """ROADMAP item 3's last leftover: the VMAPPED (replica-parallel)
    trainer publishes its host-gathered actor params every
    publish_interval episodes, and a VersionWatcher adopts exactly the
    trainer's final state — the flagship learner can feed the serving
    fleet, not just the single-env loop."""
    import dataclasses

    import jax

    import __graft_entry__ as ge
    from gsc_tpu.agents.trainer import Trainer
    from gsc_tpu.config.schema import SchedulerConfig
    from gsc_tpu.env.driver import EpisodeDriver
    from gsc_tpu.topology.compiler import compile_topology
    from gsc_tpu.topology.synthetic import triangle

    env, agent, _, _ = ge._flagship(max_nodes=8, max_edges=8,
                                    episode_steps=2, max_flows=32)
    agent = dataclasses.replace(agent, nb_steps_warmup_critic=2)
    env.agent = agent
    tA = compile_topology(triangle(), max_nodes=8, max_edges=8)
    sched = SchedulerConfig(training_network_files=("a.graphml",),
                            inference_network="a.graphml", period=1)
    driver = EpisodeDriver(sched, env.sim_cfg, env.service, 2,
                           max_nodes=8, max_edges=8, topologies=[tA],
                           inference_topology=tA)
    pub = WeightPublisher(str(tmp_path))
    trainer = Trainer(env, driver, agent, seed=0)
    state, _ = trainer.train_parallel(2, num_replicas=2, chunk=2,
                                      publisher=pub, publish_interval=1)
    assert pub.version == 2               # one publish per episode
    srv = _SwapServer()
    watcher = VersionWatcher(str(tmp_path), srv, hub=MetricsHub())
    assert watcher.poll_once() is True
    version, fingerprint = srv.applied[-1]
    assert version == 2 and srv.policy_version == 2
    # the adopted version IS the trainer's returned (host-layout) state
    leaves = [np.asarray(l) for l in
              jax.tree_util.tree_leaves(state.actor_params)]
    assert fingerprint == params_fingerprint(leaves)
    assert all(np.isfinite(l).all() for l in leaves)
    # manifests record the publishing episode
    assert read_latest(str(tmp_path))["meta"]["episode"] == 2


# ---------------------------------------------------------- cache prune GC
def _store_entry(cache, i):
    material = {"format": 1, "ckpt_fingerprint": f"fp{i}", "batch": 1}
    cache.store(material, b"blob-%d" % i)
    return cache.key_of(material), material


def test_cache_prune_retention_protection_and_half_entries(tmp_path):
    cache = ArtifactCache(str(tmp_path))
    keys = []
    for i in range(5):
        key, material = _store_entry(cache, i)
        keys.append((key, material))
        past = time.time() - (5 - i) * 100   # distinct, ordered mtimes
        for suffix in (".stablehlo", ".json"):
            os.utime(os.path.join(str(tmp_path), key + suffix),
                     (past, past))
    # a fresh process (empty active set) would keep only the 2 newest
    fresh = ArtifactCache(str(tmp_path))
    # ...but loading an OLD entry marks it active: prune must keep it
    assert fresh.load(keys[0][1]) == b"blob-0"
    pruned = fresh.prune(keep_latest=2)
    left = {os.path.splitext(p)[0] for p in os.listdir(str(tmp_path))}
    assert keys[0][0] in left          # loaded entry survives
    assert keys[3][0] in left and keys[4][0] in left   # newest two
    assert set(pruned) == {keys[1][0], keys[2][0]}
    # half-entries are collectable: blob without meta (torn write)
    orphan = os.path.join(str(tmp_path), "f" * 40 + ".stablehlo")
    with open(orphan, "wb") as f:
        f.write(b"torn")
    past = time.time() - 9999
    os.utime(orphan, (past, past))
    pruned2 = fresh.prune(keep_latest=2)
    assert "f" * 40 in pruned2 and not os.path.exists(orphan)
    # the writer's own entries are always protected
    cache2 = ArtifactCache(str(tmp_path))
    key_new, _ = _store_entry(cache2, 99)
    assert key_new not in cache2.prune(keep_latest=0)
    assert os.path.exists(os.path.join(str(tmp_path),
                                       key_new + ".stablehlo"))
    with pytest.raises(ValueError):
        cache2.prune(keep_latest=-1)


def test_publisher_prunes_artifact_cache(tmp_path):
    cache = ArtifactCache(str(tmp_path / "cache"))
    stale_keys = []
    for i in range(4):
        # stale entries from earlier server generations (not active in
        # THIS cache object — simulate a fresh publisher process)
        key, _ = _store_entry(cache, i)
        stale_keys.append(key)
        past = time.time() - (9 - i) * 100
        for suffix in (".stablehlo", ".json"):
            os.utime(os.path.join(str(tmp_path / "cache"), key + suffix),
                     (past, past))
    cache._active.clear()
    pub = WeightPublisher(str(tmp_path / "weights"), artifact_cache=cache,
                          artifact_keep=2)
    pub.publish(_params())
    left = {os.path.splitext(p)[0]
            for p in os.listdir(str(tmp_path / "cache"))}
    assert left == set(stale_keys[-2:])


# ------------------------------------------------------- hot-swap atomicity
def test_spr_tier_swap_stream_matches_stamped_version(tmp_path):
    """A fixed request stream across K hot-swaps: every answer must be
    bit-identical to what a single-shot server pinned at the answer's
    STAMPED version returns — a torn batch mixing versions would stamp
    one version and answer with another."""
    from gsc_tpu.config.schema import EnvLimits
    from tests.test_agent import line_topo, make_stack

    env, agent, topo, traffic = make_stack()
    t = line_topo()
    import jax
    _, obs0 = env.reset(jax.random.PRNGKey(0), topo, traffic)

    hub = MetricsHub()
    sink = ListSink()
    hub.add_sink(sink)
    srv = PolicyServer(fallback=SPRFallbackPolicy(t, env.limits, obs0),
                       buckets=(1, 4), deadline_ms=1.0, hub=hub,
                       mode="continuous",
                       hot_swap_dir=str(tmp_path), swap_poll_s=60.0)
    srv.start()
    try:
        base_action = np.asarray(srv.fallback.action)
        # K published versions, each a recognizable scaled action
        versions = {0: base_action}
        pub = WeightPublisher(str(tmp_path), hub=hub)
        for v in (1, 2, 3):
            versions[v] = (base_action * (v + 1)).astype(base_action.dtype)
            pub.publish([versions[v]])
        watcher = srv.watcher

        answers = []
        lock = threading.Lock()

        def client(n):
            for _ in range(n):
                fut = srv.submit(obs0)
                out = np.asarray(fut.result(30))
                with lock:
                    answers.append((fut.policy_version, out))

        threads = [threading.Thread(target=client, args=(10,))
                   for _ in range(3)]
        for th in threads:
            th.start()
        # fire the swaps while the stream runs (poll_once applies the
        # newest version; repeated polls walk through publishes as they
        # appear — here all three land as one jump, so republish to
        # step versions under fire)
        for _ in range(40):
            watcher.poll_once()
            time.sleep(0.001)
        for th in threads:
            th.join()
    finally:
        srv.close()
    assert len(answers) == 30
    swapped_to = {v for v, _ in answers}
    for v, out in answers:
        np.testing.assert_array_equal(
            out, versions[v],
            err_msg=f"answer stamped v{v} does not match that version's "
                    "single-shot action — a batch mixed versions")
    # zero drops/errors, swap events recorded with in-flight counts
    swaps = sink.of_kind("weight_swap")
    assert srv.policy_version == 3 and any(s["version"] == 3 for s in swaps)
    assert all(s["weights_applied"] for s in swaps)
    assert hub.get_counter("serve_errors_total") == 0
    assert hub.get_counter("serve_rejected_total", reason="queue_full") == 0
    assert isinstance(swapped_to, set)


@pytest.fixture(scope="module")
def learned():
    """One tiny compiled learned-tier stack shared by the module."""
    import jax

    from gsc_tpu.agents import DDPG
    from tests.test_agent import make_stack

    env, agent, topo, traffic = make_stack()
    ddpg = DDPG(env, agent)
    _, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    state = ddpg.init(jax.random.PRNGKey(2), obs)
    return env, agent, ddpg, obs, state


def _perturbed(params, eps):
    import jax
    return jax.tree_util.tree_map(
        lambda x: x + np.asarray(eps, np.asarray(x).dtype)
        if np.issubdtype(np.asarray(x).dtype, np.floating) else x, params)


def test_learned_tier_swap_bit_identical_to_single_shot(learned, tmp_path):
    """Learned tier: serve under v0, hot-swap to v1 (genuinely different
    weights), and compare each phase's answers bit-for-bit against
    fresh single-version servers — the compiled buckets must run the
    swapped params exactly, with zero requests dropped."""
    import jax

    from gsc_tpu.serve import GreedyServePolicy

    env, agent, ddpg, obs, state = learned
    p0 = state.actor_params
    p1 = _perturbed(p0, 1e-3)
    policy = GreedyServePolicy(ddpg, obs)
    kwargs = dict(buckets=(1, 2), deadline_ms=1.0,
                  precision=agent.precision,
                  graph_mode=agent.graph_mode)
    cache = ArtifactCache(str(tmp_path / "cache"))

    pub = WeightPublisher(str(tmp_path / "weights"))
    srv = PolicyServer(policy=policy, params=p0, cache=cache,
                       fingerprint="fp-v0", mode="continuous",
                       hot_swap_dir=str(tmp_path / "weights"),
                       swap_poll_s=60.0, **kwargs).start()
    try:
        a_v0 = np.asarray(srv.submit_sync(obs, timeout=60))
        assert srv.policy_version == 0
        pub.publish(jax.device_get(p1), meta={"episode": 7})
        assert srv.watcher.poll_once() is True
        assert srv.policy_version == 1
        a_v1 = np.asarray(srv.submit_sync(obs, timeout=60))
    finally:
        srv.close()

    one0 = PolicyServer(policy=policy, params=p0, cache=cache,
                        fingerprint="fp-v0", **kwargs).start()
    try:
        want0 = np.asarray(one0.submit_sync(obs, timeout=60))
    finally:
        one0.close()
    one1 = PolicyServer(policy=policy, params=p1, cache=cache,
                        fingerprint="fp-v1", **kwargs).start()
    try:
        want1 = np.asarray(one1.submit_sync(obs, timeout=60))
    finally:
        one1.close()
    np.testing.assert_array_equal(a_v0, want0)
    np.testing.assert_array_equal(a_v1, want1)
    assert not np.array_equal(want0, want1), \
        "perturbed params answered identically — the swap test is vacuous"


def test_learned_tier_rejects_mismatched_swap(learned, tmp_path):
    """A published artifact whose leaves don't fit the compiled buckets
    must be rejected with the served weights untouched."""
    import jax

    from gsc_tpu.serve import GreedyServePolicy

    env, agent, ddpg, obs, state = learned
    policy = GreedyServePolicy(ddpg, obs)
    srv = PolicyServer(policy=policy, params=state.actor_params,
                       buckets=(1,), deadline_ms=1.0,
                       cache=ArtifactCache(str(tmp_path / "cache")),
                       fingerprint="fp-v0",
                       precision=agent.precision,
                       graph_mode=agent.graph_mode,
                       hot_swap_dir=str(tmp_path / "w"),
                       swap_poll_s=60.0).start()
    try:
        before = np.asarray(srv.submit_sync(obs, timeout=60))
        pub = WeightPublisher(str(tmp_path / "w"))
        pub.publish([np.zeros((3, 3), np.float32)])   # wrong signature
        assert srv.watcher.poll_once() is False
        assert srv.policy_version == 0
        after = np.asarray(srv.submit_sync(obs, timeout=60))
        np.testing.assert_array_equal(before, after)
        # a well-formed follow-up version still lands
        pub.publish(jax.device_get(state.actor_params))
        assert srv.watcher.poll_once() is True
        assert srv.policy_version == 2
    finally:
        srv.close()


# -------------------------------------------------------- fleet dispatcher
class _StubWorker:
    def __init__(self, name, depth=0, burn=None, full=False):
        self.worker = name
        self._depth = depth
        self.full = full
        self.submitted = []
        self._completed = 0
        self.policy_version = 0
        self.swaps = 0
        self._occupancy = {}
        self.slo_engine = None
        if burn is not None:
            class _Engine:
                def snapshot(self, _burn=burn):
                    return {"burn_rate": _burn}
            self.slo_engine = _Engine()

    @property
    def queue_depth(self):
        return self._depth

    def submit(self, obs):
        if self.full:
            raise ServeError("serve queue full")
        self.submitted.append(obs)
        fut = ServeFuture()
        fut._result = np.zeros(1, np.float32)
        fut.t_completed = time.perf_counter()
        fut._event.set()
        return fut


def test_dispatcher_routes_least_queue_depth():
    w0, w1, w2 = (_StubWorker("w0", 3), _StubWorker("w1", 1),
                  _StubWorker("w2", 2))
    fleet = FleetDispatcher([w0, w1, w2], brownout_burn=None)
    for _ in range(3):
        fleet.submit(_obs())
    assert (len(w0.submitted), len(w1.submitted), len(w2.submitted)) \
        == (0, 3, 0)
    w1._depth = 9
    fleet.submit(_obs())
    assert len(w2.submitted) == 1


def test_dispatcher_sheds_overflow_and_burn_to_spr():
    hub = MetricsHub()
    spr = _StubWorker("spr")
    # reactive: a full worker queue sheds to the SPR tier, not an error
    full = _StubWorker("w0", depth=0, full=True)
    fleet = FleetDispatcher([full], spr=spr, hub=hub, brownout_burn=None)
    fleet.submit(_obs())
    assert len(spr.submitted) == 1
    assert hub.get_counter("serve_brownout_total", reason="overflow") == 1
    # proactive: budget burn past the threshold + a backlog sheds BEFORE
    # the worker is asked
    burning = _StubWorker("w1", depth=4, burn=5.0)
    fleet2 = FleetDispatcher([burning], spr=spr, hub=hub,
                             brownout_burn=2.0, burn_refresh_s=0.0)
    fleet2.submit(_obs())
    assert len(burning.submitted) == 0 and len(spr.submitted) == 2
    assert hub.get_counter("serve_brownout_total", reason="slo_burn") == 1
    # idle worker (no backlog): burn alone must NOT shed
    burning._depth = 0
    fleet2.submit(_obs())
    assert len(burning.submitted) == 1
    # without an SPR tier, overflow raises like the single server
    fleet3 = FleetDispatcher([full], brownout_burn=None)
    with pytest.raises(ServeError):
        fleet3.submit(_obs())


def test_dispatcher_merged_slo_weights_by_volume():
    from gsc_tpu.obs.slo import SLOEngine, parse_slo_spec

    def engine(n_hits, n_miss, bucket=1):
        e = SLOEngine(deadline_ms=5.0, objectives=parse_slo_spec("10"))
        for _ in range(n_hits):
            e.record_request(1.0, bucket)
        for _ in range(n_miss):
            e.record_request(50.0, bucket)
        e.record_flush(1, 2)
        return e

    w0, w1 = _StubWorker("w0"), _StubWorker("w1")
    w0.slo_engine = engine(9, 1)    # attainment .9 over 10
    w1.slo_engine = engine(2, 2)    # attainment .5 over 4
    fleet = FleetDispatcher([w0, w1], brownout_burn=None)
    doc = fleet.merged_slo()
    assert doc["requests"] == 14 and doc["deadline_misses"] == 3
    # weighted by window size: (0.9*10 + 0.5*4) / 14 (stored rounded)
    assert abs(doc["attainment"] - (0.9 * 10 + 0.5 * 4) / 14) < 1e-6
    assert doc["burn_rate"] == round((1 - doc["attainment"]) / 0.01, 4)
    assert doc["pad_waste"] == 0.5
    assert set(doc["per_worker"]) == {"w0", "w1"}
