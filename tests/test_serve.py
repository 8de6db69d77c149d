"""Serving-subsystem tests (gsc_tpu.serve): AOT-compiled policy parity
with the jit path, artifact-cache hits that skip retracing, micro-batcher
padding/batch-mate invariance, corrupt/stale cache fallback, and the SPR
fallback tier answering without a checkpoint."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.agents import DDPG
from gsc_tpu.analysis.sentinels import CompileMonitor
from gsc_tpu.obs.hub import MetricsHub
from gsc_tpu.serve import (ArtifactCache, GreedyServePolicy, MicroBatcher,
                           ObsTemplate, PolicyServer, SPRFallbackPolicy,
                           ServeError, cache_material, policy_fn_name,
                           spr_schedule_action)

from tests.test_agent import line_topo, make_stack

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def served():
    """One tiny learned-tier setup shared by the module (compiles once)."""
    env, agent, topo, traffic = make_stack()
    ddpg = DDPG(env, agent)
    _, obs = env.reset(jax.random.PRNGKey(0), topo, traffic)
    state = ddpg.init(jax.random.PRNGKey(2), obs)
    return env, agent, topo, traffic, ddpg, obs, state


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _material(policy, env, agent, batch, fingerprint="fp-test",
              gnn_impl=None):
    return cache_material(fingerprint=fingerprint, template=policy.template,
                          batch=batch, precision=agent.precision,
                          graph_mode=agent.graph_mode,
                          gnn_impl=gnn_impl or policy.ddpg.actor.gnn_impl)


# ------------------------------------------------------------ greedy policy
def test_greedy_action_is_the_evaluate_op_sequence(served):
    """DDPG.greedy_action == the inline apply/clip/process_action sequence
    Trainer.evaluate historically ran (the serving stack's AOT target must
    be the SAME function inference uses)."""
    env, agent, topo, traffic, ddpg, obs, state = served
    want = env.process_action(
        jnp.clip(ddpg.actor.apply(state.actor_params, obs), 0.0, 1.0))
    got = ddpg.greedy_action(state.actor_params, obs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_aot_export_bit_identical_to_jit_path(served):
    """The exported (serialize->deserialize) bucket answers bit-identically
    to jitting the same batched policy directly."""
    from jax import export as jax_export

    env, agent, topo, traffic, ddpg, obs, state = served
    policy = GreedyServePolicy(ddpg, obs)
    B = 2
    exported = policy.export_bucket(state.actor_params, B)
    rt = jax_export.deserialize(exported.serialize())
    leaves = policy.template.stack_pad(
        [policy.template.flatten(obs)] * B, B)
    aot = np.asarray(rt.call(state.actor_params, *leaves))
    jit_path = np.asarray(
        jax.jit(policy.batched_fn(B))(state.actor_params, *leaves))
    assert aot.shape == (B, env.limits.action_dim)
    np.testing.assert_array_equal(aot, jit_path)


def test_obs_template_rejects_malformed_requests(served):
    env, agent, topo, traffic, ddpg, obs, state = served
    t = ObsTemplate(obs)
    with pytest.raises(ValueError, match="leaf"):
        bad = jax.tree_util.tree_map(
            lambda x: np.zeros((3,) + np.asarray(x).shape,
                               np.asarray(x).dtype), obs)
        t.flatten(bad)
    with pytest.raises(ValueError, match="tree"):
        t.flatten({"not": "the-obs-pytree"})


# ------------------------------------------------------- batcher invariance
def test_batch_mate_and_padding_invariance(served):
    """A request's answer is bit-identical whether it runs alone (padded
    with repeats), padded with zeros, or batched with arbitrary mates —
    the vmap row-independence contract the batcher relies on."""
    env, agent, topo, traffic, ddpg, obs, state = served
    policy = GreedyServePolicy(ddpg, obs)
    B = 4
    exported = policy.export_bucket(state.actor_params, B)
    call = jax.jit(exported.call)
    t = policy.template
    req = t.flatten(obs)

    def mate(scale):
        return [(leaf * scale).astype(leaf.dtype)
                if np.issubdtype(leaf.dtype, np.floating) else leaf
                for leaf in req]

    solo_repeat = t.stack_pad([req], B)
    solo_zero = [np.zeros_like(leaf) for leaf in solo_repeat]
    for i, leaf in enumerate(req):
        solo_zero[i][0] = leaf
    mates = t.stack_pad([req, mate(0.5), mate(0.0), mate(2.0)], B)
    a = np.asarray(call(state.actor_params, *solo_repeat))[0]
    b = np.asarray(call(state.actor_params, *solo_zero))[0]
    c = np.asarray(call(state.actor_params, *mates))[0]
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_batcher_buckets_and_deadline(served, tmp_path):
    """Four concurrent requests fold into the 4-bucket; a lone request
    flushes after the deadline in the 1-bucket; occupancy + latency series
    land in the hub."""
    env, agent, topo, traffic, ddpg, obs, state = served
    hub = MetricsHub()
    srv = PolicyServer(policy=GreedyServePolicy(ddpg, obs),
                       params=state.actor_params, buckets=(1, 4),
                       deadline_ms=200.0, hub=hub,
                       cache=ArtifactCache(str(tmp_path / "c"))).start()
    try:
        futs = [srv.submit(obs) for _ in range(4)]
        outs = [f.result(60) for f in futs]
        ref = outs[0]
        for o in outs[1:]:
            np.testing.assert_array_equal(o, ref)
        assert hub.get_counter("serve_batches_total", bucket=4) == 1
        # lone request: the deadline (not a batch-mate) flushes it — in
        # the 1-bucket, a DIFFERENT compiled executable.  Bit-equality is
        # the contract within one bucket (above, and
        # test_batch_mate_and_padding_invariance); across buckets XLA is
        # free to tile its reductions per batch size, so answers agree to
        # f32 rounding only (one ulp, 6e-8, on jaxlib 0.9.0)
        np.testing.assert_allclose(srv.submit_sync(obs, timeout=60), ref,
                                   rtol=1e-6, atol=1e-6)
        assert hub.get_counter("serve_batches_total", bucket=1) == 1
        assert hub.get_counter("serve_requests_total") == 5
        lat = hub.histogram_summary("serve_latency_ms")
        assert lat["count"] == 5 and lat["p99"] > 0
    finally:
        srv.close()


def test_batcher_overload_drains_backlog():
    """When the device call outlasts the deadline, the backlog folds into
    large batches (non-blocking drain) instead of degenerating to
    bucket-1 flushes — the overload regime is where batching matters."""
    import time as _t

    t = ObsTemplate(np.zeros(3, np.float32))
    calls = []

    def slow_run(leaves, k, bucket):
        calls.append((k, bucket))
        _t.sleep(0.02)
        return np.zeros((bucket, 2), np.float32)

    mb = MicroBatcher(slow_run, t, buckets=(1, 8), deadline_ms=1.0).start()
    try:
        futs = [mb.submit(np.zeros(3, np.float32)) for _ in range(9)]
        for f in futs:
            f.result(30)
    finally:
        mb.stop()
    assert sum(k for k, _ in calls) == 9
    assert len(calls) <= 4, f"backlog served as too many flushes: {calls}"


def test_submit_after_stop_fails_fast():
    t = ObsTemplate(np.zeros(3, np.float32))
    mb = MicroBatcher(lambda l, k, b: np.zeros((b, 1), np.float32), t,
                      buckets=(1,), deadline_ms=1.0).start()
    mb.stop()
    with pytest.raises(ServeError, match="stopping"):
        mb.submit(np.zeros(3, np.float32))


# ------------------------------------------------------------ artifact cache
def test_cache_hit_skips_policy_retrace(served, tmp_path):
    """Cold start traces the batched policy exactly once per bucket and
    persists the artifacts; a warm start deserializes (cache_hit) without
    a single policy trace, and steady-state serving under
    assert_no_retrace sees ZERO traces of any watched name."""
    env, agent, topo, traffic, ddpg, obs, state = served
    cache = ArtifactCache(str(tmp_path / "cache"))
    policy = GreedyServePolicy(ddpg, obs)
    watch = (policy_fn_name(1), policy_fn_name(4))
    mon = CompileMonitor(watch=None).start()
    try:
        srv = PolicyServer(policy=policy, params=state.actor_params,
                           buckets=(1, 4), deadline_ms=2.0, cache=cache,
                           fingerprint="fp-test").start()
        cold = srv.submit_sync(obs, timeout=60)
        srv.close()
        assert [mon.traces(w) for w in watch] == [1, 1]
        assert not any(b["cache_hit"]
                       for b in srv.startup["buckets"].values())

        srv2 = PolicyServer(policy=policy, params=state.actor_params,
                            buckets=(1, 4), deadline_ms=2.0, cache=cache,
                            fingerprint="fp-test").start()
        assert all(b["cache_hit"]
                   for b in srv2.startup["buckets"].values())
        # the acceptance contract: a warm start never re-traces the policy
        assert [mon.traces(w) for w in watch] == [1, 1]
        with mon.assert_no_retrace():   # steady state: no traces AT ALL
            warm = [srv2.submit_sync(obs, timeout=60) for _ in range(3)]
        srv2.close()
        for w in warm:
            np.testing.assert_array_equal(w, cold)
    finally:
        mon.stop()


def test_corrupt_cache_entry_recompiles_never_crashes(served, tmp_path):
    env, agent, topo, traffic, ddpg, obs, state = served
    cache = ArtifactCache(str(tmp_path / "cache"))
    policy = GreedyServePolicy(ddpg, obs)
    kwargs = dict(policy=policy, params=state.actor_params, buckets=(2,),
                  deadline_ms=2.0, cache=cache, fingerprint="fp-test")
    srv = PolicyServer(**kwargs).start()
    baseline = srv.submit_sync(obs, timeout=60)
    srv.close()
    blob_path, _ = cache.paths(_material(policy, env, agent, 2))
    with open(blob_path, "wb") as f:
        f.write(b"\x00garbage, not a serialized module")
    srv2 = PolicyServer(**kwargs).start()   # must not raise
    assert srv2.startup["buckets"]["2"]["cache_hit"] is False
    np.testing.assert_array_equal(srv2.submit_sync(obs, timeout=60),
                                  baseline)
    srv2.close()
    # the corrupt entry was overwritten with a working one
    srv3 = PolicyServer(**kwargs).start()
    assert srv3.startup["buckets"]["2"]["cache_hit"] is True
    np.testing.assert_array_equal(srv3.submit_sync(obs, timeout=60),
                                  baseline)
    srv3.close()


def test_stale_material_and_meta_are_misses(served, tmp_path):
    """A different fingerprint keys a different entry; a torn/garbled meta
    sidecar or one describing different material is a miss, never an
    error."""
    env, agent, topo, traffic, ddpg, obs, state = served
    cache = ArtifactCache(str(tmp_path / "cache"))
    policy = GreedyServePolicy(ddpg, obs)
    mat = _material(policy, env, agent, 2)
    cache.store(mat, b"some-blob")
    assert cache.load(mat) == b"some-blob"
    # retrained checkpoint -> new fingerprint -> different key: a miss
    assert cache.load(_material(policy, env, agent, 2,
                                fingerprint="other")) is None
    # same weights lowered through the OTHER GAT impl: also a miss (the
    # two impls' compiled numerics are only interpret-mode-equal)
    assert cache.load(_material(policy, env, agent, 2,
                                gnn_impl="pallas")) is None
    # torn meta: miss
    _, meta_path = cache.paths(mat)
    with open(meta_path, "w") as f:
        f.write('{"material": {')
    assert cache.load(mat) is None
    # meta describing different material under the same filename: miss
    with open(meta_path, "w") as f:
        json.dump({"material": {"tampered": True}}, f)
    assert cache.load(mat) is None
    # restored meta: hit again
    from gsc_tpu.obs.sinks import write_atomic_json
    write_atomic_json(meta_path, {"material": mat, "bytes": 9})
    assert cache.load(mat) == b"some-blob"


# ------------------------------------------------------------ fallback tier
def test_spr_fallback_serves_without_checkpoint(served):
    env, agent, topo, traffic, ddpg, obs, state = served
    t = line_topo()
    hub = MetricsHub()
    srv = PolicyServer(fallback=SPRFallbackPolicy(t, env.limits, obs),
                       buckets=(1, 4), deadline_ms=2.0, hub=hub).start()
    try:
        out = srv.submit_sync(obs, timeout=60)
    finally:
        srv.close()
    np.testing.assert_array_equal(out, spr_schedule_action(t, env.limits))
    assert hub.histogram_summary("serve_latency_ms")["p99"] > 0
    assert srv.tier == "spr" and srv.startup["tier"] == "spr"


def test_spr_schedule_rules(served):
    """Rule 1: capable sources keep their own traffic; padded sources get
    no weight; every real source row is one-hot onto a capable node."""
    env, agent, topo, traffic, ddpg, obs, state = served
    t = line_topo()
    action = spr_schedule_action(t, env.limits)
    sched = action.reshape(env.limits.scheduling_shape)
    nm = np.asarray(t.node_mask)
    cap = np.asarray(t.node_cap)
    for src in range(env.limits.max_nodes):
        row = sched[src]
        if not nm[src]:
            assert row.sum() == 0.0
            continue
        assert (row.sum(axis=-1) == 1.0).all()   # one-hot per (c, s)
        dst = int(row[0, 0].argmax())
        assert cap[dst] > 0
        if cap[src] > 0:
            assert dst == src                     # rule 1: process HERE


# --------------------------------------------------------------- telemetry
def test_serve_stats_event_reaches_report(served, tmp_path):
    """Latency/occupancy flow through the RunObserver into events.jsonl,
    and tools/obs_report.py surfaces them as the serving section."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from obs_report import load_events, summarize

    env, agent, topo, traffic, ddpg, obs, state = served
    from gsc_tpu.obs import RunObserver

    rec = RunObserver(str(tmp_path / "run"))
    rec.start(meta={"mode": "serve", "tier": "learned"})
    srv = PolicyServer(policy=GreedyServePolicy(ddpg, obs),
                       params=state.actor_params, buckets=(1, 2),
                       deadline_ms=2.0, hub=rec.hub,
                       cache=ArtifactCache(str(tmp_path / "c"))).start()
    for _ in range(3):
        srv.submit_sync(obs, timeout=60)
    srv.close()
    rec.close(status="ok")
    summary = summarize(load_events(str(tmp_path / "run")))
    sv = summary["serving"]
    assert sv is not None and sv["tier"] == "learned"
    assert sv["requests"] == 3 and sv["p99_ms"] > 0
    assert sum(int(n) for n in sv["occupancy"].values()) == 3
    assert set(sv["bucket_prepare"]) == {"1", "2"}


def test_evaluate_reports_compile_warmup_split(served):
    """Trainer.evaluate (the `cli infer` backend) splits compile+warmup
    from steady-state wall; the parts sum to the total."""
    from gsc_tpu.agents import Trainer
    from tests.test_agent import make_driver

    env, agent, topo, traffic, ddpg, obs, state = served
    driver = make_driver(env, agent, topo, traffic)
    trainer = Trainer(env, driver, agent, seed=0)
    out = trainer.evaluate(state, episodes=1, test_mode=True)
    assert out["compile_warmup_s"] > 0
    assert out["steady_s"] >= 0
    assert abs(out["compile_warmup_s"] + out["steady_s"]
               - out["total_s"]) < 0.02
