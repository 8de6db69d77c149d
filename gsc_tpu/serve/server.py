"""PolicyServer — AOT-compiled policy + artifact cache + micro-batcher.

Lifecycle of one serving process:

1. ``start()`` prepares every batch bucket BEFORE the first request:
   artifact-cache lookup -> ``jax.export.deserialize`` on a hit (no policy
   trace at all), else trace+lower via
   :meth:`~gsc_tpu.serve.policy.GreedyServePolicy.export_bucket` and
   persist the serialized module; either way the bucket is warmed with one
   dummy device call so the backend compile is also done up front.  A
   corrupt cache entry logs, recompiles and overwrites — it never fails a
   start.
2. ``submit(obs)`` enqueues a request on the micro-batcher and returns a
   :class:`~gsc_tpu.serve.batcher.ServeFuture`; ``submit_sync`` blocks.
3. ``close()`` drains the queue and emits the final ``serve_stats`` event.

Observability rides the run's :class:`~gsc_tpu.obs.MetricsHub`: the
batcher feeds the latency/queue series (see its module doc), the server
emits one ``serve_start`` event (tier, buckets, per-bucket cache hit +
prepare wall, total startup) and periodic + final ``serve_stats`` events
(requests, requests/s, p50/p99 overall and per bucket, occupancy,
rejections, and — with a tracer attached — the latency decomposition
per bucket plus the SLO snapshot) — ``tools/obs_report.py`` renders
them as the serving section.

Request-path tracing + SLO: pass a
:class:`~gsc_tpu.obs.slo.ServeTracer` (``tracer=``) to decompose every
request's latency into queue-wait / batch-wait / device / fan-out and
emit ``serve_flush`` + head-sampled ``serve_request_span`` events;
``slo=`` (an :class:`~gsc_tpu.obs.slo.SLOObjectives`) declares latency
objectives the engine tracks rolling attainment and error-budget burn
against, and ``slo_path=`` makes :meth:`close` write the final SLO
summary as ``slo.json``.  All three default off — the historic serve
path is byte-identical without them.

Without a checkpoint the server runs the SPR fallback tier
(:class:`~gsc_tpu.serve.fallback.SPRFallbackPolicy`) through the same
batcher and accounting, so the serving surface is always available.
"""
from __future__ import annotations

import logging
import time
from typing import Dict, Optional, Sequence

import numpy as np

from .batcher import MicroBatcher, ServeFuture
from .cache import ArtifactCache, cache_material
from .fallback import SPRFallbackPolicy
from .policy import (GreedyServePolicy, exec_fn_name, policy_fn_name,
                     shape_structs)

log = logging.getLogger("gsc_tpu.serve.server")


def _make_exec(exported, name: str):
    """Jit-wrap a deserialized exported module under a stable per-bucket
    name (compile telemetry + retrace assertions key on it).  The wrapper
    trace is trivial — the policy itself was traced at export time (or
    never, on a cache hit)."""
    import jax

    def _exec(params, *leaves):
        return exported.call(params, *leaves)

    _exec.__name__ = name
    return jax.jit(_exec)


class PolicyServer:
    """One serving process: compiled buckets (learned tier) or the SPR
    heuristic (fallback tier) behind a deadline micro-batcher."""

    def __init__(self, *, policy: Optional[GreedyServePolicy] = None,
                 params=None, fallback: Optional[SPRFallbackPolicy] = None,
                 buckets: Sequence[int] = (1, 4, 8),
                 deadline_ms: float = 5.0,
                 cache: Optional[ArtifactCache] = None,
                 fingerprint: str = "none",
                 precision: str = "f32", graph_mode: bool = True,
                 hub=None, stats_interval: int = 50,
                 max_queue: int = 4096, perf=None,
                 tracer=None, slo=None, slo_path: Optional[str] = None,
                 mode: str = "deadline", worker: Optional[str] = None,
                 hot_swap_dir: Optional[str] = None,
                 swap_poll_s: float = 0.2):
        if (policy is None) == (fallback is None):
            raise ValueError("exactly one of policy (learned tier, with "
                             "params) or fallback (SPR tier) is required")
        if policy is not None and params is None:
            raise ValueError("the learned tier needs actor params")
        self.policy = policy
        self.params = params
        self.fallback = fallback
        self.tier = "learned" if policy is not None else "spr"
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.deadline_ms = float(deadline_ms)
        self.cache = cache
        self.fingerprint = fingerprint
        self.precision = precision
        self.graph_mode = graph_mode
        self.hub = hub
        # device-cost ledger (obs.perf.CostLedger): with one, every bucket
        # records its serve_policy_b<B> compile cost at start() and the
        # measured latency histograms merge in at close() — perf.json
        # then carries per-bucket MFU next to the training entry points
        self.perf = perf
        # request-path tracing + SLO engine (obs.slo): the tracer turns
        # the batcher's timestamp records into span events and latency
        # decomposition on its own drainer thread; the engine (created
        # in start() when a tracer is attached) tracks deadline misses,
        # pad waste, arrival rate and — when `slo` declares objectives —
        # rolling attainment + error-budget burn.  slo_path: where
        # close() writes the final summary document (None = don't).
        self.tracer = tracer
        self.slo = slo
        self.slo_path = slo_path
        self.slo_engine = None
        self.stats_interval = max(int(stats_interval), 1)
        self.max_queue = max_queue
        # batching discipline (serve.batcher.BATCH_MODES): "deadline" is
        # the historic flush-cycle batcher, "continuous" forms the next
        # batch while the current device call is in flight
        self.mode = mode
        # fleet worker id: tags the queue-depth gauge + per-worker
        # counters and stamps serve_start/serve_stats/weight_swap events
        # (None = the historic single-server series, untouched)
        self.worker = worker
        self._wtag = {"worker": worker} if worker else {}
        # live weight hot-swap: watch this publish directory
        # (serve.fleet.WeightPublisher layout) and swap new versions in
        # between dispatches; policy_version stamps every flush
        self.hot_swap_dir = hot_swap_dir
        self.swap_poll_s = swap_poll_s
        self.watcher = None
        self.policy_version = 0
        self.swaps = 0
        self.batcher: Optional[MicroBatcher] = None
        self.startup: Dict = {}
        self._exec: Dict[int, object] = {}
        self._occupancy: Dict[int, int] = {}
        self._completed = 0
        self._last_stats_at = 0
        self._t_started = None

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "PolicyServer":
        t0 = time.perf_counter()
        per_bucket: Dict[str, Dict] = {}
        if self.tier == "learned":
            for b in self.buckets:
                per_bucket[str(b)] = self._prepare_bucket(b)
            run_batch = self._run_learned
            template = self.policy.template
        else:
            template = self.fallback.template
            run_batch = self.fallback.run_batch
        if self.tracer is not None:
            from ..obs.slo import SLOEngine
            self.slo_engine = SLOEngine(deadline_ms=self.deadline_ms,
                                        objectives=self.slo, hub=self.hub,
                                        tags=self._wtag)
            self.tracer.bind_engine(self.slo_engine)
            self.tracer.start()
        self.batcher = MicroBatcher(
            run_batch, template, buckets=self.buckets,
            deadline_ms=self.deadline_ms, hub=self.hub,
            max_queue=self.max_queue, on_flush=self._on_flush,
            tracer=self.tracer, mode=self.mode, worker=self.worker,
            version_provider=lambda: self.policy_version).start()
        if self.hub is not None and hasattr(self.hub, "live_gauge"):
            # the /metrics endpoint snapshots the hub on every scrape —
            # a live probe keeps serve_queue_depth current mid-run
            # instead of frozen at the last flush/submit sample (tagged
            # per worker in a fleet so N probes never collide)
            batcher = self.batcher
            self.hub.live_gauge("serve_queue_depth",
                                lambda: batcher.queue_depth, **self._wtag)
        if self.hot_swap_dir is not None:
            from .fleet import VersionWatcher
            self.watcher = VersionWatcher(self.hot_swap_dir, self,
                                          poll_s=self.swap_poll_s,
                                          hub=self.hub).start()
        self._t_started = time.perf_counter()
        self.startup = {
            "tier": self.tier,
            "startup_s": round(self._t_started - t0, 3),
            "buckets": per_bucket,
            "cache_dir": self.cache.root if self.cache else None,
        }
        if self.hub is not None:
            self.hub.event("serve_start", tier=self.tier,
                           buckets=list(self.buckets),
                           deadline_ms=self.deadline_ms,
                           mode=self.mode,
                           startup_s=self.startup["startup_s"],
                           bucket_prepare=per_bucket,
                           cache_dir=self.startup["cache_dir"],
                           fingerprint=self.fingerprint,
                           **({"worker": self.worker, "hot_swap_dir":
                               self.hot_swap_dir} if self.worker
                              or self.hot_swap_dir else {}))
        return self

    def _prepare_bucket(self, b: int) -> Dict:
        """Load-or-compile + warm one bucket; returns its prepare stats."""
        from jax import export as jax_export

        t0 = time.perf_counter()
        material = cache_material(
            fingerprint=self.fingerprint, template=self.policy.template,
            batch=b, precision=self.precision, graph_mode=self.graph_mode,
            # the actor is lowered through the configured GAT impl — a
            # module artifact compiled under one impl must miss under the
            # other (their numerics are only interpret-mode-equal)
            gnn_impl=self.policy.ddpg.actor.gnn_impl)
        exported, hit = None, False
        blob = self.cache.load(material) if self.cache else None
        if blob is not None:
            try:
                exported = jax_export.deserialize(bytearray(blob))
                hit = True
            except Exception as e:  # noqa: BLE001 - corrupt entry: recompile
                log.warning(
                    "serve artifact for bucket %d failed to deserialize "
                    "(%s: %s) — recompiling and overwriting the entry",
                    b, type(e).__name__, e)
        if exported is None:
            exported = self.policy.export_bucket(self.params, b)
            if self.cache is not None:
                self.cache.store(material, bytes(exported.serialize()))
        self._exec[b] = _make_exec(exported, exec_fn_name(b))
        self._warm_bucket(b)
        if self.perf is not None:
            # shapes-only AOT capture of the bucket's compiled policy —
            # FLOPs/bytes/fusions per batched call at startup, never
            # inside a request's latency (the warm call above already
            # paid the backend compile, so this lower mostly re-wraps it)
            self.perf.capture(
                policy_fn_name(b), self._exec[b],
                (shape_structs(self.params),
                 *self.policy.template.batch_structs(b)))
        return {"cache_hit": hit,
                "prepare_s": round(time.perf_counter() - t0, 3)}

    def _warm_bucket(self, b: int):
        """One dummy call so the backend compile (and the wrapper trace)
        happen at startup, never inside a request's latency."""
        import jax

        t = self.policy.template
        zeros = [np.zeros((b,) + s, d)
                 for s, d in zip(t.leaf_shapes, t.leaf_dtypes)]
        jax.block_until_ready(self._exec[b](self.params, *zeros))

    def close(self):
        if self.watcher is not None:
            # stop watching BEFORE the drain: a swap landing mid-teardown
            # has nothing left to serve anyway
            self.watcher.stop()
            self.watcher = None
        if self.batcher is not None:
            self.batcher.stop()
            self.batcher = None
        if self.hub is not None and hasattr(self.hub, "drop_live_gauge"):
            self.hub.drop_live_gauge("serve_queue_depth", **self._wtag)
            self.hub.gauge("serve_queue_depth", 0, **self._wtag)
        if self.tracer is not None:
            # final drain BEFORE the final stats event, so the last
            # flushes' spans and SLO updates are in the summary
            self.tracer.stop()
        self._emit_stats(final=True)
        if self.slo_engine is not None and self.slo_path is not None:
            from ..obs.slo import write_slo_json
            try:
                write_slo_json(self.slo_path, self._slo_doc())
            except OSError as e:   # a full disk must not mask teardown
                log.warning("slo.json not written to %s: %s",
                            self.slo_path, e)
        if self.perf is not None and self.hub is not None:
            # measured per-bucket FLUSH wall -> ledger timings: the
            # batcher's serve_batch_ms histogram wraps exactly one
            # device call per observation (run_batch in _flush), so
            # `dispatches` counts device calls — not requests — and
            # wall_s_mean is honest per-dispatch wall.  It still
            # includes host staging around the call, so the derived MFU
            # is a serving lower bound, not a kernel-only number.
            for b in self.buckets:
                s = self.hub.histogram_summary("serve_batch_ms", bucket=b)
                if s and s.get("count"):
                    self.perf.note_timing(policy_fn_name(b),
                                          s["sum"] / 1e3, int(s["count"]))

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------ requests
    def submit(self, obs) -> ServeFuture:
        if self.batcher is None:
            raise RuntimeError("PolicyServer not started")
        return self.batcher.submit(obs)

    def submit_sync(self, obs, timeout: Optional[float] = 60.0):
        return self.submit(obs).result(timeout)

    @property
    def queue_depth(self) -> int:
        return self.batcher.queue_depth if self.batcher is not None else 0

    # ------------------------------------------------------------ hot-swap
    def apply_weights(self, leaves, version: int, fingerprint: str,
                      meta: Optional[Dict] = None):
        """Swap a published weight version in, strictly between device
        dispatches.

        Learned tier: ``leaves`` (host arrays in ``jax.tree_util``
        flatten order) must match the served params' leaf shapes/dtypes
        exactly — the AOT-compiled buckets were lowered for that
        signature, so a mismatch raises and the served weights stay
        untouched.  Device staging (``jnp.asarray``) happens BEFORE the
        flush lock is taken; the lock is held only for the reference
        swap, so a swap stalls serving by nanoseconds, not a transfer.

        SPR tier: the heuristic has no network weights — a published
        single-leaf artifact matching the precomputed action's
        shape/dtype swaps the action itself (recomputed topology), any
        other payload bumps the version stamp only.  Either way the full
        version/locking/event machinery runs, which is what a fallback-
        tier fleet exercises in CI.

        Zero requests are dropped or errored by a swap: the queue is
        never touched, and each dispatch stamps the version it actually
        ran under (the flush lock makes that exact)."""
        t0 = time.perf_counter()
        staged_params = staged_action = None
        if self.tier == "learned":
            import jax
            import jax.numpy as jnp

            cur_leaves, treedef = jax.tree_util.tree_flatten(self.params)
            if len(leaves) != len(cur_leaves):
                raise ValueError(
                    f"hot-swap version {version} has {len(leaves)} leaves, "
                    f"served params have {len(cur_leaves)}")
            for i, (new, cur) in enumerate(zip(leaves, cur_leaves)):
                new = np.asarray(new)
                if (tuple(new.shape) != tuple(jnp.shape(cur))
                        or str(new.dtype) != str(jnp.asarray(cur).dtype)):
                    raise ValueError(
                        f"hot-swap version {version} leaf {i} is "
                        f"{new.shape}/{new.dtype}, served params want "
                        f"{tuple(jnp.shape(cur))}/"
                        f"{jnp.asarray(cur).dtype} — the compiled "
                        "buckets cannot run it")
            staged_params = jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(l) for l in leaves])
        else:
            action = self.fallback.action
            if len(leaves) == 1 and tuple(np.asarray(leaves[0]).shape) \
                    == tuple(action.shape):
                staged_action = np.asarray(leaves[0]).astype(action.dtype)
        lock = self.batcher.flush_lock if self.batcher is not None else None
        if lock is not None:
            lock.acquire()
        try:
            if staged_params is not None:
                self.params = staged_params
            if staged_action is not None:
                self.fallback.action = staged_action
            self.policy_version = int(version)
            self.fingerprint = fingerprint
        finally:
            if lock is not None:
                lock.release()
        self.swaps += 1
        swap_ms = (time.perf_counter() - t0) * 1e3
        if self.hub is not None:
            self.hub.counter("serve_weight_swaps_total", **self._wtag)
            self.hub.gauge("serve_policy_version", version, **self._wtag)
            self.hub.event(
                "weight_swap", version=int(version),
                fingerprint=fingerprint, tier=self.tier,
                swap_ms=round(swap_ms, 3),
                weights_applied=bool(staged_params is not None
                                     or staged_action is not None),
                requests_in_flight=self.queue_depth,
                **({"worker": self.worker} if self.worker else {}),
                **({"meta": meta} if meta else {}))

    # ------------------------------------------------------------ internals
    def _run_learned(self, leaves, n_real: int, bucket: int) -> np.ndarray:
        return np.asarray(self._exec[bucket](self.params, *leaves))

    def _on_flush(self, n_real: int, bucket: int):
        self._occupancy[bucket] = self._occupancy.get(bucket, 0) + n_real
        self._completed += n_real
        if self._completed - self._last_stats_at >= self.stats_interval:
            self._last_stats_at = self._completed
            self._emit_stats()

    def latency_summary(self, bucket: Optional[int] = None):
        if self.hub is None:
            return None
        tags = {"bucket": bucket} if bucket is not None else {}
        return self.hub.histogram_summary("serve_latency_ms", **tags)

    def _rejected_totals(self) -> Dict[str, int]:
        if self.hub is None:
            return {}
        return {reason: int(self.hub.get_counter("serve_rejected_total",
                                                 reason=reason))
                for reason in ("queue_full", "stopping")}

    def _decomposition(self) -> Dict[str, Dict[str, float]]:
        """Per-bucket latency-split means from the tracer's histograms:
        queue-wait, batch-formation wait, device wall (the historic
        serve_batch_ms), fan-out."""
        if self.hub is None:
            return {}
        out: Dict[str, Dict[str, float]] = {}
        for b in self.buckets:
            row = {}
            for metric, key in (("serve_queue_wait_ms", "queue_ms"),
                                ("serve_batch_wait_ms", "batch_ms"),
                                ("serve_batch_ms", "device_ms"),
                                ("serve_fanout_ms", "fanout_ms")):
                s = self.hub.histogram_summary(metric, bucket=b)
                if s and s.get("count"):
                    row[key] = round(s["mean"], 4)
            if row:
                out[str(b)] = row
        return out

    def slo_summary(self) -> Optional[Dict]:
        """Compact SLO verdict for the CLI's JSON output / serve_bench
        banking (the slo.json document is the full version)."""
        if self.slo_engine is None:
            return None
        snap = self.slo_engine.snapshot()
        out = {k: snap.get(k) for k in
               ("requests", "deadline_misses", "deadline_miss_ratio",
                "attainment", "burn_rate", "pad_waste",
                "queue_wait_frac", "arrival_rate_rps", "rejected")}
        out["p99_target_ms"] = (snap.get("objectives") or {}).get("p99_ms")
        return out

    def _slo_doc(self) -> Dict:
        """The full ``slo.json`` payload: engine snapshot + serving
        context + latency decomposition + overall percentiles."""
        from ..obs.slo import SLO_SCHEMA_VERSION

        lat = self.latency_summary() or {}
        doc = {
            "schema_version": SLO_SCHEMA_VERSION,
            "ts": round(time.time(), 3),
            "run": (self.hub.base_tags.get("run")
                    if self.hub is not None else None),
            "tier": self.tier,
            "buckets": list(self.buckets),
            "requests_completed": self._completed,
            "p50_latency_ms": round(lat.get("p50", 0.0), 4),
            "p99_latency_ms": round(lat.get("p99", 0.0), 4),
            "decomposition_ms": self._decomposition(),
            "spans_dropped": (self.tracer.spans_dropped
                              if self.tracer is not None else 0),
        }
        doc.update(self.slo_engine.snapshot())
        return doc

    def _emit_stats(self, final: bool = False):
        if self.hub is None:
            return
        elapsed = (time.perf_counter() - self._t_started) \
            if self._t_started else 0.0
        lat = self.latency_summary() or {}
        per_bucket = {}
        for b in self.buckets:
            s = self.latency_summary(b)
            if s:
                per_bucket[str(b)] = {"p50_ms": round(s["p50"], 3),
                                      "p99_ms": round(s["p99"], 3),
                                      "requests": int(s["count"])}
        extra = {}
        rejected = self._rejected_totals()
        # rejections always ride a traced run's stats (zeroes included —
        # "none rejected" is itself the signal); an untraced run only
        # reports them once one actually happened
        if self.tracer is not None or any(rejected.values()):
            extra["rejected"] = rejected
        if self.tracer is not None:
            # the tracer drains on its own cadence (<= its interval
            # stale here); the FINAL stats event runs after
            # tracer.stop()'s synchronous drain, so it is exact
            extra["decomposition"] = self._decomposition()
            if self.slo_engine is not None:
                snap = self.slo_engine.snapshot()
                extra["slo"] = {
                    k: snap.get(k) for k in
                    ("deadline_miss_ratio", "deadline_misses",
                     "attainment", "burn_rate", "arrival_rate_rps",
                     "pad_waste", "queue_wait_frac")}
                extra["slo"]["p99_target_ms"] = \
                    (snap.get("objectives") or {}).get("p99_ms")
        if self.worker:
            # fleet context: per-worker request/batch counters + the
            # worker's own completion count (the untagged histograms are
            # fleet aggregates, so `requests` below is fleet-wide)
            extra["worker"] = self.worker
            extra["worker_requests"] = self._completed
        if self.policy_version or self.swaps:
            extra["policy_version"] = self.policy_version
            extra["swaps"] = self.swaps
        self.hub.event(
            "serve_stats", tier=self.tier, final=final,
            requests=self._completed,
            rps=round(self._completed / elapsed, 3) if elapsed else 0.0,
            p50_ms=round(lat.get("p50", 0.0), 3),
            p99_ms=round(lat.get("p99", 0.0), 3),
            mean_ms=round(lat.get("mean", 0.0), 3),
            max_ms=round(lat.get("max", 0.0), 3),
            queue_depth=int(self.hub.get_gauge("serve_queue_depth",
                                               **self._wtag) or 0),
            occupancy={str(b): n for b, n in
                       sorted(self._occupancy.items())},
            buckets=per_bucket, **extra)
