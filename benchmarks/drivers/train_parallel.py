"""Driver: whole-episode training windows through
``gsc_tpu.agents.trainer.Trainer.train_parallel``, built as ``cli train
<agent> <sim> <service> <scheduler> --replicas B --chunk C --ckpt-interval
1`` builds it (``cli._build``, the ``RunObserver`` and ``Trainer``
constructors with the CLI's defaults) from YAML/graphml files written
into a temporary directory from the cell's configuration file.

No copy of the episode loop lives here.  Three of the entry point's own
parameters carry the benchmark:

- ``preempt``: the harness's :class:`~benchmarks.harness.Window`, whose
  ``triggered`` the loop reads at every episode boundary;
- ``init_state``: the learner state made here from the seed (the resume
  path), so that the plain reference starts from the same weights without
  taking anything the program made.  It is handed over as host arrays,
  which the trainer copies to the device, so the trainer's copy is the
  only one there;
- ``ckpt_manager`` with ``ckpt_interval=1``: a recorder that, once, at the
  end of the first episode (in set-up), keeps a host copy of the learner
  state and of that episode's replay rows for the output check; at every
  call it keeps a host copy of the actor parameters alone (the loop's own
  finite check has just copied the whole learner state; no device
  operation), so that the parameters which drove the window's last episode
  are at hand when the window has closed.  What that copy costs is printed
  per episode (``recorder_actor_s``).  Nothing stays on the device and
  nothing is written to disk.

What the driver holds, by phase.  A configuration's device bytes are the
trainer's alone: its learner state (online and target networks, two Adam
moments: 16 B a trained parameter), replay ring, environment and traffic.

- set-up: the reference's weights are made on the device in one jitted
  call and copied to the host at once (``host_init_state``); then the
  host keeps them (4 B a trained parameter) and the learner key for the
  check, and from the end of episode 0 the recorder's copies;
- window: nothing of the driver's on the device; on the host the same,
  and the actor's parameters of the last two boundaries;
- check: the program's device state is freed before the reference runs;
  of the final carries only the ring's ``size``, ``pos`` and ``capacity``
  and the rows of the window's last episode that the policy check reads
  are kept, on the host.

``device_live_gb`` (each save) and ``memory_stats`` (run end) print what
is resident, for sizing a cell.

What depends on the policy's architecture — the plain reference the output
check follows, the model FLOPs ``step_mfu_pct`` divides and the weights
made from the seed — comes from the module the configuration names
(``"reference"``, ``harness.load_reference``), never from an import here.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

RESERVED = ("source", "deployment", "reduced", "published", "assumed",
            "network", "simulator", "service", "scheduler", "max_nodes",
            "max_edges", "matmul_precision", "factored_head_threshold",
            "reference")
EPISODES_CAP = 1_000_000   # the window stops the loop, never this count


def prepare(cell: dict) -> None:
    """Process-level JAX set-up, before any device is touched: the
    program's own compile-cache rule (``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``) and the contraction precision
    the configuration states."""
    import jax
    from gsc_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell["config"]["matmul_precision"])


# ------------------------------------------------------------------ files
def write_inputs(cfg: dict, out: str) -> Dict[str, str]:
    """The four files ``cli train`` takes, plus the network, from the
    configuration file (the writers are ``yaml`` as ``cli init-configs``
    uses it and ``topology.synthetic.write_graphml``)."""
    import yaml
    from gsc_tpu.topology import synthetic

    os.makedirs(os.path.join(out, "networks"), exist_ok=True)
    net = cfg["network"]
    spec = getattr(synthetic, net["generator"])(**net.get("kwargs", {}))
    net_path = os.path.join(out, "networks", net["file"])
    synthetic.write_graphml(spec, net_path)
    paths = {"network": net_path}
    docs = {
        "agent": {k: v for k, v in cfg.items() if k not in RESERVED},
        "simulator": cfg["simulator"],
        "service": cfg["service"],
        "scheduler": {"training_network_files": [net_path],
                      "inference_network": net_path,
                      **cfg.get("scheduler", {})},
    }
    for name, doc in docs.items():
        paths[name] = os.path.join(out, f"{name}.yaml")
        with open(paths[name], "w") as f:
            yaml.safe_dump(doc, f)
    paths["spec"] = spec
    return paths


# ---------------------------------------------------------------- weights
def leaf_name(path) -> str:
    """A pytree path as the checkpoint layout's slash-joined leaf name."""
    parts = []
    for p in path:
        for attr in ("name", "key", "idx"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def leaf_table(tree) -> Dict[str, object]:
    import jax

    return {leaf_name(p): l
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _net_shapes(state_shape):
    """Leaf name -> shape of the online networks, named as the reference
    names them (``actor/...``, ``critic/...``)."""
    import jax

    shapes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state_shape)[0]:
        head, _, rest = leaf_name(path).partition("/")
        if head in ("actor_params", "critic_params"):
            shapes[f"{head.removesuffix('_params')}/{rest}"] = \
                tuple(leaf.shape)
    return shapes


def _fill(state_shape, weights, rng, zeros):
    """The state's tree with online and target networks from ``weights``
    (one array per network leaf, shared by online and target), the
    learner key ``rng`` and every other leaf ``zeros(shape, dtype)``."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shape)
    nets = {"actor_params": "actor", "target_actor_params": "actor",
            "critic_params": "critic", "target_critic_params": "critic"}
    leaves = []
    for path, leaf in flat:
        head, _, rest = leaf_name(path).partition("/")
        if head in nets:
            leaves.append(weights[f"{nets[head]}/{rest}"])
        elif head == "rng":
            leaves.append(rng)
        else:
            leaves.append(zeros(leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_init_state(seed: int, state_shape, init_weights: Callable):
    """A ``DDPGState`` of the program's layout filled from the seed, as
    device arrays: online and target networks from the reference's
    ``init_weights(seed, shapes)``, optimiser moments and counts zero, the
    learner key folded from the seed.  Returns (state, weights by
    reference name, learner key)."""
    import jax
    import jax.numpy as jnp

    weights = init_weights(seed, _net_shapes(state_shape))
    rng = jax.random.fold_in(jax.random.PRNGKey(seed), 7)
    return _fill(state_shape, weights, rng, jnp.zeros), weights, rng


def host_init_state(seed: int, state_shape, init_weights: Callable):
    """``make_init_state``'s state, weights and key as host arrays, the
    same bits (float32 survives the copy exactly): the weights are made
    on the device in the reference's one jitted call and copied to the
    host, the zero leaves are made on the host.  No device array of
    them outlives the call, so the trainer's copy of the state
    (``train_parallel`` puts what it is given on the device) is the only
    one on the device."""
    import jax
    import numpy as np

    # np.array copies: on the CPU np.asarray's view would keep the
    # device array alive
    weights = {k: np.array(v) for k, v in
               init_weights(seed, _net_shapes(state_shape)).items()}
    rng = np.array(jax.random.fold_in(jax.random.PRNGKey(seed), 7))
    return _fill(state_shape, weights, rng, np.zeros), weights, rng


def device_live_bytes() -> int:
    """Bytes of every live device array, read from their shapes on the
    host: no device operation."""
    import jax

    return sum(a.nbytes for a in jax.live_arrays())


# --------------------------------------------------------------- recorder
class MemorySink:
    """Hub sink keeping the run's events in memory for the readers."""

    def __init__(self):
        self.events = []

    def emit(self, record):
        self.events.append(record)

    def close(self):
        pass


def ring_rows(buffers, index, prefixes=("",)):
    """Rows of the replay ring as host arrays [replicas, slots, ...], by
    leaf name and each row in its stored shape: ``index`` picks replicas
    and slots (two slices, or two index arrays that broadcast);
    ``prefixes`` selects the leaves."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_flatten_with_path(buffers.data)[0]
    shapes = buffers.shapes or (None,) * len(leaves)
    rows = {}
    for (path, leaf), shape in zip(leaves, shapes):
        name = leaf_name(path)
        if not name.startswith(tuple(prefixes)):
            continue
        part = np.asarray(leaf[index])
        if shape is not None:
            part = part.reshape(part.shape[:2] + tuple(shape))
        rows[name] = part
    return rows


class Recorder:
    """Stands where ``cli train`` puts its ``CheckpointManager``.  At the
    end of episode 0, which lies in set-up, it keeps what the output check
    follows as host copies: the learner state and the episode's replay
    rows (a device copy of the state would hold every trained parameter
    twice for the whole run).  At every call it keeps a host copy of the
    actor parameters (the last two calls' only; a device copy would put
    operations after the program's last into the traced slice) with the
    seconds it took and the bytes of every live device array
    (``device_live_bytes``), and stamps its entry: the loop's finite
    check of the learner state, which the checkpoint cadence brings, lies
    between the episode's event and that stamp.  Nothing is written to
    disk."""

    def __init__(self, episode_steps: int):
        self.steps = int(episode_steps)
        self.state = None
        self.rows = None
        self.seconds = 0.0
        self.actor = {}          # save number -> host copy, last two
        self.actor_s = {}        # save number -> seconds that copy took
        self.entered = {}        # save number -> host clock at entry
        self.live = {}           # save number -> live device bytes

    def save(self, state, buffers, episode: int, **_):
        import jax

        self.entered[episode] = time.time()
        # before the copy: on the TPU ``device_get`` leaves arrays among
        # the live ones that the chip does not hold (the actor's bytes)
        self.live[episode] = device_live_bytes()
        self.actor[episode] = jax.device_get(state.actor_params)
        self.actor.pop(episode - 2, None)
        self.actor_s[episode] = time.time() - self.entered[episode]
        if episode != 1 or self.state is not None:
            return None
        t0 = time.time()
        self.state = jax.device_get(state)
        self.rows = ring_rows(buffers, (slice(None), slice(self.steps)))
        self.seconds = time.time() - t0
        return None


class Tracer:
    """Traces the last ``slice_s`` seconds of the window's second episode
    with ``jax.profiler`` (the cell's ``trace_slice_s``: a few times the
    learn burst, which ends the episode).  A timer thread starts the
    trace that long before the episode's expected end — the window's
    first, untraced, episode gives the length — and the closing boundary
    stops it, with the device idle.

    Why a slice, and why this one (measured, PR 24): the flagship runs
    about 445 000 device operations a second in the rollout and about 5
    million a second in the learn burst; the profiler keeps some 6.2
    million and drops the rest, and handing them over costs about 30
    microseconds an event with the device idle and 105 while a program
    runs.  So neither a whole 25.6 s episode, nor a slice that has to be
    stopped in mid-episode, nor one long enough for a whole ``chunk_step``
    execution fits a run's 360 seconds with room."""

    EPISODES = 2      # a traced run's window: one clean episode, one traced

    def __init__(self, out_dir: str, slice_s: float):
        self.dir = out_dir
        self.slice_s = float(slice_s)
        self.started = None
        self.stopped = None
        self.stop_s = None
        self._opened = None
        self._timer = None

    def _start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = time.time()

    def __call__(self, k: int, now: float):
        import threading

        if k == 0:
            self._opened = now
        elif k == 1 and self._timer is None:
            delay = max(now - self._opened - self.slice_s, 0.0)
            self._timer = threading.Timer(delay, self._start)
            self._timer.daemon = True
            self._timer.start()
        elif k >= 2:
            self.finish()

    def finish(self):
        """Stop the trace if it runs; leave no timer and no session."""
        import jax

        if self._timer is not None:
            self._timer.cancel()
            self._timer.join()
        if self.started is not None and self.stopped is None:
            self.stopped = time.time()
            jax.profiler.stop_trace()
            self.stop_s = time.time() - self.stopped


# -------------------------------------------------------------------- run
def run(cell: dict, seed: int, seconds: float, traced: bool,
        t_start: float, peaks: dict, log: Callable = print,
        probe: Callable = None) -> dict:
    """One run of the cell: set-up, the measured window, the output
    check.  Returns the run record the metric readers take:

    ``cell seed replicas chunk episode_steps warm_episodes config peaks
    flops`` (what ran); ``t_start stamps opened closed_at window_episodes
    hook_s`` (the window: every boundary stamp, set-up's included);
    ``events`` (the run's hub events: ``episode`` with cumulative
    ``phases``, ``harness_episode``, ``learn_signal``, ``compile``,
    ``recovery``); ``memory_peak_bytes``; ``trace`` (``trace.reduce``'s
    result, traced runs only); ``correct compared values rng_after
    attempted failed error`` (the output check).  ``probe``
    (``control.py``) is handed the check's inputs afterwards, the
    configuration's reference module among them; a benchmark run passes
    none."""
    import jax
    import numpy as np

    from benchmarks import check
    from benchmarks.harness import Window, load_reference, memory_peak_bytes
    from gsc_tpu.agents.trainer import Trainer
    from gsc_tpu.cli import _build
    from gsc_tpu.obs import RunObserver

    cfg, wl = cell["config"], cell["cell"]
    ref = load_reference(cell)
    replicas, chunk = int(wl["replicas"]), int(wl["chunk"])
    steps = int(cfg["episode_steps"])
    warm = -(-int(cfg["nb_steps_warmup_critic"]) // steps)
    tmp = tempfile.mkdtemp(prefix="gsc-bench-")
    tracer = Tracer(os.path.join(tmp, "trace"), wl["trace_slice_s"]) \
        if traced else None
    window = Window(seconds, warm, on_boundary=tracer,
                    episodes=Tracer.EPISODES if traced else None)
    sink = MemorySink()
    record = {"cell": cell["name"], "seed": seed, "replicas": replicas,
              "chunk": chunk, "episode_steps": steps,
              "warm_episodes": warm, "t_start": t_start, "config": cfg,
              "peaks": peaks, "trace": None,
              "flops": ref.model_flops(cfg)}
    obs = None
    error = None
    try:
        paths = write_inputs(cfg, tmp)
        env, driver, agent = _build(
            paths["agent"], paths["simulator"], paths["service"],
            paths["scheduler"], seed, int(cfg["max_nodes"]),
            int(cfg["max_edges"]))
        rdir = os.path.join(tmp, "run")
        obs = RunObserver(rdir, snapshot_interval=10,
                          watchdog_budget_s=300.0, watchdog_escalate=3,
                          perf=True, learn=True, series_window=1024,
                          tags={"seed": seed})
        obs.hub.add_sink(sink)
        obs.start(meta={"replicas": replicas, "seed": seed,
                        "benchmark_cell": cell["name"]})
        trainer = Trainer(env, driver, agent, seed=seed, result_dir=rdir,
                          obs=obs)
        topo0, traffic0 = driver.episode(0, False)
        _, obs_shape = jax.eval_shape(env.reset, jax.random.PRNGKey(0),
                                      topo0, traffic0)
        state_shape = jax.eval_shape(trainer.ddpg.init,
                                     jax.random.PRNGKey(0), obs_shape)
        seeded, weights_host, rng0_host = host_init_state(
            seed, state_shape, ref.init_weights)
        node_mask = np.asarray(topo0.node_mask)
        recorder = Recorder(steps)
        try:
            # host arrays: the trainer's device copy is the only one
            state, buffers = trainer.train_parallel(
                EPISODES_CAP, num_replicas=replicas, chunk=chunk,
                verbose=False, init_state=seeded,
                ckpt_manager=recorder, ckpt_interval=1, preempt=window)
            jax.block_until_ready(state)
        except Exception as e:  # the run failed: no metric, not correct
            import traceback
            traceback.print_exc()
            error = f"{type(e).__name__}: {e}"
            state = buffers = None
        if tracer is not None:
            tracer.finish()
        record["memory_peak_bytes"] = memory_peak_bytes()
        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats:
                log("memory_stats", json.dumps({"device": d.id, **{
                    k: stats.get(k) for k in ("bytes_in_use",
                                              "peak_bytes_in_use",
                                              "bytes_limit")}}))
        final = None
        if state is not None:   # the ring's accounting, all the check reads
            final = {"size": np.asarray(buffers.size),
                     "pos": np.asarray(buffers.pos),
                     "capacity": int(jax.tree_util.tree_leaves(
                         buffers.data)[0].shape[1])}
        policy = None
        last = warm + window.episodes - 1
        if state is not None and window.episodes and last in recorder.actor:
            # the window's last episode: its rows are the ring's newest,
            # and the actor that drove it was copied as it began
            sample = check.sim_sample(seed, replicas)
            cap = final["capacity"]
            policy = {
                "episode": last, "sample": sample,
                "actor": {f"actor/{k}": np.asarray(v) for k, v in
                          leaf_table(recorder.actor[last]).items()},
                "rows": ring_rows(
                    buffers,
                    (np.asarray(sample)[:, None],
                     (last * steps + np.arange(steps))[None, :] % cap),
                    ("obs/", "action"))}
        recorder.actor.clear()
        after = None
        if recorder.state is not None:
            after = {k: np.asarray(v)
                     for k, v in leaf_table(recorder.state).items()}
        # free the program's device state before the reference runs
        del state, buffers, seeded, trainer
        obs.close(status="preempted" if error is None else "error")
        obs = None
        record.update(
            stamps=list(window.stamps), opened=window.opened,
            closed_at=window.closed_at, window_episodes=window.episodes,
            events=sink.events, recorder_s=recorder.seconds, error=error,
            hook_s=list(window.hook_s))
        for ev in sink.events:
            if ev.get("event") in ("compile", "recovery"):
                log("event", json.dumps(ev))
        log("boundaries", json.dumps(window.stamps))
        ended = {e["episode"]: e["ts"] for e in sink.events
                 if e.get("event") == "episode"}
        log("ckpt_check_s", json.dumps(
            {k: round(recorder.entered[k + 1] - ended[k], 3)
             for k in sorted(ended) if k + 1 in recorder.entered}))
        log("recorder_actor_s", json.dumps(
            {k - 1: round(v, 4) for k, v in sorted(recorder.actor_s.items())}))
        log("device_live_gb", json.dumps(
            {k - 1: v / 1e9 for k, v in sorted(recorder.live.items())}))
        if traced and tracer.started and tracer.stopped:
            from benchmarks import trace as trace_mod
            t0 = time.time()
            record["trace"] = trace_mod.reduce_dir(
                tracer.dir, tracer.started, tracer.stopped)
            log("trace", json.dumps({
                "slice_s": tracer.stopped - tracer.started,
                "stop_s": tracer.stop_s, "reduce_s": time.time() - t0,
                "events": record["trace"]["n_events"],
                "last_loops": record["trace"]["top_level_loops"][-4:]}))
        t_check = time.time()
        inputs = dict(ref=ref, weights=weights_host, rng=rng0_host,
                      rows=recorder.rows, after=after, final=final,
                      node_mask=node_mask, net_spec=paths["spec"],
                      policy=policy)
        record.update(check.decide(record, wl.get("limits", {}), log=log,
                                   **inputs))
        log("check_s", round(time.time() - t_check, 2), "recorder_s",
            round(recorder.seconds, 2), "run_s",
            round(time.time() - t_start, 2))
        if probe is not None:       # the control's and the faults' readings
            record["probe"] = probe(record, **inputs)
    finally:
        if obs is not None:
            obs.close(status="error")
        shutil.rmtree(tmp, ignore_errors=True)
    return record
