"""chip_smoke.py — the flagship train -> serve path, once, on one chip.

    python3 chip_smoke.py

One process drives the system's main path through the entry points a user
calls, at the full width ``cli init-configs`` writes (Abilene padded to 24
nodes / 37 edges, 22-feature 2-layer GATv2, actor hidden [256],
``episode_steps: 200``, M = 128 flow slots), all defaults:

1. **kernel** — the Pallas GAT, natively compiled (``interpret=False``
   forced), forward and backward through its custom VJP at the learn-burst
   shape (100 graphs x 24 x 22, f32 and bf16), against
   ``ops.gat.attention_dense`` within the tolerances stated in
   :data:`GAT_TOLERANCE`; and the rule that interpret mode is never chosen
   implicitly on a non-CPU backend;
2. **train** — ``cli train ... --replicas 256`` for 3 episodes x 200 steps
   (random-action warm-up episode, then two learned ones), the orbax
   checkpoint and the greedy test episode included;
3. **serve** — ``cli serve ... <that checkpoint> --requests 64
   --concurrency 4`` answered by the learned tier.

Every phase asserts what it ran and any failure fails the run: there is no
handler that logs and continues.  Exit code 0 and the last stdout line
``{"ok": true, "device": {...}}`` mean every phase passed on a TPU.  Where
JAX finds no TPU the script names what it found and exits non-zero before
compiling anything; it never selects a platform itself, has no CPU switch
and reads no environment variable of its own.  Run artefacts go to a fresh
temp dir (removed on success); the compile cache follows the one rule of
``gsc_tpu.runtime.enable_compile_cache``.

The phases are plain functions that take their sizes as arguments;
``main()`` is the only place that checks the platform and it fixes the
full width.  The CPU rehearsal (tiny sizes, interpret-mode Pallas) calls
the same functions from ``tests/test_chip_smoke.py``.

The seconds printed per phase (set-up = compile + warm-up, steady = the
rest) are plain facts about this run on the named device — not metrics,
and not to be quoted as rates.
"""
from __future__ import annotations

import contextlib
import io
import json
import math
import os
import shutil
import sys
import tempfile
import time

from gsc_tpu.runtime import enable_compile_cache, require_tpu

# |pallas - dense| <= atol + rtol * |dense|, dense computed at "highest"
# matmul precision.  f32: the kernel is f32-exact up to summation order.
# bf16: both sides round the pairwise features, the attention weights and
# the output to bf16 (8 mantissa bits, ulp 2^-8 relative) but XLA may keep
# excess precision between its own ops, so a few ulps of the output scale.
GAT_TOLERANCE = {"float32": {"rtol": 1e-5, "atol": 1e-5},
                 "bfloat16": {"rtol": 2e-2, "atol": 2e-2}}


def run_cli(args) -> str:
    """One click command, in this process; returns its last stdout line.
    ``standalone_mode=False``: errors propagate as exceptions."""
    from gsc_tpu.cli import cli

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cli.main(args=[str(a) for a in args], standalone_mode=False)
    return buf.getvalue().strip().splitlines()[-1]


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def _check_perf(run_dir: str, device: dict) -> dict:
    """perf.json names the device and takes its peaks from the one table
    — or, for a device not in it, carries no MFU field at all."""
    from gsc_tpu.obs.perf import DEVICE_PEAKS

    with open(os.path.join(run_dir, "perf.json")) as f:
        perf = json.load(f)
    assert perf["backend"] == device["platform"], perf["backend"]
    assert perf["device_kind"] == device["kind"], perf["device_kind"]
    assert perf["device_count"] == device["count"], perf["device_count"]
    row = DEVICE_PEAKS.get(device["kind"])
    if row is None:
        assert perf["peaks"] is None and "peaks_note" in perf, perf
        for name, e in perf["entries"].items():
            assert "mfu" not in e and "roofline" not in e, (name, e)
    else:
        assert perf["peaks"] == row, perf["peaks"]
    return perf


def phase_configs(work: str, agent_overrides=None, sim_overrides=None) -> list:
    """``cli init-configs`` into ``work/cfg``; returns the four config
    paths ``cli train|serve`` take.  The overrides (rehearsal only)
    shrink the written yaml."""
    import yaml

    cfg = os.path.join(work, "cfg")
    run_cli(["init-configs", "--out", cfg])
    for name, over in (("agent", agent_overrides),
                       ("simulator", sim_overrides)):
        if over:
            path = os.path.join(cfg, f"{name}.yaml")
            with open(path) as f:
                doc = yaml.safe_load(f)
            doc.update(over)
            with open(path, "w") as f:
                yaml.safe_dump(doc, f)
    return [os.path.join(cfg, name) for name in
            ("agent.yaml", "simulator.yaml", "service_abc.yaml",
             "scheduler.yaml")]


def phase_kernel(device: dict, *, graphs: int, nodes: int, features: int,
                 interpret: bool, steady_calls: int = 10) -> dict:
    """Pallas GAT forward + backward parity against ``attention_dense``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gsc_tpu.ops.gat import attention_dense
    from gsc_tpu.ops.pallas_gat import (_gatv2_pallas_impl, gatv2_pallas,
                                        resolve_interpret)

    on_cpu = device["platform"] == "cpu"
    # the rule: interpret is never chosen on a non-CPU backend unless the
    # caller passed interpret=True
    assert resolve_interpret(None) is on_cpu
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False

    def inputs(dtype):
        ks = jax.random.split(jax.random.PRNGKey(21), 6)
        adj = jax.random.bernoulli(ks[4], 0.3, (graphs, nodes, nodes))
        adj = adj | jnp.eye(nodes, dtype=bool)[None]
        adj = adj.at[:, nodes - 2:, :].set(False)   # padded rows: no nbrs
        return (jax.random.normal(ks[0], (graphs, nodes, features)
                                  ).astype(dtype),
                jax.random.normal(ks[1], (graphs, nodes, features)
                                  ).astype(dtype),
                jax.random.normal(ks[2], (features,)),
                jax.random.normal(ks[3], (features,)), adj,
                jax.random.normal(ks[5], (graphs, nodes, features)))

    def close(got, want, tol, what):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape, (what, got.shape, want.shape)
        assert np.isfinite(got).all(), f"{what}: non-finite values"
        # gradients sum many terms: scale atol by the reference's size
        atol = tol["atol"] * max(1.0, float(np.max(np.abs(want))))
        err = np.abs(got - want) - tol["rtol"] * np.abs(want)
        assert float(err.max()) <= atol, (
            f"{what}: max excess error {float(err.max()):.3e} over "
            f"atol {atol:.3e}")
        return float(np.max(np.abs(got - want)))

    report = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        tol = GAT_TOLERANCE[name]
        xl, xr, att, bias, adj, cot = inputs(dtype)

        def loss(fn, xl_, xr_, att_, bias_):
            return jnp.sum(fn(xl_, xr_, att_, bias_).astype(jnp.float32)
                           * cot)

        def fused(*a):
            return gatv2_pallas(*a, adj, True, None, interpret)

        def dense(*a):
            return attention_dense(*a, adj, True)

        if not on_cpu and not interpret:
            # what lowers is a Mosaic custom call, not an inlined body
            text = _gatv2_pallas_impl.lower(
                xl, xr, att, bias, adj, True, None, None).as_text()
            assert "tpu_custom_call" in text, \
                "default interpret=None did not lower natively"

        with jax.default_matmul_precision("highest"):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fused(xl, xr, att, bias))
            grads = jax.block_until_ready(jax.grad(
                lambda *a: loss(fused, *a), argnums=(0, 1, 2, 3))(
                xl, xr, att, bias))
            setup_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(steady_calls):
                last = fused(xl, xr, att, bias)
            jax.block_until_ready(last)
            steady_s = (time.perf_counter() - t0) / steady_calls
            want = dense(xl, xr, att, bias)
            want_grads = jax.grad(
                lambda *a: loss(dense, *a), argnums=(0, 1, 2, 3))(
                xl, xr, att, bias)
        assert out.dtype == dtype and out.shape == xl.shape
        errs = {"forward": close(out, want, tol, f"{name} forward")}
        for g, w, arg in zip(grads, want_grads, ("xl", "xr", "att", "bias")):
            errs[f"d_{arg}"] = close(g, w, tol, f"{name} d_{arg}")
        report[name] = {"max_abs_err": errs, "tolerance": tol,
                        "setup_s": round(setup_s, 3),
                        "steady_s_per_call": round(steady_s, 6)}
    return report


def phase_train(argv, result_dir: str, device: dict, *, replicas: int,
                episodes: int, chunk: int) -> dict:
    """``cli train --replicas B`` + checkpoint + greedy test episode."""
    from gsc_tpu.obs.trace import read_events

    t0 = time.perf_counter()
    out = json.loads(run_cli([
        "train", *argv, "--replicas", replicas, "--episodes", episodes,
        "--chunk", chunk, "--quiet", "--result-dir", result_dir]))
    wall_s = time.perf_counter() - t0
    run_dir = out["result_dir"]

    # one rewards.csv row per episode, every return finite
    with open(os.path.join(run_dir, "rewards.csv")) as f:
        rows = f.read().split()
    assert rows[0] == "r" and len(rows) == episodes + 1, rows
    assert all(math.isfinite(float(r)) for r in rows[1:]), rows

    events = read_events(run_dir)
    eps = [e for e in events if e["event"] == "episode"]
    assert [e["episode"] for e in eps] == list(range(episodes)), eps
    for e in eps:
        for key in ("episodic_return", "critic_loss", "actor_loss"):
            assert _finite(e.get(key)), (e["episode"], key, e.get(key))
    assert all(e.get("state_finite") is True for e in events
               if e["event"] == "harness_episode")
    ends = [e for e in events if e["event"] == "run_end"]
    assert len(ends) == 1 and ends[0]["status"] == "ok", ends

    # no retrace of the dispatch entry points after the second episode
    # (needs >= 3 episodes to say anything)
    assert episodes >= 3, "the retrace check needs a third episode"
    second_end = events.index(eps[1])
    late = [(e["fn"], e["count"]) for e in events[second_end:]
            if e["event"] == "compile" and e["stage"] == "trace"
            and e["fn"] in ("chunk_step", "reset_all")]
    assert not late, f"retraced after episode 1: {late}"
    assert any(e["event"] == "compile" and e["fn"] == "chunk_step"
               for e in events[:second_end]), "compile monitor saw nothing"

    # checkpoint + greedy test episode
    assert os.path.isdir(out["checkpoint"]), out["checkpoint"]
    assert _finite(out["mean_return"]) and _finite(out["final_succ_ratio"])

    # the run happened on this device and the learner state stayed there
    assert out["device"] == device, (out["device"], device)
    assert out["state_platforms"] == [device["platform"]], \
        out["state_platforms"]
    perf = _check_perf(run_dir, device)
    assert perf["entries"]["chunk_step"]["available"], perf["entries"]

    start_ts = events[0]["ts"]
    ep_end = [e["ts"] for e in eps]
    return {
        "checkpoint": out["checkpoint"],
        "returns": [float(r) for r in rows[1:]],
        "test_mean_return": out["mean_return"],
        # set-up: run start to the end of episode 0 (every dispatch entry
        # point compiles inside it); steady: the episodes after it
        "setup_s": round(ep_end[0] - start_ts, 1),
        "steady_s_per_episode": [round(b - a, 1)
                                 for a, b in zip(ep_end, ep_end[1:])],
        "test_episode_setup_s": out["compile_warmup_s"],
        "test_episode_steady_s": out["steady_s"],
        "wall_s": round(wall_s, 1),
    }


def phase_serve(argv, checkpoint: str, result_dir: str, device: dict, *,
                requests: int, concurrency: int) -> dict:
    """``cli serve`` on the trained checkpoint: the learned tier answers."""
    t0 = time.perf_counter()
    out = json.loads(run_cli([
        "serve", *argv, checkpoint, "--requests", requests,
        "--concurrency", concurrency, "--result-dir", result_dir]))
    wall_s = time.perf_counter() - t0
    assert out["tier"] == "learned", out["tier"]
    assert out["completed"] == requests and out["errors"] == 0, out
    assert out["device"] == device, (out["device"], device)
    assert sum(b["requests"] for b in out["buckets"].values()) == requests

    # each bucket that flushed has a compiled executable: prepared (and
    # warmed) at start-up, captured by the cost ledger, and timed
    perf = _check_perf(out["result_dir"], device)
    for b in out["buckets"]:
        assert b in out["startup"]["buckets"], (b, out["startup"])
        entry = perf["entries"][f"serve_policy_b{b}"]
        assert entry["available"] and entry["dispatches"] > 0, entry
    return {
        "flushed_buckets": {b: v["requests"]
                            for b, v in out["buckets"].items()},
        "setup_s": out["startup"]["startup_s"],
        "steady_s": out["wall_s"],
        "wall_s": round(wall_s, 1),
    }


def main() -> int:
    device = require_tpu("chip_smoke.py")      # first act: jax.devices()
    cache_dir = enable_compile_cache()

    import jax
    import jaxlib
    import libtpu

    print(f"[chip_smoke] device: {device['count']} x {device['kind']} "
          f"(platform {device['platform']}); jax {jax.__version__}, "
          f"jaxlib {jaxlib.__version__}, libtpu {libtpu.__version__}; "
          f"compile cache {cache_dir} "
          f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
          " entries at start)", flush=True)

    def report(phase, facts):
        print(f"[chip_smoke] {phase} on {device['kind']}: "
              f"{json.dumps(facts)}", flush=True)

    work = tempfile.mkdtemp(prefix="gsc_chip_smoke_")
    results = os.path.join(work, "results")
    report("kernel", phase_kernel(device, graphs=100, nodes=24, features=22,
                                  interpret=False))
    argv = phase_configs(work)
    train = phase_train(argv, results, device, replicas=256, episodes=3,
                        chunk=50)
    report("train", train)
    report("serve", phase_serve(argv, train["checkpoint"], results, device,
                                requests=64, concurrency=4))
    shutil.rmtree(work)
    print(f"[chip_smoke] compile cache {cache_dir}: "
          f"{len(os.listdir(cache_dir))} entries at end", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
