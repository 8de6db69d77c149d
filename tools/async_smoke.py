"""Async actor/learner smoke: the decoupled rollout/learn path end to
end through the real CLI.

CPU-only: this tool starts JAX child processes (and, for the smokes,
runs JAX in the parent first), and a chip belongs to one process at a
time — it refuses to start unless ``JAX_PLATFORMS=cpu``
(``gsc_tpu.runtime.require_cpu_env``).  Nothing it prints is a device
number.

The CI-stage proof that ``cli train --async`` actually executes the
Sebulba-style split: a tiny 3-episode, 2-replica, 2-actor CPU train run
must

- exit 0 with ``run_start`` recording the async knobs and the stream
  carrying one ``episode`` event per episode (completion order, episode
  index on every event) plus the drain-proved ``async_train`` tail
  (produced == ingested, zero transitions lost),
- stream with ZERO retraces: EXACTLY one trace each for
  ``rollout_episodes`` / ``reset_all`` / ``learn_burst`` /
  ``replay_ingest`` across every actor/learner interleaving
  (``--no-perf`` so the AOT capture does not add its own trace),
- land the staleness/decoupling gauges in metrics.json: ``policy_lag``,
  ``replay_lag``, ``learner_idle_frac``, ``replay_fill_frac`` and the
  ``actor_dispatch``/``learner_idle`` phase histograms,
- keep the learner-idle fraction under a GENEROUS smoke threshold
  (0.95 — a 3-episode CPU run is compile-dominated; the real <0.10
  bound is tools/async_bench.py's gate at measured steady state),
- gate through ``bench_diff``: an ASYNC-shaped row self-compares clean
  (rc 0) while an injected env-steps/s regression is caught (rc 1).

A second FORCED-4-DEVICE stage (fresh subprocess,
``--xla_force_host_platform_device_count=4`` — the parent's jax is
already initialised single-device) proves the ``--async --mesh``
composition end to end: ``cli train --async --mesh 4x1`` must exit 0
with the replay ring dp-sharded over all 4 devices
(``async_train.ring_shards == 4``) and ZERO collectives on the
compiled ingest (``ingest_collectives == 0`` — HLO-mined at prewarm),
the same one-trace-per-entry-point contract as the single-device
stage, a publisher version adopted by BOTH consumers — an actor
(an episode acted under ``policy_version >= 1``) and a serve-side
``VersionWatcher`` polling the ``--hot-swap-dir`` root — and a tp-only
mesh (``--mesh 1x4``) refused with recarve instructions.

Run by ``tools/ci_check.sh`` after the scenario stage; standalone:

    JAX_PLATFORMS=cpu python tools/async_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EPISODES = 3
ACTORS = 2
# compile-dominated tiny run: this only proves the ledger exists and is
# sane, not the steady-state decoupling claim (async_bench owns that)
SMOKE_IDLE_MAX = 0.95
# the mesh stage: enough episodes that a published version is adopted
# by a later-acting episode DETERMINISTICALLY under the default
# max_staleness=0 backpressure bound (two episodes per actor ahead max:
# by episode index >= 4 at least one burst has published)
MESH_DEVICES = 4
MESH_EPISODES = 6
MESH_TIMEOUT_S = 900


def _configure_jax():
    """CPU-only tool (its parent starts JAX children): refuse any other
    platform, then apply the repo's compile-cache rule."""
    from gsc_tpu.runtime import enable_compile_cache, require_cpu_env

    require_cpu_env("tools/async_smoke.py")
    enable_compile_cache()


def fail(msg: str) -> int:
    print(f"async smoke: FAIL — {msg}")
    return 1


def mesh_worker() -> int:
    """The forced-4-device stage body (own subprocess: the parent's jax
    is already initialised with one device)."""
    _configure_jax()
    import jax

    if len(jax.devices()) != MESH_DEVICES:
        return fail(f"mesh stage needs {MESH_DEVICES} forced host "
                    f"devices, found {len(jax.devices())}")
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    tmp = tempfile.mkdtemp(prefix="gsc_async_mesh_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    hot = os.path.join(tmp, "hot")

    # a tp-only carving of the same 4 devices is refused up front, with
    # recarve instructions, before any compile
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", "1", "--replicas", "4",
        "--async", "--mesh", "1x4",
        "--result-dir", os.path.join(tmp, "refused")])
    if r.exit_code == 0 or "dp" not in r.output:
        return fail(f"tp-only --async --mesh 1x4 not refused "
                    f"(rc={r.exit_code}): {r.output[-500:]}")

    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", str(MESH_EPISODES),
        "--replicas", str(MESH_DEVICES), "--chunk", "3",
        "--async", "--async-actors", str(ACTORS),
        "--mesh", f"{MESH_DEVICES}x1",
        "--hot-swap-dir", hot, "--publish-interval", "1",
        "--no-perf",
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code} under --async --mesh")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]
    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]

    # the composed-path accounting tail: ring sharded over every device,
    # zero collectives on the compiled ingest, nothing lost
    at = [e for e in events if e["event"] == "async_train"]
    if not at:
        return fail("no async_train accounting event in the stream")
    info = at[-1]
    if info.get("ring_shards") != MESH_DEVICES:
        return fail(f"ring_shards {info.get('ring_shards')} != "
                    f"{MESH_DEVICES} — the replay ring did not shard "
                    "over the mesh")
    if info.get("ingest_collectives") != 0:
        return fail(f"ingest_collectives {info.get('ingest_collectives')}"
                    " — the dp-sharded ingest is paying a gather/reshard")
    if info.get("mesh") != f"{MESH_DEVICES}x1":
        return fail(f"async_train mesh {info.get('mesh')!r}")
    if info["produced_steps"] != info["ingested_steps"] \
            or info["transitions_lost"] != 0:
        return fail(f"drain accounting broken under mesh: {info}")
    if info.get("publishes", 0) < 1:
        return fail(f"no publishes under mesh: {info}")

    # zero retrace after warmup, same contract as the single-device
    # stage: the sharded dispatch is PRE-built before actor threads
    # start, the ingest is AOT-compiled at prewarm (its one .lower()
    # counts as the single trace)
    traces = {}
    for e in events:
        if e["event"] == "compile" and e.get("stage") == "trace":
            traces[e["fn"]] = e.get("count")
    for fn in ("rollout_episodes", "reset_all", "learn_burst"):
        if traces.get(fn) != 1:
            return fail(f"expected exactly 1 {fn} trace under --mesh, "
                        f"saw {traces.get(fn)} (all: {traces})")
    if (traces.get("replay_ingest") or 0) > 1:
        return fail(f"replay_ingest traced {traces.get('replay_ingest')} "
                    f"times (want <= 1): {traces}")

    # publisher adoption, consumer 1 — an actor: with publish-interval 1
    # and the default staleness bound, a later episode must have ACTED
    # under a published version
    eps = [e for e in events if e["event"] == "episode"]
    if sorted(e["episode"] for e in eps) != list(range(MESH_EPISODES)):
        return fail(f"episode events cover "
                    f"{sorted(e['episode'] for e in eps)}")
    top_ver = max(e.get("policy_version", 0) for e in eps)
    if top_ver < 1:
        return fail("no actor adopted a published version "
                    f"(max episode policy_version {top_ver})")

    # publisher adoption, consumer 2 — a serve watcher polling the SAME
    # hot-swap root the learner published to (the one-publisher
    # contract: learner actors and the serving fleet read the same
    # bytes)
    from gsc_tpu.serve.fleet import VersionWatcher, read_latest

    rec = read_latest(hot)
    if rec is None or rec.get("version", 0) < 1:
        return fail(f"hot-swap root has no published version: {rec}")

    class _Server:
        policy_version = 0
        fingerprint = None

        def apply_weights(self, leaves, version, fingerprint, meta=None):
            self.policy_version = version
            self.fingerprint = fingerprint

    srv = _Server()
    watcher = VersionWatcher(hot, srv, publisher=None)
    if not watcher.poll_once():
        return fail("serve watcher did not swap to the published version")
    if srv.policy_version != rec["version"]:
        return fail(f"watcher adopted {srv.policy_version}, latest.json "
                    f"says {rec['version']}")

    print("async mesh smoke: OK — "
          f"{MESH_EPISODES} episodes over {ACTORS} actors on a "
          f"{MESH_DEVICES}x1 mesh, ring_shards={info['ring_shards']}, "
          f"ingest_collectives={info['ingest_collectives']}, "
          f"1 trace per entry point ({traces}), actor adopted v{top_ver}, "
          f"serve watcher adopted v{srv.policy_version}, tp-only refused")
    return 0


def main() -> int:
    _configure_jax()
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    tmp = tempfile.mkdtemp(prefix="gsc_async_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", str(EPISODES), "--replicas", "2",
        "--chunk", "3", "--async", "--async-actors", str(ACTORS),
        "--no-perf",   # the AOT cost capture would add its own trace —
                       # this stage pins the DISPATCH trace counts
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code} under --async")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]

    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]
    run_start = next(e for e in events if e["event"] == "run_start")
    knobs = run_start.get("async") or {}
    if knobs.get("actors") != ACTORS:
        return fail(f"run_start async knobs missing/wrong: {knobs}")

    # one episode event per episode, each stamped with its actor + the
    # policy version it acted under (completion order is allowed to
    # differ from index order — the index rides on every event)
    eps = [e for e in events if e["event"] == "episode"]
    if sorted(e["episode"] for e in eps) != list(range(EPISODES)):
        return fail(f"episode events cover "
                    f"{sorted(e['episode'] for e in eps)}, want "
                    f"{list(range(EPISODES))}")
    if not all("policy_version" in e and "actor" in e for e in eps):
        return fail("episode events missing actor/policy_version stamps")

    # the drain-proved tail: nothing lost, everything ingested
    at = [e for e in events if e["event"] == "async_train"]
    if not at:
        return fail("no async_train accounting event in the stream")
    info = at[-1]
    if info["produced_steps"] != info["ingested_steps"] \
            or info["transitions_lost"] != 0:
        return fail(f"drain accounting broken: {info}")
    if not (0.0 <= info["learner_idle_frac"] <= SMOKE_IDLE_MAX):
        return fail(f"learner_idle_frac {info['learner_idle_frac']} "
                    f"outside [0, {SMOKE_IDLE_MAX}]")

    # ZERO retraces across every actor/learner interleaving: exactly one
    # trace per async entry point (a second rollout_episodes trace means
    # an actor raced the jit cache; a second replay_ingest means the
    # ring/block shapes became a compile axis)
    traces = {}
    for e in events:
        if e["event"] == "compile" and e.get("stage") == "trace":
            traces[e["fn"]] = e.get("count")
    for fn in ("rollout_episodes", "reset_all", "learn_burst",
               "replay_ingest"):
        if traces.get(fn) != 1:
            return fail(f"expected exactly 1 {fn} trace across the async "
                        f"interleavings, saw {traces.get(fn)} "
                        f"(all: {traces})")

    # staleness/decoupling gauges + phase histograms in the snapshot
    snap = json.load(open(os.path.join(rdir, "metrics.json")))["metrics"]
    for g in ("gsc_policy_lag", "gsc_replay_lag", "gsc_learner_idle_frac",
              "gsc_replay_fill_frac", "gsc_actor_policy_version"):
        if not any(k.startswith(g + "{") for k in snap):
            return fail(f"metrics.json missing gauge {g}")
    for ph in ("actor_dispatch", "learner_idle", "replay_ingest"):
        if not any(f'phase="{ph}"' in k for k in snap):
            return fail(f"metrics.json missing phase histogram {ph!r}")
    end = events[-1]
    if end.get("event") != "run_end" or end.get("status") != "ok":
        return fail(f"stream tail {end}")

    # bench_diff gate over an ASYNC-shaped row: self-compare clean,
    # injected env-steps/s regression caught
    import bench_diff
    rate = (eps[-1].get("sps") if eps else None) or 1.0
    row = {"metric": "env_steps_per_sec_per_chip", "status": "ok",
           "async_actors": ACTORS,
           "sync_sps": round(float(rate), 2),
           "async2_sps": round(float(rate), 2),
           "learner_idle_frac": round(float(info["learner_idle_frac"]), 4),
           "jit_traces_async2": {fn: traces[fn] for fn in
                                 ("rollout_episodes", "reset_all",
                                  "learn_burst", "replay_ingest")}}
    row_path = os.path.join(tmp, "ASYNC_r99.json")
    with open(row_path, "w") as f:
        json.dump(row, f)
    traj = os.path.join(tmp, "traj.json")
    doc = bench_diff.ingest([row_path], traj)
    if "ASYNC_r99" not in doc["rows"]:
        return fail("bench_diff ingest did not scan the ASYNC row")
    got = doc["rows"]["ASYNC_r99"]["metrics"]
    if "learner_idle_frac" not in got or "async2_sps" not in got \
            or "async2_replay_ingest_jit_traces" not in got:
        return fail(f"ASYNC row metrics incomplete: {sorted(got)}")
    rc = bench_diff.main(["diff", "ASYNC_r99", "--baseline", "ASYNC_r99",
                          "--trajectory", traj])
    if rc != 0:
        return fail(f"ASYNC self-compare rc={rc} (want 0)")
    bad = dict(row, async2_sps=round(float(rate) * 0.5, 2))
    bad_path = os.path.join(tmp, "ASYNC_bad.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    rc = bench_diff.main(["diff", bad_path, "--baseline", "ASYNC_r99",
                          "--trajectory", traj])
    if rc != 1:
        return fail(f"injected env-steps/s regression rc={rc} (want 1)")

    print(f"async smoke: OK — {EPISODES} episodes over {ACTORS} actors "
          f"with 1 trace per entry point ({traces}), "
          f"produced==ingested=={info['ingested_steps']}, "
          f"learner_idle_frac={info['learner_idle_frac']}, "
          "ASYNC row gated both directions")

    # stage 2: the --async --mesh composition on 4 forced host devices
    # (fresh subprocess — THIS process's jax initialised single-device)
    import subprocess
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={MESH_DEVICES}"}
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker-mesh"],
            capture_output=True, text=True, timeout=MESH_TIMEOUT_S,
            env=env)
    except subprocess.TimeoutExpired:
        return fail(f"mesh stage timed out after {MESH_TIMEOUT_S}s")
    tail = (out.stdout + out.stderr).strip().splitlines()
    for line in tail[-25:]:
        print(f"  [mesh] {line}")
    if out.returncode != 0:
        return fail(f"mesh stage rc={out.returncode}")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    if "--worker-mesh" in sys.argv:
        sys.exit(mesh_worker())
    sys.exit(main())
