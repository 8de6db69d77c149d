"""Chaos smoke: tiny fault-injected train runs must self-heal to rc=0.

CPU-only: this tool starts JAX child processes (and, for the smokes,
runs JAX in the parent first), and a chip belongs to one process at a
time — it refuses to start unless ``JAX_PLATFORMS=cpu``
(``gsc_tpu.runtime.require_cpu_env``).  Nothing it prints is a device
number.

The CI-stage proof that the resilience subsystem's recovery paths actually
execute, in two legs:

**Serial leg** — a 4-episode CPU training run with an injected prefetcher
death AND a NaN-poisoned episode (``GSC_FAULT_PLAN``-style plan passed
via ``--fault-plan``) must

- exit 0 with a finite final learner state (state_finite == 1 on the last
  drained episode event),
- leave matching structured ``recovery`` events in the run's
  ``events.jsonl`` (site=prefetcher/action=restart and
  site=learner_state/action=rollback),
- end the stream with ``run_end status=ok``.

**Async leg** — a fresh-subprocess real-CLI ``train --async`` run under
``actor_die@a0:1;ring_poison@2;learner_transient@3`` must

- exit 0 with one matching ``recovery`` event per fired fleet site
  (actor/restart, replay/quarantine, learner/retry),
- carry the drain proof in its ``async_train`` event (produced ==
  ingested, transitions_lost == 0 — the poisoned block was dropped, not
  lost, and counted),
- adopt zero poisoned versions (no publish skip, no non-finite episode),
- leave no ``fault_plan_unfired`` entries.

Run by ``tools/ci_check.sh`` after the lint/report stages; standalone:

    JAX_PLATFORMS=cpu python tools/chaos_smoke.py

``--round OUT.json`` additionally banks a CHAOS_r* bench row: a
fault-free async control leg vs the chaos leg WITH a mid-run SIGTERM +
``--resume auto`` continuation — chaos_sps/control_sps land in
bench_diff's shared 15% ``_sps`` band, recoveries_total/actor_restarts
ride along as informational keys.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

# runnable from any cwd: the repo root is this file's parent's parent
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# NaN early so a post-rollback episode still drains (and proves finite)
# before the run ends; the prefetcher death hits the last staged episode
PLAN = "nan_grads@1;prefetch_die@3"
EXPECTED = {("prefetcher", "restart"), ("learner_state", "rollback")}

# the async fleet ladder: an actor death (restart), a poisoned replay
# block (quarantine) and a transient learn-burst dispatch (retry).  ONE
# actor thread so episode 1 is actor 0's (round-robin assignment keys
# actor_die@a0:<ep> to episodes that actor actually claims).
ASYNC_PLAN = "actor_die@a0:1;ring_poison@2;learner_transient@3"
ASYNC_EXPECTED = {("actor", "restart"), ("replay", "quarantine"),
                  ("learner", "retry")}


def _configure_jax():
    """CPU-only tool (its parent starts JAX children): refuse any other
    platform, then apply the repo's compile-cache rule."""
    from gsc_tpu.runtime import enable_compile_cache, require_cpu_env

    require_cpu_env("tools/chaos_smoke.py")
    enable_compile_cache()


def write_tiny_configs(cfg: str):
    """Smallest trainable scenario (mirrors the test suite's tiny-config
    shape): 3-node triangle, 3-step episodes, 8-wide nets."""
    import yaml

    from gsc_tpu.topology.synthetic import triangle, write_graphml

    os.makedirs(cfg, exist_ok=True)
    write_graphml(triangle(), os.path.join(cfg, "tri.graphml"))
    dump = lambda name, obj: yaml.safe_dump(
        obj, open(os.path.join(cfg, name), "w"))
    dump("svc.yaml", {
        "sfc_list": {"sfc_1": ["a", "b", "c"]},
        "sf_list": {n: {"processing_delay_mean": 5.0,
                        "processing_delay_stdev": 0.0} for n in "abc"}})
    dump("sim.yaml", {
        "inter_arrival_mean": 10.0, "deterministic_arrival": True,
        "flow_dr_mean": 1.0, "flow_dr_stdev": 0.0,
        "flow_size_shape": 0.001, "deterministic_size": True,
        "run_duration": 100, "ttl_choices": [100], "max_flows": 32})
    dump("agent.yaml", {
        "graph_mode": True, "episode_steps": 3, "objective": "prio-flow",
        "GNN_features": 4, "GNN_num_layers": 1, "GNN_num_iter": 1,
        "actor_hidden_layer_nodes": [8], "critic_hidden_layer_nodes": [8],
        "mem_limit": 32, "batch_size": 4, "nb_steps_warmup_critic": 3})
    dump("sched.yaml", {
        "training_network_files": [os.path.join(cfg, "tri.graphml")],
        "inference_network": os.path.join(cfg, "tri.graphml")})
    return [os.path.join(cfg, "agent.yaml"), os.path.join(cfg, "sim.yaml"),
            os.path.join(cfg, "svc.yaml"), os.path.join(cfg, "sched.yaml"),
            "--max-nodes", "8", "--max-edges", "8", "--quiet"]


def _cli_env() -> dict:
    """Fresh-subprocess environment: CPU jax (the CLI applies the repo's
    compile-cache rule itself, so the subprocess's compiles are disk
    hits)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return env


def _async_argv(args, episodes: int, res: str, plan=None, resume=False):
    argv = [sys.executable, "-m", "gsc_tpu.cli", "train", *args,
            "--episodes", str(episodes), "--replicas", "2", "--async",
            "--async-actors", "1", "--chunk", "3", "--result-dir", res]
    if plan:
        argv += ["--fault-plan", plan]
    if resume:
        argv += ["--resume", "auto"]
    return argv


def _read_events(rdir: str):
    return [json.loads(line)
            for line in open(os.path.join(rdir, "events.jsonl"))]


def _find_events_file(res_root: str):
    for root, _, files in os.walk(res_root):
        if "events.jsonl" in files:
            return os.path.join(root, "events.jsonl")
    return None


def _check_async_events(events, expect_sites=ASYNC_EXPECTED,
                        quarantined: int = 1, restarts: int = 1):
    """Shared assertions over one async chaos run's event stream; returns
    an error string or None."""
    seen = {(e.get("site"), e.get("action"))
            for e in events if e["event"] == "recovery"}
    missing = expect_sites - seen
    if missing:
        return f"recovery events missing {missing}; saw {seen}"
    at = [e for e in events if e["event"] == "async_train"]
    if not at:
        return "no async_train summary event"
    info = at[-1]
    # the drain proof: the quarantined block was dropped AND counted —
    # nothing produced went missing
    if info.get("produced_steps") != info.get("ingested_steps") \
            or info.get("transitions_lost") != 0:
        return (f"drain accounting broken: produced="
                f"{info.get('produced_steps')} ingested="
                f"{info.get('ingested_steps')} lost="
                f"{info.get('transitions_lost')}")
    if info.get("blocks_quarantined") != quarantined:
        return (f"expected {quarantined} quarantined block(s), got "
                f"{info.get('blocks_quarantined')}")
    if info.get("actor_restarts") != restarts:
        return (f"expected {restarts} actor restart(s), got "
                f"{info.get('actor_restarts')}")
    # zero poisoned versions adopted: nothing non-finite ever reached a
    # publish (no skip event) and no drained episode acted on a
    # non-finite state
    if any(e["event"] == "weight_publish_skipped" for e in events):
        return "a non-finite publish was attempted"
    bad = [e for e in events if e["event"] == "episode"
           and e.get("state_finite") not in (None, True, 1, 1.0)]
    if bad:
        return f"non-finite drained episode(s): {bad[:2]}"
    if any(e["event"] == "fault_plan_unfired" for e in events):
        return "fault plan entries never fired (mis-keyed plan)"
    return None


def serial_leg(tmp: str) -> int:
    from click.testing import CliRunner

    from gsc_tpu.cli import cli

    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", "4",
        "--result-dir", os.path.join(tmp, "res"),
        "--fault-plan", PLAN])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        print(f"chaos smoke: FAIL — train rc={r.exit_code} under plan "
              f"{PLAN!r}")
        return 1
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]
    events = _read_events(rdir)
    seen = {(e.get("site"), e.get("action"))
            for e in events if e["event"] == "recovery"}
    missing = EXPECTED - seen
    if missing:
        print(f"chaos smoke: FAIL — recovery events missing {missing}; "
              f"saw {seen}")
        return 1
    end = events[-1]
    if end.get("event") != "run_end" or end.get("status") != "ok":
        print(f"chaos smoke: FAIL — stream tail {end}")
        return 1
    episodes = [e for e in events if e["event"] == "episode"]
    # the LAST drained episode ran on the rolled-back (finite) state
    if not episodes or float(episodes[-1].get("state_finite", 0)) != 1.0:
        print("chaos smoke: FAIL — final drained episode not finite: "
              f"{episodes[-1] if episodes else None}")
        return 1
    print(f"chaos smoke: OK — serial leg survived {PLAN!r} "
          f"({sorted(seen)} recoveries, run_end status=ok)")
    return 0


def async_leg(tmp: str) -> int:
    """Fresh-subprocess real-CLI `train --async` under the fleet plan."""
    args = write_tiny_configs(os.path.join(tmp, "acfg"))
    res = os.path.join(tmp, "ares")
    proc = subprocess.run(
        _async_argv(args, 6, res, plan=ASYNC_PLAN), cwd=REPO,
        env=_cli_env(), capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print(proc.stdout)
        print(proc.stderr)
        print(f"chaos smoke: FAIL — async train rc={proc.returncode} "
              f"under plan {ASYNC_PLAN!r}")
        return 1
    rdir = json.loads(proc.stdout.strip().splitlines()[-1])["result_dir"]
    events = _read_events(rdir)
    err = _check_async_events(events)
    if err:
        print(f"chaos smoke: FAIL — async leg: {err}")
        return 1
    end = events[-1]
    if end.get("event") != "run_end" or end.get("status") != "ok":
        print(f"chaos smoke: FAIL — async stream tail {end}")
        return 1
    info = [e for e in events if e["event"] == "async_train"][-1]
    print(f"chaos smoke: OK — async leg survived {ASYNC_PLAN!r} "
          f"(restart+quarantine+retry recoveries, "
          f"produced=ingested={info['produced_steps']}, "
          f"run_end status=ok)")
    return 0


def bank_round(out_path: str) -> int:
    """The CHAOS_r* bench row: fault-free async control vs the chaos leg
    with a mid-run SIGTERM + `--resume auto` continuation.  Rates come
    from each run's async_train summary (produced_steps / wall_s — the
    fleet's own drain-proof ledger), so the chaos leg's rate folds in
    every recovery detour it took."""
    tmp = tempfile.mkdtemp(prefix="gsc_chaos_round_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    episodes = 40

    # ---- control: fault-free async run, fresh subprocess
    cres = os.path.join(tmp, "control")
    proc = subprocess.run(_async_argv(args, episodes, cres), cwd=REPO,
                          env=_cli_env(), capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        print(proc.stderr)
        print(f"chaos round: FAIL — control rc={proc.returncode}")
        return 1
    crdir = json.loads(proc.stdout.strip().splitlines()[-1])["result_dir"]
    cinfo = [e for e in _read_events(crdir)
             if e["event"] == "async_train"][-1]
    control_sps = cinfo["produced_steps"] / cinfo["wall_s"]

    # ---- chaos: plan + mid-run SIGTERM once every site has fired
    xres = os.path.join(tmp, "chaos")
    proc = subprocess.Popen(
        _async_argv(args, episodes, xres, plan=ASYNC_PLAN), cwd=REPO,
        env=_cli_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        deadline = time.time() + 600
        fired = False
        # preempt only once every site has fired AND the run has drained
        # enough episodes for the startup wall and the recovery detours
        # to amortize — a rate measured over 4 episodes is a startup
        # benchmark, not a chaos one
        min_drained = (3 * episodes) // 4
        while time.time() < deadline and proc.poll() is None:
            p = _find_events_file(xres)
            if p is not None:
                seen = set()
                drained = 0
                for line in open(p):
                    try:   # the live stream's last line may be torn
                        e = json.loads(line)
                    except ValueError:
                        continue
                    if e.get("event") == "recovery":
                        seen.add((e.get("site"), e.get("action")))
                    elif e.get("event") == "episode":
                        drained += 1
                if ASYNC_EXPECTED <= seen and drained >= min_drained:
                    fired = True
                    break
            time.sleep(0.25)
        if proc.poll() is not None:
            # every site fired before we could preempt — tolerated, the
            # resume below then continues a COMPLETED run's checkpoint
            out, err2 = proc.communicate()
        elif not fired:
            proc.kill()
            print("chaos round: FAIL — fault sites never all fired")
            return 1
        else:
            proc.send_signal(signal.SIGTERM)
            out, err2 = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    if proc.returncode != 0:
        print(err2)
        print(f"chaos round: FAIL — chaos leg rc={proc.returncode} "
              f"(SIGTERM must exit 0 with a snapshot)")
        return 1
    tail = json.loads(out.strip().splitlines()[-1])
    preempted = tail.get("status") == "preempted"
    if preempted and ((tail.get("drain") or {}).get("transitions_lost")
                      != 0):
        print(f"chaos round: FAIL — preempt drain proof missing: {tail}")
        return 1
    xrdir = tail["result_dir"]
    xevents = _read_events(xrdir)
    err = _check_async_events(xevents)
    if err:
        print(f"chaos round: FAIL — chaos leg: {err}")
        return 1
    xinfo = [e for e in xevents if e["event"] == "async_train"][-1]

    # ---- resume: fault-free continuation from the snapshot
    done = tail.get("episodes_completed", episodes)
    resumed = 0
    if preempted:
        proc = subprocess.run(
            _async_argv(args, episodes, xres, resume=True), cwd=REPO,
            env=_cli_env(), capture_output=True, text=True, timeout=1800)
        if proc.returncode != 0:
            print(proc.stderr)
            print(f"chaos round: FAIL — resume rc={proc.returncode}")
            return 1
        rrdir = json.loads(
            proc.stdout.strip().splitlines()[-1])["result_dir"]
        reps = [e["episode"] for e in _read_events(rrdir)
                if e["event"] == "episode"]
        if not reps or min(reps) < done:
            print(f"chaos round: FAIL — resume re-ran below the "
                  f"snapshot's counter ({done}): {sorted(reps)[:5]}")
            return 1
        resumed = len(reps)

    chaos_sps = xinfo["produced_steps"] / xinfo["wall_s"]
    recoveries = sum(1 for e in xevents if e["event"] == "recovery")
    row = {
        "metric": "env_steps_per_sec_per_chip", "unit": "env-steps/s",
        "status": "ok", "platform": "cpu", "round": "chaos",
        "plan": ASYNC_PLAN, "replicas": 2, "async_actors": 1,
        "chunk": 3, "episode_steps": 3, "episodes": episodes,
        "control_sps": round(control_sps, 2),
        "chaos_sps": round(chaos_sps, 2),
        "chaos_vs_control": round(chaos_sps / control_sps, 4),
        "recoveries_total": recoveries,
        "actor_restarts": xinfo["actor_restarts"],
        "blocks_quarantined": xinfo["blocks_quarantined"],
        "preempted": preempted,
        "episodes_at_preempt": done if preempted else None,
        "episodes_resumed": resumed,
    }
    with open(out_path, "w") as f:
        json.dump(row, f, indent=1)
        f.write("\n")
    print(f"chaos round: OK — banked {out_path} "
          f"(chaos {row['chaos_sps']} vs control {row['control_sps']} "
          f"env-steps/s, {recoveries} recoveries, "
          f"preempted={preempted} resumed={resumed})")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    _configure_jax()
    if argv and argv[0] == "--round":
        return bank_round(argv[1] if len(argv) > 1
                          else os.path.join(REPO, "CHAOS_r01.json"))
    tmp = tempfile.mkdtemp(prefix="gsc_chaos_")
    rc = serial_leg(tmp)
    if rc:
        return rc
    return async_leg(tmp)


if __name__ == "__main__":
    sys.exit(main())
