"""Multi-host / multi-chip dryruns on virtual CPU meshes.

Two modes, no TPU needed for either:

**Multi-PROCESS mode** (default): the full sharded train step across N
separate processes, each owning a slice of a virtual CPU mesh —
``jax.distributed.initialize`` over a localhost coordinator, a global
mesh from all processes' devices, per-process host data fed in via
``host_local_array_to_global_array``, one rollout+learn step whose
gradient psum crosses process boundaries.  Same SPMD code path a v5e-16
data-parallel run takes, with gRPC standing in for ICI/DCN.

**Mesh-MATRIX mode** (``--mesh-matrix``): the pjit-sharded single-process
path (``parallel.partition.ShardingPlan``) across a matrix of mesh
carvings and partition rulebooks, proving the PR 8 contract end to end:

- every ``DPxMP`` carving of the same device count produces a
  BIT-IDENTICAL final learner state — **including legs whose parameters
  are actually sharded over mp** (the leg rows record how many leaves
  were split);
- an elastic-resume leg checkpoints a run on an 8-device mesh and
  resumes it in a fresh 4-device process via ``cli train --resume auto``
  (host-gathered checkpoints reshard onto whatever mesh the resuming
  process builds), asserting the episode counter stays monotone;
- ``tp`` legs (PR 13: true tensor-parallel compute, psum-accumulated
  contractions) are EXEMPT from the digest set by design — their
  acceptance is BANDED: each tp leg's learning-curve envelope
  (final-window return, AUC) must land inside the bench_diff tolerance
  bands against the bit-exact control legs (``tools/bench_diff.py``'s
  ``final_window_return``/``auc_return`` rules — one definition of the
  band, shared with CI's curve gating).

Both modes are CPU-only by construction: the launcher never imports jax
and every leg runs in a fresh subprocess whose environment pins
``JAX_PLATFORMS=cpu`` with its own forced host-device count, under its
own timeout budget.  A failed leg emits a structured
``{"status": "failed", "reason": ...}`` row (never a bare timeout tail)
and the remaining legs still run.  ``--bank PATH`` writes the whole round
as a MULTICHIP_r*.json artifact with per-leg mesh shapes.

Launcher::

    python tools/dryrun_multihost.py                 # 2 procs x 4 devices
    python tools/dryrun_multihost.py --procs 2 --devices-per-proc 2
    python tools/dryrun_multihost.py --mesh-matrix   # carving bit-equality
    python tools/dryrun_multihost.py --mesh-matrix --elastic \\
        --bank MULTICHIP_r06.json                    # full banked round
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: default carving matrix: same 8 devices, three carvings, both bit-exact
#: rulebooks at the extremes — all final-state digests must agree (the
#: replicated 8x1 leg doubles as the "rules are a no-op fallback"
#: witness) — plus two tensor-parallel legs whose curves must land
#: inside the tolerance bands vs those controls (never in the digest
#: set: tp trades bit-equality for psum-parallel compute).
DEFAULT_LEGS = ("8x1:replicated,8x1:sharded,4x2:sharded,2x4:sharded,"
                "1x8:sharded,4x2:tp,2x4:tp")
LEG_TIMEOUT = 600      # per-leg budget: tiny stack, warm cache is ~1 min


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _cpu_env(n_devices: int) -> dict:
    """Subprocess env pinned to an n-device virtual CPU platform (each
    leg applies the repo's compile-cache rule itself, so repeat legs are
    disk hits)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = REPO
    return env


def _tail(text: str, n: int = 800) -> str:
    return (text or "")[-n:]


# ------------------------------------------------------------- mesh matrix
def mesh_leg(shape: str, rules: str, episodes: int, replicas: int) -> None:
    """One carving leg (runs in its own subprocess): chunked episodes of
    the tiny flagship stack under a ShardingPlan, final learner state
    digested with sha256 over the host-gathered leaves.  The recipe is
    ``__graft_entry__.sharded_training_leg`` — shared with
    tests/test_multichip.py so the CI verdict and the tier-1 test agree
    on what "bit-identical" means.  Prints ONE JSON row the launcher
    parses."""
    sys.path.insert(0, REPO)
    from __graft_entry__ import sharded_training_leg
    from gsc_tpu.parallel import ShardingPlan
    from gsc_tpu.runtime import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    plan = ShardingPlan.from_spec(shape, rules=rules)
    leg = sharded_training_leg(plan, episodes=episodes, replicas=replicas)
    print(json.dumps({
        "status": "ok", "leg": "carving", "mesh": plan.describe(),
        "rules": rules, "replicas": replicas, "episodes": episodes,
        "digest": leg["digest"],
        "final_return": round(leg["final_return"], 6),
        # the whole per-episode curve: tp legs gate on its envelope
        # (bench_diff bands) instead of joining the digest set
        "returns": [round(r, 6) for r in leg["returns"]],
        "sharded_leaves": leg["sharded_leaves"],
        "spec_counts": leg["spec_counts"],
        "wall_s": round(time.time() - t0, 1)}), flush=True)


def _parse_leg_row(stdout: str):
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            row = json.loads(line)
            if isinstance(row, dict) and "status" in row:
                return row
        except json.JSONDecodeError:
            continue
    return None


def run_leg(shape: str, rules: str, episodes: int, replicas: int,
            n_devices: int, timeout: int) -> dict:
    """Launch one carving leg in a fresh subprocess under its timeout
    budget; structured failed row on timeout / crash / unparseable
    output."""
    cmd = [sys.executable, os.path.abspath(__file__), "--mesh-leg",
           shape, rules, str(episodes), str(replicas)]
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True, env=_cpu_env(n_devices))
    except subprocess.TimeoutExpired as e:
        return {"status": "failed", "leg": "carving", "mesh": shape,
                "rules": rules,
                "reason": f"leg timed out after {timeout}s",
                "tail": _tail(e.stderr.decode() if isinstance(
                    e.stderr, bytes) else e.stderr)}
    row = _parse_leg_row(r.stdout)
    if r.returncode != 0 or row is None:
        return {"status": "failed", "leg": "carving", "mesh": shape,
                "rules": rules,
                "reason": f"leg exited rc={r.returncode}"
                          + ("" if row else " with no parseable row"),
                "tail": _tail(r.stderr)}
    return row


def _write_tiny_configs(cfg_dir: str) -> list:
    """Minimal triangle config quadruple for the elastic-resume legs
    (mirrors tests/test_agent.write_tiny_configs — duplicated here so the
    tool never imports the test tree)."""
    import yaml

    sys.path.insert(0, REPO)
    from gsc_tpu.topology.synthetic import triangle, write_graphml

    os.makedirs(cfg_dir, exist_ok=True)
    write_graphml(triangle(), os.path.join(cfg_dir, "tri.graphml"))
    with open(os.path.join(cfg_dir, "svc.yaml"), "w") as f:
        yaml.safe_dump({
            "sfc_list": {"sfc_1": ["a", "b", "c"]},
            "sf_list": {n: {"processing_delay_mean": 5.0,
                            "processing_delay_stdev": 0.0}
                        for n in "abc"}}, f)
    with open(os.path.join(cfg_dir, "sim.yaml"), "w") as f:
        yaml.safe_dump({
            "inter_arrival_mean": 10.0, "deterministic_arrival": True,
            "flow_dr_mean": 1.0, "flow_dr_stdev": 0.0,
            "flow_size_shape": 0.001, "deterministic_size": True,
            "run_duration": 100, "ttl_choices": [100], "max_flows": 32}, f)
    with open(os.path.join(cfg_dir, "agent.yaml"), "w") as f:
        yaml.safe_dump({
            "graph_mode": True, "episode_steps": 3,
            "objective": "prio-flow", "GNN_features": 4,
            "GNN_num_layers": 1, "GNN_num_iter": 1,
            "actor_hidden_layer_nodes": [8],
            "critic_hidden_layer_nodes": [8],
            "mem_limit": 32, "batch_size": 4,
            "nb_steps_warmup_critic": 3}, f)
    with open(os.path.join(cfg_dir, "sched.yaml"), "w") as f:
        yaml.safe_dump({
            "training_network_files": [os.path.join(cfg_dir,
                                                    "tri.graphml")],
            "inference_network": os.path.join(cfg_dir, "tri.graphml")}, f)
    return [os.path.join(cfg_dir, "agent.yaml"),
            os.path.join(cfg_dir, "sim.yaml"),
            os.path.join(cfg_dir, "svc.yaml"),
            os.path.join(cfg_dir, "sched.yaml"),
            "--max-nodes", "8", "--max-edges", "8", "--quiet"]


def elastic_leg(from_mesh: str, to_mesh: str, from_devices: int,
                to_devices: int, replicas: int, timeout: int) -> dict:
    """Checkpoint a sharded run on ``from_mesh`` (``from_devices``
    devices), then resume it via ``--resume auto`` in a FRESH process
    that only has ``to_devices`` devices and builds ``to_mesh`` — the
    lost-hosts scenario.  The resumed run must continue with a monotone
    episode counter.  Callers derive mesh shapes and ``replicas`` from
    the actual device counts (run_matrix does) — cli train refuses a
    mesh its backend cannot provide, so a mislabeled row cannot bank."""
    import tempfile

    t0 = time.time()
    work = tempfile.mkdtemp(prefix="gsc_elastic_")
    cfg = _write_tiny_configs(os.path.join(work, "cfg"))
    res = os.path.join(work, "res")
    base = [sys.executable, "-m", "gsc_tpu.cli", "train", *cfg,
            "--replicas", str(replicas), "--chunk", "3",
            "--partition-rules", "sharded", "--result-dir", res]
    row = {"leg": "elastic_resume", "from_mesh": from_mesh,
           "to_mesh": to_mesh, "from_devices": from_devices,
           "to_devices": to_devices}
    try:
        r1 = subprocess.run(
            base + ["--mesh", from_mesh, "--episodes", "2",
                    "--ckpt-interval", "1"],
            timeout=timeout, capture_output=True, text=True, cwd=REPO,
            env=_cpu_env(from_devices))
        if r1.returncode != 0:
            return {**row, "status": "failed",
                    "reason": f"first run exited rc={r1.returncode}",
                    "tail": _tail(r1.stderr)}
        r2 = subprocess.run(
            base + ["--mesh", to_mesh, "--episodes", "4",
                    "--resume", "auto"],
            timeout=timeout, capture_output=True, text=True, cwd=REPO,
            env=_cpu_env(to_devices))
        if r2.returncode != 0:
            return {**row, "status": "failed",
                    "reason": f"resume run exited rc={r2.returncode}",
                    "tail": _tail(r2.stderr)}
    except subprocess.TimeoutExpired as e:
        return {**row, "status": "failed",
                "reason": f"elastic leg timed out after {timeout}s",
                "tail": _tail(e.stderr.decode() if isinstance(
                    e.stderr, bytes) else e.stderr)}
    # the resumed run's events must continue past the checkpointed count.
    # Episodes are grouped PER RUN (keyed by the run_start mesh, like
    # tests/test_multichip.py) — a pooled >=2 filter would read a resume
    # that silently restarted at 0 and ran 0..3 as a monotone [2, 3]
    by_mesh: dict = {}
    for root, _, files in os.walk(res):
        if "events.jsonl" in files:
            mesh_key, eps = None, []
            with open(os.path.join(root, "events.jsonl")) as f:
                for line in f:
                    try:
                        ev = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if ev.get("event") == "run_start":
                        mesh_key = ev.get("mesh")
                    elif ev.get("event") == "episode":
                        eps.append(ev["episode"])
            by_mesh.setdefault(mesh_key, []).extend(eps)
    first = sorted(by_mesh.get(from_mesh, []))
    resumed = sorted(by_mesh.get(to_mesh, []))
    if first != [0, 1] or resumed != [2, 3]:
        return {**row, "status": "failed",
                "reason": "resumed episode counter not monotone from the "
                          f"checkpoint (expected {from_mesh}=[0, 1] then "
                          f"{to_mesh}=[2, 3], got {from_mesh}={first} "
                          f"{to_mesh}={resumed})"}
    return {**row, "status": "ok", "resumed_episodes": resumed,
            "wall_s": round(time.time() - t0, 1)}


def _curve_envelope(returns) -> dict:
    """The learning-curve envelope of a leg's per-episode returns —
    the same two length-robust metrics ``gsc_tpu.obs.curves`` banks
    (final-window return with w = min(10, len), AUC = mean), computed
    with plain arithmetic so the launcher stays jax-free."""
    returns = [float(r) for r in returns or []]
    if not returns:
        return {}
    w = min(10, len(returns))
    return {"final_window_return": sum(returns[-w:]) / w,
            "auc_return": sum(returns) / len(returns)}


def _gate_tp_legs(tp_legs: list, exact_legs: list) -> list:
    """Banded acceptance for tp carving legs: each leg's envelope vs
    the bit-exact control legs' (first ok control), under the SAME
    tolerance bands bench_diff applies to curves.json rows — one band
    definition, so this verdict and the CI curve gate can never
    disagree on what 'inside the envelope' means.  One verdict row per
    tp leg; an empty list gates nothing (no tp legs requested)."""
    if not tp_legs:
        return []
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from bench_diff import metric_rule  # stdlib-only, jax-free

    if not exact_legs:
        return [{"mesh": r.get("mesh"), "ok": False,
                 "reason": "no bit-exact control leg to band against"}
                for r in tp_legs]
    control = _curve_envelope(exact_legs[0].get("returns"))
    out = []
    for leg in tp_legs:
        env = _curve_envelope(leg.get("returns"))
        row = {"mesh": leg.get("mesh"), "ok": True,
               "control_mesh": exact_legs[0].get("mesh")}
        if not env or not control:
            row.update(ok=False,
                       reason="leg or control row carries no returns "
                              "(pre-PR13 artifact?)")
            out.append(row)
            continue
        for name, base in control.items():
            higher, tol, floor = metric_rule(name)
            band = max(tol * abs(base), floor)
            cur = env[name]
            delta = (base - cur) if higher else (cur - base)
            row[name] = {"current": round(cur, 6),
                         "baseline": round(base, 6),
                         "band": round(band, 6)}
            if delta > band:
                row["ok"] = False
                row["reason"] = (f"{name} {cur:.6g} outside band "
                                 f"{band:.6g} of control {base:.6g}")
        out.append(row)
    return out


def run_matrix(legs: str, episodes: int, replicas: int, n_devices: int,
               leg_timeout: int, elastic: bool, bank: str) -> int:
    """The full round: carving legs (per-leg budgets) + optional
    elastic-resume leg, bit-equality verdict, optional MULTICHIP_r*.json
    artifact."""
    sys.path.insert(0, REPO)
    from gsc_tpu.meshspec import (PARTITION_RULEBOOKS,  # jax-free
                                  validate_partition_rules)
    parsed = []
    for cell in legs.split(","):
        cell = cell.strip()
        if not cell:
            continue
        shape, _, rules = cell.partition(":")
        rules = rules or "replicated"
        try:
            validate_partition_rules(rules)
        except ValueError:
            print(json.dumps({
                "status": "failed",
                "reason": f"leg {cell!r}: rules must be "
                          + "|".join(PARTITION_RULEBOOKS)}))
            return 2
        parsed.append((shape, rules))

    rows = []
    for shape, rules in parsed:
        row = run_leg(shape, rules, episodes, replicas, n_devices,
                      leg_timeout)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if elastic:
        # meshes/replicas DERIVED from the device count so the banked row
        # always describes the run (8 devices: 4x2 -> 4x1, the default)
        if n_devices < 2 or n_devices % 2:
            row = {"leg": "elastic_resume", "status": "failed",
                   "reason": f"--elastic needs an even device count >= 2 "
                             f"to halve, got {n_devices}"}
        else:
            half = n_devices // 2
            row = elastic_leg(f"{half}x2", f"{half}x1",
                              from_devices=n_devices, to_devices=half,
                              replicas=n_devices, timeout=leg_timeout * 2)
        rows.append(row)
        print(json.dumps(row), flush=True)

    ok_carvings = [r for r in rows
                   if r.get("leg") == "carving" and r["status"] == "ok"]
    # tp legs trade bit-equality for psum-parallel compute: they NEVER
    # join the digest set — they gate on the curve-envelope bands below
    exact = [r for r in ok_carvings if r.get("rules") != "tp"]
    tp_legs = [r for r in ok_carvings if r.get("rules") == "tp"]
    digests = {r["digest"] for r in exact}
    sharded_proven = any(r.get("sharded_leaves", 0) > 0
                         for r in ok_carvings)
    all_ok = all(r["status"] == "ok" for r in rows)
    exact_requested = [r for r in rows if r.get("leg") == "carving"
                       and r.get("rules") != "tp"]
    # a tp-ONLY matrix has no digest claim to make — bit-equality is
    # vacuously true and the tp gate below reports the real problem
    # ("no bit-exact control leg to band against"), not an empty set
    bit_equal = len(exact) == len(exact_requested) \
        and (len(digests) == 1 if exact_requested else True)
    tp_verdicts = _gate_tp_legs(tp_legs, exact)
    tp_clean = all(v["ok"] for v in tp_verdicts)
    verdict = {
        "status": "ok" if (all_ok and bit_equal and tp_clean)
        else "failed",
        "mode": "mesh_matrix", "devices": n_devices,
        "legs_ok": len([r for r in rows if r["status"] == "ok"]),
        "legs_total": len(rows),
        "bit_equal_across_carvings": bit_equal,
        "sharded_params_proven": sharded_proven,
    }
    if tp_legs:
        verdict["tp_legs"] = len(tp_legs)
        verdict["tp_within_band"] = tp_clean
        verdict["tp_envelope"] = tp_verdicts
    if not all_ok:
        verdict["reason"] = "; ".join(
            f"{r.get('mesh', r.get('leg'))}: {r['reason']}"
            for r in rows if r["status"] != "ok")[:500]
    elif not bit_equal:
        verdict["reason"] = (f"final-state digests diverge across "
                             f"carvings: {sorted(digests)}")
    elif not tp_clean:
        verdict["reason"] = "; ".join(
            f"tp {v['mesh']}: {v['reason']}"
            for v in tp_verdicts if not v["ok"])[:500]
    print(json.dumps(verdict), flush=True)
    if bank:
        artifact = {**verdict, "ok": verdict["status"] == "ok",
                    "legs": rows}
        tmp = bank + ".tmp"
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=1)
        os.replace(tmp, bank)
        print(f"[dryrun] banked {bank}", file=sys.stderr)
    return 0 if verdict["status"] == "ok" else 1


# ----------------------------------------------------------- multi-process
def launch(procs: int, devices_per_proc: int, timeout: int = 600) -> int:
    import tempfile

    port = _free_port()
    workers = []
    for pid in range(procs):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices_per_proc}")
        # workers write to FILES, not pipes: they block on collectives
        # together, and one worker stalling on a full 64 KB stdout pipe
        # while the launcher drains another would deadlock the whole run
        log = tempfile.NamedTemporaryFile(mode="w+", prefix=f"mh{pid}_",
                                          suffix=".log", delete=False)
        workers.append((subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             str(pid), str(procs), str(port), str(devices_per_proc)],
            env=env, stdout=log, stderr=subprocess.STDOUT), log))
    rc = 0
    timed_out = []
    deadline = time.time() + timeout
    for pid, (w, log) in enumerate(workers):
        try:
            w.wait(timeout=max(deadline - time.time(), 1))
        except subprocess.TimeoutExpired:
            w.kill()
            w.wait()
            timed_out.append(pid)
            rc = rc or 124
        log.flush()
        log.seek(0)
        out = log.read()
        log.close()
        os.unlink(log.name)
        sys.stderr.write(f"--- worker {pid} (rc={w.returncode}) ---\n"
                         + out[-2000:])
        if pid == 0 and w.returncode == 0:
            for line in out.splitlines():
                if line.startswith("dryrun_multihost"):
                    print(line)
        rc = rc or w.returncode
    if rc != 0:
        # a structured reason the caller (and any banked artifact) can
        # read, never just a log tail
        print(json.dumps({
            "status": "failed", "mode": "multi_process",
            "procs": procs, "devices_per_proc": devices_per_proc,
            "reason": (f"workers {timed_out} timed out after {timeout}s"
                       if timed_out else f"a worker exited rc={rc}")}))
    return rc


def worker(pid: int, procs: int, port: int, devices_per_proc: int) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, REPO)
    from gsc_tpu.parallel.mesh import init_distributed

    init_distributed(coordinator=f"localhost:{port}",
                     num_processes=procs, process_id=pid)
    assert jax.process_count() == procs
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    print(f"[worker {pid}] global devices={n_global} local={n_local}")
    assert n_local == devices_per_proc, (n_local, devices_per_proc)

    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    from __graft_entry__ import _flagship
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.parallel.mesh import make_hybrid_mesh
    from gsc_tpu.sim.traffic import generate_traffic

    env, agent, topo, _ = _flagship(max_nodes=8, max_edges=8,
                                    episode_steps=2, max_flows=32,
                                    gen_traffic=False)
    B = n_global            # one env replica per global device
    B_local = n_local
    mesh = make_hybrid_mesh()           # [procs, local] (dcn, dp)
    spec = P(("dcn", "dp"))             # replicas sharded over both axes

    def to_global(tree):
        return multihost_utils.host_local_array_to_global_array(
            tree, mesh, spec)

    # each process materializes only ITS replicas' traffic and replay shard
    local_seeds = range(pid * B_local, (pid + 1) * B_local)
    traffic = to_global(jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[generate_traffic(env.sim_cfg, env.service, topo, 2, seed=s)
          for s in local_seeds]))
    pddpg = ParallelDDPG(env, agent, num_replicas=B, sample_mode="local")

    # replicated inputs (identical on every process) pass as host values;
    # a single-replica reset builds the learner-init example
    one_traffic = generate_traffic(env.sim_cfg, env.service, topo, 2, seed=0)
    _, one_obs = env.reset(jax.random.PRNGKey(0), topo, one_traffic)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    # allocate only the LOCAL replay shard (global B still sizes capacity)
    buffers = to_global(pddpg.init_buffers(one_obs, num_replicas=B_local))

    with mesh:
        env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo,
                                          traffic)
        state, buffers, env_states, obs, stats = pddpg.rollout_episodes(
            state, buffers, env_states, obs, topo, traffic, jnp.int32(0))
        state, metrics = pddpg.learn_burst(state, buffers)
        jax.block_until_ready((stats, metrics))

    # the reductions inside the jitted steps leave these fully replicated,
    # so every process can read them directly
    ret = float(stats["episodic_return"])
    loss = float(metrics["critic_loss"])
    if pid == 0:
        print(f"dryrun_multihost({procs}x{devices_per_proc}): ok — "
              f"return={ret:.3f} critic_loss={loss:.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--devices-per-proc", type=int, default=4)
    ap.add_argument("--worker", nargs=4, type=int, default=None,
                    metavar=("PID", "PROCS", "PORT", "DEVS"))
    ap.add_argument("--timeout", type=int, default=600,
                    help="multi-process mode: whole-run budget")
    # ---- mesh-matrix mode -------------------------------------------
    ap.add_argument("--mesh-matrix", action="store_true",
                    help="run the pjit carving matrix instead of the "
                         "multi-process dryrun")
    ap.add_argument("--legs", default=DEFAULT_LEGS,
                    help="comma-separated DPxMP:rules carving legs "
                         f"(default {DEFAULT_LEGS})")
    ap.add_argument("--episodes", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU devices per carving leg")
    ap.add_argument("--leg-timeout", type=int, default=LEG_TIMEOUT,
                    help="per-leg subprocess budget (seconds)")
    ap.add_argument("--elastic", action="store_true",
                    help="add the 8-device -> 4-device --resume auto leg")
    ap.add_argument("--bank", default=None,
                    help="write the round as a MULTICHIP_r*.json artifact")
    ap.add_argument("--mesh-leg", nargs=4, default=None,
                    metavar=("SHAPE", "RULES", "EPISODES", "REPLICAS"),
                    help=argparse.SUPPRESS)   # internal: one carving leg
    args = ap.parse_args()
    if args.worker is not None:
        worker(*args.worker)
    elif args.mesh_leg is not None:
        shape, rules, episodes, replicas = args.mesh_leg
        mesh_leg(shape, rules, int(episodes), int(replicas))
    elif args.mesh_matrix:
        sys.exit(run_matrix(args.legs, args.episodes, args.replicas,
                            args.devices, args.leg_timeout, args.elastic,
                            args.bank))
    else:
        sys.exit(launch(args.procs, args.devices_per_proc, args.timeout))


if __name__ == "__main__":
    main()
