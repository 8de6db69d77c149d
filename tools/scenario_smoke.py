"""Scenario-factory smoke: the on-device factory + auto-curriculum end
to end through the real CLI.

The CI-stage proof that the factory path actually executes: a tiny
3-episode, 2-replica CPU train run with
``--topo-mix factory:star-ring-line+shapes~faults`` must

- exit 0 with ``run_start`` recording the factory mix + curriculum
  knobs,
- stream with ZERO retraces: the compile events record EXACTLY one
  trace each for ``factory_sample`` / ``reset_all`` / ``chunk_step``
  (``--no-perf`` so the AOT capture does not add its own trace — 50
  randomized scenarios through one compiled program is the whole
  claim),
- emit one ``curriculum`` event per episode and a
  ``curriculum_weight{family=...}`` gauge per family, exposed over a
  live Prometheus ``/metrics`` endpoint (in-process scrape — the CLI
  run binds no port in CI),
- gate through ``bench_diff``: a SCEN-shaped row self-compares clean
  (rc 0) while an injected env-steps/s regression is caught (rc 1).

Run by ``tools/ci_check.sh`` before the chaos stage; standalone:

    JAX_PLATFORMS=cpu python tools/scenario_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MIX = "factory:star-ring-line+shapes~faults"
FAMILIES = ("star", "ring", "line")
EPISODES = 3


def _configure_jax():
    import jax

    from gsc_tpu.runtime import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")   # a CPU smoke, explicitly
    enable_compile_cache()


def fail(msg: str) -> int:
    print(f"scenario smoke: FAIL — {msg}")
    return 1


def check_curriculum_endpoint() -> str:
    """curriculum_weight gauges over a live /metrics scrape: the
    Curriculum emit pathway feeds the same hub the endpoint serves."""
    from gsc_tpu.env.curriculum import Curriculum, CurriculumConfig
    from gsc_tpu.obs import MetricsEndpoint, MetricsHub

    hub = MetricsHub(tags={"run": "smoke"})
    curr = Curriculum(list(FAMILIES), CurriculumConfig(floor=0.3))
    curr.fold_td([4.0, 1.0, 0.5], [2.0, 1.0, 1.0])
    curr.emit_weights(hub, episode=0)
    ep = MetricsEndpoint(hub, port=0).start()
    try:
        body = urllib.request.urlopen(ep.url, timeout=10).read().decode()
        got = {f for f in FAMILIES
               if any("curriculum_weight" in line
                      and f'family="{f}"' in line
                      for line in body.splitlines())}
        if got != set(FAMILIES):
            return (f"/metrics exposition missing curriculum_weight for "
                    f"{sorted(set(FAMILIES) - got)}")
        snap = {k: float(v) for k, v in hub.snapshot().items()}
        parsed = {}
        for line in body.strip().splitlines():
            name, value = line.rsplit(" ", 1)
            parsed[name] = float(value)
        if parsed != snap:
            return f"endpoint scrape != snapshot ({parsed} vs {snap})"
    finally:
        ep.stop()
    return ""


def main() -> int:
    _configure_jax()
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    err = check_curriculum_endpoint()
    if err:
        return fail(err)

    tmp = tempfile.mkdtemp(prefix="gsc_scenario_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", str(EPISODES), "--replicas", "2",
        "--chunk", "3", "--topo-mix", MIX, "--curriculum-floor", "0.3",
        "--no-perf",   # the AOT cost capture would add its own trace —
                       # this stage pins the DISPATCH trace counts
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code} under --topo-mix {MIX!r}")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]

    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]
    run_start = next(e for e in events if e["event"] == "run_start")
    if run_start.get("topo_mix") != MIX:
        return fail(f"run_start topo_mix {run_start.get('topo_mix')!r} "
                    f"!= {MIX!r}")
    if (run_start.get("curriculum") or {}).get("floor") != 0.3:
        return fail(f"run_start curriculum knobs missing: "
                    f"{run_start.get('curriculum')}")

    # ZERO retraces across the randomized stream: exactly one trace per
    # dispatch entry point (a second chunk_step/factory_sample trace
    # means a sampled scenario became a compile axis)
    traces = {}
    for e in events:
        if e["event"] == "compile" and e.get("stage") == "trace":
            traces[e["fn"]] = e.get("count")
    for fn in ("factory_sample", "reset_all", "chunk_step"):
        if traces.get(fn) != 1:
            return fail(f"expected exactly 1 {fn} trace across "
                        f"{EPISODES} randomized episodes, saw "
                        f"{traces.get(fn)} (all: {traces})")

    cur = [e for e in events if e["event"] == "curriculum"]
    if len(cur) != EPISODES:
        return fail(f"expected {EPISODES} curriculum events, got "
                    f"{len(cur)}")
    w = cur[-1].get("weights") or {}
    if set(w) != set(FAMILIES):
        return fail(f"curriculum weights cover {sorted(w)}, want "
                    f"{sorted(FAMILIES)}")
    if abs(sum(w.values()) - 1.0) > 1e-3 or min(w.values()) < 0.3 / 3 - 1e-6:
        return fail(f"curriculum weights not a floored distribution: {w}")
    snap = json.load(open(os.path.join(rdir, "metrics.json")))["metrics"]
    missing = [f for f in FAMILIES
               if not any("curriculum_weight" in k and f'family="{f}"' in k
                          for k in snap)]
    if missing:
        return fail(f"metrics.json missing curriculum_weight gauges for "
                    f"{missing}")
    end = events[-1]
    if end.get("event") != "run_end" or end.get("status") != "ok":
        return fail(f"stream tail {end}")

    # bench_diff gate over a SCEN-shaped row: self-compare clean,
    # injected env-steps/s regression caught
    import bench_diff
    sps = [e for e in events if e["event"] == "episode"]
    rate = (sps[-1].get("sps") if sps else None) or 1.0
    scen = {"metric": "env_steps_per_sec_per_chip", "status": "ok",
            "factory_sps": round(float(rate), 2),
            "jit_traces_factory": {fn: traces[fn] for fn in
                                   ("factory_sample", "chunk_step",
                                    "reset_all")}}
    scen_path = os.path.join(tmp, "SCEN_r99.json")
    with open(scen_path, "w") as f:
        json.dump(scen, f)
    traj = os.path.join(tmp, "traj.json")
    bench_diff.ingest([scen_path], traj)
    rc = bench_diff.main(["diff", "SCEN_r99", "--baseline", "SCEN_r99",
                          "--trajectory", traj])
    if rc != 0:
        return fail(f"SCEN self-compare rc={rc} (want 0)")
    bad = dict(scen, factory_sps=round(float(rate) * 0.5, 2))
    bad_path = os.path.join(tmp, "SCEN_bad.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    rc = bench_diff.main(["diff", bad_path, "--baseline", "SCEN_r99",
                          "--trajectory", traj])
    if rc != 1:
        return fail(f"injected env-steps/s regression rc={rc} (want 1)")

    print(f"scenario smoke: OK — {EPISODES} factory episodes over "
          f"{sorted(w)} with 1 trace per entry point ({traces}), "
          "curriculum gauges live on /metrics, SCEN row gated both "
          "directions")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
