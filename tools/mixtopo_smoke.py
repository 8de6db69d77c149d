"""Mixed-topology smoke: a tiny 2-topology mixed train run must work.

The CI-stage proof that the mix path actually executes end to end: a
2-episode, 2-replica CPU training run with ``--topo-mix "schedule,line3"``
(schedule = the triangle network, so the batch spans two networks) must

- exit 0,
- leave ``harness_episode`` events in the run's ``events.jsonl`` whose
  ``per_topology_return`` carries BOTH topology names (per-replica
  attribution survived the vmapped dispatch),
- record per-topology ``topology_return`` gauges in ``metrics.json``,
- end the stream with ``run_end status=ok``.

Run by ``tools/ci_check.sh`` before the chaos stage; standalone:

    JAX_PLATFORMS=cpu python tools/mixtopo_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MIX = "schedule,line3"


def _configure_jax():
    import jax

    from gsc_tpu.runtime import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")   # a CPU smoke, explicitly
    enable_compile_cache()


def main() -> int:
    _configure_jax()
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    tmp = tempfile.mkdtemp(prefix="gsc_mixtopo_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", "2", "--replicas", "2",
        "--chunk", "3", "--topo-mix", MIX,
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        print(f"mixtopo smoke: FAIL — train rc={r.exit_code} under "
              f"--topo-mix {MIX!r}")
        return 1
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]
    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]
    harness = [e for e in events if e["event"] == "harness_episode"]
    names = set()
    for e in harness:
        names |= set((e.get("per_topology_return") or {}))
    if len(names) < 2:
        print(f"mixtopo smoke: FAIL — expected per-topology returns for "
              f"2 networks on harness_episode events, saw {sorted(names)}")
        return 1
    snap = json.load(open(os.path.join(rdir, "metrics.json")))["metrics"]
    # hub.snapshot() flattens to prometheus exposition names:
    # gsc_topology_return{run="...",topology="<name>"}
    gauges = [k for k in snap if k.startswith("gsc_topology_return")]
    hit = {n for n in names if any(n in g for g in gauges)}
    if hit != names:
        print(f"mixtopo smoke: FAIL — topology_return gauges missing for "
              f"{sorted(names - hit)} (have {gauges})")
        return 1
    end = events[-1]
    if end.get("event") != "run_end" or end.get("status") != "ok":
        print(f"mixtopo smoke: FAIL — stream tail {end}")
        return 1
    run_start = next(e for e in events if e["event"] == "run_start")
    if run_start.get("topo_mix") != MIX:
        print(f"mixtopo smoke: FAIL — run_start topo_mix "
              f"{run_start.get('topo_mix')!r} != {MIX!r}")
        return 1
    print(f"mixtopo smoke: OK — mixed batch over {sorted(names)} "
          f"({len(harness)} harness episodes, gauges + events present, "
          "run_end status=ok)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
