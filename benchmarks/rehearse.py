"""CPU rehearsal of the command at tiny sizes: everything ``run.py`` does
after its look for a chip — the driver, the window, the trace reduction,
the output check, the metric readers and the result line — on a cell cut
down from a committed one.  A rehearsal proves plumbing; its numbers are
never device metrics and are printed under ``rehearsal`` only.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [--trace 1]
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import harness  # noqa: E402

TINY = {"replicas": 4, "chunk": 5, "episode_steps": 10,
        "nb_steps_warmup_critic": 10, "mem_limit": 160, "batch_size": 8}
# limits for the tiny CPU cell (float32 on one backend on both sides):
# counts exact, everything else two decades above what sound runs read here
LIMITS = {"episodes_not_finite": 0, "ring_rows_off": 0, "return_gap": 1e-4,
          "action_gap": 1e-6, "obs_gap": 1e-6, "reward_gap": 1e-5,
          "features_gap": 1e-5, "policy_action_gap": 1e-5, "td_gap": 1e-4,
          "moment_gap": 1e-3, "moment_mid_gap": 1e-4, "change_gap": 1e-3,
          "moment2_mid_gap": 1e-5}
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes": 1e9, "hbm_bytes_per_s": 1e11}


def tiny_cell(base: str = "flagship-b256", root: str = harness.ROOT,
              **overrides) -> dict:
    """A committed cell (or one of another tree, ``root``) cut to
    rehearsal size: same files, same driver, same reference, fewer
    replicas, shorter episodes, a ring of 40 rows per replica."""
    cell = copy.deepcopy(harness.load_cell(base, root))
    sizes = {**TINY, **overrides}
    for k in ("replicas", "chunk"):
        cell["cell"][k] = sizes.pop(k)
    cell["config"].update(sizes)
    return cell


def run_once(cell: dict, seed: int = 3, seconds: float = 0.5,
             traced: bool = False, limits: dict = None) -> dict:
    """One rehearsal run -> the result line (as ``run.py`` would print it)
    with the run record under ``record``."""
    if limits is not None:
        cell["cell"]["limits"] = limits
    driver = harness.load_driver(cell)
    driver.prepare(cell)
    record = driver.run(cell, seed=seed, seconds=seconds, traced=traced,
                        t_start=time.time(), peaks=FAKE_PEAKS,
                        log=lambda *a: print(*a, file=sys.stderr))
    bench = harness.manifest()
    names = harness.metric_names(bench, cell["name"], traced)
    metrics = harness.read_metrics(names, record, harness.units_of(bench))
    import jax
    dev = {"platform": jax.devices()[0].platform,
           "kind": jax.devices()[0].device_kind,
           "count": jax.device_count()}
    line = harness.result_line(record, metrics, dev, traced)
    line["record"] = record
    return line


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="flagship-b256")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args()
    out = run_once(tiny_cell(a.workload), a.seed, a.seconds, bool(a.trace))
    for text in harness.compared_lines(out.pop("record")["compared"]):
        print(text, file=sys.stderr)
    print(json.dumps({"rehearsal": out}))
