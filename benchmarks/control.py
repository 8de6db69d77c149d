"""The control and the planted faults, read at a cell's own size.

For each seed: the cell's set-up (its first episode through the timed
path's own programs) and a window of ``--seconds``; then, with the
reference put in the program's place,

- the **control**: the reference computed below what the configuration
  states (``bfloat16``; ``high``, three bf16 passes, beside it), against
  the reference proper; with ``--program-precision bf16`` the program's
  own bf16 path is switched on instead and the program itself is the
  control (its ``program`` numbers are then the control's readings); the
  policy branch's control is the reference's actor forward below float32
  against the actions the program stored;
- the **faults** a training cell can have: half of each batch left out
  with the mean taken over the rest; an answer altered where it is
  produced (stored actions, the warm-up's and the policy's, and rewards
  scaled by 1 + 1e-3).  A step that
  returns its state unchanged reads 1 on ``change_gap`` by the measure
  itself and needs no run.

Prints one JSON line per seed: the program's numbers, the control's, the
faults', and under ``moment_leaves`` the look behind the worst-leaf
``moment_gap``: the four leaves the program reads farthest off, each with
its norm against the median leaf's and every side's gap.  The benchmark's
own runs never run this.

    python3 benchmarks/control.py --workload flagship-b256 --seeds 11,12,13
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import check, harness  # noqa: E402


PARTS = ("learner", "policy", "answers", "sim")


def probe(record, ref, weights, rng, rows, after, final, node_mask, net_spec,
          policy=None, parts=PARTS):
    """``ref`` is the reference module the cell's configuration names, as
    the driver handed it to ``check.decide``."""
    import numpy as np

    out = {}
    if "learner" in parts:
        dev_rows = check.rows_to_device(rows)
        args = (ref, record["config"], weights, record["rng_after"],
                dev_rows, record["replicas"], record["episode_steps"])
        want = check.reference_side(*args)
        sides = {"program": check.program_side(after, record["events"])}
        for name, kw in (("control_high", {"matmul": "high"}),
                         ("control_bfloat16", {"matmul": "bfloat16"}),
                         ("fault_half_batch", {"half_batch": True})):
            sides[name] = check.reference_side(*args, **kw)
            out[name] = check.learner_numbers(sides[name], want, weights)
        out["moment_leaves"] = worst_leaves(sides, want)
        del dev_rows
    if "policy" in parts and policy is not None:
        # the policy branch: the reference's actor forward below float32
        # against the stored actions, and a stored action altered
        for name in ("control_high", "control_bfloat16"):
            out.setdefault(name, {}).update(check.policy_numbers(
                ref, record, policy, rng, matmul=name.split("_")[1]))
        moved = dict(policy, rows=dict(
            policy["rows"],
            action=policy["rows"]["action"] * np.float32(1.001)))
        out["fault_policy_action_altered"] = check.policy_numbers(
            ref, record, moved, rng)
    if "answers" in parts:
        altered = dict(rows)
        altered["action"] = rows["action"] * np.float32(1.001)
        altered["reward"] = rows["reward"] * np.float32(1.001)
        out["fault_answer_altered"], _ = check.rollout_numbers(
            ref, record, altered, rng, node_mask, net_spec)
    if "sim" in parts:
        out["fault_sim_half_rate"] = sim_fault(record, rows, rng, node_mask,
                                               net_spec)
    return out


def worst_leaves(sides, want, k=4):
    """The look behind a worst-leaf number: the ``k`` leaves whose first
    moment the program reads farthest from the reference's, each with its
    reference norm against the median leaf's and every side's gap."""
    import numpy as np

    gaps = {name: check.leaf_gaps(side["mu"], want["mu"])
            for name, side in sides.items()}
    norm = {leaf: float(np.linalg.norm(v)) for leaf, v in want["mu"].items()}
    med = float(np.median(list(norm.values())))
    worst = sorted(norm, key=lambda leaf: -gaps["program"][leaf])[:k]
    return {leaf: {"norm_over_median": norm[leaf] / max(med, 1e-30),
                   **{name: g[leaf] for name, g in gaps.items()}}
            for leaf in worst}


def sim_fault(record, rows, rng, node_mask, net_spec):
    """The plain simulation put in the program's place with a fault of the
    kind a faster simulator would tempt: every second substep skipped
    (half the timers' ticks).  Reads ``reward_gap``/``features_gap``
    against the stored rows of a sound run."""
    import numpy as np
    from benchmarks.reference import flowsim

    class Skipping(flowsim.FlowSim):
        def substep(self):
            if self.g % 2:
                self.g += 1
                return
            super().substep()

    r = check.sim_sample(record["seed"], record["replicas"])[0]
    sim = Skipping(record["config"], list(net_spec.node_caps),
                   list(net_spec.node_types), list(net_spec.edges))
    rew, feats = [], []
    for a in rows["action"][r]:
        x, f = sim.step(a)
        rew.append(x)
        feats.append(f)
    return {"reward_gap": float(np.abs(
                np.asarray(rew) - rows["reward"][r]).max()),
            "features_gap": float(np.abs(
                np.stack(feats) - rows["next_obs/nodes"][r]).max())}


def quiet_log(*words):
    """The run's earlier lines on standard error, without its events."""
    if words and words[0] != "event":
        print(*words, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="the window: the default closes it after one "
                         "episode, which the policy branch's check needs")
    ap.add_argument("--program-precision", default=None,
                    help="switch on the program's own lower-precision "
                         "path (agent `precision`, e.g. bf16): the program "
                         "itself then serves as the control")
    ap.add_argument("--parts", default=",".join(PARTS),
                    help="which controls and faults to read")
    a = ap.parse_args(argv)
    parts = tuple(a.parts.split(","))
    cell = harness.load_cell(a.workload)
    if a.program_precision:
        cell["config"]["precision"] = a.program_precision
    peaks = harness.load_peaks()
    driver = harness.load_driver(cell)
    driver.prepare(cell)
    device = harness.require_device(int(cell["cell"]["chips"]), peaks)
    for seed in (int(s) for s in a.seeds.split(",")):
        rec = driver.run(cell, seed=seed, seconds=a.seconds, traced=False,
                         t_start=time.time(),
                         peaks=peaks["devices"][device["kind"]],
                         log=quiet_log,
                         probe=lambda rec, **kw: probe(rec, parts=parts,
                                                       **kw))
        print(json.dumps({
            "seed": seed, "window_episodes": rec["window_episodes"],
            "window_s": (rec["closed_at"] or 0) - (rec["opened"] or 0),
            "correct": rec["correct"], "program": rec["values"],
            **rec.get("probe", {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
