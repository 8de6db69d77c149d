"""The learner's controls for a cell whose learner state is gigabytes:
one side at a time, each judged by ``check.decide`` itself.

``control.py`` keeps the reference and three more sides on the host at
once and prints their numbers for a reader to hold against the limits;
at ``flagship-ouro-b32``'s 4.9 GB a side that does not fit the one-chip
machine's 40 GiB.  Here, for each seed: the cell's run as the benchmark
makes it (set-up, a window of ``--seconds``, the check), then for each
control

- ``control_bfloat16``: the reference's burst computed in bfloat16, one
  step below the float32 the configuration states;
- ``fault_half_batch``: half of each batch left out, the mean over the
  rest;

the control's end state and burst readings are laid out as the recorder
would have kept them of a program (``in_programs_place``) and
``check.decide`` compares that with the reference proper under the
cell's own limits.  Its ``correct`` is what the benchmark would print
for a program that computed its first burst that way: a control that
reads ``true`` is a control the cell's limits do not hold.  One side and
the reference ``decide`` computes are alive at a time.

The policy branch's control, as in ``control.py``, is the reference's
actor forward in bfloat16 against the actions the program stored
(``control_policy_bfloat16``: the gap beside the cell's limit).

Prints one JSON line per seed.  The benchmark's own runs never run this.

    python3 benchmarks/control_one_side.py --workload flagship-ouro-b32 \\
        --seeds 11,12
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import check, harness  # noqa: E402
from benchmarks.control import quiet_log  # noqa: E402

CONTROLS = (("control_bfloat16", {"matmul": "bfloat16"}),
            ("fault_half_batch", {"half_batch": True}))


def in_programs_place(record: dict, side: dict):
    """``side`` (``check.reference_side``'s readings of the first burst)
    as what ``check.program_side`` reads of a program: the run record
    with episode 0's losses and mean |TD| replaced, and the recorder's
    leaf table of the learner state (parameters and Adam's two moments
    under the checkpoint layout's names)."""
    state = {}
    for table, place in (("params", "{net}_params/{rest}"),
                         ("mu", "{net}_opt/0/mu/{rest}"),
                         ("nu", "{net}_opt/0/nu/{rest}")):
        for name, leaf in side[table].items():
            net, _, rest = name.partition("/")
            state[place.format(net=net, rest=rest)] = leaf

    def stand_in(ev):
        if ev.get("episode") == 0 and ev.get("event") == "episode":
            return dict(ev, critic_loss=side["critic_loss"],
                        actor_loss=side["actor_loss"])
        if ev.get("episode") == 0 and ev.get("event") == "learn_signal":
            return dict(ev, td_abs_mean=side["td_abs_mean"])
        return ev

    return dict(record, events=[stand_in(e) for e in record["events"]]), state


def probe(record, limits, ref, weights, rng, rows, after, final, node_mask,
          net_spec, policy=None, controls=CONTROLS):
    """Each control's verdict by ``check.decide`` under ``limits``."""
    out = {}
    dev_rows = check.rows_to_device(rows)
    for name, kw in controls:
        side = check.reference_side(
            ref, record["config"], weights, record["rng_after"], dev_rows,
            record["replicas"], record["episode_steps"], **kw)
        stood, state = in_programs_place(record, side)
        del side
        verdict = check.decide(stood, limits, ref, weights, rng, rows, state,
                               final, node_mask, net_spec, policy=policy,
                               log=quiet_log)
        out[name] = {
            "correct": verdict["correct"],
            "fails": sorted(k for k, c in verdict["compared"].items()
                            if not c["ok"]),
            "compared": verdict["compared"]}
        del stood, state, verdict
        gc.collect()
    if policy is not None:
        read = check.policy_numbers(ref, record, policy, rng,
                                    matmul="bfloat16")
        limit = limits.get("policy_action_gap")
        out["control_policy_bfloat16"] = {
            **read, "limit": limit,
            "fails": limit is not None and read["policy_action_gap"] > limit}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="the window: the default closes it after one "
                         "episode, which the policy branch's check needs")
    a = ap.parse_args(argv)
    cell = harness.load_cell(a.workload)
    limits = cell["cell"].get("limits", {})
    peaks = harness.load_peaks()
    driver = harness.load_driver(cell)
    driver.prepare(cell)
    device = harness.require_device(int(cell["cell"]["chips"]), peaks)
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.time()
        rec = driver.run(cell, seed=seed, seconds=a.seconds, traced=False,
                         t_start=t0, peaks=peaks["devices"][device["kind"]],
                         log=quiet_log,
                         probe=lambda rec, **kw: probe(rec, limits, **kw))
        print(json.dumps({
            "seed": seed, "window_episodes": rec["window_episodes"],
            "correct": rec["correct"], "compared": rec["compared"],
            "seconds": round(time.time() - t0, 1),
            **rec.get("probe", {})}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
