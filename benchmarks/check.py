"""The comparison that decides ``correct``.

What the timed path produced — the first episode, driven in set-up through
the same compiled ``chunk_step`` programs and the same learner object that
the window then drives, and the policy-driven actions of the window's own
last episode — is compared with the plain references (the policy's and the
learner's is the module the configuration names, ``reference/<name>.py``,
handed in here as ``ref``; the simulator's is ``reference/flowsim.py``)
once the window has closed and the program's device state is freed.  Each
number compared has a limit of its own, kept in the cell's workload file;
``PERF.md`` gives the readings each was set from.  A number for which a
cell's file gives no limit is not compared in that cell (it is printed on
an earlier line); ``PERF.md`` names each such number with its readings and
the reason.

Numbers (all "lower is closer"; a number whose inputs are missing reads
``inf`` and fails):

- ``episodes_not_finite``: episodes, set-up's and the window's, whose
  ``harness_episode.state_finite`` is not true.  Limit 0.
- ``ring_rows_off``: replicas whose ring ``size``/``pos`` after the run are
  not what ``episodes x episode_steps`` writes give.  Limit 0.
- ``return_gap``: widest gap between a replica's first-episode return as
  the loop reported it and the sum of that replica's reward rows in the
  ring (the stats path against the replay write).
- ``action_gap``: widest gap between a stored first-episode action and the
  reference's warm-up action (uniform * mask from the key schedule, then
  threshold + renormalise twice).
- ``obs_gap``: widest gap between the stored observations' columns that
  follow from the configuration alone (requested ingress traffic under
  deterministic arrivals, node capacity) and the reference's statement of
  them.
- ``reward_gap``, ``features_gap``: for a sample of replicas drawn from
  the seed, the widest gap between the stored first-episode rewards and
  next-observation node features (requested traffic, node load, node
  capacity) and those of the plain per-flow simulation
  (``reference/flowsim.py``) of the same replica under the same actions:
  flow arrivals, the substep chain, observation and reward.
- ``td_gap``: the first learn burst's mean |TD| over all its gradient
  steps and batch rows, program against reference, relative.  (The
  burst's last-step critic and actor losses are printed on the
  ``reference``/``program`` lines and not compared: a burst of 200 Adam
  steps amplifies a rounding-sized difference unevenly from seed to seed,
  and no limit on a last-step loss separates a sound run from any control
  or fault; PERF.md section 2 has the readings.)
- ``moment_gap``, ``change_gap``: by the worst leaf, the gap between the
  program's and the reference's norm of Adam's first moment (the
  gradients as the optimiser got them) and of the parameters' change over
  the burst, against the reference's norm of that leaf or of the median
  leaf, whichever is larger.  Leaves whose reference moment is under a
  thousandth of the median leaf's are left out of ``change_gap``.
- ``moment_mid_gap``: the per-leaf gap of Adam's first moment, as in
  ``moment_gap``, by the median leaf instead of the worst: what holds
  where a single small leaf's noise swings the worst one from seed to
  seed (PERF.md section 2 has both readings).
- ``moment2_mid_gap``: the same per-leaf gap of Adam's second moment, by
  the median leaf.  After a long burst the worst leaf of the numbers
  above swings with the later steps' noise from seed to seed (PERF.md
  section 2).  The second moment, with its decay of 0.999, is the
  gradients' squares averaged over the whole burst at nearly even
  weight, and the median leaf leaves the small ones out: the steadiest
  number the end state gives, and the one that a batch half left out
  fails on every seed.
- ``policy_action_gap``: the policy branch.  For the sampled replicas,
  every step of the window's last episode: the stored action against the
  reference's actor forward on the stored observation with the actor
  parameters that drove that episode (copied from the program as the
  episode began), the exploration noise from the reference's own key
  schedule, scaling, clipping, threshold + renormalise.  Widest gap over
  the destination rows; a row in which the reference's weight comes
  within ``NEAR_TIE`` of the threshold is left out, since whether it
  survives there turns on rounding.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import numpy as np

NUMBERS = ("episodes_not_finite", "ring_rows_off", "return_gap",
           "action_gap", "obs_gap", "reward_gap", "features_gap",
           "policy_action_gap", "td_gap", "moment_gap", "moment_mid_gap",
           "change_gap", "moment2_mid_gap")
SIM_REPLICAS = 6
NEAR_TIE = 1e-4
REF_ROW_KEYS = ("obs/", "next_obs/", "action", "reward", "done")


def rel(a: float, b: float, floor: float = 1e-6) -> float:
    return abs(a - b) / max(abs(b), floor)


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep: Optional[Dict[str, bool]] = None) -> Dict[str, float]:
    """Per leaf, the gap between the two norms against the larger of the
    reference's norm of that leaf and of its median leaf."""
    names = sorted(ref)
    rn = {k: float(np.linalg.norm(ref[k])) for k in names}
    med = float(np.median(list(rn.values())))
    return {k: abs(float(np.linalg.norm(prog[k])) - rn[k])
            / max(rn[k], med, 1e-30)
            for k in names if keep is None or keep[k]}


def learner_numbers(prog: dict, ref: dict, weights: Dict[str, np.ndarray]
                    ) -> Dict[str, float]:
    """The learner's numbers from two sides' readings: each a dict of
    ``td_abs_mean``, ``params``, ``mu`` and ``nu`` (leaf tables under the
    reference's names)."""
    med = float(np.median([np.linalg.norm(v) for v in ref["mu"].values()]))
    keep = {k: float(np.linalg.norm(v)) >= 1e-3 * med
            for k, v in ref["mu"].items()}
    change = lambda side: {k: np.asarray(side["params"][k]) - weights[k]
                           for k in weights}
    moment = list(leaf_gaps(prog["mu"], ref["mu"]).values())
    moved = leaf_gaps(change(prog), change(ref), keep).values()
    moment2 = list(leaf_gaps(prog["nu"], ref["nu"]).values())
    return {
        "td_gap": rel(prog["td_abs_mean"], ref["td_abs_mean"]),
        "moment_gap": max(moment),
        "moment_mid_gap": float(np.median(moment)),
        "change_gap": max(moved),
        "moment2_mid_gap": float(np.median(moment2)),
    }


def program_side(after: Dict[str, np.ndarray], events: list) -> dict:
    """The program's readings of its first learn burst: the state the
    recorder kept and the loop's own episode-0 events."""
    params, mu, nu = {}, {}, {}
    for name, leaf in after.items():
        head, _, rest = name.partition("/")
        for net in ("actor", "critic"):
            if head == f"{net}_params":
                params[f"{net}/{rest}"] = leaf
            for moment, table in (("mu", mu), ("nu", nu)):
                if head == f"{net}_opt" and rest.startswith(f"0/{moment}/"):
                    table[f"{net}/{rest[len(moment) + 3:]}"] = leaf
    ep = next((e for e in events if e.get("event") == "episode"
               and e.get("episode") == 0), {})
    sig = next((e for e in events if e.get("event") == "learn_signal"
                and e.get("episode") == 0), {})
    nan = float("nan")
    return {"params": params, "mu": mu, "nu": nu,
            "critic_loss": float(ep.get("critic_loss", nan)),
            "actor_loss": float(ep.get("actor_loss", nan)),
            "td_abs_mean": float(sig.get("td_abs_mean") or nan)}


def reference_side(ref, cfg: dict, weights: Dict[str, np.ndarray], rng,
                   rows, replicas: int, steps: int, matmul: str = "highest",
                   half_batch: bool = False) -> dict:
    """The reference's readings of the same burst (``ref`` the
    configuration's reference module, ``rows`` already on the device,
    ``rng`` the learner key as it stands after the rollouts)."""
    import jax.numpy as jnp

    spec = ref.spec_from_config(cfg)
    st, out = ref.learn_burst(
        matmul, spec, {k: jnp.asarray(v) for k, v in weights.items()},
        rng, rows, replicas, steps, steps, half_batch=half_batch)
    return {"params": {k: np.asarray(v) for k, v in st["params"].items()},
            "mu": {k: np.asarray(v) for k, v in st["mu"].items()},
            "nu": {k: np.asarray(v) for k, v in st["nu"].items()}, **out}


def rows_to_device(rows: Dict[str, np.ndarray]) -> dict:
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in rows.items()
            if k.startswith(REF_ROW_KEYS)}


def sim_sample(seed: int, replicas: int, k: int = SIM_REPLICAS):
    """The replicas the per-flow simulation follows, drawn from the seed."""
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(replicas, size=min(k, replicas),
                             replace=False).tolist())


def rollout_numbers(ref, record: dict, rows, rng, node_mask, net_spec):
    """``return_gap``, ``action_gap``, ``obs_gap``, ``reward_gap``,
    ``features_gap`` and the learner key after the first episode's
    rollouts."""
    import jax.numpy as jnp

    cfg = record["config"]
    spec = ref.spec_from_config(cfg)
    out = {}
    ev = next((e for e in record["events"]
               if e.get("event") == "harness_episode"
               and e.get("episode") == 0), None)
    out["return_gap"] = float("inf")
    if ev is not None and ev.get("per_replica_return") is not None:
        summed = rows["reward"].astype(np.float64).sum(axis=1)
        out["return_gap"] = float(np.abs(
            summed - np.asarray(ev["per_replica_return"])).max())
    m = jnp.asarray(node_mask, jnp.float32)
    mask = jnp.broadcast_to(
        m[:, None, None, None] * m[None, None, None, :],
        (spec.max_nodes, spec.num_sfcs, spec.max_sfs,
         spec.max_nodes)).reshape(-1)
    acts, rng_after = ref.warmup_actions(
        jnp.asarray(rng), record["replicas"], record["episode_steps"],
        record["chunk"], mask, spec)
    out["action_gap"] = float(jnp.abs(
        acts - jnp.asarray(rows["action"])).max())
    sample = sim_sample(record["seed"], record["replicas"])
    sample_acts = np.asarray(acts[np.asarray(sample)])
    del acts
    from benchmarks.reference import flowsim
    out["reward_gap"] = out["features_gap"] = 0.0
    out["live_flows_peak"] = 0       # printed, not compared: a count
    for r, ref_actions in zip(sample, sample_acts):
        rew, feats, live = flowsim.follow(cfg, net_spec, ref_actions)
        out["live_flows_peak"] = max(out["live_flows_peak"], live)
        out["reward_gap"] = max(out["reward_gap"], float(
            np.abs(rew - rows["reward"][r]).max()))
        out["features_gap"] = max(out["features_gap"], float(
            np.abs(feats - rows["next_obs/nodes"][r]).max()))
    space = list(cfg["observation_space"])
    cols = ref.expected_static_columns(
        cfg, list(net_spec.node_caps),
        [t == "Ingress" for t in net_spec.node_types], space)
    gap = 0.0
    for name, want in cols.items():
        got = rows["next_obs/nodes"][..., space.index(name)]
        gap = max(gap, float(np.abs(got - want).max()))
    out["obs_gap"] = gap if cols else float("inf")
    return out, rng_after


def policy_numbers(ref, record: dict, policy: Optional[dict], rng,
                   matmul: str = "highest") -> Dict[str, float]:
    """``policy_action_gap`` from what the driver kept of the window's
    last episode (``policy``: its index, the sampled replicas, their rows
    and the actor parameters that drove it), and how many destination
    rows were compared and left out as near-ties."""
    import jax.numpy as jnp

    if policy is None:
        return {"policy_action_gap": float("inf")}
    cfg = record["config"]
    spec = ref.spec_from_config(cfg)
    chunk = record["chunk"]
    chunks = record["episode_steps"] // chunk
    key = ref.advance_key(jnp.asarray(rng), policy["episode"] * (chunks + 1))
    obs = {k[len("obs/"):]: v for k, v in policy["rows"].items()
           if k.startswith("obs/")}
    acts, margin = ref.policy_actions(
        matmul, spec, {k: jnp.asarray(v) for k, v in policy["actor"].items()},
        obs, key, record["replicas"], policy["sample"], chunk,
        float(cfg["rand_mu"]), float(cfg["rand_sigma"]))
    diff = np.abs(np.asarray(acts) - policy["rows"]["action"])
    by_row = diff.reshape(diff.shape[:-1] + (-1, spec.max_nodes)).max(-1)
    held = np.asarray(margin) >= NEAR_TIE
    return {"policy_action_gap": float(by_row[held].max()) if held.any()
            else float("inf"),
            "policy_rows_compared": int(held.sum()),
            "policy_rows_near_tie": int((~held).sum())}


def accounting(record: dict, final: Optional[dict]):
    """``episodes_not_finite`` and ``ring_rows_off``, and the window's
    failed episodes."""
    warm = record["warm_episodes"]
    eps = [e for e in record["events"]
           if e.get("event") == "harness_episode"]
    total = warm + record["window_episodes"]
    bad = sum(1 for e in eps if e.get("state_finite") is not True)
    bad += max(total - len(eps), 0)        # an episode with no verdict
    out = {"episodes_not_finite": float(bad),
           "ring_rows_off": float("inf")}
    if final is not None:
        writes = total * record["episode_steps"]
        cap = final["capacity"]
        off = (final["size"] != min(writes, cap)) \
            | (final["pos"] != writes % cap)
        out["ring_rows_off"] = float(off.sum())
    failed = sum(1 for e in eps if e.get("episode", -1) >= warm
                 and e.get("state_finite") is not True)
    return out, failed


def decide(record: dict, limits: Dict[str, float], ref, weights, rng, rows,
           after, final, node_mask, net_spec, policy=None,
           log: Callable = print) -> dict:
    """``correct`` (``ref``: the reference module the cell's configuration
    names) with every number compared beside its limit, every
    number read (``values``), the learner key after the first episode's
    rollouts (``rng_after``), ``attempted`` and ``failed``."""
    values = {k: float("inf") for k in NUMBERS}
    acct, failed = accounting(record, final)
    values.update(acct)
    if record.get("error"):
        failed += 1
    rng_after = None
    if rows is not None and after is not None:
        roll, rng_after = rollout_numbers(ref, record, rows, rng, node_mask,
                                          net_spec)
        log("live_flows_peak", roll.pop("live_flows_peak"), "of",
            record["config"]["simulator"]["max_flows"], "slots")
        values.update(roll)
        pol = policy_numbers(ref, record, policy, rng)
        values["policy_action_gap"] = pol.pop("policy_action_gap")
        log("policy rows", pol)
        dev_rows = rows_to_device(rows)
        want = reference_side(ref, record["config"], weights, rng_after,
                              dev_rows, record["replicas"],
                              record["episode_steps"])
        prog = program_side(after, record["events"])
        values.update(learner_numbers(prog, want, weights))
        log("reference", {k: want[k] for k in
                          ("critic_loss", "actor_loss", "td_abs_mean")})
        log("program", {k: prog[k] for k in
                        ("critic_loss", "actor_loss", "td_abs_mean")})
    compared = {}
    for name in NUMBERS:
        lim = limits.get(name)
        v = values[name]
        if lim is None:      # the cell does not compare this number
            log("not compared", name, v)
            continue
        compared[name] = {"value": v if math.isfinite(v) else repr(v),
                          "limit": lim,
                          "ok": not math.isnan(v) and v <= lim}
    correct = all(c["ok"] for c in compared.values()) \
        and not record.get("error") and record["window_episodes"] >= 1
    return {"correct": correct, "compared": compared, "values": values,
            "rng_after": rng_after,
            "attempted": record["window_episodes"], "failed": failed}
