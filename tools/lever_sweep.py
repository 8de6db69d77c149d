"""Lever sweep — rollout DEVICE rate across the engine knobs that the r4
profile work identified but never measured on chip:

- ``scan_unroll``: the substep loop is a chain of small fusions, so scan
  loop machinery is a visible wall fraction (engine.py:283-286);
- ``substep_impl``: the XLA one-hot engine vs the pallas substep
  megakernel (SimConfig.substep_impl).  The megakernel has a CPU role
  only — TPU Pallas cannot lower it and the engine refuses it there — so
  chip grids stay xla and only the ``smoke`` grid carries a pallas cell.
  Every cell also records ``hlo_fusions``
  (gsc_tpu.analysis.hlo.count_fusions — the op-count proxy that gates
  substep changes; ``--no-fusions`` skips the extra AOT compile);
- ``max_flows``: every [M,*] one-hot contraction scales with the flow
  table; the flagship's M=128 has headroom over its ~64-flow peak
  occupancy (arrival budget right-sizing, VERDICT r4 item 2);
- replicas x chunk: throughput against the wall of one device call.

Each cell times ``--calls`` chunked rollout calls (compile + 1 warm call
excluded) and prints a JSON row naming the device it ran on; the last
line is the winner.  The whole grid runs in THIS process — one process
per chip — and a faulted cell ends the sweep with its traceback:

    python tools/lever_sweep.py                        # default grid
    JAX_PLATFORMS=cpu python tools/lever_sweep.py --grid smoke

A row from a CPU run says ``"platform": "cpu"`` and is a plumbing check,
not a rate.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

GRIDS = {
    # (replicas, chunk, max_flows, scan_unroll, substep_impl).  The chip
    # grids sweep the XLA engine's unroll knob (the never-swept r4 lever);
    # the pallas megakernel is CPU-only (ops/pallas_substep.py docstring),
    # so only the smoke grid carries a pallas cell.
    "default": list(itertools.product((256, 512), (50,), (96, 128),
                                      (1, 2, 4), ("xla",))),
    "wide": list(itertools.product((256, 512), (25, 50, 100), (96, 128),
                                   (1, 2, 4), ("xla",))),
    "smoke": [(2, 5, 32, 1, "xla"), (2, 5, 32, 2, "xla"),
              (2, 5, 32, 1, "pallas")],
}


def measure(B, chunk, max_flows, unroll, impl, calls, episode_steps,
            fusions=True):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _flagship
    from gsc_tpu.analysis.hlo import count_fusions
    from gsc_tpu.env.env import ServiceCoordEnv
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.sim.traffic_device import DeviceTraffic

    env0, agent, topo, _ = _flagship(episode_steps=episode_steps,
                                     max_flows=max_flows,
                                     gen_traffic=False)
    if unroll != 1 or impl != "xla":
        env0 = ServiceCoordEnv(
            env0.service, dataclasses.replace(env0.sim_cfg,
                                              scan_unroll=unroll,
                                              substep_impl=impl),
            agent, env0.limits)
    dt = DeviceTraffic(env0.sim_cfg, env0.service, topo, episode_steps)
    traffic = jax.jit(lambda k: dt.sample_batch(k, B))(jax.random.PRNGKey(0))
    pddpg = ParallelDDPG(env0, agent, num_replicas=B, donate=True)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)

    def call(carry, start):
        state, buffers, env_states, obs = carry
        out = pddpg.rollout_episodes(state, buffers, env_states, obs,
                                     topo, traffic, jnp.int32(start), chunk)
        return out[:4]

    t_c = time.time()
    carry = call((state, buffers, env_states, obs), jnp.int32(0))
    jax.block_until_ready(carry)
    compile_s = time.time() - t_c
    carry = call(carry, jnp.int32(chunk))   # warm (donation steady state)
    jax.block_until_ready(carry)
    t0 = time.time()
    for c in range(calls):
        carry = call(carry, jnp.int32((c + 2) * chunk))
    jax.block_until_ready(carry)
    wall = time.time() - t0
    row = {"replicas": B, "chunk": chunk, "max_flows": max_flows,
           "scan_unroll": unroll, "substep_impl": impl,
           "env_steps_per_sec": round(calls * chunk * B / wall, 1),
           "per_call_s": round(wall / calls, 3),
           "compile_s": round(compile_s, 1)}
    if fusions:
        # the op-count proxy next to every rate (analysis.hlo — the gate
        # that caught the bit-exact 281->294 scatter-merge).  AOT-lowers
        # a wrapper program; the persistent cache absorbs the inner
        # executable, --no-fusions skips it on tightly budgeted windows.
        row["hlo_fusions"] = count_fusions(
            jax.jit(call).lower(carry,
                                jnp.int32((calls + 2) * chunk)).compile())
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", choices=sorted(GRIDS), default="default")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--episode-steps", type=int, default=200)
    ap.add_argument("--no-fusions", action="store_true",
                    help="skip the per-cell hlo_fusions count (saves one "
                         "AOT wrapper compile per cell)")
    args = ap.parse_args()

    import jax

    from gsc_tpu.runtime import device_fields, enable_compile_cache

    device = device_fields()
    enable_compile_cache()
    rows = []
    for B, chunk, mf, unroll, impl in GRIDS[args.grid]:
        row = {**device, **measure(B, chunk, mf, unroll, impl, args.calls,
                                   args.episode_steps,
                                   fusions=not args.no_fusions)}
        jax.clear_caches()  # cap live executables/HBM across cells
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"winner": max(rows,
                                    key=lambda r: r["env_steps_per_sec"])}))


if __name__ == "__main__":
    main()
