"""Compile a cell's ``chunk_step`` (both variants) at its real size for a
described, not attached, TPU v5e and print ``memory_analysis()`` with the
bytes the cell's state holds, and each program's ``device_total_gb``
(arguments + temporaries + outputs not aliased to an argument) with its
share of the chip (``chip_share_pct``) — rehearsal 3 of the
on-chip-measurement guide.  Nothing runs; a compile that passes is not a
chip run.

    JAX_PLATFORMS=cpu python3 benchmarks/compile_v5e.py --workload flagship-b256

Run before a cell's first chip call: what the chip's compiler refuses here
(a program that does not fit the device) costs no chip time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# what one v5e chip lets a process hold: ``memory_stats()["bytes_limit"]``
# on the chip (my chip run, PR 37), below the 16 GiB of HBM in peaks.json
CHIP_BYTES = 16.9e9
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_cell(name: str, topology: str = "v5e:2x2") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import harness
    from gsc_tpu.cli import _build
    from gsc_tpu.obs.learning import LearnLedgerSpec
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.sim.traffic_device import DeviceTraffic

    cell = harness.load_cell(name)
    cfg, wl = cell["config"], cell["cell"]
    drv = harness.load_driver(cell)
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    jax.config.update("jax_enable_compilation_cache", False)
    topo_desc = topologies.get_topology_desc(platform="tpu",
                                             topology_name=topology)
    chip = SingleDeviceSharding(topo_desc.devices[0])
    replicas, chunk = int(wl["replicas"]), int(wl["chunk"])
    with tempfile.TemporaryDirectory(prefix="gsc-compile-") as tmp:
        paths = drv.write_inputs(cfg, tmp)
        env, driver, agent = _build(
            paths["agent"], paths["simulator"], paths["service"],
            paths["scheduler"], 0, int(cfg["max_nodes"]),
            int(cfg["max_edges"]))
        topo = driver.topology_for(0)
    pddpg = ParallelDDPG(env, agent, num_replicas=replicas, donate=True,
                         learn_ledger=LearnLedgerSpec(num_topos=1))
    sampler = DeviceTraffic(env.sim_cfg, env.service, topo,
                            agent.episode_steps, trace=driver.trace,
                            capacity=driver.capacity)
    key = jax.random.PRNGKey(0)
    traffic = jax.eval_shape(
        lambda k: sampler.sample_batch(k, replicas), key)
    one_traffic = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), traffic)
    _, one_obs = jax.eval_shape(env.reset, key, topo, one_traffic)
    state = jax.eval_shape(pddpg.init, key, one_obs)
    buffers = jax.eval_shape(pddpg.init_buffers, one_obs)
    env_states, obs = type(pddpg).reset_all.eval_shape(pddpg, key, topo,
                                                       traffic)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    def nbytes(tree):
        return int(sum(np.prod(x.shape) * np.dtype(x.dtype).itemsize
                       for x in jax.tree_util.tree_leaves(tree)))

    args = tuple(on_chip(t) for t in (state, buffers, env_states, obs, topo,
                                      traffic))
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    out = {"cell": name, "topology": topology,
           "bytes": {"replay": nbytes(buffers), "env_state":
                     nbytes(env_states), "traffic": nbytes(traffic),
                     "learner_state": nbytes(state), "obs": nbytes(obs)},
           "programs": {}}
    jitted = pddpg.chunk_step.func      # the donating jit of the dispatch
    for learn in (False, True):
        t0 = time.time()
        compiled = jitted.lower(pddpg, *args, start, chunk, learn).compile()
        mem = compiled.memory_analysis()
        # the program's whole footprint: its arguments, its temporaries
        # and the outputs that do not reuse a donated argument's buffer
        total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        out["programs"][f"chunk_step(learn={learn})"] = {
            "compile_s": round(time.time() - t0, 1),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "generated_code_bytes": int(mem.generated_code_size_in_bytes),
            "device_total_gb": total / 1e9,
            "chip_share_pct": 100 * total / CHIP_BYTES,
        }
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    a = ap.parse_args()
    print(json.dumps(compile_cell(a.workload, a.topology), indent=1))
