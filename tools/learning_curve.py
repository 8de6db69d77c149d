"""Full-scale learning-curve run (BASELINE.md protocol: reproduce the
reference's quality metrics on the flagship scenario, then measure
throughput).

Trains ParallelDDPG on Abilene rand-cap1-2 (the reference benchmark
workload) for ``--episodes`` full 200-step episodes across ``--replicas``
vmapped envs and prints per-episode mean return / success ratio plus the
first-10 vs last-10 summary.  Episodes run CHUNKED
(``parallel.harness.run_chunked_episodes``) so the TPU never sees a
200-step single-call scan.

On the single shared TPU run it via::

    python tools/learning_curve.py --replicas 64 --episodes 40

(CPU works too, smaller: --replicas 4 --episode-steps 50.)
"""
from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=64)
    ap.add_argument("--episodes", type=int, default=40)
    ap.add_argument("--episode-steps", type=int, default=200)
    ap.add_argument("--chunk", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--host-traffic", action="store_true",
                    help="per-episode traffic on the HOST (the r3 path; "
                    "ships ~90 MB/episode host->device at B=256).  "
                    "Default is on-device sampling.")
    # multi-host: launch one process per host with identical arguments
    # plus --coordinator host0:port --num-processes P --process-id i.
    # --replicas is then the GLOBAL replica count (must divide by P).
    ap.add_argument("--sample-mode", choices=("across", "local"),
                    default=None,
                    help="replay sampling: uniform across all shards vs "
                    "shard-local stratified (default: across single-host, "
                    "local multihost)")
    ap.add_argument("--coordinator", default=None,
                    help="multi-host coordinator address host:port")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    multihost = args.coordinator is not None
    if multihost:
        from gsc_tpu.parallel.mesh import init_distributed
        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
    import jax.numpy as jnp

    from __graft_entry__ import _flagship
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.sim.traffic import generate_traffic
    from gsc_tpu.sim.traffic_device import DeviceTraffic

    T, B, chunk = args.episode_steps, args.replicas, args.chunk
    assert T % chunk == 0
    env, agent, topo, _ = _flagship(episode_steps=T)

    # multi-host: global (dcn, dp) mesh, replicas sharded over both axes,
    # per-process host data fed in as local shards (same SPMD pattern as
    # tools/dryrun_multihost.py); single-host: everything below is a no-op
    # passthrough
    if multihost:
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec as P

        from gsc_tpu.parallel.mesh import make_hybrid_mesh
        n_proc = jax.process_count()
        pid = jax.process_index()
        n_local = len(jax.local_devices())
        # replicas shard over (process, local-device), so B must divide by
        # the full device grid — fail here, not with an opaque sharding
        # error mid-run
        assert B % (n_proc * n_local) == 0, \
            f"--replicas {B} must be a multiple of " \
            f"processes*local_devices = {n_proc}*{n_local}"
        B_local = B // n_proc
        mesh = make_hybrid_mesh()
        spec = P(("dcn", "dp"))
        sharded = NamedSharding(mesh, spec)
        to_global = lambda tree: \
            multihost_utils.host_local_array_to_global_array(tree, mesh, spec)
        mesh_ctx = mesh
    else:
        import contextlib
        n_proc, pid, B_local = 1, 0, B
        sharded = None
        to_global = lambda tree: tree
        mesh_ctx = contextlib.nullcontext()

    if args.host_traffic:
        def episode_traffic(ep):
            # each process builds only its replicas' traces
            t0 = [generate_traffic(env.sim_cfg, env.service, topo, T,
                                   seed=1000 * ep + pid * B_local + s)
                  for s in range(B_local)]
            return to_global(
                jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *t0))
    else:
        dt = DeviceTraffic(env.sim_cfg, env.service, topo, T)
        sample_batch = jax.jit(lambda k: dt.sample_batch(k, B),
                               out_shardings=sharded)

        def episode_traffic(ep):
            return sample_batch(jax.random.fold_in(
                jax.random.PRNGKey(args.seed + 3), ep))

    # replay sampling: multihost defaults to shard-local stratified
    # sampling (no cross-process gather in the learn loop); note the
    # effective batch becomes B * max(batch_size // B, 1), which differs
    # from single-host 'across' sampling — the output JSON records the
    # mode so curves are never compared across semantics unknowingly
    sample_mode = args.sample_mode or ("local" if multihost else "across")
    pddpg = ParallelDDPG(env, agent, num_replicas=B,
                         sample_mode=sample_mode, donate=True)
    # single-replica reset (identical on every process) for learner init
    one_traffic = generate_traffic(env.sim_cfg, env.service, topo, T, seed=0)
    _, one_obs = env.reset(jax.random.PRNGKey(args.seed), topo, one_traffic)
    state = pddpg.init(jax.random.PRNGKey(args.seed + 1), one_obs)
    # each process allocates only its local replay shard
    buffers = to_global(pddpg.init_buffers(
        one_obs, num_replicas=B_local if multihost else None))
    traffic = episode_traffic(0)

    from gsc_tpu.parallel.harness import run_chunked_episodes

    t0 = time.time()

    def log_episode(ep, r, s, metrics):
        if pid == 0:
            print(f"episode={ep} return={r:.3f} succ={s:.3f} "
                  f"critic_loss={float(metrics['critic_loss']):.4f} "
                  f"elapsed={time.time() - t0:.0f}s", file=sys.stderr)

    with mesh_ctx:
        # episode 0 reuses the pre-loop traffic sample
        _, _, returns, succ, final_succ = run_chunked_episodes(
            pddpg, topo,
            lambda ep: episode_traffic(ep) if ep else traffic,
            state, buffers, args.episodes, T, chunk, args.seed,
            on_episode=log_episode)
    k = min(10, max(1, len(returns) // 4))
    if pid == 0:
        print(json.dumps({
            "replicas": B, "episodes": args.episodes, "episode_steps": T,
            "processes": n_proc, "sample_mode": sample_mode,
            "first_k_return": round(sum(returns[:k]) / k, 3),
            "last_k_return": round(sum(returns[-k:]) / k, 3),
            "first_k_succ": round(sum(succ[:k]) / k, 4),
            "last_k_succ": round(sum(succ[-k:]) / k, 4),
            "first_k_final_succ": round(sum(final_succ[:k]) / k, 4),
            "last_k_final_succ": round(sum(final_succ[-k:]) / k, 4),
            "wall_s": round(time.time() - t0, 1),
        }))


if __name__ == "__main__":
    main()
