"""Substep profiler: trace the engine's control-interval scan and rank
fusions by self-time.

The r3 perf unlocks all came from exactly this loop (trace -> aggregate ->
kill the dominant op class); this makes it a one-command repo tool instead
of ad-hoc /tmp scripts.  Captures a fresh jax.profiler trace of ``--calls``
chunked rollout calls at the given replica count, parses the
trace-events JSON (.gz) for the device track, and prints the top-K ops by
total self duration plus the per-substep wall.

    python tools/profile_substep.py --replicas 256 --chunk 50
    JAX_PLATFORMS=cpu python tools/profile_substep.py --replicas 4 --chunk 5  # smoke

Only FRESH trace dirs are globbed (stale files double-count — r3 gotcha).

``--mfu`` switches to the roofline sweep: for each replica count it lowers
the chunked rollout call, reads XLA's own per-executable cost analysis
(flops + bytes accessed — exact for the one-hot engine, whose FLOPs are
static dot shapes), times the call, and prints sustained FLOP/s vs chip
peak plus the arithmetic-intensity regime.  This is the VERDICT r4 item:
"what fraction of peak does the chip sustain, and is the substep
FLOP-bound or op-count-bound at B=256?"  The peaks come from the ONE
table, ``gsc_tpu.obs.perf.DEVICE_PEAKS``, keyed by ``device_kind``; on a
device that is not in it (any CPU) ``--mfu`` is an error, not a default.

    python tools/profile_substep.py --mfu --replicas 64 256 512
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _build(env_steps, B, chunk):
    """Shared setup: flagship scenario, device traffic, chunked rollout."""
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _flagship
    from gsc_tpu.parallel import ParallelDDPG
    from gsc_tpu.sim.traffic_device import DeviceTraffic

    env, agent, topo, _ = _flagship(episode_steps=env_steps,
                                    gen_traffic=False)
    dt = DeviceTraffic(env.sim_cfg, env.service, topo, env_steps)
    traffic = jax.jit(lambda k: dt.sample_batch(k, B))(
        jax.random.PRNGKey(0))
    pddpg = ParallelDDPG(env, agent, num_replicas=B)
    env_states, obs = pddpg.reset_all(jax.random.PRNGKey(0), topo, traffic)
    one_obs = jax.tree_util.tree_map(lambda x: x[0], obs)
    state = pddpg.init(jax.random.PRNGKey(1), one_obs)
    buffers = pddpg.init_buffers(one_obs)

    def call(state, buffers, env_states, obs, start):
        return pddpg.rollout_episodes(state, buffers, env_states, obs,
                                      topo, traffic, jnp.int32(start), chunk)

    return call, (state, buffers, env_states, obs)


def _cost(compiled):
    """Flops/bytes from XLA's executable cost analysis."""
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def mfu_sweep(args):
    """Roofline table: XLA-counted FLOPs/bytes per rollout call vs measured
    wall, at each replica count.  Regime call: compare the measured wall to
    the compute-roof time (flops/peak) and memory-roof time (bytes/bw) —
    if the wall dwarfs both roofs, the substep is op-COUNT (launch/fusion
    latency) bound, which is what the r3 trace showed pre-one-hot."""
    import jax

    from gsc_tpu.analysis.hlo import count_fusions
    from gsc_tpu.obs.perf import device_peaks
    from gsc_tpu.runtime import device_fields

    device = device_fields()
    peaks = device_peaks(device["device_kind"])
    if peaks is None:
        raise SystemExit(
            f"--mfu: device_kind {device['device_kind']!r} is not in "
            "gsc_tpu.obs.perf.DEVICE_PEAKS — add its published peaks "
            "with their source there; no default is applied")
    peak_flops, peak_bps = peaks["flops_per_s"], peaks["bytes_per_s"]
    chunk = args.chunk
    rows = []
    for B in args.replicas:
        call, carry = _build(args.episode_steps, B, chunk)
        lowered = jax.jit(call).lower(*carry, 0)
        compiled = lowered.compile()
        flops, byts = _cost(compiled)
        n_fusions = count_fusions(compiled)
        out = compiled(*carry, 0)           # warm (engine already compiled)
        jax.block_until_ready(out)
        t0 = time.time()
        for c in range(args.calls):
            out = compiled(*out[:4], (c + 1) * chunk)
        jax.block_until_ready(out)
        wall = (time.time() - t0) / args.calls
        # per-substep figures: one rollout call = chunk control steps, each
        # sim_cfg.run_duration/dt substeps; flops is per CALL
        t_flops = flops / peak_flops
        t_bytes = byts / peak_bps
        roof = max(t_flops, t_bytes)
        if wall > 3 * roof:
            regime = "op-count-bound"
        elif t_flops >= t_bytes:
            regime = "FLOP-bound"
        else:
            regime = "bytes-bound"
        rows.append({
            **device, "replicas": B, "chunk": chunk,
            "wall_per_call_s": round(wall, 4),
            "env_steps_per_sec": round(chunk * B / wall, 1),
            "gflops_per_call": round(flops / 1e9, 2),
            "gbytes_per_call": round(byts / 1e9, 3),
            "sustained_tflops": round(flops / wall / 1e12, 3),
            "mfu_vs_bf16_peak": round(flops / wall / peak_flops, 4),
            "hbm_frac": round(byts / wall / peak_bps, 4),
            "arith_intensity": round(flops / max(byts, 1.0), 2),
            "compute_roof_s": round(t_flops, 5),
            "memory_roof_s": round(t_bytes, 5),
            "hlo_fusions": n_fusions,
            "regime": regime,
        })
        print(json.dumps(rows[-1]))
    print(json.dumps({**device,
                      "peak_bf16_tflops": peak_flops / 1e12,
                      "peak_hbm_gbps": peak_bps / 1e9,
                      "peaks_source": peaks["source"],
                      "note": ("engine dots run f32 Precision.HIGHEST "
                               "(multi-pass bf16 on the MXU), so MXU "
                               "issue-slot occupancy is ~3-6x the raw "
                               "mfu_vs_bf16_peak figure"),
                      "rows": rows}, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, nargs="+", default=[256])
    ap.add_argument("--chunk", type=int, default=50)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--episode-steps", type=int, default=200)
    ap.add_argument("--mfu", action="store_true",
                    help="roofline sweep over --replicas instead of a trace")
    args = ap.parse_args()

    import jax

    if args.mfu:
        mfu_sweep(args)
        return

    if len(args.replicas) > 1:
        raise SystemExit("trace mode profiles ONE replica count; pass a "
                         "single --replicas value (or use --mfu to sweep)")
    from gsc_tpu.runtime import device_fields

    B, chunk = args.replicas[0], args.chunk
    call, (state, buffers, env_states, obs) = _build(
        args.episode_steps, B, chunk)

    # compile + warm
    out = call(state, buffers, env_states, obs, 0)
    jax.block_until_ready(out)
    state, buffers, env_states, obs = out[:4]

    trace_dir = tempfile.mkdtemp(prefix="substep_trace_")
    t0 = time.time()
    with jax.profiler.trace(trace_dir):
        for c in range(args.calls):
            out = call(state, buffers, env_states, obs, (c + 1) * chunk)
            state, buffers, env_states, obs = out[:4]
        jax.block_until_ready(out)
    wall = time.time() - t0

    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not files:
        print(json.dumps({"error": "no trace written", "dir": trace_dir}))
        return
    agg = collections.Counter()
    counts = collections.Counter()
    for fp in files:
        with gzip.open(fp, "rt") as f:
            data = json.load(f)
        events = data.get("traceEvents", [])
        # restrict to DEVICE lanes (XLA ops): host python/TSL lanes also
        # carry dur and would otherwise pollute the ranking.  pid names
        # come from process_name metadata events; fall back to all lanes
        # if no device track exists (plain CPU backend).
        dev_pids = {ev.get("pid") for ev in events
                    if ev.get("ph") == "M"
                    and ev.get("name") == "process_name"
                    and any(s in str((ev.get("args") or {}).get("name", ""))
                            .lower() for s in ("/device:", "tpu", "gpu",
                                               "xla"))}
        for ev in events:
            if ev.get("ph") != "X" or "dur" not in ev:
                continue
            if dev_pids and ev.get("pid") not in dev_pids:
                continue
            name = ev.get("name", "")
            args_d = ev.get("args") or {}
            key = args_d.get("long_name") or name
            agg[key.split("(")[0][:80]] += ev["dur"]
            counts[key.split("(")[0][:80]] += 1
    total = sum(agg.values())
    env_steps = args.calls * chunk * B
    print(json.dumps({
        **device_fields(), "replicas": B, "chunk": chunk,
        "calls": args.calls, "wall_s": round(wall, 3),
        "env_steps_per_sec": round(env_steps / wall, 1),
        "trace_total_us": total,
    }))
    width = max((len(k) for k, _ in agg.most_common(args.top)), default=10)
    for name, dur in agg.most_common(args.top):
        print(f"{dur/1e3:10.2f} ms  {100*dur/max(total,1):5.1f}%  "
              f"x{counts[name]:<6} {name:<{width}}")


if __name__ == "__main__":
    main()
