"""Latency-SLA serving bench — banks SERVE_r*.json next to BENCH_*.json.

Measures the serving subsystem end to end, with each leg in a FRESH
subprocess so the startup numbers mean what they claim:

- **cold leg** (empty artifact cache, concurrency 1): ``cold_start_s`` =
  trace + lower + backend-compile of every bucket; request latencies land
  in the smallest bucket;
- **warm leg** (same artifact cache, concurrency = largest bucket):
  ``cache_hit_start_s`` = deserialize + warm only — the number that must
  be seconds, not minutes; every bucket must report a cache hit or the
  bench fails; the concurrent closed-loop load fills the large bucket;
- **sustained trio** (unless ``--no-sustained``): one closed-loop load
  shape (concurrency >= 8, largest bucket > concurrency so the deadline
  batcher pays its wait every flush) through the deadline batcher, then
  continuous batching, then continuous batching with ``--swaps`` live
  weight hot-swaps fired mid-load.  Bank-time gates: zero errors on
  every leg, all fired swaps completed, continuous rps >= deadline rps,
  and the swap leg inside the bench_diff p99/slo_* bands vs the no-swap
  control — SERVE_r02's acceptance criteria, enforced by the tool.

Output artifact (``--out``, default SERVE_r01.json): requests/s and
p50/p99 per leg and per batch bucket, the two startup walls, each leg's
SLO summary (deadline-miss ratio, pad waste, queue-wait fraction,
error-budget burn rate and attainment against ``--slo-p99-ms`` — the
``slo_*`` axes ``tools/bench_diff.py`` gates), and the
scenario/platform provenance.  Usage:

    JAX_PLATFORMS=cpu python tools/serve_bench.py --out SERVE_r01.json

Scenario: the tiny triangle stack (chaos_smoke configs) by default so the
bench runs anywhere; pass --configs agent.yaml,sim.yaml,svc.yaml,sched.yaml
plus --ckpt to bench a real checkpoint/scenario instead.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # both caches on: the artifact cache is the subject under test; the
    # persistent XLA cache (on by default in the CLI) is what makes the
    # deserialized module's backend compile skippable across processes
    return env


def _train_tiny(tmp: str):
    from chaos_smoke import write_tiny_configs
    from click.testing import CliRunner

    from gsc_tpu.cli import cli

    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, ["train", *args, "--episodes", "2",
                                 "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        raise SystemExit(f"tiny train failed rc={r.exit_code}")
    ckpt = json.loads(r.output.strip().splitlines()[-1])["checkpoint"]
    configs = args[:4]
    extra = [a for a in args[4:] if a != "--quiet"]
    return configs, ckpt, extra


def _serve_leg(configs, ckpt, extra, *, requests, concurrency, buckets,
               deadline_ms, cache_dir, result_dir, slo_p99_ms=None,
               timeout_s=900, flags=()):
    cmd = [sys.executable, "-m", "gsc_tpu.cli", "serve", *configs, ckpt,
           *extra, "--requests", str(requests),
           "--concurrency", str(concurrency), "--buckets", buckets,
           "--deadline-ms", str(deadline_ms),
           "--artifact-cache", cache_dir, "--result-dir", result_dir,
           *flags]
    if slo_p99_ms is not None:
        cmd += ["--slo-p99-ms", str(slo_p99_ms)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=REPO, env=_env(), capture_output=True,
                          text=True, timeout=timeout_s)
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise SystemExit(f"serve leg failed rc={proc.returncode}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    if out["errors"]:
        raise SystemExit(f"serve leg answered with errors: "
                         f"{out['error_detail']}")
    out["process_wall_s"] = round(wall, 3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="SERVE_r01.json")
    ap.add_argument("--requests", type=int, default=200,
                    help="requests per leg [default 200]")
    ap.add_argument("--buckets", default="1,8")
    ap.add_argument("--deadline-ms", type=float, default=5.0)
    ap.add_argument("--slo-p99-ms", type=float, default=250.0,
                    help="latency objective handed to each leg's SLO "
                         "engine — generous by default so attainment/"
                         "burn reflect real trouble, not CPU jitter; "
                         "the banked per-leg `slo` block (deadline-miss "
                         "ratio, pad waste, queue-wait fraction, burn "
                         "rate, attainment) is what bench_diff gates "
                         "under the slo_* bands [default 250]")
    ap.add_argument("--configs", default=None,
                    help="agent,sim,service,scheduler yaml paths (comma-"
                         "separated) for a non-tiny scenario")
    ap.add_argument("--ckpt", default=None,
                    help="existing checkpoint to serve (with --configs)")
    ap.add_argument("--scenario", default=None,
                    help="scenario label recorded in the artifact")
    ap.add_argument("--no-sustained", action="store_true",
                    help="skip the sustained-load trio (deadline "
                         "reference, continuous control, continuous + "
                         "hot-swaps under fire) and bank only the "
                         "historic cold/warm legs")
    ap.add_argument("--sustained-requests", type=int, default=240,
                    help="requests per sustained leg [default 240]")
    ap.add_argument("--sustained-concurrency", type=int, default=8,
                    help="closed-loop clients per sustained leg — the "
                         "acceptance floor is 8 [default 8]")
    ap.add_argument("--sustained-buckets", default="1,8,16",
                    help="buckets for the sustained legs: the largest "
                         "deliberately exceeds the concurrency, so the "
                         "deadline batcher pays its full wait per flush "
                         "while continuous mode never does — the regime "
                         "continuous batching exists for [default 1,8,16]")
    ap.add_argument("--swaps", type=int, default=3,
                    help="hot-swaps fired during the swap leg "
                         "(acceptance floor: 3) [default 3]")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    tmp = tempfile.mkdtemp(prefix="gsc_serve_bench_")
    if args.configs:
        configs = args.configs.split(",")
        if len(configs) != 4 or not args.ckpt:
            raise SystemExit("--configs wants 4 comma-separated yamls "
                             "plus --ckpt")
        ckpt, extra = args.ckpt, []
        scenario = args.scenario or "custom"
    else:
        configs, ckpt, extra = _train_tiny(tmp)
        scenario = args.scenario or \
            "triangle-3node tiny (chaos_smoke configs), graph-mode GNN actor"

    cache_dir = os.path.join(tmp, "artifact_cache")
    bucket_list = [int(b) for b in args.buckets.split(",")]
    legs = {}
    # cold: empty artifact cache, serial clients -> smallest bucket
    legs["cold"] = _serve_leg(
        configs, ckpt, extra, requests=args.requests, concurrency=1,
        buckets=args.buckets, deadline_ms=args.deadline_ms,
        cache_dir=cache_dir, result_dir=os.path.join(tmp, "serve_cold"),
        slo_p99_ms=args.slo_p99_ms)
    # warm: same cache, fresh process, concurrent clients -> large bucket
    legs["warm"] = _serve_leg(
        configs, ckpt, extra, requests=args.requests,
        concurrency=max(bucket_list), buckets=args.buckets,
        deadline_ms=args.deadline_ms, cache_dir=cache_dir,
        result_dir=os.path.join(tmp, "serve_warm"),
        slo_p99_ms=args.slo_p99_ms)

    hits = {b: rec["cache_hit"]
            for b, rec in legs["warm"]["startup"]["buckets"].items()}
    if not all(hits.values()):
        raise SystemExit(f"warm leg missed the artifact cache: {hits}")
    if any(rec["cache_hit"]
           for rec in legs["cold"]["startup"]["buckets"].values()):
        raise SystemExit("cold leg unexpectedly hit a pre-existing cache "
                         f"— stale --artifact-cache dir? {cache_dir}")

    # sustained trio (the hot-swap-under-fire acceptance legs): the same
    # closed-loop load through (a) the deadline batcher, (b) continuous
    # batching, (c) continuous batching with --swaps live weight swaps
    # fired mid-load.  Every leg must answer with zero errors; the swap
    # leg must stay inside the bench_diff p99/slo_* bands vs the no-swap
    # control, and continuous throughput must meet the deadline
    # batcher's — the fleet claims, machine-checked at bank time.
    sustained = None
    if not args.no_sustained:
        sus = dict(requests=args.sustained_requests,
                   concurrency=args.sustained_concurrency,
                   buckets=args.sustained_buckets,
                   deadline_ms=args.deadline_ms, cache_dir=cache_dir,
                   slo_p99_ms=args.slo_p99_ms)
        legs["sustained_deadline"] = _serve_leg(
            configs, ckpt, extra,
            result_dir=os.path.join(tmp, "serve_sus_deadline"), **sus)
        legs["sustained_control"] = _serve_leg(
            configs, ckpt, extra, flags=["--continuous"],
            result_dir=os.path.join(tmp, "serve_sus_control"), **sus)
        swap_dir = os.path.join(tmp, "hot_swap")
        legs["sustained_swap"] = _serve_leg(
            configs, ckpt, extra,
            flags=["--continuous", "--hot-swap-dir", swap_dir,
                   "--swap-poll-s", "0.02",
                   "--fire-swaps", str(args.swaps)],
            result_dir=os.path.join(tmp, "serve_sus_swap"), **sus)

        swap_leg = legs["sustained_swap"]
        if swap_leg["swaps"] < args.swaps:
            raise SystemExit(
                f"swap leg completed {swap_leg['swaps']} swaps < "
                f"{args.swaps} fired — hot-swap-under-fire not proven")
        dl_rps = legs["sustained_deadline"]["rps"]
        for name in ("sustained_control", "sustained_swap"):
            if legs[name]["rps"] < dl_rps:
                raise SystemExit(
                    f"continuous leg {name} rps {legs[name]['rps']} < "
                    f"deadline batcher {dl_rps} — continuous batching "
                    "must not cost throughput")

        # swap-vs-control through the real bench_diff bands: p99 plus
        # every slo_* axis — the acceptance gate, applied at bank time
        # so a red artifact can never be committed green.  p50/rps stay
        # recorded context rather than gates on this comparison: on a
        # single-core host the publisher + watcher threads legitimately
        # steal cycles from the serve path (the throughput floor is
        # enforced separately against the deadline batcher above)
        import bench_diff

        def _row(name, leg):
            metrics = {"p99_ms": leg["p99_ms"]}
            for k in ("deadline_miss_ratio", "pad_waste",
                      "queue_wait_frac", "burn_rate", "attainment"):
                v = (leg.get("slo") or {}).get(k)
                if isinstance(v, (int, float)):
                    metrics[f"slo_{k}"] = float(v)
            return {"name": name, "status": "ok", "metrics": metrics}

        verdict = bench_diff.diff_rows(
            _row("sustained_swap", legs["sustained_swap"]),
            _row("sustained_control", legs["sustained_control"]))
        if verdict["verdict"] == "regression":
            raise SystemExit(
                "hot-swap leg regressed out of the bench_diff bands vs "
                f"the no-swap control: {verdict['regressions']}")
        sustained = {
            "concurrency": args.sustained_concurrency,
            "buckets": [int(b)
                        for b in args.sustained_buckets.split(",")],
            "requests_per_leg": args.sustained_requests,
            "swaps_fired": args.swaps,
            "swaps_completed": swap_leg["swaps"],
            "published_versions": swap_leg["published_versions"],
            "continuous_vs_deadline_rps": round(
                legs["sustained_control"]["rps"] / dl_rps, 3),
            "swap_vs_control": {
                "verdict": verdict["verdict"],
                "gated_metrics": verdict["gated_metrics"],
                "regressions": verdict["regressions"]},
        }

    bucket_stats = {}
    for leg in legs.values():
        for b, rec in leg["buckets"].items():
            agg = bucket_stats.setdefault(b, {"requests": 0})
            agg["requests"] += rec["requests"]
            # per-bucket latency: keep the leg that actually exercised the
            # bucket hardest (most requests)
            if rec["requests"] >= agg.get("_n", 0):
                agg.update({"p50_ms": rec["p50_ms"],
                            "p99_ms": rec["p99_ms"], "_n": rec["requests"]})
    for agg in bucket_stats.values():
        agg.pop("_n", None)

    artifact = {
        "artifact": os.path.splitext(os.path.basename(args.out))[0],
        "metric": "serve_requests_per_sec",
        "scenario": scenario,
        "platform": jax.default_backend(),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "tier": legs["cold"]["tier"],
        "buckets": bucket_list,
        "deadline_ms": args.deadline_ms,
        "requests_per_leg": args.requests,
        "slo_p99_ms": args.slo_p99_ms,
        "cold_start_s": legs["cold"]["startup"]["startup_s"],
        "cache_hit_start_s": legs["warm"]["startup"]["startup_s"],
        "sustained": sustained,
        "legs": {
            name: {"concurrency": (
                       1 if name == "cold"
                       else args.sustained_concurrency
                       if name.startswith("sustained")
                       else max(bucket_list)),
                   "mode": leg.get("mode", "deadline"),
                   "rps": leg["rps"], "p50_ms": leg["p50_ms"],
                   "p99_ms": leg["p99_ms"],
                   "process_wall_s": leg["process_wall_s"],
                   # the leg's SLO verdict (deadline-miss ratio, pad
                   # waste, queue-wait fraction, burn rate, attainment)
                   # — bench_diff gates these under the slo_* bands
                   "slo": leg.get("slo"),
                   # hot-swap provenance on the swap leg
                   **({"swaps": leg["swaps"]} if leg.get("swaps")
                      else {}),
                   "startup": leg["startup"],
                   "buckets": leg["buckets"]}
            for name, leg in legs.items()},
        "bucket_stats": bucket_stats,
        "notes": ("closed-loop client threads; latency = submit->answer "
                  "including queue+padding+device call; each leg is a "
                  "fresh process, so cache_hit_start_s is a true process "
                  "restart against the persisted artifacts; sustained_* "
                  "legs share one load shape — deadline batcher vs "
                  "continuous batching vs continuous with live weight "
                  "hot-swaps fired mid-load (swap leg gated against the "
                  "control through the bench_diff p99/slo_* bands at "
                  "bank time)"),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")
    summary = {"out": args.out,
               "cold_start_s": artifact["cold_start_s"],
               "cache_hit_start_s": artifact["cache_hit_start_s"],
               "cold_rps": legs["cold"]["rps"],
               "warm_rps": legs["warm"]["rps"]}
    if sustained is not None:
        summary.update({
            "deadline_rps": legs["sustained_deadline"]["rps"],
            "continuous_rps": legs["sustained_control"]["rps"],
            "swap_rps": legs["sustained_swap"]["rps"],
            "swaps": sustained["swaps_completed"],
            "swap_vs_control": sustained["swap_vs_control"]["verdict"]})
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
