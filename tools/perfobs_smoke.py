"""Perf-observability smoke: ledger + trace + bench_diff on a tiny run.

The CI-stage proof that the performance-observability layer actually
produces its artifacts end to end: a 3-episode CPU training run (with a
deliberately tiny ``--obs-rotate-mb`` so segment rotation is exercised
too) must

- write a ``perf.json`` cost ledger whose ``episode_step`` entry carries
  FLOPs, bytes, a fusion count, per-dispatch wall and an MFU estimate
  (schema-versioned, arithmetically consistent);
- yield an events stream that ``tools/trace_export.py`` renders into
  trace-event JSON passing the strict validator (monotone ts, matched
  B/E pairs, pid/tid everywhere) — across the rotated segments;
- ingest cleanly into a ``BENCH_TRAJECTORY.json`` next to the repo's
  banked BENCH_r*/MULTICHIP_r*/SERVE_r* artifacts, SELF-COMPARE clean
  (rc 0), and FAIL (rc != 0) against an injected synthetic regression.

Run by ``tools/ci_check.sh`` before the chaos stage; standalone:

    JAX_PLATFORMS=cpu python tools/perfobs_smoke.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

# runnable from any cwd: the repo root is this file's parent's parent
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))


def _configure_jax():
    import jax

    from gsc_tpu.runtime import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")   # a CPU smoke, explicitly
    enable_compile_cache()


def fail(msg: str) -> int:
    print(f"perfobs smoke: FAIL — {msg}")
    return 1


def main() -> int:
    _configure_jax()
    from click.testing import CliRunner

    from chaos_smoke import write_tiny_configs
    from gsc_tpu.cli import cli

    tmp = tempfile.mkdtemp(prefix="gsc_perfobs_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", "3",
        "--result-dir", os.path.join(tmp, "res"),
        "--obs-rotate-mb", "0.002"])     # ~2 KiB: forces rotation
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code}")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]

    # ---- cost ledger --------------------------------------------------
    perf_path = os.path.join(rdir, "perf.json")
    if not os.path.exists(perf_path):
        return fail(f"no perf.json in {rdir}")
    perf = json.load(open(perf_path))
    e = (perf.get("entries") or {}).get("episode_step") or {}
    for field in ("flops", "bytes_accessed", "fusions", "dispatches",
                  "wall_s_mean", "achieved_flops_per_s"):
        if not e.get(field):
            return fail(f"perf.json episode_step missing/zero {field!r}: "
                        f"{e}")
    if e["dispatches"] != 3:
        return fail(f"expected 3 dispatches, ledger has {e['dispatches']}")
    # a CPU is not in the peaks table: no MFU, and the document says so
    if perf.get("peaks") is not None or "mfu" in e \
            or perf.get("device_kind") not in (perf.get("peaks_note") or ""):
        return fail("a CPU run must carry no peaks/mfu and a peaks_note "
                    f"naming the device: {perf.get('peaks')!r} / {e}")
    print(f"perfobs smoke: ledger ok (schema v{perf['schema_version']}, "
          f"{e['fusions']} fusions, no mfu on {perf['device_kind']!r})")

    # rotation actually happened and the report reader reassembles it
    if not os.path.exists(os.path.join(rdir, "events.jsonl.1")):
        return fail("--obs-rotate-mb 0.002 produced no rotated segment")
    import obs_report
    summary = obs_report.summarize(obs_report.load_events(rdir),
                                   perf=obs_report.load_perf(rdir))
    if summary["episodes"] != 3 or summary["status"] != "ok":
        return fail(f"rotated-stream summary wrong: "
                    f"episodes={summary['episodes']} "
                    f"status={summary['status']}")
    if not summary["perf"]:
        return fail("obs_report did not surface the perf section")

    # ---- trace export -------------------------------------------------
    trace_out = os.path.join(tmp, "trace.json")
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_export.py"),
         rdir, "-o", trace_out], capture_output=True, text=True)
    if r2.returncode != 0:
        return fail(f"trace_export rc={r2.returncode}: {r2.stderr}")
    print(r2.stdout.strip())

    # ---- bench_diff ---------------------------------------------------
    import bench_diff
    traj = os.path.join(tmp, "BENCH_TRAJECTORY.json")
    doc = bench_diff.ingest([perf_path], traj, scan=REPO)
    row_name = next((n for n, row in doc["rows"].items()
                     if row["kind"] == "perf_ledger"
                     and row["source"] == os.path.normpath(perf_path)),
                    None)
    if row_name is None:
        return fail("run's perf.json did not ingest into the trajectory")
    rc = bench_diff.main(["diff", row_name, "--baseline", row_name,
                          "--trajectory", traj])
    if rc != 0:
        return fail(f"self-compare rc={rc} (expected 0)")
    # injected regression: halve the rate-like metrics, bloat the counts
    bad = json.loads(json.dumps(doc["rows"][row_name]))
    bad["metrics"] = {k: (v * 2 if k.endswith(("fusions", "jit_traces"))
                          else v * 0.5)
                      for k, v in bad["metrics"].items()}
    doc["rows"]["perf_injected"] = bad
    bench_diff.write_trajectory(traj, doc)
    rc = bench_diff.main(["diff", "perf_injected", "--baseline", row_name,
                          "--trajectory", traj])
    if rc == 0:
        return fail("injected regression passed the diff gate")
    rc = bench_diff.main(["diff", row_name, "--baseline", "no_such_row",
                          "--trajectory", traj])
    if rc != 3:
        return fail(f"missing baseline rc={rc} (expected 3)")
    print("perfobs smoke: OK (ledger + rotation + trace + bench_diff)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
