"""Tensor-parallel smoke: the `tp` rulebook end to end through the CLI.

The CI-stage proof that true tensor-parallel compute actually executes
and is GATED the way PR 13 promises — by tolerance bands, not digests.
A tiny 3-episode, 2-replica CPU train run on a 1x2 mesh with
``--partition-rules tp`` must

- exit 0 with ``run_start`` recording ``mesh 1x2`` / ``rules tp`` and a
  partition summary that genuinely splits leaves over ``mp``,
- write a ``perf.json`` whose ledger carries BOTH the carving-comparable
  plain ``chunk_step`` entry and the ``chunk_step_sharded`` capture of
  the partitioned executable — the latter with a non-empty collective
  block (the psum-accumulated contractions are all-reduces the HLO
  can't hide),
- write a complete ``curves.json`` and gate through ``bench_diff``:
  self-compare clean (rc 0), an injected curve regression caught
  (rc 1) — the banded-acceptance workflow the tp contract rests on.

Run by ``tools/ci_check.sh`` after the multihost stage; standalone:

    JAX_PLATFORMS=cpu python tools/tp_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

# the 1x2 mesh needs 2 virtual CPU devices — the flag is read at backend
# init (first jax.devices()), so setting it before any device work is
# enough even though jax may already be imported
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=2").strip()

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EPISODES = 3


def _configure_jax():
    import jax

    from gsc_tpu.runtime import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")   # a CPU smoke, explicitly
    enable_compile_cache()


def fail(msg: str) -> int:
    print(f"tp smoke: FAIL — {msg}")
    return 1


def main() -> int:
    _configure_jax()
    import jax

    if len(jax.devices()) < 2:
        return fail(f"needs 2 virtual CPU devices, backend has "
                    f"{len(jax.devices())} (XLA_FLAGS latched too late?)")
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    tmp = tempfile.mkdtemp(prefix="gsc_tp_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", str(EPISODES), "--replicas", "2",
        "--chunk", "3", "--mesh", "1x2", "--partition-rules", "tp",
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code} under --partition-rules tp")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]

    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]
    start = [e for e in events if e["event"] == "run_start"][0]
    if start.get("mesh") != "1x2" or start.get("partition_rules") != "tp":
        return fail(f"run_start records mesh={start.get('mesh')!r} "
                    f"rules={start.get('partition_rules')!r}")
    specs = start.get("partition_specs") or {}
    split = sum(n for spec, n in specs.items()
                if spec != "PartitionSpec()")
    if split <= 0:
        return fail(f"tp partition summary splits no leaf: {specs}")

    perf_path = os.path.join(rdir, "perf.json")
    if not os.path.exists(perf_path):
        return fail("perf.json not written")
    entries = json.load(open(perf_path)).get("entries") or {}
    plain = entries.get("chunk_step") or {}
    sharded = entries.get("chunk_step_sharded") or {}
    if not plain.get("available"):
        return fail(f"plain chunk_step capture missing/failed: {plain}")
    if not sharded.get("available"):
        return fail(f"chunk_step_sharded capture missing/failed: "
                    f"{sharded}")
    col = sharded.get("collectives") or {}
    if not col.get("count"):
        return fail(f"partitioned executable shows no collectives — "
                    f"tp contractions should all-reduce: {col}")
    if "collectives" not in plain:
        return fail("plain capture predates the collective-mining "
                    "ledger (no collectives block)")

    curves_path = os.path.join(rdir, "curves.json")
    if not os.path.exists(curves_path):
        return fail("curves.json not written")
    curves = json.load(open(curves_path))
    if curves.get("episodes") != EPISODES \
            or curves["summary"].get("final_window_return") is None:
        return fail(f"curves.json incomplete: episodes="
                    f"{curves.get('episodes')} "
                    f"summary={curves.get('summary')}")

    # the banded-acceptance gate itself: self-compare clean, injected
    # envelope regression caught — rc discipline identical to CI's
    import bench_diff
    traj = os.path.join(tmp, "traj.json")
    doc = bench_diff.ingest([curves_path], traj)
    (row_name,) = [n for n in doc["rows"] if n.startswith("curves_")]
    rc = bench_diff.main(["diff", row_name, "--baseline", row_name,
                          "--trajectory", traj])
    if rc != 0:
        return fail(f"tp curves self-compare rc={rc} (want 0)")
    base_final = doc["rows"][row_name]["metrics"]["final_window_return"]
    bad = dict(curves)
    bad["summary"] = {**curves["summary"],
                      "final_window_return":
                          base_final - 10 * abs(base_final) - 100.0}
    bad_path = os.path.join(tmp, "bad_curves.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    rc = bench_diff.main(["diff", bad_path, "--baseline", row_name,
                          "--trajectory", traj])
    if rc != 1:
        return fail(f"injected tp curve regression rc={rc} (want 1)")

    print(f"tp smoke: OK — 1x2 tp run green, {split} leaves split, "
          f"{col['count']} collectives / {col['bytes']} B banked in "
          "perf.json, curves envelope-gated both directions")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
