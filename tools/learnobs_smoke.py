"""Learn-obs smoke: the training-quality observability layer end to end.

The CI-stage proof that the learn ledger actually executes through the
real CLI: a tiny 3-episode, 2-replica mixed-topology CPU train run
(``--topo-mix "schedule,line3"``, learn obs on by default) must

- exit 0 and write a complete schema-versioned ``curves.json`` (return +
  TD series as long as the run, per-topology series for BOTH mixture
  members, envelope summary present),
- leave one ``learn_signal`` event per episode in ``events.jsonl`` with
  per-topology |TD| covering both networks, plus ``td_abs_mean`` /
  ``grad_norm`` / ``topology_return`` gauges in ``metrics.json``,
- expose a scrapeable Prometheus ``/metrics`` endpoint (in-process
  roundtrip: every snapshot series parses back identically),
- gate through ``bench_diff``: the run's curves row self-compares clean
  (rc 0) while an injected envelope regression is caught (rc 1).

Run by ``tools/ci_check.sh`` after the perfobs stage; standalone:

    JAX_PLATFORMS=cpu python tools/learnobs_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import urllib.request

# runnable from any cwd: the repo root is this file's parent's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MIX = "schedule,line3"
EPISODES = 3


def _configure_jax():
    import jax

    from gsc_tpu.runtime import enable_compile_cache

    jax.config.update("jax_platforms", "cpu")   # a CPU smoke, explicitly
    enable_compile_cache()


def fail(msg: str) -> int:
    print(f"learnobs smoke: FAIL — {msg}")
    return 1


def check_endpoint() -> str:
    """In-process /metrics scrape roundtrip (the CLI run binds no port in
    CI — a fixed port would collide across concurrent stages)."""
    from gsc_tpu.obs import MetricsEndpoint, MetricsHub

    hub = MetricsHub(tags={"run": "smoke"})
    hub.gauge("td_abs_mean", 0.75, topology="line3")
    hub.counter("episodes_drained", 2)
    ep = MetricsEndpoint(hub, port=0).start()
    try:
        body = urllib.request.urlopen(ep.url, timeout=10).read().decode()
        parsed = {}
        for line in body.strip().splitlines():
            name, value = line.rsplit(" ", 1)
            parsed[name] = float(value)
        snap = {k: float(v) for k, v in hub.snapshot().items()}
        if parsed != snap:
            return f"endpoint scrape != snapshot ({parsed} vs {snap})"
    finally:
        ep.stop()
    return ""


def main() -> int:
    _configure_jax()
    from click.testing import CliRunner

    from gsc_tpu.cli import cli
    from tools.chaos_smoke import write_tiny_configs

    err = check_endpoint()
    if err:
        return fail(err)

    tmp = tempfile.mkdtemp(prefix="gsc_learnobs_")
    args = write_tiny_configs(os.path.join(tmp, "cfg"))
    r = CliRunner().invoke(cli, [
        "train", *args, "--episodes", str(EPISODES), "--replicas", "2",
        "--chunk", "3", "--topo-mix", MIX,
        "--result-dir", os.path.join(tmp, "res")])
    if r.exit_code != 0:
        print(r.output)
        if r.exception is not None:
            import traceback
            traceback.print_exception(type(r.exception), r.exception,
                                      r.exception.__traceback__)
        return fail(f"train rc={r.exit_code} under --topo-mix {MIX!r}")
    rdir = json.loads(r.output.strip().splitlines()[-1])["result_dir"]

    events = [json.loads(line)
              for line in open(os.path.join(rdir, "events.jsonl"))]
    signals = [e for e in events if e["event"] == "learn_signal"]
    if len(signals) != EPISODES:
        return fail(f"expected {EPISODES} learn_signal events, got "
                    f"{len(signals)}")
    names = set()
    for e in signals:
        names |= set(e.get("per_topology_td") or {})
    if len(names) < 2:
        return fail(f"per-topology |TD| should cover both mixture "
                    f"members, saw {sorted(names)}")
    snap = json.load(open(os.path.join(rdir, "metrics.json")))["metrics"]
    for prefix in ("gsc_td_abs_mean", "gsc_grad_norm{",
                   "gsc_topology_return", "gsc_replay_fill"):
        if not any(k.startswith(prefix) for k in snap):
            return fail(f"no {prefix}* gauge in metrics.json")

    curves_path = os.path.join(rdir, "curves.json")
    if not os.path.exists(curves_path):
        return fail("curves.json not written")
    curves = json.load(open(curves_path))
    if curves.get("schema_version") != 1 \
            or curves.get("episodes") != EPISODES:
        return fail(f"curves.json header wrong: "
                    f"schema={curves.get('schema_version')} "
                    f"episodes={curves.get('episodes')}")
    for key in ("episodic_return", "td_abs_mean"):
        col = curves["series"].get(key)
        if not col or len(col) != EPISODES:
            return fail(f"curves series {key!r} incomplete: {col}")
    if set(curves.get("per_topology") or {}) != names:
        return fail(f"curves per_topology {sorted(curves['per_topology'])} "
                    f"!= event names {sorted(names)}")
    if curves["summary"].get("final_window_return") is None:
        return fail("curves summary missing final_window_return")

    # bench_diff gate: self-compare clean, injected regression caught
    import bench_diff
    traj = os.path.join(tmp, "traj.json")
    doc = bench_diff.ingest([curves_path], traj)
    (row_name,) = [n for n in doc["rows"] if n.startswith("curves_")]
    rc = bench_diff.main(["diff", row_name, "--baseline", row_name,
                          "--trajectory", traj])
    if rc != 0:
        return fail(f"curves self-compare rc={rc} (want 0)")
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
        return fail(f"injected curve regression rc={rc} (want 1)")

    print(f"learnobs smoke: OK — {len(signals)} learn_signal episodes "
          f"over {sorted(names)}, curves.json complete + gated, "
          "/metrics scrape roundtrip clean")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
