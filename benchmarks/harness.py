"""The benchmark's harness: finds a cell's files by name, opens and closes
the measured window, loads one reader per metric, decides ``correct`` and
prints the result line.

Driven by data: a cell is ``workloads/<cell>.json`` (its configuration,
its driver, its traffic parameters and the limits of its output check), a
configuration is ``configs/<name>.json``, a metric is
``metrics/<name>.py`` with one ``read(record)`` function, a driver is
``drivers/<name>.py`` with one ``run(...)`` function, a plain reference
is ``reference/<name>.py``, named by the configuration's ``reference``
key and exporting ``CONTRACT``.  Nothing here switches on a cell's, a
configuration's or a reference's name.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(ROOT)
# what a policy's plain reference module exports (README: the contract)
CONTRACT = ("spec_from_config", "warmup_actions", "advance_key",
            "policy_actions", "learn_burst", "expected_static_columns",
            "model_flops", "init_weights")


# ------------------------------------------------------------------ files
def load_json(kind: str, name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind.rstrip('s')} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind.rstrip('s')} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def list_names(kind: str, ext: str, root: str = ROOT) -> List[str]:
    d = os.path.join(root, kind)
    return sorted(f[: -len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and not f.startswith("_"))


def load_cell(name: str, root: str = ROOT) -> Dict:
    """A cell with its configuration resolved: ``{"name", "cell",
    "config_name", "config", "root"}``.  A configuration that names no
    plain reference is refused: there is no default."""
    cell = load_json("workloads", name, root)
    config = load_json("configs", cell["config"], root)
    if not config.get("reference"):
        path = os.path.join(root, "configs", f"{cell['config']}.json")
        raise SystemExit(f"benchmark: configuration file {path} names no "
                         f"`reference` (reference/<name>.py)")
    return {"name": name, "cell": cell, "config_name": cell["config"],
            "config": config, "root": root}


def load_driver(cell: Dict):
    """The cell's driver module, from the tree the cell was loaded from."""
    return load_module("drivers", cell["cell"]["driver"], cell["root"])


def load_reference(cell: Dict):
    """The plain reference the cell's configuration names, from the tree
    the cell was loaded from; a module that lacks part of ``CONTRACT`` is
    refused with what it lacks."""
    ref = load_module("reference", cell["config"]["reference"], cell["root"])
    lacks = [f for f in CONTRACT if not callable(getattr(ref, f, None))]
    if lacks:
        raise SystemExit(f"benchmark: reference module {ref.__file__} "
                         f"lacks {', '.join(lacks)}")
    return ref


def load_peaks(root: str = ROOT) -> dict:
    with open(os.path.join(root, "peaks.json")) as f:
        return json.load(f)


def manifest(checkout: str = CHECKOUT) -> dict:
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_names(bench: dict, cell: str, traced: bool) -> List[str]:
    """The metrics ``BENCHMARK.json`` lists for this cell and this kind of
    run: end-to-end ones untraced, per-layer ones traced."""
    out = []
    for m in bench["per_layer" if traced else "end_to_end"]:
        if "workloads" not in m or cell in m["workloads"]:
            out.append(m["name"])
    return out


# ----------------------------------------------------------------- window
class Window:
    """The measured window, bounded through the episode loop's own stop.

    The loop reads ``triggered`` at the top of every episode, after the
    previous episode's synchronous drain; each read is an episode
    boundary and is stamped.  The first ``warm_episodes`` boundaries
    belong to set-up; the window opens at the next one and closes at the
    first boundary ``seconds`` or more after it opened — whole episodes
    only, the episode in flight always finishes.

    ``on_boundary(k, now)`` is called at window boundary ``k`` (0 = the
    opening) after the boundary is stamped, so what it costs falls into
    the episode that follows, never into the one it closes.  ``episodes``
    fixes a traced run's window to that many episodes, whatever
    ``seconds`` says: the tracer needs exactly its episodes, and a traced
    run reports no end-to-end metric.
    """

    signame = "benchmark_window"

    def __init__(self, seconds: float, warm_episodes: int,
                 clock: Callable[[], float] = time.time,
                 on_boundary: Optional[Callable[[int, float], None]] = None,
                 episodes: Optional[int] = None):
        self.seconds = float(seconds)
        self.warm = int(warm_episodes)
        self.fixed = episodes
        self.clock = clock
        self.on_boundary = on_boundary
        self.stamps: List[float] = []
        self.hook_s: List[float] = []     # per window boundary, what the
        self.closed = False               # hook cost the next episode

    @property
    def triggered(self) -> bool:
        if self.closed:
            return True
        k = len(self.stamps) - self.warm
        now = self.clock()
        self.stamps.append(now)
        if k < 0:
            return False
        if self.fixed is None:
            self.closed = now - self.opened >= self.seconds
        else:
            self.closed = k >= self.fixed
        if self.on_boundary is not None:
            self.on_boundary(k, now)
        self.hook_s.append(self.clock() - now)
        return self.closed

    @property
    def opened(self) -> Optional[float]:
        return self.stamps[self.warm] if len(self.stamps) > self.warm \
            else None

    @property
    def closed_at(self) -> Optional[float]:
        return self.stamps[-1] if self.closed else None

    @property
    def episodes(self) -> int:
        """Whole episodes inside the window."""
        return max(len(self.stamps) - self.warm - 1, 0)

    def boundaries(self) -> List[float]:
        return self.stamps[self.warm:]


# ----------------------------------------------------------------- device
def require_device(chips: int, peaks: dict) -> Dict:
    """The accelerator as JAX reports it, or exit non-zero: a platform
    other than the TPU, a kind the peaks table lacks, or fewer chips than
    the cell asks for never prints a result."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["kind"] not in peaks["devices"]:
        raise SystemExit(
            f"benchmark: needs an accelerator of the peaks table and JAX "
            f"found {dev['count']} x {dev['kind']!r} (platform "
            f"{dev['platform']!r}); nothing was run")
    if dev["count"] < chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chips and JAX found "
            f"{dev['count']}; nothing was run")
    return dev


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------- results
def read_metrics(names: List[str], record: dict, units: Dict[str, str],
                 root: str = ROOT) -> Dict[str, dict]:
    """One reader per metric; a reader that finds nothing returns None and
    the metric is left out of the line."""
    out = {}
    for name in names:
        value = load_module("metrics", name, root).read(record)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def units_of(bench: dict) -> Dict[str, str]:
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def compared_lines(compared: Dict[str, dict]) -> List[str]:
    return [f"compared {k}: {v['value']!r} limit {v['limit']!r}"
            f"{'' if v['ok'] else '  <-- over'}"
            for k, v in compared.items()]


def result_line(record: dict, metrics: Dict[str, dict], device: dict,
                traced: bool) -> dict:
    dev = dict(device)
    dev["memory_peak_bytes"] = record["memory_peak_bytes"]
    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]),
            "metrics": metrics, "device": dev}
    if traced and record.get("trace"):
        dev["busy_s"] = record["trace"]["busy_s"]
        dev["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = record["trace"]["breakdown"]
    line["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in record["compared"].items()}
    return line


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.time() if t_start is None else t_start
    args = parse_args(argv)
    bench = manifest()
    cell = load_cell(args.workload)
    peaks = load_peaks()
    driver = load_driver(cell)
    driver.prepare(cell)       # cache directory, precision: before JAX starts
    device = require_device(int(cell["cell"]["chips"]), peaks)
    record = driver.run(cell, seed=args.seed, seconds=args.seconds,
                        traced=bool(args.trace), t_start=t_start,
                        peaks=peaks["devices"][device["kind"]],
                        log=lambda *a: print(*a, flush=True))
    names = metric_names(bench, args.workload, bool(args.trace))
    metrics = read_metrics(names, record, units_of(bench))
    line = result_line(record, metrics, device, bool(args.trace))
    for text in compared_lines(record["compared"]):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
