"""A later PR adds a configuration, a cell and a metric by adding files:
here as temp files in a copy of the benchmark's tree, with no file that
was there touched."""
import hashlib
import json
import os
import shutil

from benchmarks import harness


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in d or f.endswith(".pyc"):
                continue
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_added_files_are_listed_and_resolved(tmp_path):
    root = str(tmp_path / "benchmarks")
    shutil.copytree(harness.ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)
    cfg = harness.load_json("configs", "flagship", root)
    cfg["mem_limit"] = 51_200
    with open(os.path.join(root, "configs", "flagship-small.json"), "w") as f:
        json.dump(cfg, f)
    cell = harness.load_json("workloads", "flagship-b256", root)
    cell.update(config="flagship-small", traffic="b64", replicas=64)
    with open(os.path.join(root, "workloads", "flagship-small-b64.json"),
              "w") as f:
        json.dump(cell, f)
    with open(os.path.join(root, "metrics", "window_episodes.py"), "w") as f:
        f.write("def read(record):\n"
                "    return record.get('window_episodes') or None\n")

    assert "flagship-small-b64" in harness.list_names("workloads", ".json",
                                                      root)
    assert "flagship-small" in harness.list_names("configs", ".json", root)
    assert "window_episodes" in harness.list_names("metrics", ".py", root)
    got = harness.load_cell("flagship-small-b64", root)
    assert got["config"]["mem_limit"] == 51_200
    assert got["cell"]["replicas"] == 64
    assert got["cell"]["driver"] == "train_parallel"
    bench = {"end_to_end": [],
             "per_layer": [{"name": "window_episodes", "unit": "count"},
                           {"name": "peak_hbm_gb", "unit": "GB",
                            "workloads": ["other-cell"]}]}
    names = harness.metric_names(bench, "flagship-small-b64", traced=True)
    assert names == ["window_episodes"]
    out = harness.read_metrics(names, {"window_episodes": 3},
                               harness.units_of(bench), root)
    assert out == {"window_episodes": {"value": 3.0, "unit": "count"}}
    # a reader that finds nothing to read leaves its metric out
    assert harness.read_metrics(names, {}, harness.units_of(bench),
                                root) == {}
    after = digest(root)
    assert {k: after[k] for k in before} == before


def test_manifest_names_resolve_to_files():
    bench = harness.manifest()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config_name"] == w["config"]
        assert cell["cell"]["traffic"] == w["traffic"]
        assert cell["cell"]["chips"] == w["chips"]
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.CHECKOUT, c["file"]))
        assert harness.load_json("configs", c["name"])["reduced"] == \
            c["reduced"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
