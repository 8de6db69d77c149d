"""A later PR adds a configuration, a cell, a metric and a policy
architecture's plain reference by adding files: here as temp files in a
copy of the benchmark's tree, with no file that was there touched."""
import hashlib
import json
import os
import shutil

import pytest

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


# ------------------------------------------- the reference named by a file
def copy_tree(tmp_path):
    root = str(tmp_path / "benchmarks")
    shutil.copytree(harness.ROOT, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def add_config(root, name, **changes):
    """``configs/<name>.json`` = the flagship's with ``changes`` (None
    drops a key) and ``workloads/<name>-b256.json`` that runs it."""
    cfg = harness.load_json("configs", "flagship", root)
    cfg.update(changes)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    with open(os.path.join(root, "configs", f"{name}.json"), "w") as f:
        json.dump(cfg, f)
    cell = harness.load_json("workloads", "flagship-b256", root)
    cell["config"] = name
    with open(os.path.join(root, "workloads", f"{name}-b256.json"),
              "w") as f:
        json.dump(cell, f)
    return f"{name}-b256"


@pytest.mark.parametrize("config", harness.list_names("configs", ".json"))
def test_named_reference_exists_and_meets_the_contract(config):
    cfg = harness.load_json("configs", config)
    path = os.path.join(harness.ROOT, "reference", f"{cfg['reference']}.py")
    assert os.path.isfile(path)
    ref = harness.load_reference({"config": cfg, "root": harness.ROOT})
    assert len(harness.CONTRACT) == 8
    for name in harness.CONTRACT:
        assert callable(getattr(ref, name)), name
    got = ref.model_flops(cfg)
    assert set(got) == {"actor_fwd", "critic_fwd", "env_step", "grad_step"}
    assert all(v > 0 for v in got.values())


def test_configuration_without_a_reference_is_refused(tmp_path):
    root = copy_tree(tmp_path)
    cell = add_config(root, "nameless", reference=None)
    with pytest.raises(SystemExit) as e:
        harness.load_cell(cell, root)
    assert os.path.join(root, "configs", "nameless.json") in str(e.value)
    cell = add_config(root, "absent", reference="no_such_torso")
    with pytest.raises(SystemExit) as e:
        harness.load_reference(harness.load_cell(cell, root))
    assert os.path.join(root, "reference", "no_such_torso.py") in str(e.value)
    with open(os.path.join(root, "reference", "partial.py"), "w") as f:
        f.write("def spec_from_config(cfg):\n    return None\n")
    cell = add_config(root, "partial", reference="partial")
    with pytest.raises(SystemExit) as e:
        harness.load_reference(harness.load_cell(cell, root))
    assert "lacks warmup_actions" in str(e.value)
    assert "init_weights" in str(e.value)


OFF_BY_A_THOUSANDTH = '''
from benchmarks.reference import ddpg as plain
from benchmarks.reference.ddpg import *  # noqa: F401,F403


def policy_actions(*args, **kwargs):
    acts, margin = plain.policy_actions(*args, **kwargs)
    return acts * 1.001, margin


def model_flops(cfg):
    return {k: 2 * v for k, v in plain.model_flops(cfg).items()}
'''


def test_the_configurations_key_decides_what_is_compared_and_counted(
        tmp_path):
    """The seam, on the CPU at rehearsal size: a second reference module,
    added as a file and named by an added configuration, is the one the
    output check follows (its actor's answer, off by a thousandth, fails
    ``policy_action_gap`` and nothing else) and the one whose FLOPs are
    counted.  The same cell under ``ddpg`` is ``test_rehearsal``'s sound
    run."""
    from benchmarks import rehearse

    root = copy_tree(tmp_path)
    before = digest(root)
    with open(os.path.join(root, "reference", "ddpg_off.py"), "w") as f:
        f.write(OFF_BY_A_THOUSANDTH)
    name = add_config(root, "flagship-off", reference="ddpg_off")
    cell = rehearse.tiny_cell(name, root=root)
    line = rehearse.run_once(cell, limits=dict(rehearse.LIMITS))
    over = [k for k, v in line["compared"].items()
            if not v["value"] <= v["limit"]]
    assert over == ["policy_action_gap"], line["compared"]
    assert line["correct"] is False
    plain = harness.load_module("reference", "ddpg").model_flops(
        cell["config"])
    assert line["record"]["flops"] == {k: 2 * v for k, v in plain.items()}
    after = digest(root)
    assert {k: after[k] for k in before} == before


# ------------------------------------------------- the weights from a seed
def gat_shapes(root, f_in, f):
    return {f"{root}/att": (f, 1), f"{root}/b_l": (f,), f"{root}/b_r": (f,),
            f"{root}/bias": (f,), f"{root}/w_l": (f_in, f),
            f"{root}/w_r": (f_in, f)}


def dense_shapes(root, f_in, f):
    return {f"{root}/bias": (f,), f"{root}/kernel": (f_in, f)}


def leaf_shapes(heads):
    """The leaf shapes of a configuration's two networks as the program
    lays them out: the shared 22-feature embedder and each net's head."""
    out = {}
    for net, head in heads.items():
        emb = f"{net}/params/GNNEmbedder_0"
        out.update(gat_shapes(f"{emb}/encoder", 3, 22))
        out.update(gat_shapes(f"{emb}/process_0", 22, 22))
        for name, (f_in, f) in head.items():
            out.update(dense_shapes(f"{net}/params/{name}", f_in, f))
    return out


SHAPES = {
    "flagship": leaf_shapes({
        "actor": {"MLP_0/Dense_0": (1750, 256), "MLP_0/Dense_1": (256, 1728)},
        "critic": {"MLP_0/Dense_0": (3478, 64), "MLP_0/Dense_1": (64, 1)}}),
    "interroute": leaf_shapes({
        "actor": {"MLP_0/Dense_0": (44, 256), "key": (22, 32),
                  "query": (256, 96)},
        "critic": {"MLP_0/Dense_0": (44, 64), "MLP_0/Dense_1": (64, 1),
                   "key": (22, 32), "src": (118, 22)}}),
}
# sha256 over each leaf's name and bytes in name order, of the parent's
# `drivers/train_parallel.make_weights` (commit eda23bc, on the CPU)
PARENT = {
    ("flagship", 3):
        "37f89db6fd5303e42b4180b2c9ca8040af177f99dda6a4d1b170af90dbe5915c",
    ("flagship", 2**31 + 77):
        "23289a1f6f187547262890e3c5bb65baf0f98f768863498e8304a8319f89fed8",
    ("interroute", 3):
        "a37bc8d61e798b79e20adc361314610b9c334ee98eed4b3efa2d232f74b7910d",
    ("interroute", 2**31 + 77):
        "07949097c4091b75910bc28e8bd6a10ccb4de48423d858c88b4f83569230fbf3",
}


@pytest.mark.parametrize("config,seed", sorted(PARENT))
def test_seeded_weights_are_the_parents_bits(config, seed):
    import numpy as np

    ref = harness.load_module("reference", "ddpg")
    weights = ref.init_weights(seed, SHAPES[config])
    h = hashlib.sha256()
    for name in sorted(weights):
        h.update(name.encode())
        h.update(np.asarray(weights[name]).tobytes())
    assert h.hexdigest() == PARENT[(config, seed)]
