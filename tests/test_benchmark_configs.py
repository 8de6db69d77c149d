"""Every configuration the benchmark runs names a plain reference that
holds the harness's contract (``benchmarks/README.md``), on the CPU: one
case per file under ``benchmarks/configs/``."""
import copy

import pytest

from benchmarks import flops, harness

CONFIGS = harness.list_names("configs", ".json")
CELLS = {harness.load_json("workloads", w)["config"]: w
         for w in harness.list_names("workloads", ".json")}


def looplm_hand_count(cfg: dict) -> float:
    """``grad_step`` of a looped torso with the dense head, counted by
    hand from the README's rule: a multiply-add is two, applications are
    counted and not parameters, nine torso forwards a batch row."""
    t = cfg["torso"]
    n, f, d = int(cfg["max_nodes"]), int(cfg["GNN_features"]), \
        t["hidden_size"]
    a = n * 1 * 3 * n                          # one chain of three functions
    heads_dim = t["num_attention_heads"] * t["head_dim"]
    kv_dim = t["num_key_value_heads"] * t["head_dim"]
    layer = 2 * (d * heads_dim + 2 * d * kv_dim + heads_dim * d
                 + 3 * d * t["intermediate_size"]) + 4 * n * heads_dim
    torso = n * t["num_hidden_layers"] * t["total_ut_steps"] * layer \
        + 2 * n * f * d + t["total_ut_steps"] * 2 * d
    emb = flops.embedder_flops(n, len(cfg["observation_space"]), f,
                               int(cfg["GNN_num_layers"]),
                               int(cfg["GNN_num_iter"]))
    (ah,), (ch,) = cfg["actor_hidden_layer_nodes"], \
        cfg["critic_hidden_layer_nodes"]
    head_a = 2 * ((d + a) * ah + ah * a)
    head_c = 2 * ((d + 2 * a) * ch + ch)
    body, passes = emb + torso, t["total_ut_steps"]
    row = 9 * body + (1 + 3 * passes) * head_a + (1 + 5 * passes) * head_c
    return float(int(cfg["batch_size"]) * row)


@pytest.mark.parametrize("name", CONFIGS)
def test_configuration_names_a_reference_that_holds_the_contract(name):
    assert name in CELLS, f"no cell runs configuration {name}"
    cell = harness.load_cell(CELLS[name])
    cfg = cell["config"]
    assert cfg["source"] and isinstance(cfg["reduced"], list)
    for key in cfg["reduced"]:
        assert key in cfg["published"], key
    ref = harness.load_reference(cell)          # refuses a missing function
    for fn in harness.CONTRACT:
        assert callable(getattr(ref, fn))
    spec = ref.spec_from_config(cfg)
    assert spec.max_nodes == cfg["max_nodes"] and spec.num_sfcs >= 1
    got = ref.model_flops(cfg)
    assert set(got) == {"actor_fwd", "critic_fwd", "env_step", "grad_step"}
    assert all(v > 0 for v in got.values())
    if cfg["reference"] == "ddpg":
        assert got == flops.model_flops(cfg)
    if cfg["reference"] == "looplm":
        # the nested mapping the program reads is the published file's
        for key, value in cfg["torso"].items():
            assert cfg.get(key, value) == value, key
        tiny = copy.deepcopy(cfg)
        tiny["torso"].update(hidden_size=64, num_attention_heads=4,
                             num_key_value_heads=4, head_dim=16,
                             intermediate_size=176, num_hidden_layers=2)
        assert ref.model_flops(tiny)["grad_step"] == looplm_hand_count(tiny)
        # at the published widths: 39.5 GFLOP a sample and network forward
        assert 39.4e9 < got["critic_fwd"] < 39.7e9
        assert 9 * 32 * 39.4e9 < got["grad_step"] < 9 * 32 * 39.7e9
