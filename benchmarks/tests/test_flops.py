"""The model-FLOPs function against a hand count for the flagship."""
import json
import os

from benchmarks import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def test_flagship_hand_count():
    with open(os.path.join(HERE, "..", "configs", "flagship.json")) as f:
        cfg = json.load(f)
    n, f_, a = 24, 22, 24 * 1 * 3 * 24
    # one GATv2 layer: two projections, pairwise add + LeakyReLU, logits,
    # aggregation
    enc = 2 * (2 * n * 3 * f_) + 3 * (2 * n * n * f_)      # 6 336 + 76 032
    proc = 2 * (2 * n * f_ * f_) + 3 * (2 * n * n * f_)    # 46 464 + 76 032
    emb = enc + 2 * proc                                   # 2 iterations
    assert emb == 327_360
    actor = emb + 2 * (f_ + a) * 256 + 2 * 256 * a
    critic = emb + 2 * (f_ + 2 * a) * 64 + 2 * 64
    assert actor == 2_108_096 and critic == 772_672
    got = flops.model_flops(cfg)
    assert got["actor_fwd"] == actor and got["critic_fwd"] == critic
    assert got["env_step"] == actor
    # target actor+critic forward, critic fwd+bwd, actor fwd+bwd, critic
    # fwd + input-gradient backward; batch 100
    assert got["grad_step"] == 100 * (4 * actor + 6 * critic)


def test_factored_head_is_counted_by_its_own_shapes():
    with open(os.path.join(HERE, "..", "configs", "interroute.json")) as f:
        cfg = json.load(f)
    got = flops.model_flops(cfg)
    n, g = 128, 32
    assert 128 * 3 * 128 >= cfg["factored_head_threshold"]
    # the bilinear logits alone: N x (C*S*G) x N'
    assert got["actor_fwd"] > 2 * n * 3 * g * n
    assert got["grad_step"] > got["actor_fwd"] * cfg["batch_size"]
