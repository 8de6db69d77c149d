"""Plain reference of what one training episode's policy/learner side
computes — straightforward ``jax.numpy`` float32, no kernels, no replay
object, no program import.

It follows the published description (arXiv 2311.02657 / farzad1132/GSC
``src/rlsp/agents``: GATv2 encoder + weight-tied process convs, masked
mean-pool, MLP actor/critic, CleanRL-style DDPG with Adam and Polyak
targets, warm-up by uniform random masked actions, threshold+renormalise
action post-processing) and the system's own factored bilinear head for
action spaces too wide for a dense output layer.

Parameters are addressed by the checkpoint layout's leaf paths
(``actor/params/GNNEmbedder_0/encoder/w_l`` ...) — the one interface
shared with the system under test.  Everything the reference consumes is
made by the benchmark from the seed (weights, PRNG key) or is the replay
feed copied out of the timed run (rows of transitions); it takes no
weights, scales or tables from the program.

``matmul`` selects the precision of every contraction: ``"highest"`` is
the reference, ``"high"`` (three bf16 passes) and ``"bfloat16"`` are the
controls one step below what a configuration states.

A configuration file names this module (``"reference": "ddpg"``) and the
harness loads it by that name; what a reference module exports is listed
in ``benchmarks/README.md`` (``CONTRACT`` in ``harness.py``).
"""
from __future__ import annotations

import zlib
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.flops import model_flops  # noqa: F401  (the contract's)

NEG_INF = -1e30
LEAKY = 0.2
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


class Spec(NamedTuple):
    """The sizes a configuration file states (static)."""

    max_nodes: int
    num_sfcs: int
    max_sfs: int
    num_layers: int
    num_iter: int
    mean_aggr: bool
    factored: bool
    key_dim: int
    gamma: float
    tau: float
    lr: float
    batch_size: int
    threshold: float


def spec_from_config(cfg: dict) -> Spec:
    n = int(cfg["max_nodes"])
    sfcs = len(cfg["service"]["sfc_list"])
    sfs = max(len(c) for c in cfg["service"]["sfc_list"].values())
    action_dim = n * sfcs * sfs * n
    return Spec(
        max_nodes=n, num_sfcs=sfcs, max_sfs=sfs,
        num_layers=int(cfg["GNN_num_layers"]),
        num_iter=int(cfg["GNN_num_iter"]),
        mean_aggr=cfg["GNN_aggr"] == "mean",
        factored=action_dim >= int(cfg["factored_head_threshold"]),
        key_dim=int(cfg["factored_key_dim"]),
        gamma=float(cfg["gamma"]), tau=float(cfg["target_model_update"]),
        lr=float(cfg["learning_rate"]), batch_size=int(cfg["batch_size"]),
        threshold=float(cfg["schedule_threshold"]))


# ---------------------------------------------------------------- weights
def init_weights(seed: int, shapes: Dict[str, tuple]):
    """Every network leaf from the seed in one jitted call on the device:
    matrices Glorot-uniform from their own shape, vectors zero (the
    initialisers of the program's modules, keyed here by leaf name so the
    values do not depend on the program's own key schedule)."""

    def make(key):
        out = {}
        for name, shape in sorted(shapes.items()):
            if len(shape) < 2:
                out[name] = jnp.zeros(shape, jnp.float32)
                continue
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            lim = (6.0 / (shape[0] + shape[1])) ** 0.5
            out[name] = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ------------------------------------------------------------ contractions
class Math:
    """Contractions at one stated precision."""

    def __init__(self, matmul: str = "highest"):
        if matmul not in ("highest", "high", "bfloat16"):
            raise ValueError(f"unknown matmul precision {matmul!r}")
        self.matmul = matmul

    def einsum(self, eq: str, a, b):
        if self.matmul == "bfloat16":
            return jnp.einsum(eq, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return jnp.einsum(eq, a, b, precision=self.matmul)

    def dense(self, x, p: Dict, name: str):
        return self.einsum("...i,io->...o", x, p[f"{name}/kernel"]) \
            + p[f"{name}/bias"]


# ------------------------------------------------------------------ layers
def adjacency(edge_index, edge_mask, node_mask):
    """adj[i, j]: j is an in-neighbour of i; self-loops on real nodes."""
    n = node_mask.shape[-1]
    ids = jnp.arange(n)
    live = edge_mask[..., None].astype(jnp.float32)
    src = (edge_index[..., 0, :, None] == ids).astype(jnp.float32) * live
    dst = (edge_index[..., 1, :, None] == ids).astype(jnp.float32)
    adj = jnp.einsum("...ei,...ej->...ij", dst, src,
                     precision="highest") > 0.5
    return adj | (jnp.eye(n, dtype=bool) & node_mask[..., :, None])


def gatv2(m: Math, x, adj, p: Dict, name: str, mean_aggr: bool):
    """e_ij = a.LeakyReLU(W_l x_j + W_r x_i); alpha = softmax_j; out_i =
    aggr_j alpha_ij W_l x_j + b (torch_geometric GATv2Conv, one head)."""
    xl = m.einsum("...ni,io->...no", x, p[f"{name}/w_l"]) + p[f"{name}/b_l"]
    xr = m.einsum("...ni,io->...no", x, p[f"{name}/w_r"]) + p[f"{name}/b_r"]
    e = xl[..., None, :, :] + xr[..., :, None, :]
    e = jnp.where(e >= 0, e, LEAKY * e)
    logits = m.einsum("...ijf,f->...ij", e, p[f"{name}/att"][:, 0])
    logits = jnp.where(adj, logits, NEG_INF)
    ex = jnp.where(adj, jnp.exp(logits - jax.lax.stop_gradient(
        logits.max(-1, keepdims=True))), 0.0)
    alpha = ex / jnp.maximum(ex.sum(-1, keepdims=True), 1e-30)
    out = m.einsum("...ij,...jf->...if", alpha, xl)
    if mean_aggr:
        out = out / jnp.maximum(adj.sum(-1, keepdims=True), 1)
    return jnp.where(adj.any(-1, keepdims=True), out + p[f"{name}/bias"],
                     0.0)


def mean_pool(x, node_mask):
    w = node_mask.astype(x.dtype)[..., None]
    return (x * w).sum(-2) / jnp.maximum(w.sum(-2), 1.0)


def embed(m: Math, s: Spec, obs: Dict, p: Dict, root: str, pool: bool):
    adj = adjacency(obs["edge_index"], obs["edge_mask"], obs["node_mask"])
    x = jax.nn.relu(gatv2(m, obs["nodes"], adj, p, f"{root}/encoder",
                          s.mean_aggr))
    if s.num_layers > 1:
        for it in range(s.num_iter):
            for i in range(s.num_layers - 1):
                x = gatv2(m, x, adj, p, f"{root}/process_{i}", s.mean_aggr)
                last = i == s.num_layers - 2 and it == s.num_iter - 1
                if not last:
                    x = jax.nn.relu(x)
    return mean_pool(x, obs["node_mask"]) if pool else x


def mlp(m: Math, x, p: Dict, root: str, plain_last: bool = True):
    n = 0
    while f"{root}/Dense_{n}/kernel" in p:
        n += 1
    for i in range(n):
        x = m.dense(x, p, f"{root}/Dense_{i}")
        if i < n - 1 or not plain_last:
            x = jax.nn.relu(x)
    return x


def actor(m: Math, s: Spec, p: Dict, obs: Dict):
    g = "actor/params/GNNEmbedder_0"
    if not s.factored:
        emb = embed(m, s, obs, p, g, pool=True)
        out = mlp(m, jnp.concatenate([emb, obs["mask"]], -1), p,
                  "actor/params/MLP_0")
        return out * obs["mask"]
    n, c, k, gd = s.max_nodes, s.num_sfcs, s.max_sfs, s.key_dim
    feats = embed(m, s, obs, p, g, pool=False)
    pooled = mean_pool(feats, obs["node_mask"])
    h = jnp.concatenate([feats, jnp.broadcast_to(
        pooled[..., None, :], feats.shape[:-1] + pooled.shape[-1:])], -1)
    h = mlp(m, h, p, "actor/params/MLP_0", plain_last=False)
    q = m.dense(h, p, "actor/params/query")
    key = m.dense(feats, p, "actor/params/key")
    q = q.reshape(q.shape[:-2] + (n, c, k, gd))
    out = m.einsum("...ncsg,...mg->...ncsm", q, key)
    return out.reshape(out.shape[:-4] + (-1,)) * obs["mask"]


def critic(m: Math, s: Spec, p: Dict, obs: Dict, action, root="critic"):
    g = f"{root}/params/GNNEmbedder_0"
    if not s.factored:
        emb = embed(m, s, obs, p, g, pool=True)
        h = jnp.concatenate([emb, obs["mask"], action], -1)
        return mlp(m, h, p, f"{root}/params/MLP_0")[..., 0]
    n, c, k, gd = s.max_nodes, s.num_sfcs, s.max_sfs, s.key_dim
    feats = embed(m, s, obs, p, g, pool=False)
    pooled = mean_pool(feats, obs["node_mask"])
    a4 = action.reshape(action.shape[:-1] + (n, c, k, n))
    key = m.dense(feats, p, f"{root}/params/key")
    a_enc = m.einsum("...ncsm,...mg->...ncsg", a4, key)
    z = jnp.concatenate(
        [feats, a_enc.reshape(a_enc.shape[:-3] + (c * k * gd,))], -1)
    z = jax.nn.relu(m.dense(z, p, f"{root}/params/src"))
    h = jnp.concatenate([pooled, mean_pool(z, obs["node_mask"])], -1)
    return mlp(m, h, p, f"{root}/params/MLP_0")[..., 0]


# --------------------------------------------------------------- the agent
def post_process(action, num_dst: int, threshold: float):
    """Threshold small weights to zero and renormalise each destination
    row, twice; an emptied row becomes uniform (simple_ddpg.py:374-395)."""
    rows = action.reshape(action.shape[:-1] + (-1, num_dst))
    for _ in range(2):
        kept = jnp.where(rows >= threshold, rows, 0.0)
        tot = kept.sum(-1, keepdims=True)
        rows = jnp.where(tot > 0, kept / jnp.maximum(tot, 1e-30),
                         1.0 / num_dst)
    return rows.reshape(action.shape)


def warmup_actions(rng, replicas: int, steps: int, chunk: int, mask,
                   s: Spec) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The warm-up episode's stored actions, [replicas, steps, A], and the
    learner key as it stands after the episode's rollouts.  Key schedule:
    each dispatched chunk splits the learner key, derives one key per
    (step, replica), and each action is ``uniform * mask`` post-processed
    (simple_ddpg.py:184-187, 248-249)."""

    def one_chunk(rng):
        rng, sub = jax.random.split(rng)
        sub, _ = jax.random.split(sub)

        def step(i):
            keys = jax.random.split(jax.random.fold_in(sub, i), replicas)
            k1 = jax.vmap(lambda k: jax.random.split(k)[0])(keys)
            u = jax.vmap(lambda k: jax.random.uniform(k, mask.shape))(k1)
            return post_process(u * mask, s.max_nodes, s.threshold)

        return rng, jax.lax.map(step, jnp.arange(chunk))  # [chunk, B, A]

    parts = []
    for _ in range(steps // chunk):
        rng, acts = jax.jit(one_chunk)(rng)
        parts.append(acts)
    return jnp.swapaxes(jnp.concatenate(parts, 0), 0, 1), rng


def advance_key(rng, calls: int):
    """The learner key after ``calls`` of the calls that split it: every
    dispatched rollout chunk and every learn burst keeps the first half
    of a split."""
    for _ in range(calls):
        rng = jax.random.split(rng)[0]
    return rng


def threshold_margin(action, num_dst: int, threshold: float):
    """Per destination row, how near any weight comes to the threshold in
    either pass of :func:`post_process`: a row whose margin is under what
    rounding can move is a near-tie, and whether a weight survives there
    is not for a comparison to hold."""
    rows = action.reshape(action.shape[:-1] + (-1, num_dst))
    margin = jnp.full(rows.shape[:-1], jnp.inf, rows.dtype)
    for _ in range(2):
        margin = jnp.minimum(margin, jnp.abs(rows - threshold).min(-1))
        kept = jnp.where(rows >= threshold, rows, 0.0)
        tot = kept.sum(-1, keepdims=True)
        rows = jnp.where(tot > 0, kept / jnp.maximum(tot, 1e-30),
                         1.0 / num_dst)
    return margin


def policy_actions(matmul: str, s: Spec, actor_params: Dict, obs: Dict, rng,
                   replicas: int, sample, chunk: int, noise_mu: float,
                   noise_sigma: float):
    """The policy branch's stored actions for the replicas ``sample`` of
    one episode, [R, T, A], and each destination row's threshold margin,
    [R, T, A / N]: actor forward on the stored observations ``obs``
    ([R, T, ...] leaves), output scaled to [-1, 1], Gaussian noise from
    the key schedule added there, scaled back and clipped to [0, 1]
    (simple_ddpg.py:188-201), then threshold + renormalise.  ``rng`` is
    the learner key as the episode starts."""
    m = Math(matmul)
    sample = jnp.asarray(sample)
    steps = obs["nodes"].shape[1]

    def one_chunk(rng, obs_c):
        rng, sub = jax.random.split(rng)
        sub, _ = jax.random.split(sub)

        def step(args):
            i, ob = args
            keys = jax.random.split(jax.random.fold_in(sub, i),
                                    replicas)[sample]
            k2 = jax.vmap(lambda k: jax.random.split(k)[1])(keys)
            a = actor(m, s, actor_params, ob)
            noise = noise_mu + noise_sigma * jax.vmap(
                lambda k: jax.random.normal(k, a.shape[-1:]))(k2)
            raw = jnp.clip(0.5 * ((2.0 * a - 1.0) + noise + 1.0), 0.0, 1.0)
            return (post_process(raw, s.max_nodes, s.threshold),
                    threshold_margin(raw, s.max_nodes, s.threshold))

        return rng, jax.lax.map(step, (jnp.arange(chunk), obs_c))

    acts, margins = [], []
    for c in range(steps // chunk):
        obs_c = {k: jnp.swapaxes(jnp.asarray(v)[:, c * chunk:(c + 1) * chunk],
                                 0, 1) for k, v in obs.items()}
        rng, (a, g) = jax.jit(one_chunk)(rng, obs_c)
        acts.append(a)
        margins.append(g)
    return (jnp.swapaxes(jnp.concatenate(acts, 0), 0, 1),
            jnp.swapaxes(jnp.concatenate(margins, 0), 0, 1))


def sample_indices(key, s: Spec, replicas: int, filled: int):
    """Uniform (replica, slot) pairs over every shard's valid rows."""
    kb, ks = jax.random.split(key)
    b = jax.random.randint(kb, (s.batch_size,), 0, replicas)
    t = jax.random.randint(ks, (s.batch_size,), 0, max(filled, 1))
    return b, t


def critic_loss(m: Math, s: Spec, cp: Dict, targets: Dict, batch: Dict):
    next_a = jnp.clip(actor(m, s, targets, batch["next_obs"]), -1.0, 1.0)
    q_next = critic(m, s, targets, batch["next_obs"], next_a)
    y = batch["reward"] + (1.0 - batch["done"]) * s.gamma * q_next
    td = critic(m, s, cp, batch["obs"], batch["action"]) \
        - jax.lax.stop_gradient(y)
    return jnp.mean(td ** 2), td


def actor_loss(m: Math, s: Spec, ap: Dict, cp: Dict, batch: Dict):
    a = actor(m, s, ap, batch["obs"])
    return -jnp.mean(critic(m, s, cp, batch["obs"], a))


def adam(params: Dict, grads: Dict, mu: Dict, nu: Dict, count, lr: float):
    count = count + 1
    mu = {k: ADAM_B1 * mu[k] + (1 - ADAM_B1) * grads[k] for k in params}
    nu = {k: ADAM_B2 * nu[k] + (1 - ADAM_B2) * grads[k] ** 2
          for k in params}
    c1 = 1 - ADAM_B1 ** count.astype(jnp.float32)
    c2 = 1 - ADAM_B2 ** count.astype(jnp.float32)
    new = {k: params[k] - lr * (mu[k] / c1)
           / (jnp.sqrt(nu[k] / c2) + ADAM_EPS) for k in params}
    return new, mu, nu, count


def _split(p: Dict, root: str) -> Dict:
    return {k: v for k, v in p.items() if k.startswith(root + "/")}


def gradient_step(m: Math, s: Spec, st: Dict, batch: Dict,
                  half_batch: bool = False):
    """One critic, actor and Polyak update (simple_ddpg.py:204-234).
    ``half_batch`` plants the fault of a batch half left out (the mean
    taken over the rest) — a control, never the reference."""
    if half_batch:
        batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2],
                                       batch)
    ap, cp = _split(st["params"], "actor"), _split(st["params"], "critic")
    tgt = st["targets"]
    (closs, td), cg = jax.value_and_grad(
        lambda c: critic_loss(m, s, c, tgt, batch), has_aux=True)(cp)
    cp, cmu, cnu, cc = adam(cp, cg, _split(st["mu"], "critic"),
                            _split(st["nu"], "critic"), st["count_c"], s.lr)
    aloss, ag = jax.value_and_grad(
        lambda a: actor_loss(m, s, a, cp, batch))(ap)
    ap, amu, anu, ac = adam(ap, ag, _split(st["mu"], "actor"),
                            _split(st["nu"], "actor"), st["count_a"], s.lr)
    params = {**ap, **cp}
    targets = {k: s.tau * params[k] + (1 - s.tau) * tgt[k] for k in tgt}
    st = {"params": params, "targets": targets, "mu": {**amu, **cmu},
          "nu": {**anu, **cnu}, "count_a": ac, "count_c": cc}
    return st, {"critic_loss": closs, "actor_loss": aloss,
                "td_abs_sum": jnp.abs(td).sum()}


def gather_batch(rows: Dict, b, t) -> Dict:
    """Rows [B, T, ...] of the replay feed -> one batch of transitions."""
    def pick(x):
        return x[b, t]
    obs = {k[len("obs/"):]: pick(v) for k, v in rows.items()
           if k.startswith("obs/")}
    nxt = {k[len("next_obs/"):]: pick(v) for k, v in rows.items()
           if k.startswith("next_obs/")}
    return {"obs": obs, "next_obs": nxt, "action": pick(rows["action"]),
            "reward": pick(rows["reward"]), "done": pick(rows["done"])}


def learn_burst(matmul: str, s: Spec, params: Dict, rng, rows: Dict,
                replicas: int, filled: int, steps: int,
                half_batch: bool = False):
    """``steps`` gradient steps from freshly made weights, batches drawn
    from ``rows`` by the learner key (simple_ddpg.py:307-325).  Returns
    the final learner state, the last step's losses and the burst's mean
    |TD|."""
    m = Math(matmul)
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    st = {"params": dict(params), "targets": dict(params), "mu": zeros,
          "nu": dict(zeros), "count_a": jnp.zeros((), jnp.int32),
          "count_c": jnp.zeros((), jnp.int32)}
    _, sub = jax.random.split(rng)

    @jax.jit
    def ref_grad_step(st, rows, i):
        b, t = sample_indices(jax.random.fold_in(sub, i), s, replicas,
                              filled)
        return gradient_step(m, s, st, gather_batch(rows, b, t), half_batch)

    td_sum = 0.0
    out = None
    for i in range(steps):
        st, out = ref_grad_step(st, rows, jnp.int32(i))
        td_sum += float(out["td_abs_sum"])
    return st, {"critic_loss": float(out["critic_loss"]),
                "actor_loss": float(out["actor_loss"]),
                "td_abs_mean": td_sum / (steps * (s.batch_size // 2
                                                  if half_batch
                                                  else s.batch_size))}


# ------------------------------------------------------- observation model
def expected_static_columns(cfg: dict, node_caps, is_ingress,
                            observation_space) -> Dict[str, np.ndarray]:
    """The observation columns that follow from the configuration alone
    under deterministic arrivals: every active ingress requests the same
    traffic per interval (``run_duration / inter_arrival_mean`` flows of
    ``flow_dr_mean``), and node capacity is the network file's.  Each is
    max-normalised as ``clip(x / (max x + 1e-3), 0, 1)``
    (simulator_wrapper.py:255-292).  Returns {column name: [N] values}
    for the columns this model can state; load depends on the simulation
    and is not stated here."""
    sim = cfg["simulator"]
    out = {}
    n = int(cfg["max_nodes"])

    def maxnorm(x):
        x = np.asarray(x, np.float32)
        return np.clip(x / (x.max() + np.float32(1e-3)), 0.0, 1.0)

    caps = np.zeros(n, np.float32)
    caps[: len(node_caps)] = node_caps
    ing = np.zeros(n, np.float32)
    if sim.get("deterministic_arrival") and not sim.get("flow_dr_stdev"):
        per = np.float32(sim["run_duration"]) / np.float32(
            sim["inter_arrival_mean"]) * np.float32(sim["flow_dr_mean"])
        ing[: len(is_ingress)] = np.where(is_ingress, per, 0.0)
        if "ingress_traffic" in observation_space:
            out["ingress_traffic"] = maxnorm(ing)
    if "node_cap" in observation_space:
        out["node_cap"] = maxnorm(caps)
    return out
