"""Plain reference of a looped decoder stack as the torso of actor and
critic, and of DDPG with the exit objective as its learner —
straightforward ``jax.numpy`` float32, layer by layer in a Python loop,
no scan, no kernels, no program import.  The learner's reverse pass is
written out the same way: each layer's transpose taken on its own, the
passes walked last to first, a shared leaf's gradient summed over them.

The architecture is ByteDance/Ouro's looped language model ("Scaling
Latent Reasoning via Looped Language Models", arXiv 2510.25741; the
configuration file carries the published ``config.json``): ``L`` decoder
layers applied ``T`` times on the same weights.  Its tokens here are the
network's nodes.  Equations (``N1..N4`` and ``FinalNorm`` are RMSNorm with
a learned scale, ``d`` the hidden size, ``hd`` the head size)::

    h0 = x W_in                      x: the GATv2 embedder's per-node output
    a  = h + N2(Attn(N1(h)))         Attn: q, k, v = u Wq, u Wk, u Wv per head;
    h' = a + N4(MLP(N3(a)))          rotary embedding on all hd dimensions of
    MLP(u) = (silu(u Wg) * u Wu) Wd  q and k, position = the node's slot;
                                     softmax(q k^T / sqrt(hd) + mask) v; Wo
    h_t = FinalNorm(Stack(h_{t-1}))  the same weights every pass, t = 1..T
    z_t = mean over real nodes of h_t
    lambda_t = sigmoid(w_g . z_t + b_g)
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T),  p_T = the remainder
    a_t = mask * MLP_a([z_t, mask])           q_t = MLP_c([z_t, mask, action])

Acting, the targets and the Q inside the actor's loss read the pass the
exit threshold picks: the first t whose cumulative ``p`` reaches it; a
threshold of one is reached by the remainder alone, so that is ``T``.
Losses (the paper's objective with DDPG's as the task loss)::

    critic: mean_b[sum_t p_t (q_t - y)^2] - beta mean_b[H(p)]
            y = r + gamma (1 - done) Q'_pick(s', clip(pi'_pick(s')))
    actor:  mean_b[sum_t p_t (-Q_pick(s, a_t))] - beta mean_b[H(p)]

each network with its own gate; the candidate actions ``a_t`` share one
pass of the critic's torso, since the action enters after it.

What is not the torso — key schedule, warm-up actions, replay sampling,
the GATv2 embedder, the heads' MLP, Adam, Polyak, the observation's static
columns — is ``reference/ddpg.py``'s, imported.  Leaves are addressed by
the checkpoint layout's names (``actor/params/LoopedTorso_0/wq`` is the
``[L, d, heads x hd]`` stack of query projections; layer ``l`` is its
``[l]``).
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp

from benchmarks import flops
from benchmarks.reference import ddpg
from benchmarks.reference.ddpg import (Math, advance_key,  # noqa: F401
                                       expected_static_columns)

NEG_INF = -1e30
TORSO = "LoopedTorso_0"
TORSO_LEAVES = ("w_in", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "norm_attn_in", "norm_attn_out", "norm_mlp_in",
                "norm_mlp_out", "final_norm", "gate_w", "gate_b")


class Spec(NamedTuple):
    """The sizes a configuration file states (static): ``base`` is what
    ``reference/ddpg.py`` reads, the rest is the ``torso`` mapping."""

    base: ddpg.Spec
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    inter: int
    layers: int
    passes: int
    exit_threshold: float
    eps: float
    theta: float
    beta: float

    # the action's layout, which the contract asks of every spec
    @property
    def max_nodes(self) -> int:
        return self.base.max_nodes

    @property
    def num_sfcs(self) -> int:
        return self.base.num_sfcs

    @property
    def max_sfs(self) -> int:
        return self.base.max_sfs


def spec_from_config(cfg: dict) -> Spec:
    t = cfg["torso"]
    if t.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {t['hidden_act']!r}: only silu")
    return Spec(
        base=ddpg.spec_from_config(cfg), d=int(t["hidden_size"]),
        heads=int(t["num_attention_heads"]),
        kv_heads=int(t["num_key_value_heads"]), head_dim=int(t["head_dim"]),
        inter=int(t["intermediate_size"]),
        layers=int(t["num_hidden_layers"]), passes=int(t["total_ut_steps"]),
        exit_threshold=float(t.get("early_exit_threshold", 1.0)),
        eps=float(t.get("rms_norm_eps", 1e-6)),
        theta=float(t.get("rope_theta", 1e6)),
        beta=float(t.get("exit_entropy_beta", 0.05)))


# ---------------------------------------------------------------- weights
def init_weights(seed: int, shapes: Dict[str, tuple]):
    """Every network leaf from the seed in one jitted call on the device:
    a norm's scale one, any other vector zero, a matrix Glorot-uniform
    with its fan from the last two axes (a stacked ``[L, in, out]`` leaf
    is L matrices), keyed by leaf name.  A state that lacks a torso leaf
    is refused by name: a program that dropped the configuration's
    ``torso`` key would otherwise be handed, and run, the small policy."""
    for net in ("actor", "critic"):
        for leaf in TORSO_LEAVES:
            name = f"{net}/params/{TORSO}/{leaf}"
            if name not in shapes:
                raise SystemExit(
                    f"benchmark: the program's learner state has no leaf "
                    f"{name}: it does not build the configuration's "
                    f"`torso` (reference/looplm.py)")

    def make(key):
        out = {}
        for name, shape in sorted(shapes.items()):
            leaf = name.rsplit("/", 1)[-1]
            if "norm" in leaf:
                out[name] = jnp.ones(shape, jnp.float32)
            elif len(shape) < 2:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                k = jax.random.fold_in(
                    key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
                lim = (6.0 / (shape[-2] + shape[-1])) ** 0.5
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -lim, lim)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ------------------------------------------------------------------ torso
def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta: float):
    """Rotary embedding of ``x`` [..., N, heads, hd] on all ``hd``
    dimensions, position = slot index; dimension i pairs with i + hd/2."""
    n, hd = x.shape[-3], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + turned * sin


def attention(m: Math, s: Spec, u, w: Dict, node_mask):
    lead = u.shape[:-1]
    q = m.einsum("...i,io->...o", u, w["wq"]).reshape(
        lead + (s.heads, s.head_dim))
    k = m.einsum("...i,io->...o", u, w["wk"]).reshape(
        lead + (s.kv_heads, s.head_dim))
    v = m.einsum("...i,io->...o", u, w["wv"]).reshape(
        lead + (s.kv_heads, s.head_dim))
    q, k = rotary(q, s.theta), rotary(k, s.theta)
    group = s.heads // s.kv_heads
    if group > 1:
        k, v = jnp.repeat(k, group, -2), jnp.repeat(v, group, -2)
    logits = m.einsum("...qhd,...khd->...hqk", q, k) / (s.head_dim ** 0.5)
    logits = logits + jnp.where(node_mask, 0.0,
                                NEG_INF)[..., None, None, :]
    out = m.einsum("...hqk,...khd->...qhd", jax.nn.softmax(logits, -1), v)
    return m.einsum("...i,io->...o",
                    out.reshape(lead + (s.heads * s.head_dim,)), w["wo"])


def gated_mlp(m: Math, u, w: Dict):
    gate = jax.nn.silu(m.einsum("...i,io->...o", u, w["w_gate"]))
    up = m.einsum("...i,io->...o", u, w["w_up"])
    return m.einsum("...i,io->...o", gate * up, w["w_down"])


def exit_distribution(lams: List):
    """Gate outputs of the T passes -> ``p`` [T, ...]; the last pass takes
    the remainder."""
    ps, alive = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        ps.append(lam * alive)
        alive = alive * (1.0 - lam)
    return jnp.stack(ps + [alive])


def entropy(p):
    return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)


# A network's leaves are read through a *view*: the table cut to one network
# (``actor/params`` or ``critic/params``) with that prefix taken off, so
# one statement serves actor, critic and both targets.
STEM = ("GNNEmbedder_0/", f"{TORSO}/w_in")       # embedder and W_in
LAYER_LEAVES = TORSO_LEAVES[1:12]                  # one layer's eleven
STACK = tuple(f"{TORSO}/{k}" for k in LAYER_LEAVES + ("final_norm",))


def view(p: Dict, net: str) -> Dict:
    root = f"{net}/params/"
    return {k[len(root):]: v for k, v in p.items() if k.startswith(root)}


def stem(m: Math, s: Spec, v: Dict, obs: Dict):
    """h0: the GATv2 embedder's per-node output through W_in."""
    x = ddpg.embed(m, s.base, obs, v, "GNNEmbedder_0", pool=False)
    return m.einsum("...i,io->...o", x, v[f"{TORSO}/w_in"])


def layer(m: Math, s: Spec, h, w: Dict, node_mask):
    """One sandwich-normed layer; ``w`` is the layer's eleven leaves."""
    a = h + rms_norm(
        attention(m, s, rms_norm(h, w["norm_attn_in"], s.eps), w, node_mask),
        w["norm_attn_out"], s.eps)
    return a + rms_norm(
        gated_mlp(m, rms_norm(a, w["norm_mlp_in"], s.eps), w),
        w["norm_mlp_out"], s.eps)


def layer_leaves(v: Dict, l: int) -> Dict:
    return {k: v[f"{TORSO}/{k}"][l] for k in LAYER_LEAVES}


def exit_gate(m: Math, s: Spec, v: Dict, hs: List, node_mask):
    """Per pass the masked mean ``z_t`` of the node states, and the exit
    distribution [T, ...]."""
    real = node_mask.astype(jnp.float32)[..., None]
    zs = [(h * real).sum(-2) / jnp.maximum(real.sum(-2), 1.0) for h in hs]
    lams = [jax.nn.sigmoid(
        m.einsum("...i,i->...", z, v[f"{TORSO}/gate_w"][:, 0])
        + v[f"{TORSO}/gate_b"][0]) for z in zs]
    return zs, exit_distribution(lams)


def states(m: Math, s: Spec, v: Dict, obs: Dict):
    """Embedder, then the loop: per pass the node states ``h_t``
    [..., N, d], their masked means and the exit distribution."""
    h = stem(m, s, v, obs)
    hs = []
    for _ in range(s.passes):
        for l in range(s.layers):
            h = layer(m, s, h, layer_leaves(v, l), obs["node_mask"])
        h = rms_norm(h, v[f"{TORSO}/final_norm"], s.eps)
        hs.append(h)
    zs, p_exit = exit_gate(m, s, v, hs, obs["node_mask"])
    return hs, zs, p_exit


def picked(s: Spec, p_exit, items: List):
    """Of one entry per pass, the one the exit threshold picks: the first
    whose cumulative ``p`` reaches it, the last where none does."""
    if s.exit_threshold >= 1.0:      # only the remainder reaches one
        return items[-1]
    reached = jnp.cumsum(p_exit, axis=0) >= s.exit_threshold
    idx = jnp.where(reached.any(0), jnp.argmax(reached, axis=0),
                    len(items) - 1)
    out = items[-1]
    for t in range(len(items) - 2, -1, -1):
        hit = (idx == t).reshape(idx.shape + (1,) * (out.ndim - idx.ndim))
        out = jnp.where(hit, items[t], out)
    return out


# ------------------------------------------------------------------ heads
def actor_head(m: Math, s: Spec, v: Dict, h, z, obs: Dict):
    """The repo's two heads, fed the torso's states where they were fed
    the embedder's (``reference/ddpg.py`` ``actor``)."""
    b = s.base
    if not b.factored:
        out = ddpg.mlp(m, jnp.concatenate([z, obs["mask"]], -1), v, "MLP_0")
        return out * obs["mask"]
    n, c, k, gd = b.max_nodes, b.num_sfcs, b.max_sfs, b.key_dim
    x = jnp.concatenate([h, jnp.broadcast_to(
        z[..., None, :], h.shape[:-1] + z.shape[-1:])], -1)
    x = ddpg.mlp(m, x, v, "MLP_0", plain_last=False)
    q = m.dense(x, v, "query")
    key = m.dense(h, v, "key")
    q = q.reshape(q.shape[:-2] + (n, c, k, gd))
    out = m.einsum("...ncsg,...mg->...ncsm", q, key)
    return out.reshape(out.shape[:-4] + (-1,)) * obs["mask"]


def critic_head(m: Math, s: Spec, v: Dict, h, z, obs: Dict, action):
    b = s.base
    if not b.factored:
        x = jnp.concatenate([z, obs["mask"], action], -1)
        return ddpg.mlp(m, x, v, "MLP_0")[..., 0]
    n, c, k, gd = b.max_nodes, b.num_sfcs, b.max_sfs, b.key_dim
    a4 = action.reshape(action.shape[:-1] + (n, c, k, n))
    key = m.dense(h, v, "key")
    a_enc = m.einsum("...ncsm,...mg->...ncsg", a4, key)
    x = jnp.concatenate(
        [h, a_enc.reshape(a_enc.shape[:-3] + (c * k * gd,))], -1)
    x = jax.nn.relu(m.dense(x, v, "src"))
    x = jnp.concatenate([z, ddpg.mean_pool(x, obs["node_mask"])], -1)
    return ddpg.mlp(m, x, v, "MLP_0")[..., 0]


def actor(m: Math, s: Spec, p: Dict, obs: Dict):
    """One answer per pass, [T, ..., A], and the actor's exit
    distribution."""
    v = view(p, "actor")
    hs, zs, p_exit = states(m, s, v, obs)
    return [actor_head(m, s, v, h, z, obs) for h, z in zip(hs, zs)], p_exit


def critic(m: Math, s: Spec, p: Dict, obs: Dict, action):
    v = view(p, "critic")
    hs, zs, p_exit = states(m, s, v, obs)
    return [critic_head(m, s, v, h, z, obs, action)
            for h, z in zip(hs, zs)], p_exit


# ---------------------------------------------------- the losses' last part
def critic_tail(m: Math, s: Spec, v: Dict, hs: List, obs: Dict, action, y):
    """From the critic's node states on: its exit objective and the picked
    pass's TD residual."""
    zs, p_exit = exit_gate(m, s, v, hs, obs["node_mask"])
    qs = [critic_head(m, s, v, h, z, obs, action) for h, z in zip(hs, zs)]
    task = sum(p_exit[t] * (q - y) ** 2 for t, q in enumerate(qs))
    loss = jnp.mean(task) - s.beta * jnp.mean(entropy(p_exit))
    return loss, picked(s, p_exit, qs) - y


def actor_tail(m: Math, s: Spec, v: Dict, hs: List, vc: Dict, hs_c: List,
               obs: Dict):
    """From the actor's node states on: its exit objective.  The T
    candidate actions meet the critic's picked pass (``vc``, ``hs_c``:
    the critic's leaves and node states, not differentiated), since the
    action enters after the critic's torso."""
    zs, p_exit = exit_gate(m, s, v, hs, obs["node_mask"])
    answers = [actor_head(m, s, v, h, z, obs) for h, z in zip(hs, zs)]
    zs_c, p_c = exit_gate(m, s, vc, hs_c, obs["node_mask"])
    h, z = picked(s, p_c, hs_c), picked(s, p_c, zs_c)
    task = sum(p_exit[t] * -critic_head(m, s, vc, h, z, obs, a)
               for t, a in enumerate(answers))
    return jnp.mean(task) - s.beta * jnp.mean(entropy(p_exit))


def picked_action(m: Math, s: Spec, v: Dict, hs: List, obs: Dict):
    zs, p_exit = exit_gate(m, s, v, hs, obs["node_mask"])
    return picked(s, p_exit, [actor_head(m, s, v, h, z, obs)
                              for h, z in zip(hs, zs)])


def target_value(m: Math, s: Spec, va: Dict, hs_a: List, vc: Dict,
                 hs_c: List, batch: Dict):
    """y = r + gamma (1 - done) Q'_pick(s', clip(pi'_pick(s')))."""
    obs = batch["next_obs"]
    next_a = jnp.clip(picked_action(m, s, va, hs_a, obs), -1.0, 1.0)
    zs, p_c = exit_gate(m, s, vc, hs_c, obs["node_mask"])
    q_next = critic_head(m, s, vc, picked(s, p_c, hs_c), picked(s, p_c, zs),
                         obs, next_a)
    return batch["reward"] + (1.0 - batch["done"]) * s.base.gamma * q_next


# -------------------------------------------------- the learner, by pieces
class Pieces(NamedTuple):
    """The jitted pieces one gradient step is put together from, at one
    precision.  A reverse pass written out over them — layer by layer,
    pass by pass, each piece's transpose taken on its own — keeps every
    compiled program one layer large (the whole step in one program is
    some 150 layer applications of text, minutes to compile at the
    published widths), holds one layer's activations at a time, and
    states the weight tying in the open: a shared leaf's gradient is the
    sum of what each pass hands back."""

    stem: callable
    stem_back: callable
    layer: callable
    layer_back: callable
    norm: callable
    norm_back: callable
    critic_tail: callable
    actor_tail: callable
    target_value: callable
    picked_action: callable
    adam: callable
    polyak: callable
    batch: callable


@functools.lru_cache(maxsize=None)
def pieces(matmul: str, s: Spec) -> Pieces:
    m = Math(matmul)

    def back(fn):
        """``fn(x, w, *rest)`` -> jitted ``(x, w, *rest, g) -> (dx, dw)``,
        the forward computed again inside."""
        def run(x, w, *rest):
            *rest, g = rest
            return jax.vjp(lambda x_, w_: fn(x_, w_, *rest), x, w)[1](g)
        return jax.jit(run)

    one_layer = lambda h, w, mask: layer(m, s, h, w, mask)
    norm = lambda h, scale: rms_norm(h, scale, s.eps)
    # the stem's only differentiated input is its leaves
    stem_fn = lambda v, obs: stem(m, s, v, obs)
    return Pieces(
        stem=jax.jit(stem_fn),
        stem_back=jax.jit(lambda v, obs, g: jax.vjp(
            lambda v_: stem_fn(v_, obs), v)[1](g)[0]),
        layer=jax.jit(one_layer), layer_back=back(one_layer),
        norm=jax.jit(norm), norm_back=back(norm),
        critic_tail=jax.jit(jax.value_and_grad(
            lambda v, hs, obs, action, y: critic_tail(m, s, v, hs, obs,
                                                      action, y),
            argnums=(0, 1), has_aux=True)),
        actor_tail=jax.jit(jax.value_and_grad(
            lambda v, hs, vc, hs_c, obs: actor_tail(m, s, v, hs, vc, hs_c,
                                                    obs), argnums=(0, 1))),
        target_value=jax.jit(lambda va, hs_a, vc, hs_c, batch: target_value(
            m, s, va, hs_a, vc, hs_c, batch)),
        picked_action=jax.jit(lambda v, hs, obs: picked_action(m, s, v, hs,
                                                               obs)),
        adam=jax.jit(lambda p, g, mu, nu, count: ddpg.adam(
            p, g, mu, nu, count, s.base.lr), donate_argnums=(0, 2, 3)),
        polyak=jax.jit(lambda tgt, p: {
            k: s.base.tau * p[k] + (1 - s.base.tau) * tgt[k] for k in tgt},
            donate_argnums=(0,)),
        batch=jax.jit(ddpg.gather_batch))


def forward(pc: Pieces, s: Spec, v: Dict, obs: Dict, keep: bool = False):
    """The node states of every pass, piece by piece; with ``keep`` also
    what the reverse pass needs: each layer's leaves, every layer's
    input and every final norm's."""
    h = pc.stem(v, obs)
    ws = [layer_leaves(v, l) for l in range(s.layers)]
    hs, kept = [], []
    for _ in range(s.passes):
        inputs = []
        for w in ws:
            inputs.append(h)
            h = pc.layer(h, w, obs["node_mask"])
        kept.append((inputs, h))
        h = pc.norm(h, v[f"{TORSO}/final_norm"])
        hs.append(h)
    return (hs, (ws, kept)) if keep else hs


def backward(pc: Pieces, s: Spec, v: Dict, obs: Dict, kept, g_hs: List
             ) -> Dict:
    """The gradient of everything under the node states from their
    cotangents ``g_hs`` (one per pass): back through the passes, last
    first, each layer's leaves summed over the passes."""
    ws, kept = kept
    scale = v[f"{TORSO}/final_norm"]
    d_scale = jnp.zeros_like(scale)
    d_layers = [None] * s.layers
    g = jnp.zeros_like(g_hs[-1])
    for t in reversed(range(s.passes)):
        inputs, before_norm = kept[t]
        g, d = pc.norm_back(before_norm, scale, g + g_hs[t])
        d_scale = d_scale + d
        for l in reversed(range(s.layers)):
            g, d = pc.layer_back(inputs[l], ws[l], obs["node_mask"], g)
            d_layers[l] = d if d_layers[l] is None else \
                jax.tree_util.tree_map(jnp.add, d_layers[l], d)
    grads = pc.stem_back({k: x for k, x in v.items() if k.startswith(STEM)},
                         obs, g)
    grads[f"{TORSO}/final_norm"] = d_scale
    for k in LAYER_LEAVES:
        grads[f"{TORSO}/{k}"] = jnp.stack([d[k] for d in d_layers])
    return grads


def tail_leaves(v: Dict) -> Dict:
    """The exit gate and the head: what the losses' last part trains."""
    return {k: x for k, x in v.items() if not k.startswith(STEM + STACK)}


def gradient_step(pc: Pieces, s: Spec, st: Dict, batch: Dict,
                  half_batch: bool = False):
    """One critic, actor and Polyak update, as ``reference/ddpg.py``
    ``gradient_step``, with the exit objective's two losses."""
    if half_batch:
        batch = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2],
                                       batch)
    obs, nxt = batch["obs"], batch["next_obs"]
    y = pc.target_value(st["targets"]["actor"],
                        forward(pc, s, st["targets"]["actor"], nxt),
                        st["targets"]["critic"],
                        forward(pc, s, st["targets"]["critic"], nxt), batch)

    def update(net, loss_and_grad, *rest):
        v = st["params"][net]
        hs, kept = forward(pc, s, v, obs, keep=True)
        out, (d_tail, g_hs) = loss_and_grad(tail_leaves(v), hs, *rest)
        grads = {**backward(pc, s, v, obs, kept, g_hs), **d_tail}
        st["params"][net], st["mu"][net], st["nu"][net], \
            st["count"][net] = pc.adam(v, grads, st["mu"][net],
                                       st["nu"][net], st["count"][net])
        return out

    closs, td = update("critic", pc.critic_tail, obs, batch["action"], y)
    vc = st["params"]["critic"]               # the critic just updated
    aloss = update("actor", pc.actor_tail, tail_leaves(vc),
                   forward(pc, s, vc, obs), obs)
    for net in ("actor", "critic"):
        st["targets"][net] = pc.polyak(st["targets"][net],
                                       st["params"][net])
    return st, {"critic_loss": closs, "actor_loss": aloss,
                "td_abs_sum": jnp.abs(td).sum()}


def learn_burst(matmul: str, s: Spec, params: Dict, rng, rows: Dict,
                replicas: int, filled: int, steps: int,
                half_batch: bool = False):
    """``steps`` gradient steps from freshly made weights, batches drawn
    from ``rows`` by the learner key, as ``reference/ddpg.py``
    ``learn_burst``."""
    pc = pieces(matmul, s)
    nets = ("actor", "critic")
    fresh = lambda make: {net: {k: make(x) for k, x in
                                view(params, net).items()} for net in nets}
    st = {"params": fresh(jnp.copy), "targets": fresh(jnp.copy),
          "mu": fresh(jnp.zeros_like), "nu": fresh(jnp.zeros_like),
          "count": {net: jnp.zeros((), jnp.int32) for net in nets}}
    _, sub = jax.random.split(rng)
    td_sum = 0.0
    out = None
    for i in range(steps):
        b, t = ddpg.sample_indices(jax.random.fold_in(sub, i), s.base,
                                   replicas, filled)
        st, out = gradient_step(pc, s, st, pc.batch(rows, b, t), half_batch)
        td_sum += float(out["td_abs_sum"])
    rows_per_step = s.base.batch_size // 2 if half_batch \
        else s.base.batch_size
    named = lambda table: {f"{net}/params/{k}": x for net in nets
                           for k, x in table[net].items()}
    return ({k: named(st[k]) for k in ("params", "targets", "mu", "nu")},
            {"critic_loss": float(out["critic_loss"]),
             "actor_loss": float(out["actor_loss"]),
             "td_abs_mean": td_sum / (steps * rows_per_step)})


# ----------------------------------------------------------------- acting
def policy_actions(matmul: str, s: Spec, actor_params: Dict, obs: Dict, rng,
                   replicas: int, sample, chunk: int, noise_mu: float,
                   noise_sigma: float):
    """As ``reference/ddpg.py`` ``policy_actions`` (same key schedule,
    noise, scaling, clipping, threshold + renormalise), the actor's
    answer being the picked pass's: one forward over all [R, T] stored
    observations, piece by piece, then the noise step by step."""
    pc = pieces(matmul, s)
    b = s.base
    sample = jnp.asarray(sample)
    obs = {k: jnp.asarray(x) for k, x in obs.items()}
    steps = obs["nodes"].shape[1]
    v = view(actor_params, "actor")
    answer = pc.picked_action(tail_leaves(v), forward(pc, s, v, obs), obs)

    def one_chunk(rng, answer_c):
        rng, sub = jax.random.split(rng)
        sub, _ = jax.random.split(sub)

        def step(args):
            i, a = args
            keys = jax.random.split(jax.random.fold_in(sub, i),
                                    replicas)[sample]
            k2 = jax.vmap(lambda k: jax.random.split(k)[1])(keys)
            noise = noise_mu + noise_sigma * jax.vmap(
                lambda k: jax.random.normal(k, a.shape[-1:]))(k2)
            raw = jnp.clip(0.5 * ((2.0 * a - 1.0) + noise + 1.0), 0.0, 1.0)
            return (ddpg.post_process(raw, b.max_nodes, b.threshold),
                    ddpg.threshold_margin(raw, b.max_nodes, b.threshold))

        return rng, jax.lax.map(step, (jnp.arange(chunk), answer_c))

    acts, margins = [], []
    for c in range(steps // chunk):
        answer_c = jnp.swapaxes(answer[:, c * chunk:(c + 1) * chunk], 0, 1)
        rng, (a, g) = jax.jit(one_chunk)(rng, answer_c)
        acts.append(a)
        margins.append(g)
    return (jnp.swapaxes(jnp.concatenate(acts, 0), 0, 1),
            jnp.swapaxes(jnp.concatenate(margins, 0), 0, 1))


def warmup_actions(rng, replicas: int, steps: int, chunk: int, mask,
                   s: Spec):
    return ddpg.warmup_actions(rng, replicas, steps, chunk, mask, s.base)


# ------------------------------------------------------------------ FLOPs
def model_flops(cfg: dict) -> Dict[str, float]:
    """Model FLOPs by ``flops.py``'s conventions (a multiply-add counts
    two, a backward pass twice its forward), counting **applications, not
    parameters**: the L layers are applied T times on every one of the N
    node slots.  Per slot and layer application the four attention
    projections and the three of the gated MLP, plus attention's 4 N d for
    the logits and the weighted sum.  ``env_step`` is one acting forward
    (the head on the picked pass); ``grad_step`` is, per batch row, nine
    torso forwards' worth — target actor 1, target critic 1, critic
    forward and backward 3, actor forward and backward 3, the critic's
    forward inside the actor's loss 1 (its backward reaches the action
    through the head alone) — with the embedder beside each and the heads
    once per pass or candidate action where the loss reads them all."""
    s = spec_from_config(cfg)
    b = s.base
    n = b.max_nodes
    a = n * b.num_sfcs * b.max_sfs * n
    f = int(cfg["GNN_features"])
    emb = flops.embedder_flops(n, len(cfg["observation_space"]), f,
                               int(cfg["GNN_num_layers"]),
                               int(cfg["GNN_num_iter"]))
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_slot = 2 * (s.d * qd + 2 * s.d * kvd + qd * s.d
                    + 3 * s.d * s.inter) + 4 * n * qd
    torso_fwd = n * s.layers * s.passes * per_slot \
        + 2 * n * f * s.d + s.passes * 2 * s.d
    ah = list(cfg["actor_hidden_layer_nodes"])
    ch = list(cfg["critic_hidden_layer_nodes"])
    if b.factored:
        g = b.key_dim
        csg = b.num_sfcs * b.max_sfs * g
        head_a = n * flops.mlp_flops([2 * s.d] + ah) \
            + n * 2 * ah[-1] * csg + n * 2 * s.d * g + 2 * n * csg * n
        head_c = n * 2 * s.d * g + 2 * n * csg * n \
            + n * 2 * (s.d + csg) * f + flops.mlp_flops([s.d + f] + ch + [1])
    else:
        head_a = flops.mlp_flops([s.d + a] + ah + [a])
        head_c = flops.mlp_flops([s.d + 2 * a] + ch + [1])
    body = emb + torso_fwd
    t = s.passes
    row = (body + head_a) + (body + head_c) \
        + 3 * (body + t * head_c) + 3 * (body + t * head_a) \
        + (body + t * head_c) + t * head_c
    return {"actor_fwd": float(body + head_a),
            "critic_fwd": float(body + head_c),
            "env_step": float(body + head_a),
            "grad_step": float(b.batch_size * row)}
