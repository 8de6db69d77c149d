"""On-device learning-signal ledger — the learning-quality counterpart of
the :mod:`~gsc_tpu.obs.perf` CostLedger.

PR 10 made *performance* a per-run artifact (FLOPs/MFU/roofline); training
QUALITY was still archaeology: losses and a mean Q rode the episode
events, but nothing said WHICH topology's transitions still carry TD
error, whether a layer's gradients are exploding, or how spread the Q
distribution is — the per-scenario signal the auto-curriculum item needs
and the banded learning-curve envelopes item 2 trades bit-exactness
against.  Podracer (arXiv:2104.06272) keeps learner statistics resident
on-device and drains them with the existing dispatch cadence; Jumanji
(arXiv:2306.09884) computes the per-scenario signal inside the compiled
program.  Both patterns apply directly here:

**Device half** (traced inside the agents' jitted programs, keyed on a
static :class:`LearnLedgerSpec` so the no-ledger trace stays byte-identical
to the pre-ledger stack):

- :func:`learn_signal` — per-transition |TD-error| aggregated per
  ``topo_idx`` via ``segment_sum`` (replay rows already carry the
  topology id), Q-value distribution moments (mean/std/min/max — not
  just the mean the loss logs), and per-layer param/grad norm tree
  summaries (grouped by top-level module, e.g. ``actor/GNNEmbedder_0``).
- :func:`replay_stats` — replay fill/age folded into the rollout stats.

Everything folds into the EXISTING dispatch outputs and drains with the
deferred metric drain — zero new host syncs on the dispatch path (the
same ``no_host_sync`` contract the CostLedger is tested under).

**Host half** (after the deferred drain has already synced the values):

- :func:`emit_learn_signal` — one structured ``learn_signal`` event per
  episode into events.jsonl plus hub gauges (``td_abs_mean`` overall and
  tagged ``topology=<name>``, ``q_mean``/``q_std``/``q_min``/``q_max``,
  ``grad_norm{layer=...}``, ``param_norm{layer=...}``, ``replay_fill``).
- :class:`LearnLedger` — the RunObserver-owned facade that remembers the
  topo-id -> name mapping and hands the trainer its static spec.

``RunObserver.close()`` then extracts the per-run learning curves from
the event stream into schema-versioned ``curves.json``
(:mod:`~gsc_tpu.obs.curves`), which ``tools/bench_diff.py`` gates.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass(frozen=True)
class LearnLedgerSpec:
    """Static ledger config threaded into the jitted agents.

    Hashable/frozen on purpose: it rides on the agent instance, which is
    a static argnum of every dispatch entry point — two agents that
    differ only in spec share no trace, and ``None`` (no ledger) traces
    the historic program byte for byte.

    ``num_topos`` sizes the TD-error segment axis: topo ids are the
    schedule position (plain runs) or the mix-entry index (mixed-topology
    batches), clipped into ``[0, num_topos)`` on device.
    """

    num_topos: int = 1


def _key_str(entry) -> str:
    """One pytree path entry -> readable component (DictKey / GetAttrKey /
    SequenceKey across jax versions)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _layer_groups(tree) -> Dict[str, list]:
    """Group a (params-like) pytree's leaves by top-level module:
    ``{'actor': {'params': {'Dense_0': {'kernel': ...}}}}`` groups under
    ``actor/Dense_0``.  Grouping is purely structural (static at trace
    time), so the signal pytree has a fixed shape the fori-loop carry can
    hold."""
    import jax

    groups: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [_key_str(p) for p in path if _key_str(p) != "params"]
        if len(keys) > 1:
            keys = keys[:-1]     # drop the leaf name (kernel/bias/...)
        name = "/".join(keys[:2]) or "leaf"
        groups.setdefault(name, []).append(leaf)
    return groups


def layer_norms(tree) -> Dict[str, "object"]:
    """Per-layer global norms of a params/grads pytree (device scalars)."""
    import jax.numpy as jnp

    return {name: jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves))
            for name, leaves in _layer_groups(tree).items()}


def exit_stats(p) -> Dict:
    """Where an exit distribution ``p`` [T, batch] (a looped torso's, see
    models/torso.py) puts its mass: the expected exit pass, counted from
    one, and the distribution's entropy, each the batch's mean."""
    import jax.numpy as jnp

    from ..models.torso import exit_entropy

    steps = jnp.arange(1, p.shape[0] + 1, dtype=p.dtype)
    return {"exit_step_mean": jnp.mean(jnp.tensordot(steps, p, axes=1)),
            "exit_entropy": jnp.mean(exit_entropy(p))}


def learn_signal(spec: LearnLedgerSpec, topo_idx, td, q, params, grads,
                 exits: Optional[Dict] = None) -> Dict:
    """One gradient step's learning signal (traced inside the learn
    burst).  ``td`` is the critic residual ``q - stop_grad(target)`` the
    loss already computes; ``params``/``grads`` are the post-update trees
    — everything here CONSUMES tensors the update path materialized, so
    the update math is untouched and ledger-on runs stay bit-identical
    to ledger-off runs.  ``exits`` ({"actor": p, "critic": p}, networks
    with a looped torso only) adds each network's :func:`exit_stats`."""
    import jax
    import jax.numpy as jnp

    # num_topos is a static Python int (frozen spec) — no cast, so the
    # R1 host-sync scan never mistakes it for a traced value
    k = max(spec.num_topos, 1)
    seg = jnp.clip(jnp.asarray(topo_idx).astype(jnp.int32), 0, k - 1)
    td_abs = jnp.abs(td)
    extra = {} if exits is None else {
        "exits": {net: exit_stats(p) for net, p in sorted(exits.items())}}
    return {
        **extra,
        # accumulated across the burst by _learn_burst's carry
        "td_abs_sum": jax.ops.segment_sum(td_abs, seg, num_segments=k),
        "td_count": jax.ops.segment_sum(jnp.ones_like(td_abs), seg,
                                        num_segments=k),
        # distribution moments, not just the mean the loss logs — a
        # collapsing critic shows as q_std -> 0 long before the loss does
        "q_mean": q.mean(), "q_std": q.std(),
        "q_min": q.min(), "q_max": q.max(),
        "param_norms": layer_norms(params),
        "grad_norms": layer_norms(grads),
    }


def zero_learn_signal(spec: LearnLedgerSpec, state,
                      exits: bool = False) -> Dict:
    """The fori-loop carry template matching :func:`learn_signal`'s
    structure (layer names derive from the state's static tree, so the
    two always agree; ``exits`` as the networks have a looped torso)."""
    import jax.numpy as jnp

    k = max(spec.num_topos, 1)
    trees = {"actor": state.actor_params, "critic": state.critic_params}
    zeros = {name: jnp.zeros(()) for name in _layer_groups(trees)}
    extra = {} if not exits else {
        "exits": {net: {"exit_step_mean": jnp.zeros(()),
                        "exit_entropy": jnp.zeros(())}
                  for net in ("actor", "critic")}}
    return {
        **extra,
        "td_abs_sum": jnp.zeros((k,)), "td_count": jnp.zeros((k,)),
        "q_mean": jnp.zeros(()), "q_std": jnp.zeros(()),
        "q_min": jnp.zeros(()), "q_max": jnp.zeros(()),
        "param_norms": dict(zeros), "grad_norms": dict(zeros),
    }


def accumulate_signal(acc: Dict, sig: Dict) -> Dict:
    """Fold one gradient step's signal into the burst carry: TD segments
    ACCUMULATE over the whole burst (the per-topology learning pressure),
    moments and norms keep the last step's values (the same last-write
    semantics as the existing loss metrics)."""
    return {**sig,
            "td_abs_sum": acc["td_abs_sum"] + sig["td_abs_sum"],
            "td_count": acc["td_count"] + sig["td_count"]}


def replay_stats(buffer) -> Dict:
    """Replay fill/age stats from the live buffer, on device (reading
    ``buffer.size`` host-side would sync the dispatch head).  Handles the
    single-agent ``[capacity, ...]`` layout and the replica-sharded
    ``[B, capacity, ...]`` layout (``size`` is then ``[B]``)."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(buffer.data)[0]
    size = buffer.size
    cap = leaf.shape[1] if jnp.ndim(size) else leaf.shape[0]
    s = size.astype(jnp.float32)
    return {
        "size": size,
        # cap is a static Python int off the leaf shape — plain division,
        # no float() cast for the R1 scan to misread
        "fill": s / max(cap, 1),
        # ring semantics: entries age 0..size-1 until the ring wraps, so
        # mean insertion-age in env steps is (size-1)/2
        "age_mean_steps": jnp.maximum(s - 1.0, 0.0) / 2.0,
    }


# ----------------------------------------------------------------- host
def _scalar(v) -> Optional[float]:
    try:
        return round(float(np.asarray(v)), 6)
    except (TypeError, ValueError):
        return None


def emit_learn_signal(hub, episode: int, signal: Optional[Dict] = None,
                      replay: Optional[Dict] = None,
                      segment_names: Optional[Sequence[str]] = None
                      ) -> Optional[Dict]:
    """Drain one episode's learn signal into the hub: gauges + one
    ``learn_signal`` event.  Called AFTER the deferred drain has blocked
    on the episode's device work, so every ``np.asarray`` here reads an
    already-synced value — the dispatch path never waits on this."""
    if hub is None or (signal is None and replay is None):
        return None
    fields: Dict = {"episode": episode}
    if signal is not None:
        sums = np.asarray(signal["td_abs_sum"], dtype=np.float64)
        counts = np.asarray(signal["td_count"], dtype=np.float64)
        total = counts.sum()
        td_mean = (round(float(sums.sum() / total), 6) if total > 0
                   else None)
        per_topo = {}
        for i in range(sums.shape[0]):
            if counts[i] > 0:
                name = (str(segment_names[i]) if segment_names is not None
                        and i < len(segment_names) else f"topo{i}")
                per_topo[name] = round(float(sums[i] / counts[i]), 6)
        q = {k: _scalar(signal[k])
             for k in ("q_mean", "q_std", "q_min", "q_max")}
        grad_norms = {k: _scalar(v)
                      for k, v in (signal.get("grad_norms") or {}).items()}
        param_norms = {k: _scalar(v)
                       for k, v in (signal.get("param_norms") or {}).items()}
        fields.update(td_abs_mean=td_mean, per_topology_td=per_topo, **q,
                      grad_norms=grad_norms, param_norms=param_norms)
        for net, stats in (signal.get("exits") or {}).items():
            # a looped torso's exit distribution, the burst's last step
            for k, v in stats.items():
                fields[f"{k}_{net}"] = _scalar(v)
                if fields[f"{k}_{net}"] is not None:
                    hub.gauge(k, fields[f"{k}_{net}"], network=net)
        if td_mean is not None:
            hub.gauge("td_abs_mean", td_mean)
        for name, v in per_topo.items():
            hub.gauge("td_abs_mean", v, topology=name)
        for k, v in q.items():
            if v is not None:
                hub.gauge(k, v)
        for name, v in grad_norms.items():
            if v is not None:
                hub.gauge("grad_norm", v, layer=name)
        for name, v in param_norms.items():
            if v is not None:
                hub.gauge("param_norm", v, layer=name)
    if replay is not None:
        fill = np.asarray(replay["fill"], dtype=np.float64)
        fields["replay"] = {
            "size": np.asarray(replay["size"]).tolist(),
            "fill": round(float(fill.mean()), 6),
            "age_mean_steps": round(float(
                np.asarray(replay["age_mean_steps"]).mean()), 3),
        }
        hub.gauge("replay_fill", float(fill.mean()))
    return hub.event("learn_signal", **fields)


class LearnLedger:
    """Host-side facade the :class:`~gsc_tpu.obs.run.RunObserver` owns
    when constructed with ``learn=True``: hands the trainer the static
    device spec (:meth:`spec`), remembers the topo-id -> name mapping,
    and drains per-episode signals through :func:`emit_learn_signal`."""

    def __init__(self, hub):
        self.hub = hub
        self.segment_names: Optional[List[str]] = None
        self.episodes = 0

    def spec(self, num_topos: int,
             names: Optional[Sequence[str]] = None) -> LearnLedgerSpec:
        if names:
            self.segment_names = [str(n) for n in names]
        return LearnLedgerSpec(num_topos=max(int(num_topos or 1), 1))

    def episode(self, episode: int, signal: Optional[Dict] = None,
                replay: Optional[Dict] = None) -> Optional[Dict]:
        self.episodes += 1
        return emit_learn_signal(self.hub, episode, signal=signal,
                                 replay=replay,
                                 segment_names=self.segment_names)
