"""Divergence guardrails: on-device all-finite flags + rollback snapshots.

One NaN gradient poisons the learner state forever — every later episode's
actions, replay writes and updates inherit it, and the run quietly trains
garbage until someone reads the loss curve.  The guard is two pieces:

- :func:`all_finite` — a scalar flag over a pytree's inexact leaves,
  computed ON DEVICE inside the fused ``episode_step``/``chunk_step``
  programs (``DDPG._rollout_body`` flags the state entering the episode,
  ``_learn_burst`` flags the post-update state) and drained with the
  existing deferred metrics — zero extra host syncs.
- :class:`RollbackGuard` — the trainer's last-good in-memory snapshot.
  Because the pipelined loop dispatches episode k+1 before episode k's
  metrics (and its finite flag) drain, the snapshot taken at a dispatch
  boundary is *unverified*; the guard stages it as a candidate and only
  promotes it to ``last_good`` once the matching episode drains finite.
  On a violation the trainer restores ``last_good`` (always a verified
  state), drops the in-flight episode, and continues.

Cost: two device-side pytree copies per episode (learner state + replay
buffer) and one retained copy of each — ~2 extra replay-buffer residents
in HBM.  ``Trainer(rollback=False)`` disables the snapshots (the flag is
still computed and surfaced).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


def all_finite(tree: Any) -> jnp.ndarray:
    """Scalar f32 flag (1.0/0.0): every inexact leaf of ``tree`` is
    finite.  Pure jnp — safe to trace inside the fused episode programs;
    integer leaves (PRNG keys, ring-buffer counters) are skipped."""
    with jax.named_scope("finite_guard"):
        flags = [jnp.isfinite(leaf).all()
                 for leaf in jax.tree_util.tree_leaves(tree)
                 if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)]
        if not flags:
            return jnp.float32(1.0)
        return jnp.stack(flags).all().astype(jnp.float32)


def tree_copy(tree: Any) -> Any:
    """Device-side copy of every array leaf — snapshots must not alias
    buffers that the next dispatch donates."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def poison_tree(tree: Any) -> Any:
    """NaN every inexact leaf (the ``nan_grads`` fault: the effect of a
    NaN gradient update on the learner state)."""
    return jax.tree_util.tree_map(
        lambda x: x * jnp.asarray(float("nan"), jnp.asarray(x).dtype)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact) else x, tree)


class RollbackGuard:
    """Last-good (state, buffer) snapshot with deferred-verification
    promotion — see the module docstring for why a candidate stage is
    needed under the asynchronous pipeline."""

    def __init__(self):
        # (episode_tag, state, buffer): "state after all episodes <= tag"
        self.last_good: Optional[Tuple[int, Any, Any]] = None
        self._candidate: Optional[Tuple[int, Any, Any]] = None
        self.rollbacks = 0

    def init(self, episode_tag: int, state, buffer):
        """Seed ``last_good`` with the (trivially finite) initial state so
        a violation on the very first episode still has a rollback
        target."""
        self.last_good = (episode_tag, tree_copy(state), tree_copy(buffer))

    def stage(self, episode_tag: int, state, buffer):
        """Candidate snapshot at a dispatch boundary (state after episode
        ``episode_tag``, not yet drained/verified).  Called BEFORE any
        fault injection and before the dispatch donates the carries."""
        self._candidate = (episode_tag, tree_copy(state), tree_copy(buffer))

    def promote(self, drained_episode: int, state, buffer,
                pending_empty: bool):
        """Episode ``drained_episode`` drained with a finite flag: promote
        the matching candidate to ``last_good``.  When nothing is in
        flight (serial loop, or the pipeline's tail drain) the live
        carries ARE the verified state — snapshot them directly, which
        also advances past the one-episode candidate lag."""
        c = self._candidate
        if c is not None and c[0] == drained_episode:
            self.last_good = c
            self._candidate = None
        elif pending_empty:
            self.last_good = (drained_episode, tree_copy(state),
                              tree_copy(buffer))
            self._candidate = None

    def restore(self) -> Tuple[int, Any, Any]:
        """Copies of ``last_good`` (the retained snapshot must survive a
        later rollback, and the returned carries will be donated)."""
        self.rollbacks += 1
        self._candidate = None   # descendant of the poisoned state
        tag, state, buffer = self.last_good
        return tag, tree_copy(state), tree_copy(buffer)
