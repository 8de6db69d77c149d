"""Partition rules + shard/gather fns: pjit-sharded training state.

The scale-out story so far (``parallel/mesh.py``, ``parallel/dp.py``)
shards the *data* — env replicas, replay shards, traffic — over a 1-D
``dp`` mesh and keeps every parameter replicated.  This module adds the
other half of the Podracer/Anakin pattern (arXiv 2104.06272): a regex
rulebook over the ``/``-joined leaf paths of the DDPG param/opt pytree
(the ``match_partition_rules`` idiom, SNIPPETS.md [1]-[2]) producing a
``NamedSharding`` tree over a 2-D ``dp x mp`` mesh, plus per-leaf shard
and gather functions (SNIPPETS.md [3]) so any host-resident pytree can be
placed onto — or pulled off — the mesh without retracing the train step.

Two axes, two jobs:

- the REPLICA axis of every data pytree is sharded over BOTH mesh axes,
  ``P(("dp", "mp"))`` — so however the device grid is carved (``8x1``,
  ``4x2``, ``2x4``), the per-device data layout is identical (one layout
  per device COUNT, not per carving).  Every float contraction that
  touches the batch therefore keeps the same partial-sum structure across
  carvings, which is what makes the final learner state BIT-IDENTICAL
  across mesh shapes — the same invariance the multi-process dryrun
  proves for process carvings;
- parameter leaves matched by a sharding rule split their OUTPUT-feature
  (last) dimension over ``mp`` only.  An output dim is never a
  contraction dim, so each output element is still computed on exactly
  one device with the unchanged op sequence: sharded params are bit-exact
  against replicated params by construction, and against each other
  across carvings.

``REPLICATED_RULES`` (everything ``P()``) is the default rulebook — with
it the plan is a pure no-op fallback reproducing today's data-parallel
stack bit-for-bit.  Scalars and single-element leaves are never
partitioned regardless of rules, and a rule whose sharded dimension does
not divide the mesh axis is clamped back to replication (logged), so one
rulebook ports across mesh shapes and model widths unchanged.
"""
from __future__ import annotations

import logging
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..meshspec import (PARTITION_RULEBOOKS, parse_mesh_shape,
                        validate_partition_rules)
from .mesh import require_devices

log = logging.getLogger("gsc_tpu.parallel.partition")

#: axis names of the 2-D training mesh: ``dp`` carves replicas across
#: hosts/chips, ``mp`` additionally splits wide parameter matrices.
TRAIN_AXES = ("dp", "mp")

#: the no-op rulebook: every leaf replicated — byte-for-byte the
#: pre-partition data-parallel stack (params live on every device).
REPLICATED_RULES: Tuple[Tuple[str, P], ...] = ((r".*", P()),)


def sharded_rules(mp_axis: str = "mp") -> Tuple[Tuple[str, P], ...]:
    """The DDPG rulebook: wide output-feature matrices split over ``mp``.

    Matches the actor/critic MLP ``kernel`` leaves and the GATv2
    projections ``w_l``/``w_r`` — in the online params, the Polyak
    targets AND both Adam moment trees (the optimizer state mirrors the
    param paths, so one ``kernel$`` rule shards all of them alike; a
    moment sharded differently from its param would force a reshard
    every update).  Only the LAST (output-feature) dimension is sharded:
    it is never contracted over, so the op-by-op float sequence — and
    therefore the training math — is unchanged (see module docstring).
    Attention vectors (``att``: output dim 1), biases, scalars, PRNG
    keys and step counts fall through to replication.
    """
    return (
        (r"(kernel|w_l|w_r)$", P(None, mp_axis)),
        (r".*", P()),
    )


def tp_rules(mp_axis: str = "mp") -> Tuple[Tuple[str, P], ...]:
    """The TRUE tensor-parallel rulebook: contraction dims split over
    ``mp``, partial products psum-accumulated by GSPMD.

    Where :func:`sharded_rules` only ever splits output-feature dims
    (keeping the float sequence — and therefore bit-equality — intact),
    this book spends the precision contract for genuinely parallel
    compute, Megatron-style within each block:

    - first projections (``Dense_0`` kernels, GATv2 ``w_l``/``w_r``) are
      COLUMN-parallel: the hidden/feature OUTPUT dim splits over ``mp``,
      so each device computes its slice of the hidden activation;
    - deeper MLP kernels (``Dense_1``..) are ROW-parallel: the hidden
      CONTRACTION dim splits over ``mp`` — each device dots its
      activation slice against its weight rows and GSPMD psums the
      partial products (one all-reduce per column/row pair, not one per
      layer);
    - ``Dense_0`` biases follow their sharded pre-activation.

    The psum reduces shards in a carving-dependent order, so a ``tp``
    run drifts ~1e-7 per mp size against the replicated program per
    gradient step — the documented floor.  Acceptance is BANDED, not
    bit-exact: learning curves and bench rows must land inside
    ``tools/bench_diff.py``'s tolerance envelope vs a replicated control
    (ROADMAP item 2's trade).  Polyak targets and both Adam moments
    share the param paths, so one rule shards all of them alike —
    moments never reshard per update.  Attention vectors (``att``:
    contraction over the sharded feature dim — GSPMD psums the logit),
    remaining biases, scalars and PRNG keys fall through to
    replication."""
    return (
        (r"Dense_0/kernel$", P(None, mp_axis)),
        (r"Dense_0/bias$", P(mp_axis)),
        (r"Dense_\d+/kernel$", P(mp_axis, None)),
        (r"(w_l|w_r)$", P(None, mp_axis)),
        (r".*", P()),
    )


#: rulebook-name -> builder for the named books every surface accepts
#: (the vocabulary itself lives jax-free in ``gsc_tpu.meshspec``)
NAMED_RULEBOOKS = {
    "replicated": lambda: REPLICATED_RULES,
    "sharded": sharded_rules,
    "tp": tp_rules,
}
assert tuple(NAMED_RULEBOOKS) == PARTITION_RULEBOOKS


# ------------------------------------------------------------- mesh shapes
# the "DPxMP" grammar lives jax-free in gsc_tpu.meshspec; parse_mesh_shape
# is imported above and re-exported so every historic import site keeps
# working.


def make_train_mesh(dp: int, mp: int = 1,
                    axes: Tuple[str, str] = TRAIN_AXES) -> Mesh:
    """2-D ``(dp, mp)`` mesh over the first ``dp*mp`` devices.  Like
    :func:`..mesh.make_mesh`, raises ``ValueError`` when the backend has
    fewer — it never changes the platform."""
    n = dp * mp
    devs = require_devices(n, f"a {dp}x{mp} mesh")
    grid = np.asarray(devs[:n]).reshape(dp, mp)
    return Mesh(grid, axes)


# ----------------------------------------------------------- rule matching
def leaf_path_names(tree) -> List[str]:
    """``/``-joined path name per leaf, in ``tree_leaves`` order.

    ``actor_opt[0].mu['params']['MLP_0']['Dense_0']['kernel']`` becomes
    ``actor_opt/0/mu/params/MLP_0/Dense_0/kernel`` — the namespace the
    rule regexes match against."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)

    def name(entry) -> str:
        for attr in ("name", "key", "idx"):
            if hasattr(entry, attr):
                return str(getattr(entry, attr))
        return str(entry)

    return ["/".join(name(k) for k in path) for path, _ in flat]


def match_partition_rules(rules: Sequence[Tuple[str, P]], tree) -> Any:
    """Pytree of ``PartitionSpec`` per leaf: first rule whose regex
    ``re.search``-matches the leaf's ``/``-joined path wins.

    Scalars and single-element leaves are never partitioned (``P()``)
    regardless of rules — splitting a step counter or a PRNG key buys
    nothing and breaks dtype-agnostic resume.  A leaf no rule matches is
    an error: end every rulebook with ``(".*", P())`` to make
    replication the explicit default rather than a silent one."""
    names = leaf_path_names(tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree)

    def spec_for(name: str, leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()) or ())
        # trace-time shape arithmetic, never a traced value
        if len(shape) == 0 or int(np.prod(shape)) == 1:  # gsc-lint: disable=R1
            return P()
        for rule, ps in rules:
            if re.search(rule, name) is not None:
                return ps
        raise ValueError(f"no partition rule matched leaf {name!r} — "
                         "append a ('.*', P()) default rule")

    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(n, l) for n, l in zip(names, leaves)])


def clamp_specs_to_mesh(specs, tree, mesh: Mesh) -> Tuple[Any, int]:
    """Downgrade any spec whose sharded dimension the mesh cannot split
    evenly (or that out-ranks its leaf) to ``P()``.

    Returns ``(clamped_specs, n_clamped)``.  This is what makes ONE
    rulebook portable across mesh shapes: ``(kernel, P(None, 'mp'))``
    shards a 256-wide layer on ``mp=4`` and quietly replicates a 22-wide
    GNN projection the same mesh cannot divide — the elastic-resume path
    leans on exactly this when a checkpoint reshards onto a differently
    carved mesh."""
    names = leaf_path_names(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    flat_specs, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    clamped = 0

    def ok(spec: P, shape: Tuple[int, ...]) -> bool:
        if len(spec) > len(shape):
            return False
        for dim, entry in zip(shape, spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            # mesh axis sizes are Python ints — trace-time constants
            size = int(np.prod([mesh.shape[a] for a in axes]))  # gsc-lint: disable=R1
            if size > 1 and dim % size != 0:
                return False
        return True

    out = []
    for name, leaf, spec in zip(names, leaves, flat_specs):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if spec != P() and not ok(spec, shape):
            log.debug("partition rule clamped to replication: %s %s on "
                      "mesh %s", name, shape, dict(mesh.shape))
            spec = P()
            clamped += 1
        out.append(spec)
    return jax.tree_util.tree_unflatten(treedef, out), clamped


def spec_summary(specs) -> Dict[str, int]:
    """``{spec-string: leaf count}`` — the compact partition-layout
    record ``run_start`` obs meta carries (counts by spec, never the
    full tree: a rung-5 state has hundreds of leaves)."""
    counts: Dict[str, int] = {}
    for spec in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)):
        key = str(spec)
        counts[key] = counts.get(key, 0) + 1
    return dict(sorted(counts.items()))


# -------------------------------------------------------- shard/gather fns
def make_shard_and_gather_fns(shardings) -> Tuple[Any, Any]:
    """Pytrees of per-leaf ``shard(x)`` / ``gather(x)`` callables from a
    pytree of ``NamedSharding`` (the SNIPPETS.md [1]-[3] idiom).

    ``shard`` places a host or differently-placed leaf onto the mesh
    (``jax.device_put`` — a layout move, never a retrace); ``gather``
    pulls a (possibly sharded) leaf back to one host ``np.ndarray`` —
    the portable layout checkpoints are written in."""
    def make_shard(s):
        return lambda x: jax.device_put(x, s)

    def make_gather(_s):
        # gather IS the device->host sync, by contract; host-side only,
        # never called from traced code
        return lambda x: np.asarray(jax.device_get(x))  # gsc-lint: disable=R1

    is_s = lambda x: isinstance(x, NamedSharding)
    shard_fns = jax.tree_util.tree_map(make_shard, shardings, is_leaf=is_s)
    gather_fns = jax.tree_util.tree_map(make_gather, shardings, is_leaf=is_s)
    return shard_fns, gather_fns


def apply_fns(fns, tree):
    """Apply a pytree of per-leaf callables to a matching pytree."""
    return jax.tree_util.tree_map(lambda f, x: f(x), fns, tree)


# ------------------------------------------------------------ the plan
class ShardingPlan:
    """One mesh + one rulebook, bound to concrete sharding trees lazily.

    The object the sharded train path threads around: ``ParallelDDPG``
    reads ``state_shardings``/``data_sharding``/``replicated`` to build
    its ``in_shardings``/``out_shardings``, the trainer/CLI use
    ``place_state``/``gather_state`` to move the learner state on and
    off the mesh (elastic resume = ``gather`` on the old mesh shape,
    ``place`` on the new one), and obs meta records ``describe()`` +
    ``summary()``.

    ``rules`` is either a rulebook (sequence of ``(regex, spec)``) or
    one of the named books ``"replicated"`` (default — the bit-identical
    no-op fallback) / ``"sharded"`` (:func:`sharded_rules`) / ``"tp"``
    (:func:`tp_rules` — true tensor-parallel compute: the learner state
    stays RESIDENT-sharded through the compiled program, accepted under
    tolerance bands instead of bit-equality)."""

    def __init__(self, mesh: Mesh, rules="replicated"):
        self.rules_name = rules if isinstance(rules, str) else "custom"
        if isinstance(rules, str):
            rules = NAMED_RULEBOOKS[validate_partition_rules(rules)]()
        self.mesh = mesh
        self.rules = tuple(rules)
        self.dp = int(mesh.shape.get("dp", 1))
        self.mp = int(mesh.shape.get("mp", 1))
        # replicas/batch sharded over the WHOLE grid: the per-device data
        # layout depends only on dp*mp, so recarving the same devices
        # never changes a float reduction (module docstring)
        self.data_sharding = NamedSharding(mesh, P(TRAIN_AXES))
        self.replicated = NamedSharding(mesh, P())
        self._state_shardings = None   # bound on first state sighting
        self._shard_fns = None
        self._gather_fns = None
        self.clamped = 0

    @classmethod
    def from_spec(cls, spec: str, rules="replicated") -> "ShardingPlan":
        dp, mp = parse_mesh_shape(spec)
        return cls(make_train_mesh(dp, mp), rules=rules)

    @property
    def n_devices(self) -> int:
        return self.dp * self.mp

    def describe(self) -> str:
        return f"{self.dp}x{self.mp}"

    # -------------------------------------------------------- state trees
    def state_shardings(self, state):
        """NamedSharding tree for the learner state; bound once (the
        state's tree structure is static for the life of a run) and
        reused by every subsequent dispatch — shard/gather moves never
        re-derive it, hence never retrace."""
        if self._state_shardings is None:
            specs = match_partition_rules(self.rules, state)
            specs, self.clamped = clamp_specs_to_mesh(specs, state,
                                                      self.mesh)
            self._state_shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            self._shard_fns, self._gather_fns = make_shard_and_gather_fns(
                self._state_shardings)
        return self._state_shardings

    def place_state(self, state):
        """Shard a host-resident (or any-mesh) learner state onto THIS
        plan's mesh — the reshard-on-load half of elastic resume."""
        return apply_fns(self._ensure_fns(state)[0], state)

    def gather_state(self, state):
        """Learner state as host ``np.ndarray`` leaves — the
        mesh-shape-agnostic layout checkpoints persist."""
        return apply_fns(self._ensure_fns(state)[1], state)

    def place_data(self, tree):
        """Shard a data pytree's leading replica axis over the grid."""
        return jax.device_put(tree, self.data_sharding)

    def place_replicated(self, tree):
        return jax.device_put(tree, self.replicated)

    def _ensure_fns(self, state):
        self.state_shardings(state)
        return self._shard_fns, self._gather_fns

    def summary(self, state_or_shapes) -> Dict[str, int]:
        """Partition layout as ``{spec: leaf count}`` (obs meta).  Works
        on concrete states AND ``jax.eval_shape`` trees — the CLI
        records it before any device work runs."""
        specs = match_partition_rules(self.rules, state_or_shapes)
        specs, _ = clamp_specs_to_mesh(specs, state_or_shapes, self.mesh)
        return spec_summary(specs)

    @property
    def is_sharded(self) -> bool:
        """True iff any rule can split a leaf (mp>1 with a non-P() rule)
        — the replicated book or an mp=1 mesh is the no-op fallback."""
        return self.mp > 1 and any(spec != P() for _, spec in self.rules)

    @property
    def resident_sharded(self) -> bool:
        """True for the ``tp`` book: the learner state stays sharded
        THROUGH the compiled program (in_/out_shardings are the plan's
        partition layout, entry-allgather/exit-slice layout moves are
        deleted, psum accumulates the partial products).  The
        replicated/sharded books keep the PR 8 ZeRO-residency design —
        sharded BETWEEN dispatches, replicated inside the program — so
        their bit-equality contract is untouched."""
        return self.rules_name == "tp"

    # --------------------------------------------------- async replay ring
    @property
    def ring_sharding(self) -> NamedSharding:
        """Sharding of the device-resident ``[B, cap]`` async replay ring
        — identical to ``data_sharding`` (replica axis 0 over the whole
        grid) ON PURPOSE: the sharded rollout already emits transition
        blocks in this layout, so an ingest whose ring, block, pos and
        size all share it is a row-aligned scatter GSPMD partitions
        per-shard with ZERO collectives.  Blocks land on the learner
        mesh once, in their final shard, and never move again."""
        return self.data_sharding

    def assert_async_capable(self):
        """Refuse meshes the decoupled actor/learner cannot shard replay
        over: a tp-only grid (``dp == 1`` with more than one device) has
        no data-parallel axis to carve the ``[B, cap]`` ring along, so
        every ingest would reshard tensor-parallel state instead of
        writing its own rows.  Raises with the recarve instructions."""
        if self.dp == 1 and self.n_devices > 1:
            raise ValueError(
                f"--async composes with --mesh over the dp axis only: "
                f"mesh {self.describe()} is tensor-parallel-only (dp=1), "
                f"so the replay ring has no dp axis to shard over. "
                f"Recarve the same {self.n_devices} devices as "
                f"{self.n_devices}x1 (pure dp) or {max(2, self.dp)}x"
                f"{self.n_devices // max(2, self.dp)}, or drop --async "
                f"to run tensor-parallel synchronously.")


def ring_shard_rows(num_replicas: int,
                    n_shards: int) -> Tuple[Tuple[int, int], ...]:
    """The STATIC row->shard map of the dp-sharded replay ring: GSPMD
    carves axis 0 of a ``P(TRAIN_AXES)``-sharded ``[B, ...]`` leaf into
    contiguous row blocks, so shard ``s`` owns rows ``[s*B/n, (s+1)*B/n)``
    — returned as one ``(lo, hi)`` per shard.  This is the contract the
    per-shard ingest heartbeats, the ``replay_shard`` flight-recorder
    tags and the parity tests all read from; it never changes for the
    life of a mesh shape."""
    B, n = int(num_replicas), int(n_shards)
    if n <= 0 or B % n != 0:
        raise ValueError(
            f"num_replicas ({B}) must divide evenly over {n} ring shards")
    per = B // n
    return tuple((s * per, (s + 1) * per) for s in range(n))


def actor_shard_assignment(n_actors: int, n_shards: int) -> Tuple[int, ...]:
    """Stable actor->dp-shard assignment: actor ``a`` reports against
    shard ``a % n_shards``, forever.  Every actor's block spans all
    shards (rollout keeps the full replica batch row-aligned), so the
    assignment is an OBSERVABILITY contract, not a routing table: it
    names which shard's ingest heartbeat an actor's blocks bump and
    which ``replay_shard`` tag its flight-recorder spans carry, so a
    cold shard points at a specific wedged actor."""
    return tuple(a % max(1, int(n_shards)) for a in range(int(n_actors)))
