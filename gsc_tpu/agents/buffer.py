"""On-device replay buffer — generic pytree ring buffer in HBM.

The reference's GraphReplayBuffer stores torch-geometric ``Data`` objects in
a numpy *object* array and re-batches them on every sample
(src/rlsp/agents/buffer.py:16-89) — host memory, pointer chasing, CPU
collation.  Here observations are already fixed-shape pytrees (GraphObs or
flat vectors), so the whole buffer is a pytree with a leading [capacity]
axis resident in device memory: ``add`` is one in-place
``dynamic_update_slice`` per leaf at the write cursor, ``sample`` a gather —
both jit/scan-able, so rollout and learning never leave the device.  Works
for any transition pytree (graph obs store nodes, edge_index, masks per
transition, which also preserves cross-topology replay when the topology
schedule swaps networks mid-training).

Two writers share the layout.  ``buffer_add`` writes ONE ring at its own
cursor (the single-environment agent).  The replica path holds B rings as
one ``[B, capacity, ...]`` pytree whose cursors advance in lockstep, and
writes a whole control step's B transitions with
``buffer_write_lockstep``: one ``[B, 1, ...]`` slab per leaf at one SCALAR
cursor.  ``buffer_add`` under ``vmap`` stores the same bits, but its
per-replica cursor turns each leaf's update into a scatter with ``[B]``
indices, which the TPU compiler expands into a sequential loop over the
replicas — per leaf, per control step.

Storage layout: per-transition leaves with ndim >= 2 (e.g. GraphObs.nodes
[N, F], edge_index [2, E]) are stored FLATTENED to 1-D — [capacity, N*F] —
and restored to their original shapes on sampling.  Ragged trailing dims
like [24, 3] tile poorly on TPU and made XLA ping-pong the whole buffer
between layouts on every rollout step (two full-buffer copies per step,
~25% of the measured step wall at B=512); flat trailing dims keep one
layout end-to-end.  The original shapes ride on the buffer as static aux
data (``shapes``, aligned with ``tree_leaves(data)`` order; None for
leaves stored as-is).

Every sampler reads its rows through ``take_rows``: a stored row wider
than ``ROW_GATHER_LIMIT`` elements is fetched in column pieces by one
gather, so the TPU compiler never copies the whole ring to gather it.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct


@struct.dataclass
class ReplayBuffer:
    """Ring buffer (reference: buffer.py:16-54 ring semantics)."""

    data: Any                # pytree, each leaf [capacity, ...]
    pos: jnp.ndarray         # [] i32 next write slot
    size: jnp.ndarray       # [] i32 valid entries
    # per-leaf original trailing shape for flattened (ndim>=2) leaves,
    # aligned with tree_leaves(data); None = leaf stored unflattened
    shapes: Tuple = struct.field(pytree_node=False, default=None)


def transition_shapes(example: Any) -> Tuple:
    """Static per-leaf storage spec from an example transition."""
    return tuple(
        tuple(jnp.shape(x)) if jnp.ndim(x) >= 2 else None
        for x in jax.tree_util.tree_leaves(example))


def flatten_transition(item: Any) -> Any:
    """Flatten ndim>=2 leaves of one transition to 1-D (storage form)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.asarray(x).reshape(-1) if jnp.ndim(x) >= 2
        else jnp.asarray(x), item)


def restore_batch(shapes: Tuple, batch: Any, lead: int = 1) -> Any:
    """Reshape a sampled batch's flattened leaves back to their original
    per-transition shapes (``lead`` = number of leading batch axes).
    ``shapes=None`` (a buffer built without the storage spec) means nothing
    was flattened — return the batch as-is."""
    if shapes is None:
        return batch
    leaves, treedef = jax.tree_util.tree_flatten(batch)
    out = [l if s is None else l.reshape(l.shape[:lead] + s)
           for l, s in zip(leaves, shapes)]
    return jax.tree_util.tree_unflatten(treedef, out)


# The widest row, in elements, that the TPU compiler gathers where the ring
# lies.  Gathering 100 rows of a [32, 256, W] ring for a described v5e
# compiles to one gather and no temporaries up to W = 32 768, in float32,
# int32, bfloat16 and bool alike; from W = 33 024 the compiler splits the
# gather into ``mini-gather``s that each read a copied slice of the WHOLE
# ring (1.08 GB of temporaries at 33 024 float32, 1.61 GB at 49 152), once
# per sample.
ROW_GATHER_LIMIT = 32768


def row_pieces(width: int) -> int:
    """How many column pieces ``take_rows`` fetches a ``width``-element row
    in: 1 (a plain gather) up to ``ROW_GATHER_LIMIT``."""
    return -(-width // ROW_GATHER_LIMIT)


def take_rows(leaf, *index):
    """``leaf[index]`` for a ring leaf stored as ``[*lead, row]`` (or with
    no row axis), ``index`` one integer array per lead axis, all of one
    shape ``[n]``.

    A row of at most ``ROW_GATHER_LIMIT`` elements is gathered as
    ``leaf[index]`` is.  A wider row is fetched as ``k = row_pieces(W)``
    column pieces of ``ceil(W / k)`` elements by ONE gather whose start
    index carries each piece's column offset (the last piece's start
    clamped to ``W - piece``, its overlap with the one before dropped):
    the gather reads the ring where it lies, the same bits as
    ``leaf[index]``."""
    width = leaf.shape[-1] if leaf.ndim == len(index) + 1 else 0
    k = row_pieces(width)
    if k <= 1:
        return leaf[index]
    piece = -(-width // k)
    starts = np.minimum(np.arange(k) * piece, width - piece)

    def one(*at):           # the lead indices of one row, then a column
        return jax.lax.dynamic_slice(
            leaf, at, (1,) * len(index) + (piece,)).reshape(piece)

    parts = jax.vmap(lambda *row: jax.vmap(
        lambda col: one(*row, col))(jnp.asarray(starts, jnp.int32)))(
            *index)                                     # [n, k, piece]
    overlap = k * piece - width
    if overlap == 0:
        return parts.reshape(parts.shape[:1] + (width,))
    return jnp.concatenate(
        [parts[:, :-1].reshape(parts.shape[:1] + ((k - 1) * piece,)),
         parts[:, -1, overlap:]], axis=1)


def pieced_leaves(buf: ReplayBuffer) -> Tuple[int, ...]:
    """The piece count of every ring leaf that ``take_rows`` fetches in
    pieces (rows wider than ``ROW_GATHER_LIMIT``): ``()`` where every row
    is gathered in place.  ``buf`` is one ring or the replica path's
    ``[B, capacity, ...]`` rings (``pos`` of shape ``[B]``)."""
    lead = 1 + jnp.ndim(buf.pos)
    widths = (l.shape[-1] if l.ndim == lead + 1 else 0
              for l in jax.tree_util.tree_leaves(buf.data))
    return tuple(k for k in map(row_pieces, widths) if k > 1)


def buffer_init(example: Any, capacity: int) -> ReplayBuffer:
    """Allocate from an example transition pytree (shapes/dtypes copied)."""
    flat = flatten_transition(example)
    data = jax.tree_util.tree_map(
        lambda x: jnp.zeros((capacity,) + jnp.shape(x), jnp.asarray(x).dtype),
        flat)
    return ReplayBuffer(data=data, pos=jnp.zeros((), jnp.int32),
                        size=jnp.zeros((), jnp.int32),
                        shapes=transition_shapes(example))


def buffer_add(buf: ReplayBuffer, item: Any) -> ReplayBuffer:
    """Insert one transition (buffer.py:33-54)."""
    capacity = jax.tree_util.tree_leaves(buf.data)[0].shape[0]
    with jax.named_scope("replay_write"):
        data = jax.tree_util.tree_map(
            lambda d, x: jax.lax.dynamic_update_index_in_dim(
                d, jnp.asarray(x).astype(d.dtype), buf.pos, 0),
            buf.data, flatten_transition(item))
        return ReplayBuffer(data=data, pos=(buf.pos + 1) % capacity,
                            size=jnp.minimum(buf.size + 1, capacity),
                            shapes=buf.shapes)


def buffer_write_lockstep(data: Any, items: Any, cursor) -> Any:
    """Write one transition per replica into the ``data`` of the replica
    path's ``[B, capacity, ...]`` rings: ``items`` is the ``[B]``-stacked
    transition pytree, ``cursor`` the SCALAR slot every ring writes next.

    The rings of the replica path advance in lockstep (every ``pos`` starts
    at 0, a rollout adds its steps to every replica, ``replay_ingest`` adds
    T to every row), so the write is one ``[B, 1, ...]`` slab per leaf at
    ``cursor`` along axis 1: in place on a donated ring, no gather, no
    scatter, no loop over the replicas — and, sharded on axis 0, no
    collective.  Stores the bits ``jax.vmap(buffer_add)`` stores (leaves
    flattened per replica as ``flatten_transition`` does, cast to the
    leaf's storage dtype).  ``pos``/``size`` are the caller's to advance,
    once for all the steps it wrote (``buffer_advance``), and the
    invariant is the caller's too: ``cursor`` follows the rings' common
    ``pos`` (``lockstep_cursor`` checks it where rings come from outside)."""
    with jax.named_scope("replay_write"):
        return jax.tree_util.tree_map(
            lambda d, x: jax.lax.dynamic_update_slice_in_dim(
                d, jnp.asarray(x).astype(d.dtype).reshape(
                    d.shape[:1] + (1,) + d.shape[2:]), cursor, axis=1,
                # a cursor is never negative: no index normalisation
                allow_negative_indices=False),
            data, items)


def buffer_advance(buf: ReplayBuffer, data: Any, n: int) -> ReplayBuffer:
    """``buf`` with ``data`` in place and every ring of the ``[B, capacity,
    ...]`` pytree advanced by the ``n`` slots written into it."""
    capacity = jax.tree_util.tree_leaves(data)[0].shape[1]
    return buf.replace(data=data, pos=(buf.pos + n) % capacity,
                       size=jnp.minimum(buf.size + n, capacity))


def lockstep_cursor(buf: ReplayBuffer) -> int:
    """The common write cursor of ``[B, capacity, ...]`` rings, checked on
    the host: ``buffer_write_lockstep`` writes every replica's row at ONE
    slot, so rings handed in from outside (a restored checkpoint) whose
    cursors differ are refused, the replicas that differ named."""
    import numpy as np

    pos = np.asarray(jax.device_get(buf.pos)).reshape(-1)
    values, counts = np.unique(pos, return_counts=True)
    common = int(values[np.argmax(counts)])
    off = np.flatnonzero(pos != common)
    if off.size:
        shown = ", ".join(f"{int(r)} (pos {int(pos[r])})" for r in off[:16])
        raise ValueError(
            f"replay rings out of lockstep: {off.size} of {pos.size} "
            f"replicas have a write cursor other than {common}: replicas "
            f"{shown}{', ...' if off.size > 16 else ''} — the replica "
            "path writes every ring at one cursor")
    return common


def buffer_nbytes(buf: ReplayBuffer, local: bool = False) -> int:
    """Total replay storage footprint in bytes.  The buffer is the largest
    HBM resident of a training run; the pipeline telemetry logs this so the
    copy traffic that ``donate_argnums`` eliminates (one full-buffer copy
    per episode on the non-donating path) is attributable.

    Summed per leaf from the ACTUAL storage dtype (``l.dtype.itemsize``),
    never from an assumed element size — under a mixed-dtype policy
    (bf16 obs/action leaves next to f32 reward/done, PrecisionPolicy.
    replay_dtype) the ``replay bytes`` gauge must reflect the halved
    residency, not double-count bf16 leaves as f32
    (tests/test_precision.py::test_buffer_nbytes_mixed_dtypes).

    ``local=True`` reports the bytes RESIDENT ON THIS PROCESS'S devices
    when the ring is dp-sharded under a mesh plan: ``l.size`` on a jax
    Array is the GLOBAL element count, so the default accounting
    overstates a sharded ring's per-host residency by the dp factor —
    local sums each leaf's addressable shards instead (identical to the
    global number for host numpy leaves and unsharded device arrays)."""
    total = 0
    for l in jax.tree_util.tree_leaves(buf.data):
        shards = getattr(l, "addressable_shards", None) if local else None
        if shards is not None:
            total += sum(s.data.size * s.data.dtype.itemsize
                         for s in shards)
        else:
            total += l.size * l.dtype.itemsize
    return total


def buffer_fill_frac(buf: ReplayBuffer) -> float:
    """Global fill fraction of the ring: valid entries over capacity,
    summed across every replica row when ``size`` is batched [B] (the
    parallel ring) and correct when ``size``/``data`` live sharded under
    a plan — ``jnp.sum`` reduces over the GLOBAL array, so per-shard
    fills never masquerade as the whole ring's (the async replay-fill
    gauge; scalar rings divide by their scalar capacity)."""
    import numpy as np

    capacity = jax.tree_util.tree_leaves(buf.data)[0].shape[
        1 if jnp.ndim(buf.size) >= 1 else 0]
    rows = max(1, int(np.prod(jnp.shape(buf.size)) or 1))
    denom = rows * int(capacity)
    return float(jnp.sum(buf.size)) / denom if denom else 0.0


def buffer_sample(buf: ReplayBuffer, key, batch_size: int) -> Any:
    """Uniform sample of ``batch_size`` transitions (buffer.py:56-67),
    restored to original per-transition shapes."""
    idx = jax.random.randint(key, (batch_size,), 0,
                             jnp.maximum(buf.size, 1))
    raw = jax.tree_util.tree_map(lambda d: take_rows(d, idx), buf.data)
    return restore_batch(buf.shapes, raw)
