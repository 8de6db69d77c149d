"""The learn burst's replay sample reads its rows where the ring lies.

A stored row wider than ``buffer.ROW_GATHER_LIMIT`` elements is fetched in
column pieces by one gather (``buffer.take_rows``): the TPU compiler would
otherwise copy the whole ring leaf to gather it, at every gradient step.
Each sampler is held to the plain indexing it replaced, bit for bit; the
plain indexing is kept here as the reference."""
import tempfile
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gsc_tpu.agents.buffer import (ROW_GATHER_LIMIT, ReplayBuffer,
                                   buffer_sample, pieced_leaves,
                                   restore_batch, take_rows)
from gsc_tpu.parallel import ParallelDDPG

B, CAP, BATCH = 2, 4, 5


# -------------------------------------------- the samplers as they were
def plain_buffer_sample(buf, key, batch_size):
    idx = jax.random.randint(key, (batch_size,), 0,
                             jnp.maximum(buf.size, 1))
    raw = jax.tree_util.tree_map(lambda d: d[idx], buf.data)
    return restore_batch(buf.shapes, raw)


def plain_sample_across(self, buffers, key):
    kb, ks = jax.random.split(key)
    bidx = jax.random.randint(kb, (self.agent.batch_size,), 0, self.B)
    sidx = jax.random.randint(ks, (self.agent.batch_size,), 0,
                              jnp.maximum(buffers.size[bidx], 1))
    raw = jax.tree_util.tree_map(lambda d: d[bidx, sidx], buffers.data)
    return restore_batch(buffers.shapes, raw)


def plain_sample_local(self, buffers, key):
    b_per = max(self.agent.batch_size // self.B, 1)
    keys = jax.random.split(key, self.B)

    def pick(shard, size, k):
        idx = jax.random.randint(k, (b_per,), 0, jnp.maximum(size, 1))
        return jax.tree_util.tree_map(lambda d: d[idx], shard)

    batch = jax.vmap(pick)(buffers.data, buffers.size, keys)
    raw = jax.tree_util.tree_map(
        lambda d: d.reshape((self.B * b_per,) + d.shape[2:]), batch)
    return restore_batch(buffers.shapes, raw)


def _owner(replicas=B, batch=BATCH):
    """What the replica samplers read of their ``ParallelDDPG``."""
    return SimpleNamespace(B=replicas,
                           agent=SimpleNamespace(batch_size=batch))


SAMPLERS = {
    "take_rows": (lambda buf, k: take_rows(buf.data["x"], jnp.array(
                      [0, 1, 1, 0, 1]), jnp.array([3, 0, 2, 2, 1])),
                  lambda buf, k: buf.data["x"][jnp.array([0, 1, 1, 0, 1]),
                                               jnp.array([3, 0, 2, 2, 1])]),
    "buffer_sample": (
        lambda buf, k: buffer_sample(_serial(buf), k, BATCH),
        lambda buf, k: plain_buffer_sample(_serial(buf), k, BATCH)),
    "sample_across": (
        lambda buf, k: ParallelDDPG._sample_across(_owner(), buf, k),
        lambda buf, k: plain_sample_across(_owner(), buf, k)),
    "sample_local": (
        lambda buf, k: ParallelDDPG._sample_local(_owner(), buf, k),
        lambda buf, k: plain_sample_local(_owner(), buf, k)),
}


def _serial(buf):
    """Replica 1's ring as a single-environment ring."""
    return ReplayBuffer(
        data=jax.tree_util.tree_map(lambda d: d[1], buf.data),
        pos=buf.pos[1], size=buf.size[1], shapes=buf.shapes)


def _rings(width, dtype):
    """``[B, CAP, width]`` rings of distinct values beside a scalar leaf,
    ``size`` short of full in one replica."""
    x = jnp.arange(B * CAP * width, dtype=jnp.int32).reshape(B, CAP, width)
    x = (x % 2 == 1) if dtype == jnp.bool_ else (x * 7 + 3).astype(dtype)
    r = jnp.arange(B * CAP, dtype=jnp.int32).reshape(B, CAP).astype(dtype)
    return ReplayBuffer(data={"x": x, "r": r},
                        pos=jnp.array([0, 3], jnp.int32),
                        size=jnp.array([CAP, 3], jnp.int32),
                        shapes=(None, None))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bool_],
                         ids=["f32", "s32", "bool"])
@pytest.mark.parametrize("width", [1728, 49152, 40001, 70001],
                         ids=["narrow", "wide_divisible", "wide_indivisible",
                              "three_pieces"])
@pytest.mark.parametrize("sampler", list(SAMPLERS))
def test_samplers_fetch_the_bits_plain_indexing_fetches(sampler, width,
                                                        dtype):
    """Each sampler returns, leaf for leaf, the bits the plain ``d[b, s]``
    (``d[idx]``) indexing it replaced returns from the same key: under the
    limit, wider and divided evenly into pieces, wider and not (the last
    piece overlaps the one before), and in three pieces."""
    new, old = SAMPLERS[sampler]
    rings = _rings(width, dtype)
    key = jax.random.PRNGKey(width)
    got, want = jax.jit(new)(rings, key), jax.jit(old)(rings, key)
    got_l, want_l = jax.tree_util.tree_leaves(got), \
        jax.tree_util.tree_leaves(want)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(got_l, want_l):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert any(g.shape[-1] == width for g in got_l)


# ------------------------------------------------- the cells' ring shapes
def _cell_ring(name):
    """The abstract ``[B, capacity, ...]`` rings of a benchmark cell, from
    its configuration as the benchmark writes it (shapes only)."""
    from benchmarks import harness
    from gsc_tpu.cli import _build
    from gsc_tpu.sim.traffic_device import DeviceTraffic

    cell = harness.load_cell(name)
    cfg, wl = cell["config"], cell["cell"]
    with tempfile.TemporaryDirectory(prefix="gsc-ring-") as tmp:
        paths = harness.load_driver(cell).write_inputs(cfg, tmp)
        env, driver, agent = _build(
            paths["agent"], paths["simulator"], paths["service"],
            paths["scheduler"], 0, int(cfg["max_nodes"]),
            int(cfg["max_edges"]))
        topo = driver.topology_for(0)
    pddpg = ParallelDDPG(env, agent, num_replicas=int(wl["replicas"]))
    sampler = DeviceTraffic(env.sim_cfg, env.service, topo,
                            agent.episode_steps, trace=driver.trace,
                            capacity=driver.capacity)
    key = jax.random.PRNGKey(0)
    traffic = jax.eval_shape(lambda k: sampler.sample_batch(k, 1), key)
    one = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), traffic)
    _, obs = jax.eval_shape(env.reset, key, topo, one)
    return pddpg, jax.eval_shape(pddpg.init_buffers, obs)


@pytest.fixture(scope="module")
def cell_rings():
    return {name: _cell_ring(name)
            for name in ("interroute-b32", "flagship-b256")}


def _slices(jaxpr):
    """The slice sizes of every gather and dynamic slice in ``jaxpr``,
    sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("gather", "dynamic_slice"):
            yield eqn.primitive.name, tuple(eqn.params["slice_sizes"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _slices(sub)


def _sample_jaxpr(sample, pddpg, rings):
    return jax.make_jaxpr(lambda buf, k: sample(pddpg, buf, k))(
        rings, jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.mark.parametrize("sampler", ["_sample_across", "_sample_local"])
def test_wide_rows_are_sampled_in_pieces_narrow_rows_as_before(cell_rings,
                                                               sampler):
    """Interroute's burst sample slices no row wider than the limit out of
    its 49 152-wide ring leaves (a wider slice is what the compiler copies
    the whole leaf for); the flagship's, whose rows are all narrower, is
    the very jaxpr the plain indexing traces."""
    sample = getattr(ParallelDDPG, sampler)
    plain = {"_sample_across": plain_sample_across,
             "_sample_local": plain_sample_local}[sampler]
    pddpg, rings = cell_rings["interroute-b32"]
    assert max(l.shape[-1] for l in jax.tree_util.tree_leaves(rings.data)
               ) > ROW_GATHER_LIMIT
    slices = list(_slices(_sample_jaxpr(sample, pddpg, rings).jaxpr))
    assert any(s[-1] == 49152 // 2 for _, s in slices)
    assert all(s[-1] <= ROW_GATHER_LIMIT for _, s in slices), slices
    # the plain indexing slices whole rows: what the pieces replace
    assert any(s[-1] > ROW_GATHER_LIMIT for _, s in
               _slices(_sample_jaxpr(plain, pddpg, rings).jaxpr))

    pddpg, rings = cell_rings["flagship-b256"]
    assert str(_sample_jaxpr(sample, pddpg, rings)) == \
        str(_sample_jaxpr(plain, pddpg, rings))


@pytest.mark.parametrize("name,leaves,pieces", [
    ("interroute-b32", 3, 6), ("flagship-b256", 0, 0)])
def test_row_pieces_gauge_counts_the_leaves_fetched_in_pieces(
        cell_rings, name, leaves, pieces):
    """``replay_leaves_in_pieces`` reads 3 on interroute's rings (the
    observation's node rows, the next observation's, the action: 49 152
    elements each, two pieces each) and 0 on the flagship's."""
    from gsc_tpu.agents.trainer import Trainer
    from gsc_tpu.obs.hub import MetricsHub

    _, rings = cell_rings[name]
    ring = ReplayBuffer(data=rings.data, pos=jnp.zeros(rings.pos.shape,
                                                       jnp.int32),
                        size=jnp.zeros(rings.size.shape, jnp.int32),
                        shapes=rings.shapes)
    hub = MetricsHub()
    Trainer._gauge_row_pieces(hub, ring)
    assert hub.get_gauge("replay_leaves_in_pieces") == leaves
    assert hub.get_gauge("replay_row_pieces") == pieces
    assert len(pieced_leaves(ring)) == leaves
