"""On-device scenario factory — jitted topology/traffic/fault sampling.

PR 9's :class:`~gsc_tpu.topology.scenarios.ScenarioRegistry` generates
every episode's scenario on the host: topology parse, shortest paths,
traffic trace and fault plan are rebuilt in Python per episode per
replica, serialized against the dispatch loop.  Jumanji (PAPERS.md,
arXiv 2306.09884) puts the generator *inside* the compiled program; this
module does the same for the whole scenario: each episode of
``chunk_step`` draws a fresh randomized (topology, traffic, fault plan)
per replica entirely on device — zero host regen, zero retraces across
the stream (every sampled scenario lives in the same fixed
``[max_nodes, max_edges]`` shape bucket, so the dispatch jit sees
identical shapes forever), and an effectively unbounded scenario
distribution instead of a fixed mix string.

Mix grammar (the ``factory:`` extension of the PR 9 mix string,
``EpisodeDriver(topo_mix=...)`` / ``cli train --topo-mix``)::

    factory  := "factory:" families ["+shapes"] ["~faults"]
    families := "all" | family ("-" family)*
    family   := "star" | "ring" | "line" | "random"

A factory mix fills the WHOLE replica axis (it cannot be combined with
registry entries — the registry's round-robin assignment is static,
the factory's is sampled per episode).  ``+shapes`` additionally samples
a traffic shape per replica per episode (uniform / bursty / diurnal /
flash-crowd arrival-mean profiles, the on-device twin of the registry's
``+<shape>`` suffix); ``~faults`` samples a capacity fault plan per
replica per episode (one link- or node-capacity zeroing event from a
random control interval on, riding the same per-interval
``node_cap`` / ``edge_cap_t`` tables the host fault plans use).

What is sampled where (one :meth:`ScenarioFactory.sample` call,
per replica):

- **family** ~ the curriculum's sampling weights (``probs``, a traced
  ``[K]`` vector — uniform without a curriculum), stamped as
  ``topo_id`` so replay rows / the learn ledger attribute per family;
- **topology**: node count within the bucket, integer node caps,
  family-shaped edge list (random family: uniform spanning tree +
  deduplicated extra chords, uniform integer delays), then all-pairs
  shortest paths via an on-device Floyd–Warshall over the reference's
  edge weight ``1/(cap + 1/delay)`` (compiler.py) with path-delay and
  next-hop accumulation — the [N,N] matrices the simulator consumes;
- **traffic**: the shared renewal merge scan
  (:func:`~gsc_tpu.sim.traffic_device.renewal_stream`) over interval
  tables derived from the *sampled* topology and shape row;
- **faults**: Bernoulli(fault_rate) per replica; site (link/node),
  start interval and element index uniform over the topology's REAL
  elements.

Curriculum: :mod:`gsc_tpu.env.curriculum` turns the learn ledger's
per-``topo_idx`` |TD| segment sums into EWMA-driven sampling logits; the
factory just consumes the resulting ``probs`` vector — a fresh tiny
``[K]`` array per episode is data, never a compile axis.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Tuple

import numpy as np

from .compiler import INF_DELAY, Topology

FACTORY_PREFIX = "factory:"
FAMILIES = ("star", "ring", "line", "random")

# traffic-shape profile ids (shape 0 = the plain uniform profile, so a
# shapes-on factory still samples un-shaped episodes)
SHAPE_NAMES = ("uniform", "bursty", "diurnal", "flash_crowd")


@dataclass(frozen=True)
class FactorySpec:
    """Parsed ``factory:`` mix entry + the sampler's static knobs.

    Only the grammar-visible fields come from the mix string; the rest
    are programmatic defaults (construct a spec directly to change
    them).  Frozen/hashable so it can key caches and ride static
    arguments."""

    families: Tuple[str, ...] = FAMILIES
    traffic_shapes: bool = False
    faults: bool = False
    # topology knobs
    n_min: int = 4
    n_max: int = 0                    # 0 = the bucket's max_nodes
    num_ingress: int = 1
    node_cap_range: Tuple[int, int] = (1, 4)   # [lo, hi) integers
    link_cap: float = 100.0
    link_delay: float = 1.0           # star/ring/line fixed delay
    delay_range: Tuple[float, float] = (1.0, 10.0)  # random family
    extra_edge_frac: float = 0.25     # random family chords per node
    # fault knobs
    fault_rate: float = 0.5           # P(any fault) per replica episode

    @property
    def num_families(self) -> int:
        return len(self.families)


_FACTORY_RE = re.compile(r"factory:([a-z-]+)((?:\+shapes|~faults)*)$")


def is_factory_mix(mix) -> bool:
    """True when a mix string selects the on-device factory path."""
    return bool(mix) and mix.strip().startswith(FACTORY_PREFIX)


def parse_factory(mix: str) -> FactorySpec:
    """Parse a ``factory:`` mix entry (grammar in the module docstring).

    A factory mix must be the WHOLE mix string: the registry's
    round-robin replica assignment is static while the factory samples
    per episode, so mixing the two would need two dispatch programs."""
    raw = (mix or "").strip()
    if not is_factory_mix(raw):
        raise ValueError(f"not a factory mix: {mix!r} (expected "
                         f"'{FACTORY_PREFIX}<families>[+shapes][~faults]')")
    if "," in raw:
        raise ValueError(
            "a factory mix fills the whole replica axis and cannot be "
            f"combined with registry entries: {mix!r} (drop the comma "
            "entries or use a pure registry mix)")
    m = _FACTORY_RE.fullmatch(raw)
    if not m:
        raise ValueError(
            f"bad factory mix {mix!r}: expected "
            f"'{FACTORY_PREFIX}<fam>[-<fam>...][+shapes][~faults]' with "
            f"families from {', '.join(FAMILIES)} (or 'all')")
    fams_raw, flags = m.group(1), m.group(2)
    if fams_raw == "all":
        families = FAMILIES
    else:
        families = tuple(fams_raw.split("-"))
        unknown = [f for f in families if f not in FAMILIES]
        if unknown:
            raise ValueError(
                f"unknown factory families {unknown} in {mix!r} "
                f"(known: {', '.join(FAMILIES)}, or 'all')")
        if len(set(families)) != len(families):
            raise ValueError(
                f"duplicate factory families in {mix!r}: two copies of "
                "one family would be identical distributions labeled as "
                "distinct curriculum arms")
    return FactorySpec(families=families,
                       traffic_shapes="+shapes" in flags,
                       faults="~faults" in flags)


# --------------------------------------------------------------- profiles
def _shape_profiles(steps: int) -> np.ndarray:
    """[S, steps] arrival-mean scale profiles, row order
    :data:`SHAPE_NAMES`.  Rows 1..3 call the registry's own profile
    functions (scenarios.TRAFFIC_SHAPES) so the on-device shapes can
    never drift from the host ``+<shape>`` suffix semantics."""
    from .scenarios import TRAFFIC_SHAPES

    rows = [np.ones(steps)]
    for name in SHAPE_NAMES[1:]:
        rows.append(TRAFFIC_SHAPES[name][0](steps))
    return np.stack(rows).astype(np.float32)


def _max_shape_factor(spec: FactorySpec) -> float:
    from .scenarios import TRAFFIC_SHAPES

    if not spec.traffic_shapes:
        return 1.0
    return max(f for _, f in TRAFFIC_SHAPES.values())


# ---------------------------------------------------------------- factory
class ScenarioFactory:
    """Jitted per-(replica, episode) scenario sampler over one shape
    bucket.  Build once per run; ``sample_batch(key, probs, B)`` is one
    device call producing a ``[B]``-stacked ``(Topology,
    TrafficSchedule)`` pair the vmapped dispatch consumes in place of
    the host-staged MixPlan products."""

    def __init__(self, spec: FactorySpec, sim_cfg, service,
                 episode_steps: int, max_nodes: int = 24,
                 max_edges: int = 37):
        from ..sim.traffic import traffic_capacity

        if sim_cfg.use_states:
            raise ValueError(
                "the scenario factory samples arrival means from the "
                "base inter_arrival_mean (+ shape profiles); MMPP state "
                "chains (SimConfig.use_states) are host-table-driven — "
                "use a registry --topo-mix for MMPP scenarios")
        if not spec.families:
            raise ValueError("factory spec has no families")
        # worst-case edge demand per family at node count n: ring needs
        # n, random (n-1) tree edges + extra chords — the bucket must
        # hold the densest possible draw
        def edges_needed(n):
            need = n if "ring" in spec.families else n - 1
            if "random" in spec.families:
                need = max(need, n - 1
                           + int(math.ceil(spec.extra_edge_frac * n)))
            return need

        n_max = spec.n_max
        if not n_max:
            # default: the largest node count whose densest family fits
            # this bucket (so one grammar string works on the 24/37
            # flagship bucket AND the 8/8 test buckets alike)
            n_max = max_nodes
            while n_max > spec.n_min and edges_needed(n_max) > max_edges:
                n_max -= 1
        if not 3 <= spec.n_min <= n_max <= max_nodes:
            raise ValueError(
                f"factory node range [{spec.n_min}, {n_max}] must satisfy "
                f"3 <= n_min <= n_max <= bucket max_nodes ({max_nodes})")
        if edges_needed(n_max) > max_edges:
            raise ValueError(
                f"factory families need up to {edges_needed(n_max)} edges "
                f"at n_max={n_max}, bucket has max_edges={max_edges} — "
                "shrink n_max or widen the bucket")
        self.spec = spec
        self.cfg = sim_cfg
        self.episode_steps = int(episode_steps)
        self.max_nodes = int(max_nodes)
        self.max_edges = int(max_edges)
        self.n_max = int(n_max)
        self.n_sfcs = max(len(service.sfc_names), 1)
        self.horizon = float(episode_steps * sim_cfg.run_duration)
        # one shared traffic capacity across every sampled scenario (the
        # plan_mix convention: densest shape profile, re-rounded to 64)
        cap = traffic_capacity(sim_cfg, spec.num_ingress, episode_steps)
        self.capacity = int(math.ceil(
            cap * _max_shape_factor(spec) / 64.0)) * 64
        # device-resident constants (closed over by the jitted sampler)
        import jax.numpy as jnp
        self.ttl_choices = jnp.asarray(sim_cfg.ttl_choices, jnp.float32)
        self.profiles = jnp.asarray(_shape_profiles(episode_steps))
        self.num_shapes = (len(SHAPE_NAMES) if spec.traffic_shapes else 1)
        self._jit = {}   # B -> jitted sample_batch

    @property
    def family_names(self):
        """topo_id -> family name (the curriculum / learn-ledger segment
        axis)."""
        return list(self.spec.families)

    # ------------------------------------------------------- topology half
    def _random_edges(self, key, n):
        """Random-family edge tensors: a uniform random spanning tree
        (node i's parent uniform over [0, i) — guaranteed connected)
        plus up to ``extra_edge_frac * n`` deduplicated random chords,
        compacted behind the tree edges so ``edge_mask == arange <
        n_edges`` holds like every compiled topology."""
        import jax
        import jax.numpy as jnp

        N, E = self.max_nodes, self.max_edges
        k_par, k_extra, k_delay = jax.random.split(key, 3)
        i = jnp.arange(E)
        # tree slot i connects node i+1 to a uniform parent in [0, i+1)
        parent = jnp.floor(
            jax.random.uniform(k_par, (E,)) * (i + 1)).astype(jnp.int32)
        parent = jnp.minimum(parent, i)   # guard the u==1.0 edge case
        tree_mask = i < n - 1
        tu = jnp.where(tree_mask, parent, N)
        tv = jnp.where(tree_mask, i + 1, N)
        # adjacency over an [N+1] padded grid so masked slots scatter
        # into a discard row; diag blocked so chords never self-loop
        adj = jnp.zeros((N + 1, N + 1), bool)
        adj = adj.at[tu, tv].set(True).at[tv, tu].set(True)
        adj = adj | jnp.eye(N + 1, dtype=bool)
        wanted = jnp.minimum(
            jnp.round(self.spec.extra_edge_frac * n).astype(jnp.int32),
            jnp.int32(E) - (n - 1))

        def chord(carry, c):
            adj, cnt = carry
            ka, kb = jax.random.split(jax.random.fold_in(k_extra, c))
            a = jax.random.randint(ka, (), 0, n)
            b = jax.random.randint(kb, (), 0, n)
            ok = (~adj[a, b]) & (cnt < wanted)
            adj = adj.at[a, b].set(adj[a, b] | ok)
            adj = adj.at[b, a].set(adj[b, a] | ok)
            slot = jnp.where(ok, n - 1 + cnt, E)   # E = discard
            return (adj, cnt + ok.astype(jnp.int32)), (a, b, slot)

        (_, n_extra), (ca, cb, cslot) = jax.lax.scan(
            chord, (adj, jnp.int32(0)), jnp.arange(E))
        eu = jnp.where(tree_mask, parent, 0).astype(jnp.int32)
        ev = jnp.where(tree_mask, i + 1, 0).astype(jnp.int32)
        eu = eu.at[cslot].set(ca.astype(jnp.int32), mode="drop")
        ev = ev.at[cslot].set(cb.astype(jnp.int32), mode="drop")
        n_edges = n - 1 + n_extra
        delay = jnp.round(jax.random.uniform(
            k_delay, (E,), minval=self.spec.delay_range[0],
            maxval=self.spec.delay_range[1]))
        return eu, ev, n_edges, delay

    def _family_edges(self, key, fam, n):
        """(edge_u, edge_v, n_edges, edge_delay) of the sampled family:
        every family's tensors are built (they are a few index ops; the
        random family's tree+chord scan is the only real work) and the
        ``fam`` index selects — one program, no branches to retrace."""
        import jax.numpy as jnp

        E = self.max_edges
        i = jnp.arange(E)
        fixed_delay = jnp.full((E,), jnp.float32(self.spec.link_delay))
        builders = []
        for name in self.spec.families:
            if name == "line":
                builders.append((i, i + 1, n - 1, fixed_delay))
            elif name == "ring":
                builders.append((i, (i + 1) % jnp.maximum(n, 1), n,
                                 fixed_delay))
            elif name == "star":
                builders.append((jnp.zeros((E,), jnp.int32), i + 1, n - 1,
                                 fixed_delay))
            elif name == "random":
                builders.append(self._random_edges(key, n))
            else:   # pragma: no cover - parse_factory validates
                raise ValueError(f"unknown factory family {name!r}")
        eu = jnp.stack([jnp.broadcast_to(b[0], (E,)).astype(jnp.int32)
                        for b in builders])[fam]
        ev = jnp.stack([jnp.broadcast_to(b[1], (E,)).astype(jnp.int32)
                        for b in builders])[fam]
        ne = jnp.stack([jnp.asarray(b[2], jnp.int32)
                        for b in builders])[fam]
        ed = jnp.stack([b[3] for b in builders])[fam]
        mask = i < ne
        return (jnp.where(mask, eu, 0), jnp.where(mask, ev, 0), ne,
                jnp.where(mask, ed, 0.0), mask)

    def _shortest_paths(self, eu, ev, edge_delay, edge_mask, node_mask):
        """On-device all-pairs shortest paths: Floyd–Warshall over the
        reference's edge weight ``1/(cap + 1/delay)`` (compiler.py
        edge_weight; link caps are uniform here, so weights reduce to a
        delay-monotone constant family) with path-DELAY accumulation
        along the chosen paths and next-hop propagation — the same three
        matrices ``compile_topology`` derives via networkx Johnson.
        Tie-breaks may differ from Johnson's (both are valid shortest
        paths); families with unique shortest paths match exactly."""
        import jax
        import jax.numpy as jnp

        N = self.max_nodes
        w = 1.0 / (self.spec.link_cap + 1.0 / jnp.maximum(edge_delay,
                                                          1e-9))
        uu = jnp.where(edge_mask, eu, N)
        vv = jnp.where(edge_mask, ev, N)
        inf = jnp.float32(jnp.inf)
        wadj = jnp.full((N + 1, N + 1), inf)
        wadj = wadj.at[uu, vv].min(w).at[vv, uu].min(w)
        dadj = jnp.full((N + 1, N + 1), inf)
        dadj = dadj.at[uu, vv].min(edge_delay).at[vv, uu].min(edge_delay)
        wadj, dadj = wadj[:N, :N], dadj[:N, :N]
        eye = jnp.eye(N, dtype=bool)
        ii = jnp.arange(N, dtype=jnp.int32)
        dist = jnp.where(eye, 0.0, wadj)
        delay = jnp.where(eye, 0.0, dadj)
        nxt = jnp.where(jnp.isfinite(wadj),
                        jnp.broadcast_to(ii[None, :], (N, N)), -1)
        nxt = jnp.where(eye, ii[:, None], nxt).astype(jnp.int32)

        def relax(k, carry):
            dist, delay, nxt = carry
            alt = dist[:, k][:, None] + dist[k, :][None, :]
            better = alt < dist
            dist = jnp.where(better, alt, dist)
            delay = jnp.where(
                better, delay[:, k][:, None] + delay[k, :][None, :], delay)
            nxt = jnp.where(better,
                            jnp.broadcast_to(nxt[:, k][:, None], (N, N)),
                            nxt)
            return dist, delay, nxt

        dist, delay, nxt = jax.lax.fori_loop(0, N, relax,
                                             (dist, delay, nxt))
        real = node_mask[:, None] & node_mask[None, :]
        reach = real & jnp.isfinite(dist)
        path_delay = jnp.where(reach, delay, INF_DELAY).astype(jnp.float32)
        next_hop = jnp.where(reach, nxt, -1).astype(jnp.int32)
        diameter = jnp.max(jnp.where(reach, path_delay, 0.0))
        return next_hop, path_delay, diameter

    def _sample_topology(self, key, fam, n) -> Topology:
        import jax
        import jax.numpy as jnp

        N, E = self.max_nodes, self.max_edges
        k_edges, k_caps = jax.random.split(key)
        eu, ev, n_edges, edge_delay, edge_mask = self._family_edges(
            k_edges, fam, n)
        node_mask = jnp.arange(N) < n
        lo, hi = self.spec.node_cap_range
        node_cap = jax.random.randint(
            k_caps, (N,), lo, hi).astype(jnp.float32) * node_mask
        n_ing = jnp.maximum(
            jnp.minimum(jnp.int32(self.spec.num_ingress), n - 1), 1)
        is_ingress = jnp.arange(N) < n_ing
        edge_cap = jnp.where(edge_mask, jnp.float32(self.spec.link_cap),
                             0.0)
        # adjacency ids over the [N+1] padded grid (masked slots discard)
        uu = jnp.where(edge_mask, eu, N)
        vv = jnp.where(edge_mask, ev, N)
        ids = jnp.arange(E, dtype=jnp.int32)
        aei = jnp.full((N + 1, N + 1), -1, jnp.int32)
        aei = aei.at[uu, vv].set(ids).at[vv, uu].set(ids)[:N, :N]
        next_hop, path_delay, diameter = self._shortest_paths(
            eu, ev, edge_delay, edge_mask, node_mask)
        return Topology(
            node_cap=node_cap, node_mask=node_mask,
            is_ingress=is_ingress,
            is_egress=jnp.zeros((N,), bool),
            edge_u=eu, edge_v=ev, edge_cap=edge_cap,
            edge_delay=jnp.where(edge_mask, edge_delay, 0.0),
            edge_mask=edge_mask, adj_edge_id=aei,
            next_hop=next_hop, path_delay=path_delay,
            n_nodes=n.astype(jnp.int32), n_edges=n_edges,
            diameter=diameter,
            # family index = the curriculum/learn-ledger segment axis:
            # replay rows collected on this replica attribute to it
            topo_id=fam.astype(jnp.int32),
        )

    # -------------------------------------------------------- traffic half
    def _sample_traffic(self, key, topo: Topology):
        import jax
        import jax.numpy as jnp

        from ..sim.state import TrafficSchedule
        from ..sim.traffic_device import renewal_stream

        steps, N = self.episode_steps, self.max_nodes
        k_shape, k_fault, k_flows = jax.random.split(key, 3)
        ing = topo.is_ingress & topo.node_mask
        shape = (jax.random.randint(k_shape, (), 0, self.num_shapes)
                 if self.num_shapes > 1 else jnp.int32(0))
        profile = self.profiles[shape]                     # [steps]
        means = jnp.where(
            ing[None, :],
            jnp.float32(self.cfg.inter_arrival_mean) * profile[:, None],
            jnp.inf)
        active = jnp.broadcast_to(ing[None, :], (steps, N))
        # activity is time-invariant here, so the next-active table is
        # the identity on ingress columns (steps = never active)
        next_active = jnp.where(
            ing[None, :], jnp.arange(steps, dtype=jnp.int32)[:, None],
            jnp.int32(steps))
        caps = jnp.broadcast_to(topo.node_cap[None, :], (steps, N))
        edge_cap_t = None
        if self.spec.faults:
            k_occ, k_site, k_k0, k_n, k_e = jax.random.split(k_fault, 5)
            occurs = (jax.random.uniform(k_occ, ())
                      < self.spec.fault_rate)
            is_link = jax.random.bernoulli(k_site)
            k0 = jax.random.randint(k_k0, (), 1, max(steps, 2))
            nidx = jax.random.randint(k_n, (), 0, topo.n_nodes)
            eidx = jax.random.randint(k_e, (), 0,
                                      jnp.maximum(topo.n_edges, 1))
            from_k0 = jnp.arange(steps)[:, None] >= k0
            caps = jnp.where(
                (occurs & ~is_link) & from_k0
                & (jnp.arange(N)[None, :] == nidx), 0.0, caps)
            edge_cap_t = jnp.broadcast_to(
                topo.edge_cap[None, :], (steps, self.max_edges))
            edge_cap_t = jnp.where(
                (occurs & is_link) & from_k0
                & (jnp.arange(self.max_edges)[None, :] == eidx),
                0.0, edge_cap_t)
        times, ingress, drs, durs, ttls, sfcs, egs = renewal_stream(
            self.cfg, means, active, next_active, self.horizon,
            self.capacity, self.n_sfcs, self.ttl_choices,
            jnp.zeros((1,), jnp.int32), 0, k_flows)
        return TrafficSchedule.pack(
            arr_time=times, arr_ingress=ingress, arr_dr=drs,
            arr_duration=durs, arr_ttl=ttls, arr_sfc=sfcs, arr_egress=egs,
            ingress_active=active, node_cap=caps, edge_cap_t=edge_cap_t)

    # ------------------------------------------------------------ sampling
    def sample(self, key, probs):
        """One replica's scenario: ``probs`` is the curriculum's ``[K]``
        family-sampling distribution (traced data — fresh values never
        retrace).  Returns ``(Topology, TrafficSchedule)``."""
        import jax
        import jax.numpy as jnp

        k_fam, k_n, k_topo, k_traffic = jax.random.split(key, 4)
        fam = jax.random.choice(k_fam, self.spec.num_families, p=probs)
        n = jax.random.randint(k_n, (), self.spec.n_min, self.n_max + 1)
        topo = self._sample_topology(k_topo, fam.astype(jnp.int32), n)
        return topo, self._sample_traffic(k_traffic, topo)

    def lowerable(self, num_replicas: int):
        """The jitted batch sampler for ``num_replicas`` (built on first
        use, memoized — ONE trace per B for the whole run).  Exposed so
        the cost ledger can AOT-mine the factory-inclusive program."""
        fn = self._jit.get(num_replicas)
        if fn is None:
            import jax

            def factory_sample(key, probs):
                keys = jax.random.split(key, num_replicas)
                return jax.vmap(lambda k: self.sample(k, probs))(keys)

            fn = jax.jit(factory_sample)
            self._jit[num_replicas] = fn
        return fn

    def sample_batch(self, key, probs, num_replicas: int):
        """[B]-stacked (Topology, TrafficSchedule) for one episode — ONE
        jitted device call, the drop-in replacement for the host-staged
        ``MixPlan`` topology + ``mix_traffic`` products."""
        import jax.numpy as jnp

        probs = jnp.asarray(probs, jnp.float32)
        if probs.shape != (self.spec.num_families,):
            raise ValueError(
                f"probs must be [{self.spec.num_families}] (one weight "
                f"per family {self.spec.families}), got {probs.shape}")
        return self.lowerable(num_replicas)(key, probs)
