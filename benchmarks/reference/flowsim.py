"""Plain per-flow reference of the flow simulation, the observation and the
reward: one replica, one flow at a time, Python and numpy only, no program
import.

It states the fixed-step semantics the system documents for its engine
(coordsim's per-flow state machine, flowsimulator.py:72-128, quantised to
``dt``; same-instant work in flow-slot order): each substep releases
expired capacity, advances hop and processing timers, admits due arrivals
into free slots, decides next nodes by weighted round robin against the
schedule with realised-ratio counters, forwards hop by hop with whole-path
TTL check and per-edge admission, processes with placement check, TTL
check and per-node capacity admission, and records departures and drops.
Then the observation columns (simulator_wrapper.py:178-308) and the
``prio-flow`` reward (gym_env.py:223-323).

Scope, checked at construction: deterministic arrivals, sizes and
processing delays, the default (identity) resource function, no startup
delay, no egress nodes, no traces.  Delays in the committed networks are
whole milliseconds, so every quantity here is exact in float32 and the
comparison with the program's rows is to rounding of the reward only.
Shortest paths follow the upstream reader (reader.py:114-160): weight
``1 / (cap + 1/delay)``, networkx Johnson.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

EPS = 1e-4
FREE, DECIDE, HOP, PROC = 0, 1, 2, 3
ARRIVALS_PER_SUBSTEP = 8
f32 = np.float32


def shortest_paths(n: int, edges) -> Tuple[np.ndarray, np.ndarray]:
    """(next_hop [n, n], path_delay [n, n]) as the upstream reader builds
    them; unreachable pairs keep next hop -1 and an infinite delay."""
    import networkx as nx

    g = nx.Graph()
    g.add_nodes_from(range(n))
    delay_of = {}
    for u, v, cap, delay in edges:
        if cap == 0:
            continue
        w = 0.0 if delay == 0 else 1.0 / (cap + 1.0 / delay)
        g.add_edge(u, v, weight=w)
        delay_of[(u, v)] = delay_of[(v, u)] = delay
    nh = np.full((n, n), -1, np.int64)
    pd = np.full((n, n), np.inf)
    for s, targets in dict(nx.johnson(g, weight="weight")).items():
        for t, path in targets.items():
            pd[s, t] = sum(delay_of[(path[i], path[i + 1])]
                           for i in range(len(path) - 1))
            nh[s, t] = path[1] if len(path) > 1 else s
    return nh, pd


def arrivals(cfg: dict, ingress: List[int], horizon: float) -> List[tuple]:
    """Deterministic renewal streams merged by time, ties to the lowest
    node: (time, node, dr, duration, ttl)."""
    sim = cfg["simulator"]
    gap = float(sim["inter_arrival_mean"])
    dr = float(sim["flow_dr_mean"])
    dur = float(sim["flow_size_shape"]) / dr * 1000.0
    ttl = float(sim["ttl_choices"][0])
    out = []
    for node in ingress:
        t = 0.0
        while t < horizon:
            out.append((t, node, dr, dur, ttl))
            t += gap
    return sorted(out, key=lambda a: (a[0], a[1]))


class FlowSim:
    """One replica of the configuration's network under its traffic."""

    def __init__(self, cfg: dict, node_caps, node_types, edges):
        sim, svc = cfg["simulator"], cfg["service"]
        if not (sim.get("deterministic_arrival")
                and sim.get("deterministic_size")
                and not sim.get("flow_dr_stdev")
                and len(sim["ttl_choices"]) == 1
                and len(svc["sfc_list"]) == 1
                and "Egress" not in node_types
                and all(not sf.get("processing_delay_stdev")
                        and not sf.get("startup_delay")
                        and sf.get("resource_function_id", "default")
                        == "default" for sf in svc["sf_list"].values())):
            raise ValueError("the plain flow reference states the "
                             "deterministic single-chain case only")
        self.cfg = cfg
        self.N = int(cfg["max_nodes"])
        self.M = int(sim["max_flows"])
        self.n = len(node_caps)
        self.caps = np.zeros(self.N)
        self.caps[: self.n] = node_caps
        self.node_mask = np.arange(self.N) < self.n
        self.ingress = [i for i, t in enumerate(node_types)
                        if t == "Ingress"]
        chain = next(iter(svc["sfc_list"].values()))
        names = list(svc["sf_list"])
        self.chain = [names.index(s) for s in chain]
        self.S = len(chain)
        self.P = len(names)
        self.proc = [abs(float(svc["sf_list"][s]["processing_delay_mean"]))
                     for s in names]
        self.edge_of = {}
        self.edge_cap, self.edge_delay = [], []
        for i, (u, v, cap, delay) in enumerate(edges):
            self.edge_of[(u, v)] = self.edge_of[(v, u)] = i
            self.edge_cap.append(float(cap))
            self.edge_delay.append(float(delay))
        self.next_hop, self.path_delay = shortest_paths(self.n, edges)
        self.substeps = int(round(float(sim["run_duration"])))
        self.steps = int(cfg["episode_steps"])
        self.H = 256
        self.R = 4                        # exact WRR rounds per substep
        self.min_delay = f32(sum(self.proc[s] for s in self.chain))
        self.diameter = f32(15.0)         # gym_env.py:56
        self.space = list(cfg["observation_space"])
        self.reset()

    def reset(self):
        self.flows: List[dict] = [None] * self.M   # None = free slot
        self.peak_live = 0          # most slots in use at once, so far
        self.arr = arrivals(self.cfg, self.ingress,
                            self.steps * float(self.substeps))
        self.cursor = 0
        self.g = 0
        self.node_load = np.zeros((self.N, self.P))
        self.edge_used = np.zeros(len(self.edge_cap))
        self.rel_node: Dict[int, list] = {}
        self.rel_edge: Dict[int, list] = {}
        self.sf_available = np.zeros((self.N, self.P), bool)
        self.placed = np.zeros((self.N, self.P), bool)
        self.ewma = f32(1.0)

    # ------------------------------------------------------- control step
    def placement(self, schedule: np.ndarray) -> np.ndarray:
        placed = np.zeros((self.N, self.P), bool)
        reach = np.zeros(self.N, bool)
        reach[self.ingress] = True
        for pos, sf in enumerate(self.chain):
            targets = ((schedule[:, 0, pos, :] > 0)
                       & reach[:, None]).any(axis=0)
            placed[:, sf] |= targets
            reach = targets
        return placed

    def step(self, action: np.ndarray):
        """One control interval under a post-processed flat action ->
        (reward, node feature matrix [N, F])."""
        m = self.node_mask.astype(np.float64)
        sched = action.astype(np.float64).reshape(self.N, 1, self.S, self.N) \
            * m[:, None, None, None] * m[None, None, None, :]
        self.schedule = sched
        self.placed = self.placement(sched)
        self.sf_available = self.placed | (self.node_load > EPS)
        self.run = {"processed": 0, "dropped": 0, "e2e_sum": 0.0,
                    "requested": np.zeros((self.N, self.S)),
                    "traffic": np.zeros((self.N, self.P)),
                    "counts": np.zeros((self.N, self.S, self.N))}
        for _ in range(self.substeps):
            self.substep()
        return self.reward(), self.features()

    # ------------------------------------------------------------ substep
    def substep(self):
        g, t = self.g, float(self.g)
        run = self.run
        # 1. releases
        for n, s, dr in self.rel_node.pop(g, ()):
            self.node_load[n, s] = max(self.node_load[n, s] - dr, 0.0)
        for e, dr in self.rel_edge.pop(g, ()):
            self.edge_used[e] = max(self.edge_used[e] - dr, 0.0)
        self.sf_available &= self.placed | (self.node_load > EPS)
        # 2. timers
        hop_req, need_proc, depart, gone = [], [], [], []
        for i, f in enumerate(self.flows):
            if f is None or f["phase"] not in (HOP, PROC):
                continue
            f["timer"] -= 1.0
            if f["timer"] > EPS:
                continue
            if f["phase"] == PROC:
                f["pos"] += 1
                f["phase"] = DECIDE
            else:
                f["node"] = f["hop_next"]
                if f["node"] != f["dest"]:
                    hop_req.append(i)               # continue the path
                    continue
                f["e2e"] += f["pend"]
                f["ttl"] -= f["pend"]
                (depart if f["pos"] >= self.S else need_proc).append(i)
        # 3. arrivals into free slots, in slot order
        free = [i for i, f in enumerate(self.flows) if f is None]
        due = [a for a in self.arr[self.cursor:
                                   self.cursor + ARRIVALS_PER_SUBSTEP]
               if a[0] < t + 1.0 - EPS]
        self.peak_live = max(self.peak_live,
                             self.M - len(free) + min(len(due), len(free)))
        for a, slot in zip(due, free):
            self.flows[slot] = {
                "phase": DECIDE, "node": a[1], "pos": 0, "dest": -1,
                "dr": a[2], "dur": a[3], "ttl": a[4], "e2e": 0.0,
                "pend": 0.0, "timer": 0.0, "hop_next": -1}
            self.cursor += 1
        # 4. decisions
        wrr_cells: Dict[tuple, list] = {}
        deciding = [i for i, f in enumerate(self.flows)
                    if f is not None and f["phase"] == DECIDE]
        start_path = []
        for i in deciding:
            f = self.flows[i]
            if f["ttl"] <= EPS:
                gone.append(i)
                continue
            if f["pos"] >= self.S:                  # chain done, no egress
                depart.append(i)
                continue
            wrr_cells.setdefault((f["node"], f["pos"]), []).append(i)
        for (node, pos), members in wrr_cells.items():
            probs = self.schedule[node, 0, pos]
            run["requested"][node, pos] += sum(self.flows[i]["dr"]
                                               for i in members)
            rounds: Dict[int, list] = {}
            for rank, i in enumerate(members):
                rounds.setdefault(min(rank, self.R - 1), []).append(i)
            for r in sorted(rounds):
                counts = run["counts"][node, pos]
                total = counts.sum()
                ratios = counts / total if total > 0 else np.zeros(self.N)
                diffs = np.where(probs > 0, probs - ratios, -1.0)
                choice = int(np.argmax(diffs))
                for i in rounds[r]:
                    self.flows[i]["dest"] = choice
                run["counts"][node, pos, choice] += len(rounds[r])
        # 5. forwarding
        for i in sorted(i for ms in wrr_cells.values() for i in ms):
            f = self.flows[i]
            if f["dest"] == f["node"]:
                need_proc.append(i)
                continue
            pd = self.path(f["node"], f["dest"])
            if f["ttl"] - pd <= EPS:
                f["ttl"] = 0.0
                gone.append(i)
                continue
            f["pend_new"] = pd
            start_path.append(i)
        for i in sorted(hop_req + start_path):      # greedy, slot order
            f = self.flows[i]
            nh = int(self.next_hop[f["node"], f["dest"]]) \
                if f["node"] < self.n and 0 <= f["dest"] < self.n else -1
            e = self.edge_of.get((f["node"], nh), -1)
            if e < 0 or self.edge_used[e] + f["dr"] > \
                    self.edge_cap[e] + EPS:
                gone.append(i)
                continue
            self.edge_used[e] += f["dr"]
            hold = self.edge_delay[e] + f["dur"]
            off = min(max(math.ceil(hold), 1), self.H - 1)
            self.rel_edge.setdefault(g + off, []).append((e, f["dr"]))
            if "pend_new" in f:
                f["pend"] = f.pop("pend_new")
            f["hop_next"] = nh
            f["timer"] = self.edge_delay[e]
            f["phase"] = HOP
        # 6. processing: placement, TTL, node capacity in slot order
        for i in sorted(need_proc):
            f = self.flows[i]
            node, sf = f["node"], self.chain[f["pos"]]
            pdel = self.proc[sf]
            if not self.placed[node, sf]:
                gone.append(i)
                continue
            if f["ttl"] - pdel <= EPS:
                f["ttl"] = 0.0
                gone.append(i)
                continue
            f["e2e"] += pdel
            f["ttl"] -= pdel
            demand = self.node_load[node][self.sf_available[node]].sum() \
                + f["dr"]
            if demand > self.caps[node] + EPS:
                gone.append(i)
                continue
            self.node_load[node, sf] += f["dr"]
            run["traffic"][node, sf] += f["dr"]
            f["timer"] = pdel
            f["phase"] = PROC
            off = min(max(math.ceil(pdel + f["dur"]), 1), self.H - 1)
            self.rel_node.setdefault(g + off, []).append(
                (node, sf, f["dr"]))
        # 7. departures and drops
        for i in depart:
            run["processed"] += 1
            run["e2e_sum"] += self.flows[i]["e2e"]
            self.flows[i] = None
        for i in gone:
            run["dropped"] += 1
            self.flows[i] = None
        self.g += 1

    def path(self, a: int, b: int) -> float:
        if a < self.n and 0 <= b < self.n:
            return float(self.path_delay[a, b])
        return math.inf

    # ------------------------------------------------ reward, observation
    def reward(self) -> np.float32:
        """``prio-flow`` with ``target_success: auto`` in float32, the
        operations in the order the published formula gives them."""
        succ, drop = f32(self.run["processed"]), f32(self.run["dropped"])
        total = succ + drop
        ratio = succ / max(total, f32(1)) if total > 0 else f32(0)
        flow = (succ - drop) / max(total, f32(1)) if total > 0 else f32(0)
        avg = f32(self.run["e2e_sum"]) / max(succ, f32(1)) \
            if succ > 0 else f32(0)
        delay = max(avg, self.min_delay)
        dr = np.clip((self.min_delay - delay) / self.diameter + f32(1),
                     f32(-1), f32(1))
        if ratio == 0:
            dr = f32(-1)
        target = f32(0.9) * self.ewma
        self.ewma = f32(0.5) * ratio + f32(0.5) * self.ewma
        if ratio < target:
            dr = f32(-1)
        return f32(flow + dr)

    def features(self) -> np.ndarray:
        def maxnorm(x):
            x = np.asarray(x, f32)
            return np.clip(x / (x.max() + f32(1e-3)), f32(0), f32(1))

        cols = []
        for name in self.space:
            if name == "ingress_traffic":
                cols.append(maxnorm(self.run["requested"][:, 0]))
            elif name == "node_load":
                usage = self.run["traffic"].sum(-1).astype(f32)
                caps = self.caps.astype(f32)
                util = np.where(caps > 0, usage / np.maximum(caps, f32(1e-30)),
                                f32(1))
                cols.append(maxnorm(np.where(self.node_mask, util, f32(0))))
            else:
                cols.append(maxnorm(np.where(self.node_mask, self.caps, 0)))
        return np.where(self.node_mask[:, None], np.stack(cols, -1), f32(0))


def follow(cfg: dict, net_spec, actions: np.ndarray):
    """Rewards [T] and next-observation node features [T, N, F] of one
    replica under its stored actions, and the most flow slots that were
    in use at once."""
    sim = FlowSim(cfg, list(net_spec.node_caps), list(net_spec.node_types),
                  list(net_spec.edges))
    rewards, feats = [], []
    for a in actions:
        r, x = sim.step(np.asarray(a))
        rewards.append(r)
        feats.append(x)
    return np.asarray(rewards, f32), np.stack(feats), sim.peak_live
