"""Typed configuration schema for the five config namespaces.

The reference spreads configuration over five YAML namespaces — agent
(configs/config/agent/sample_agent.yaml), simulator
(configs/config/simulator/sample_config.yaml), service functions
(configs/service_functions/abc.yaml), scheduler (configs/config/scheduler.yaml)
and a GraphML network — validated ad hoc in src/rlsp/agents/main.py:249-276
and coordsim/reader/reader.py:74-111, with component implementations selected
by ``eval()`` of class-name strings (coordsim/simulation/simulatorparams.py:29-38,
siminterface/simulator.py:130).

Here every namespace is a frozen dataclass of plain Python scalars/tuples so
configs are hashable and can be closed over by ``jax.jit``.  Component
selection goes through a string->callable registry (``gsc_tpu.config.registry``)
instead of ``eval``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Mapping, Optional, Tuple


class FrozenMap(Mapping):
    """Immutable, hashable mapping (insertion-ordered) so configs that carry
    mappings stay usable as static jit arguments."""

    __slots__ = ("_items", "_lookup")

    def __init__(self, data):
        items = tuple(data.items()) if isinstance(data, Mapping) else tuple(data)
        object.__setattr__(self, "_items", items)
        object.__setattr__(self, "_lookup", dict(items))

    def __getitem__(self, key):
        return self._lookup[key]

    def __iter__(self):
        return (k for k, _ in self._items)

    def __len__(self):
        return len(self._items)

    def __hash__(self):
        return hash(self._items)

    def __eq__(self, other):
        if isinstance(other, FrozenMap):
            return self._items == other._items
        if isinstance(other, Mapping):
            return dict(self._items) == dict(other)
        return NotImplemented

    def __repr__(self):
        return f"FrozenMap({dict(self._items)!r})"

SUPPORTED_OBJECTIVES = ("prio-flow", "soft-deadline", "soft-deadline-exp", "weighted")
# Dtypes a mixed-precision compute/replay slot may take.  float16 is
# deliberately absent: bf16 shares f32's exponent range so the policy needs
# no loss scaling — the property the whole PrecisionPolicy design leans on.
_COMPUTE_DTYPES = ("float32", "bfloat16")


@dataclass(frozen=True)
class PrecisionPolicy:
    """End-to-end dtype policy for the training stack.

    The policy separates three concerns per module family:

    - ``param_dtype``: the MASTER storage dtype of network parameters and
      optimizer state.  Always float32 — Polyak target updates at
      tau=1e-4 (AgentConfig.target_model_update) underflow to no-ops in
      bf16's 8-bit mantissa, and Adam's second-moment EMA degrades the
      same way, so masters never leave f32 (the Podracer/MindSpeed-RL
      "mixed compute, full-precision state" recipe).
    - ``gnn_compute`` / ``mlp_compute``: the activation/matmul dtype of
      the GATv2 embedder and the actor/critic Dense stacks.  bf16 halves
      the dominant [B, N, N, F] attention intermediate and runs the MXU
      at ~2x f32 throughput; every contraction still ACCUMULATES in f32
      via ``preferred_element_type`` and the attention softmax runs on
      f32 logits.
    - ``replay_dtype``: storage dtype of replay obs/action leaves
      (agents/buffer.py) — halves the largest HBM resident.  Rewards and
      done flags always stay f32 so TD-target scale survives.

    Network OUTPUTS (actions, Q-values) are always f32: exploration
    noise, TD targets and target-network soft updates run at full
    precision regardless of the compute dtype.  ``f32`` everywhere is the
    default and is bit-identical to a stack with no dtype policy at all
    (the pre-policy code paths are taken verbatim when a slot is f32).
    """

    name: str = "f32"
    param_dtype: str = "float32"
    gnn_compute: str = "float32"
    mlp_compute: str = "float32"
    accum_dtype: str = "float32"
    output_dtype: str = "float32"
    replay_dtype: str = "float32"

    def __post_init__(self):
        for slot in ("param_dtype", "accum_dtype", "output_dtype"):
            if getattr(self, slot) != "float32":
                raise ValueError(
                    f"{slot} must be float32 (f32 master params/accumulators"
                    f"/outputs are the policy contract), got "
                    f"{getattr(self, slot)!r}")
        for slot in ("gnn_compute", "mlp_compute", "replay_dtype"):
            if getattr(self, slot) not in _COMPUTE_DTYPES:
                raise ValueError(
                    f"{slot} must be one of {_COMPUTE_DTYPES}, got "
                    f"{getattr(self, slot)!r}")

    # -- consumers key on None = "take the legacy exact-f32 code path" --
    @property
    def gnn_dtype(self) -> Optional[str]:
        return None if self.gnn_compute == "float32" else self.gnn_compute

    @property
    def mlp_dtype(self) -> Optional[str]:
        return None if self.mlp_compute == "float32" else self.mlp_compute

    @property
    def replay_cast_dtype(self) -> Optional[str]:
        return None if self.replay_dtype == "float32" else self.replay_dtype

    @property
    def mixed(self) -> bool:
        return any(getattr(self, s) != "float32"
                   for s in ("gnn_compute", "mlp_compute", "replay_dtype"))


# Named policies selectable via AgentConfig.precision / `cli train
# --precision`.  "f32" is bit-identical to the pre-policy stack; "bf16" is
# the TPU mixed-precision recipe.
PRECISION_POLICIES = {
    "f32": PrecisionPolicy(name="f32"),
    "bf16": PrecisionPolicy(name="bf16", gnn_compute="bfloat16",
                            mlp_compute="bfloat16",
                            replay_dtype="bfloat16"),
}


def precision_policy(name: str) -> PrecisionPolicy:
    """Resolve a policy name (AgentConfig.precision) to its PrecisionPolicy."""
    try:
        return PRECISION_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown precision {name!r} (expected one of "
            f"{tuple(PRECISION_POLICIES)})") from None

# Observation components supported by the env (reference:
# src/rlsp/envs/simulator_wrapper.py:178-235 builds these three vectors).
SUPPORTED_OBSERVATIONS = ("ingress_traffic", "node_load", "node_cap")
DROP_REASONS = ("TTL", "DECISION", "LINK_CAP", "NODE_CAP")


@dataclass(frozen=True)
class ServiceFunction:
    """One SF's properties (reference: coordsim/reader/reader.py:74-111)."""

    name: str
    processing_delay_mean: float = 1.0
    processing_delay_stdev: float = 1.0
    startup_delay: float = 0.0
    # Registry key of the resource demand function load -> demanded capacity
    # (reference: dynamically imported per-SF ``resource_function``,
    # coordsim/reader/reader.py:60-72; default is identity, reader.py:86-87).
    resource_function_id: str = "default"


@dataclass(frozen=True)
class ServiceConfig:
    """SFC catalog: chains of SFs (reference: configs/service_functions/abc.yaml)."""

    # sfc name -> ordered tuple of SF names
    sfc_list: Mapping[str, Tuple[str, ...]]
    sf_list: Mapping[str, ServiceFunction]

    def __post_init__(self):
        # normalize to hashable mappings (dataclass is frozen -> object.__setattr__)
        object.__setattr__(self, "sfc_list", FrozenMap(self.sfc_list))
        object.__setattr__(self, "sf_list", FrozenMap(self.sf_list))
        for sfc, chain in self.sfc_list.items():
            for sf in chain:
                if sf not in self.sf_list:
                    raise ValueError(f"SFC {sfc!r} references unknown SF {sf!r}")

    @property
    def num_sfcs(self) -> int:
        return len(self.sfc_list)

    @property
    def max_chain_len(self) -> int:
        return max(len(c) for c in self.sfc_list.values())

    @property
    def sf_names(self) -> Tuple[str, ...]:
        return tuple(self.sf_list.keys())

    @property
    def sfc_names(self) -> Tuple[str, ...]:
        return tuple(self.sfc_list.keys())


@dataclass(frozen=True)
class MMPPState:
    """One state of the two-state Markov-modulated Poisson arrival process
    (reference: coordsim/simulation/simulatorparams.py:100-121, 143-176)."""

    name: str
    inter_arr_mean: float
    switch_p: float


@dataclass(frozen=True)
class SimConfig:
    """Simulator/traffic configuration
    (reference: configs/config/simulator/sample_config.yaml +
    coordsim/simulation/simulatorparams.py:13-131).
    """

    inter_arrival_mean: float = 10.0
    deterministic_arrival: bool = True
    flow_dr_mean: float = 1.0
    flow_dr_stdev: float = 0.0
    flow_size_shape: float = 0.001
    deterministic_size: bool = True
    run_duration: float = 100.0
    ttl_choices: Tuple[float, ...] = (100.0,)
    vnf_timeout: float = 100.0

    # Capacity overrides (reference: coordsim/reader/builders.py:9-26)
    force_link_cap: Optional[float] = None
    force_node_cap: Optional[Tuple[float, float]] = None

    # MMPP two-state arrival model (reference: simulatorparams.py:100-121)
    use_states: bool = False
    init_state: Optional[str] = None
    rand_init_state: bool = False
    states: Tuple[MMPPState, ...] = ()

    # Trace-driven traffic (reference: coordsim/trace_processor/trace_processor.py)
    trace_path: Optional[str] = None

    # Traffic prediction: observations show *upcoming* ingress traffic
    # instead of the last interval's (reference 'prediction' flag plumbing,
    # siminterface/simulator.py:47 + traffic_predictor.py:22-56)
    prediction: bool = False

    # Control granularity (replaces the eval()-resolved controller_class,
    # siminterface/simulator.py:130): "duration" = one (placement, schedule)
    # action per interval (DurationController); "per_flow" = per-flow
    # destination decisions with place-on-decision + idle-VNF GC
    # (FlowController).  The external decision-maker semantics
    # (external_decision_maker.py) are the per_flow path's ext_decisions.
    controller: str = "duration"

    # --- TPU engine parameters (new; no reference analogue) ---
    # Substep quantum in ms for the fixed-step lax.scan engine.  The reference
    # engine is continuous-time event-driven (SimPy); with default configs all
    # delays are integer ms so dt=1.0 reproduces it exactly.
    dt: float = 1.0
    # Max concurrently active flows per replica (flow-table slots).
    max_flows: int = 128
    # Ring-buffer horizon (in substeps) for delayed capacity release.
    release_horizon: int = 256
    # Iterations of the monotone greedy-admission refinement (within-substep
    # sequential capacity-admission semantics).
    admission_iters: int = 3
    # Rank levels for exact sequential WRR among same-substep collisions.
    wrr_rank_levels: int = 4
    # lax.scan unroll factor for the substep loop: >1 trades compile time
    # (and a run_duration/dt divisibility requirement) for less scan
    # overhead on a substep made of many small fusions.
    scan_unroll: int = 1

    def __post_init__(self):
        if self.use_states and len(self.states) != 2:
            raise ValueError("MMPP model requires exactly 2 states")
        if self.run_duration <= 0 or self.dt <= 0:
            raise ValueError("run_duration and dt must be positive")
        if not self.ttl_choices:
            raise ValueError("TTL must be set in config file")  # simulatorparams.py:41
        if self.controller not in ("duration", "per_flow"):
            raise ValueError(
                f"unknown controller {self.controller!r} (expected "
                "'duration' or 'per_flow'; reference spellings "
                "DurationController/FlowController are mapped by the "
                "loader)")
        if self.scan_unroll < 1:
            raise ValueError("scan_unroll must be >= 1")

    @property
    def substeps_per_run(self) -> int:
        n = round(self.run_duration / self.dt)
        if abs(n * self.dt - self.run_duration) > 1e-9:
            raise ValueError("run_duration must be a multiple of dt")
        return int(n)


@dataclass(frozen=True)
class TorsoConfig:
    """A looped decoder stack between the GNN embedder and the heads of
    actor and critic (``AgentConfig.torso``; models/torso.py): ``num_hidden_layers``
    sandwich-normed attention + gated-MLP layers applied
    ``total_ut_steps`` times on the same weights, with one exit gate per
    graph.  Key names are the published ``config.json``'s (Ouro,
    arXiv 2510.25741); ``exit_entropy_beta`` weighs the entropy term of
    the exit objective and is not in that file.  Frozen and hashable: it
    rides on ``AgentConfig``, a static argument of every jitted entry
    point."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    num_hidden_layers: int
    total_ut_steps: int
    early_exit_threshold: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    hidden_act: str = "silu"
    exit_entropy_beta: float = 0.05

    def __post_init__(self):
        for key in ("hidden_size", "num_attention_heads",
                    "num_key_value_heads", "head_dim", "intermediate_size",
                    "num_hidden_layers", "total_ut_steps"):
            if int(getattr(self, key)) < 1:
                raise ValueError(f"torso.{key} must be >= 1")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("torso.num_attention_heads must be a multiple "
                             "of torso.num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("torso.head_dim must be even (rotary pairs)")
        if self.hidden_act != "silu":
            raise ValueError(f"unsupported torso.hidden_act "
                             f"{self.hidden_act!r} (only silu)")
        if not 0.0 < self.early_exit_threshold <= 1.0:
            raise ValueError("torso.early_exit_threshold must be in (0, 1]")

    @classmethod
    def from_mapping(cls, doc: Mapping) -> "TorsoConfig":
        """From a config file's nested ``torso`` mapping; a key this
        class lacks is refused (a misspelt width would otherwise run as
        its default)."""
        fields = cls.__dataclass_fields__
        unknown = sorted(set(doc) - set(fields))
        if unknown:
            raise ValueError(f"unknown torso key(s) {unknown} (known: "
                             f"{sorted(fields)})")
        kinds = {"int": int, "float": float, "str": str}   # annotations
        return cls(**{k: kinds[fields[k].type](v) for k, v in doc.items()})


@dataclass(frozen=True)
class AgentConfig:
    """Agent/learning configuration
    (reference: configs/config/agent/sample_agent.yaml, validated in
    src/rlsp/agents/main.py:249-276).
    """

    observation_space: Tuple[str, ...] = ("ingress_traffic", "node_load", "node_cap")
    # (the reference also parses link_observation_space, but its only
    # consumer is commented out, environment_limits.py:88 — not carried)
    graph_mode: bool = True
    shuffle_nodes: bool = False
    episode_steps: int = 200
    agent_type: str = "DDPG"

    # GNN (reference: sample_agent.yaml:29-32, models.py:10-53)
    gnn_features: int = 22
    gnn_num_layers: int = 2
    gnn_num_iter: int = 2
    gnn_aggr: str = "mean"
    # GNN embedder implementation: "dense" (XLA-fused masked dense
    # attention) or "pallas" (fused TPU kernel, gsc_tpu/ops/pallas_gat.py;
    # interpret-mode on CPU).  New key — the reference's torch-geometric
    # GATv2 has no such switch.
    gnn_impl: str = "dense"
    actor_hidden_layer_nodes: Tuple[int, ...] = (256,)
    critic_hidden_layer_nodes: Tuple[int, ...] = (64,)
    # Factored (per-node bilinear) action head for large scheduling
    # tensors.  None = automatic: enabled in graph mode when the action
    # dim crosses models/nets.py:FACTORED_HEAD_THRESHOLD (the monolithic
    # Dense output layer OOMs one chip near rung-5 padding).  New keys —
    # the reference's monolithic head (models.py:97-153) has no analogue.
    factored_head: Optional[bool] = None
    factored_key_dim: int = 32

    # objective / reward (reference: gym_env.py:300-380)
    objective: str = "weighted"
    flow_weight: float = 1.0
    delay_weight: float = 0.0
    node_weight: float = 0.0
    instance_weight: float = 0.0
    target_success: float | str = "auto"
    soft_deadline: float = 10.0
    dropoff: float = 10.0

    # replay / exploration / optimization (reference: sample_agent.yaml:38-65)
    mem_limit: int = 10000
    rand_mu: float = 0.0
    rand_sigma: float = 0.3
    # single warmup horizon: the reference only ever consumes
    # nb_steps_warmup_critic (simple_ddpg.py:183, 308); the *_actor twin in
    # its sample yaml is dead and not carried
    nb_steps_warmup_critic: int = 200
    gamma: float = 0.99
    target_model_update: float = 1e-4
    learning_rate: float = 1e-3
    batch_size: int = 100
    # gradient steps per end-of-episode learn burst; None = episode_steps
    # (the reference's train-at-episode-end schedule, simple_ddpg.py:
    # 307-325).  A sweep knob: large-B replica runs gather B x
    # episode_steps transitions per episode, so the reference's burst
    # length under-trains relative to data collected.
    learn_steps: Optional[int] = None

    # action post-processing (reference: simple_ddpg.py:130-131)
    schedule_threshold: float = 0.1

    # Precision policy name (PRECISION_POLICIES): "f32" (default,
    # bit-identical to the dtype-unaware stack) or "bf16" (mixed-precision
    # compute + replay with f32 master params/optimizer state).  New key —
    # the reference is implicitly f32 end to end.
    precision: str = "f32"

    # Optional looped decoder stack between the embedder and the heads of
    # actor and critic (TorsoConfig; a mapping is parsed on construction).
    # None = the networks as they were.  New key.
    torso: Optional[TorsoConfig] = None

    def __post_init__(self):
        if self.torso is not None and not isinstance(self.torso, TorsoConfig):
            object.__setattr__(self, "torso",
                               TorsoConfig.from_mapping(self.torso))
        if self.torso is not None and not self.graph_mode:
            raise ValueError("torso needs graph_mode (its tokens are the "
                             "network's nodes)")
        # the reference's agent_type dispatch (main.py:374-381) is broken
        # upstream (SAC_Agent is never defined); here unknown types fail fast
        if self.agent_type != "DDPG":
            raise ValueError(
                f"unsupported agent_type {self.agent_type!r} (only DDPG)")
        if self.gnn_num_layers < 1 or self.gnn_num_iter < 1:
            raise ValueError("gnn_num_layers and gnn_num_iter must be >= 1")
        if self.gnn_impl not in ("dense", "pallas"):
            raise ValueError(f"unknown gnn_impl {self.gnn_impl!r}")
        if self.objective not in SUPPORTED_OBJECTIVES:
            raise ValueError(
                f"Unexpected objective {self.objective}. Must be in {SUPPORTED_OBJECTIVES}."
            )
        for obs in self.observation_space:
            if obs not in SUPPORTED_OBSERVATIONS:
                raise ValueError(f"Unsupported observation component {obs!r}")
        if self.objective == "prio-flow" and self.target_success != "auto":
            if not 0 <= float(self.target_success) <= 1:
                raise ValueError("target_success must be in [0,1] or 'auto'")
        if self.learn_steps is not None and self.learn_steps < 1:
            # 0 would silently run zero gradient steps per learn burst;
            # use None (= episode_steps) for the reference schedule
            raise ValueError("learn_steps must be >= 1 (or None)")
        if self.precision not in PRECISION_POLICIES:
            raise ValueError(
                f"unknown precision {self.precision!r} (expected one of "
                f"{tuple(PRECISION_POLICIES)})")

    @property
    def precision_policy(self) -> PrecisionPolicy:
        """The resolved dtype policy (models/agents consume this)."""
        return PRECISION_POLICIES[self.precision]


@dataclass(frozen=True)
class SchedulerConfig:
    """Topology schedule across training (reference: configs/config/scheduler.yaml,
    consumed by src/rlsp/envs/gym_env.py:103-128)."""

    training_network_files: Tuple[str, ...]
    inference_network: str
    period: int = 10

    def __post_init__(self):
        if not self.training_network_files:
            raise ValueError("training_network_files must not be empty")
        if self.period <= 0:
            raise ValueError("period must be positive")


@dataclass(frozen=True)
class EnvLimits:
    """Fixed padded dimensions enabling cross-topology generalization
    (reference: src/rlsp/envs/environment_limits.py:9-106 and the hard-coded
    24-node/37-edge limits at gym_env.py:59-66)."""

    max_nodes: int = 24
    max_edges: int = 37
    num_sfcs: int = 1
    # max chain length — sizes the schedule tensor's SF-POSITION axis
    max_sfs: int = 3
    # distinct SFs in the catalog — sizes all per-(node, SF-id) state
    # (placement, load, proc tables).  None = max_sfs (single-chain configs,
    # where position and id coincide).  A mixed catalog (e.g. abc + de)
    # needs the two axes separated: chain positions stay <= max_sfs while
    # SF ids run over the whole pool.
    num_sfs: Optional[int] = None

    @property
    def sf_pool(self) -> int:
        return self.num_sfs if self.num_sfs is not None else self.max_sfs

    @property
    def scheduling_shape(self) -> Tuple[int, int, int, int]:
        # (src node, sfc, sf, dst node) — environment_limits.py:44-51
        return (self.max_nodes, self.num_sfcs, self.max_sfs, self.max_nodes)

    @property
    def action_dim(self) -> int:
        n = 1
        for s in self.scheduling_shape:
            n *= s
        return n

    @classmethod
    def for_service(cls, service: ServiceConfig, max_nodes: int = 24,
                    max_edges: int = 37) -> "EnvLimits":
        return cls(max_nodes=max_nodes, max_edges=max_edges,
                   num_sfcs=service.num_sfcs, max_sfs=service.max_chain_len,
                   num_sfs=len(service.sf_list))


def replace(cfg, **kw):
    """Convenience dataclasses.replace passthrough."""
    return dataclasses.replace(cfg, **kw)
