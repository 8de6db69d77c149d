"""YAML loaders for the five config namespaces.

Mirrors the reference's loaders — get_config/get_sfc/get_sf
(coordsim/reader/reader.py:37-111), agent-config load+validate
(src/rlsp/agents/main.py:249-276), scheduler load
(src/rlsp/agents/main.py:73-75) — but parses into the frozen dataclasses of
``gsc_tpu.config.schema``.  Accepts the reference's YAML key spelling so
existing config files keep working (e.g. ``GNN_features`` -> gnn_features).
"""
from __future__ import annotations

from typing import Any, Dict

import yaml

from .schema import (
    AgentConfig,
    MMPPState,
    SchedulerConfig,
    ServiceConfig,
    ServiceFunction,
    SimConfig,
)


def _load_yaml(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return yaml.safe_load(f)


def load_service(path: str,
                 resource_functions_path: str = None) -> ServiceConfig:
    """Parse an SFC/SF catalog yaml (reference: reader.py:47-111).

    ``resource_functions_path`` loads user resource-function plugins first
    (registry.load_resource_function_plugins — the explicit analogue of
    the reference's per-SF dynamic imports, reader.py:60-72).  Like the
    reference, an SF naming an unknown function falls back to "default"
    with a warning rather than failing the run (reader.py:99-104)."""
    import logging

    from .registry import has_resource_function, load_resource_function_plugins

    if resource_functions_path:
        load_resource_function_plugins(resource_functions_path)
    data = _load_yaml(path)
    sfc_list = {name: tuple(chain) for name, chain in data["sfc_list"].items()}
    sf_list = {}
    for name, details in data["sf_list"].items():
        details = details or {}
        rf_id = details.get("resource_function_id", "default")
        if not has_resource_function(rf_id):
            logging.getLogger("gsc_tpu.config").warning(
                "SF %s names unknown resource function %r (pass "
                "--resource-functions-path to load plugins); using default",
                name, rf_id)
            rf_id = "default"
        sf_list[name] = ServiceFunction(
            name=name,
            processing_delay_mean=float(details.get("processing_delay_mean", 1.0)),
            processing_delay_stdev=float(details.get("processing_delay_stdev", 1.0)),
            startup_delay=float(details.get("startup_delay", 0.0)),
            resource_function_id=rf_id,
        )
    return ServiceConfig(sfc_list=sfc_list, sf_list=sf_list)


def load_sim(path: str, **overrides) -> SimConfig:
    """Parse a simulator config yaml (reference: simulatorparams.py:13-131)."""
    cfg = _load_yaml(path)
    kw: Dict[str, Any] = {}
    det = cfg.get("deterministic", None)
    if det is not None:
        kw["deterministic_arrival"] = bool(det)
        kw["deterministic_size"] = bool(det)
    # deterministic_arrival/size override 'deterministic' (simulatorparams.py:88-92)
    for key in ("deterministic_arrival", "deterministic_size"):
        if key in cfg:
            kw[key] = bool(cfg[key])
    if "deterministic_arrival" not in kw or "deterministic_size" not in kw:
        raise ValueError(
            "'deterministic_arrival' or 'deterministic_size' are not set in simulator config."
        )  # simulatorparams.py:93-94
    for key in ("inter_arrival_mean", "flow_dr_mean", "flow_dr_stdev",
                "flow_size_shape", "run_duration", "vnf_timeout", "dt"):
        if key in cfg:
            kw[key] = float(cfg[key])
    if "ttl_choices" in cfg:
        kw["ttl_choices"] = tuple(float(t) for t in cfg["ttl_choices"])
    else:
        raise ValueError("TTL must be set in config file")  # simulatorparams.py:41
    if "force_link_cap" in cfg:
        kw["force_link_cap"] = float(cfg["force_link_cap"])
    if "force_node_cap" in cfg:
        kw["force_node_cap"] = tuple(float(c) for c in cfg["force_node_cap"])
    if cfg.get("use_states"):
        kw["use_states"] = True
        kw["init_state"] = cfg["init_state"]
        kw["rand_init_state"] = bool(cfg.get("rand_init_state", False))
        kw["states"] = tuple(
            MMPPState(name=k, inter_arr_mean=float(v["inter_arr_mean"]),
                      switch_p=float(v["switch_p"]))
            for k, v in cfg["states"].items()
        )
    if "trace_path" in cfg:
        kw["trace_path"] = cfg["trace_path"]
    if "prediction" in cfg:
        kw["prediction"] = bool(cfg["prediction"])
    for key in ("max_flows", "release_horizon",
                "admission_iters", "wrr_rank_levels", "scan_unroll"):
        if key in cfg:
            kw[key] = int(cfg[key])
    if "controller_class" in cfg:
        kw["controller"] = {"DurationController": "duration",
                            "FlowController": "per_flow"}.get(
            cfg["controller_class"], cfg["controller_class"])
    if "controller" in cfg:
        # the rebuild's native spelling; silently ignoring it would make
        # `controller: per_flow` run the duration controller
        if "controller_class" in cfg and kw["controller"] != cfg["controller"]:
            raise ValueError(
                f"conflicting controller_class={cfg['controller_class']!r} "
                f"and controller={cfg['controller']!r} in {path}")
        kw["controller"] = cfg["controller"]
    kw.update(overrides)
    return SimConfig(**kw)


# Reference agent-yaml key -> AgentConfig field.
_AGENT_KEYMAP = {
    "GNN_features": "gnn_features",
    "GNN_num_layers": "gnn_num_layers",
    "GNN_num_iter": "gnn_num_iter",
    "GNN_aggr": "gnn_aggr",
}


def load_agent(path: str, **overrides) -> AgentConfig:
    """Parse an agent config yaml (reference: sample_agent.yaml +
    src/rlsp/agents/main.py:249-276 validation)."""
    cfg = _load_yaml(path)
    kw: Dict[str, Any] = {}
    fields = AgentConfig.__dataclass_fields__
    for key, val in cfg.items():
        key = _AGENT_KEYMAP.get(key, key)
        if key not in fields:
            continue  # tolerate unknown keys like the reference
        if isinstance(val, list):
            val = tuple(val)
        kw[key] = val
    kw.update(overrides)
    return AgentConfig(**kw)


def _resolve_network_path(p: str, anchor: str) -> str:
    """Resolve a scheduler network path the way the reference experiment
    layout expects: verbatim (cwd-relative / absolute) first, then against
    each ancestor of the scheduler yaml.  Reference scheduler files carry
    repo-root-relative paths like ``configs/networks/...`` (scheduler.yaml
    sits at configs/config/), which only resolve when running FROM the
    repo root — the ancestor walk makes the same file drop-in from any
    working directory."""
    import os

    if os.path.isabs(p) or os.path.exists(p):
        return p
    d = os.path.dirname(os.path.abspath(anchor))
    while True:
        cand = os.path.join(d, p)
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(d)
        if parent == d:
            return p  # unresolvable: let load_topology raise with the raw path
        d = parent


def load_scheduler(path: str) -> SchedulerConfig:
    """Parse a scheduler yaml (reference: configs/config/scheduler.yaml)."""
    cfg = _load_yaml(path)
    return SchedulerConfig(
        training_network_files=tuple(
            _resolve_network_path(p, path)
            for p in cfg["training_network_files"]),
        inference_network=_resolve_network_path(cfg["inference_network"],
                                                path),
        period=int(cfg.get("period", 10)),
    )
