"""Every config field must have a real consumer — no parsed-but-dead keys.

The reference carries config keys whose consumers are commented out or
missing (link_observation_space: environment_limits.py:88; agent_type's
SAC dispatch: main.py:374-381); this rebuild's rule is wired-or-deleted.
The test introspects each config dataclass and requires an attribute
access (``.field`` or ``["field"]``-style via getattr chains) somewhere in
``gsc_tpu`` OUTSIDE the config package itself, so schema defaults and YAML
parsing don't count as consumption.
"""
import dataclasses
import os
import re

import pytest

from gsc_tpu.config import schema

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "gsc_tpu")


def _package_source():
    chunks = []
    for root, _dirs, files in os.walk(PKG):
        if os.path.sep + "config" in root:
            continue
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    chunks.append(fh.read())
    # the CLI and graft entry also consume config fields
    with open(os.path.normpath(
            os.path.join(PKG, "../__graft_entry__.py"))) as fh:
        chunks.append(fh.read())
    with open(os.path.join(PKG, "cli.py")) as fh:
        chunks.append(fh.read())
    return "\n".join(chunks)


# fields consumed structurally rather than via attribute reads
ALLOWED_INDIRECT = {
    # ServiceFunction.name keys the FrozenMap; resource_function_id goes
    # through the registry at ServiceTables.build (engine.py)
    ("ServiceFunction", "name"),
    # validated (fail-fast) in AgentConfig.__post_init__, replacing the
    # reference's broken SAC dispatch (main.py:374-381)
    ("AgentConfig", "agent_type"),
}


@pytest.mark.parametrize("cls", [
    schema.ServiceFunction, schema.ServiceConfig, schema.MMPPState,
    schema.SimConfig, schema.AgentConfig, schema.SchedulerConfig,
    schema.EnvLimits,
])
def test_every_field_has_a_consumer(cls):
    src = _package_source()
    dead = []
    for f in dataclasses.fields(cls):
        if (cls.__name__, f.name) in ALLOWED_INDIRECT:
            continue
        if not re.search(rf"\.{re.escape(f.name)}\b", src):
            dead.append(f.name)
    assert not dead, (
        f"{cls.__name__} fields with no consumer outside gsc_tpu/config: "
        f"{dead} — wire them or delete them")


@pytest.fixture
def _registry_snapshot():
    """Plugin registration is process-global; snapshot/restore so no other
    test's unknown-id/fallback assertions depend on execution order."""
    from gsc_tpu.config import registry

    saved = dict(registry._RESOURCE_FUNCTIONS)
    yield
    registry._RESOURCE_FUNCTIONS.clear()
    registry._RESOURCE_FUNCTIONS.update(saved)


def test_resource_function_plugins(tmp_path, caplog, _registry_snapshot):
    """User resource-function plugins load from a path and resolve in the
    service catalog; unknown ids fall back to default with a warning
    (reference: reader.py:60-72, 99-104) — and a YAML naming a plugin
    function drives a real simulator run end-to-end."""
    import logging

    import yaml

    from gsc_tpu.config.loader import load_service
    from gsc_tpu.config.registry import (get_resource_function,
                                         load_resource_function_plugins)

    plug = tmp_path / "plugins"
    plug.mkdir()
    # reference-style: bare resource_function(load), registered by stem
    (plug / "quadratic.py").write_text(
        "def resource_function(load):\n    return load * load\n")
    # explicit-style: module registers itself
    (plug / "explicit.py").write_text(
        "from gsc_tpu.config.registry import register_resource_function\n"
        "@register_resource_function('capped')\n"
        "def _capped(load):\n"
        "    import jax.numpy as jnp\n"
        "    return jnp.minimum(load, 3.0)\n")
    names = load_resource_function_plugins(str(plug))
    assert set(names) >= {"quadratic", "capped"}
    assert get_resource_function("quadratic")(3.0) == 9.0

    svc_yaml = tmp_path / "svc.yaml"
    yaml.safe_dump({
        "sfc_list": {"sfc_1": ["a"]},
        "sf_list": {"a": {"processing_delay_mean": 5.0,
                          "processing_delay_stdev": 0.0,
                          "resource_function_id": "quadratic"}},
    }, open(svc_yaml, "w"))
    svc = load_service(str(svc_yaml), resource_functions_path=str(plug))
    assert svc.sf_list["a"].resource_function_id == "quadratic"

    # the plugin function reaches the jitted node-admission path
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gsc_tpu.config.schema import EnvLimits, SimConfig
    from gsc_tpu.sim.engine import SimEngine
    from gsc_tpu.sim.traffic import generate_traffic
    from gsc_tpu.topology.compiler import NetworkSpec, compile_topology

    topo = compile_topology(NetworkSpec(
        node_caps=[10.0, 10.0], node_types=["Ingress", "Normal"],
        edges=[(0, 1, 100.0, 3.0)]), max_nodes=4, max_edges=4)
    cfg = SimConfig(ttl_choices=(100.0,), max_flows=16)
    limits = EnvLimits(max_nodes=4, max_edges=4, num_sfcs=1, max_sfs=1)
    engine = SimEngine(svc, cfg, limits)
    sched = np.zeros(limits.scheduling_shape, np.float32)
    nm = np.asarray(topo.node_mask)
    sched[:, :, :, nm] = 1.0 / nm.sum()
    placement = jnp.asarray(np.broadcast_to(nm[:, None], (4, 1)).copy())
    traffic = generate_traffic(cfg, svc, topo, 2, seed=0)
    state = engine.init(jax.random.PRNGKey(0), topo)
    state, metrics = engine.apply(state, topo, traffic,
                                  jnp.asarray(sched), placement)
    assert int(metrics.generated) > 0

    # unknown id -> default with a warning, not a failure
    yaml.safe_dump({
        "sfc_list": {"sfc_1": ["a"]},
        "sf_list": {"a": {"resource_function_id": "no_such_fn"}},
    }, open(svc_yaml, "w"))
    with caplog.at_level(logging.WARNING, logger="gsc_tpu.config"):
        svc2 = load_service(str(svc_yaml))
    assert svc2.sf_list["a"].resource_function_id == "default"
    assert any("unknown resource function" in r.message.lower()
               for r in caplog.records)
