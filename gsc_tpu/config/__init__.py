from .schema import (
    AgentConfig,
    EnvLimits,
    MMPPState,
    PRECISION_POLICIES,
    PrecisionPolicy,
    SchedulerConfig,
    ServiceConfig,
    ServiceFunction,
    SimConfig,
    TorsoConfig,
    SUPPORTED_OBJECTIVES,
    SUPPORTED_OBSERVATIONS,
    DROP_REASONS,
    precision_policy,
)
from .loader import load_agent, load_scheduler, load_service, load_sim
from .registry import get_resource_function, register_resource_function

__all__ = [
    "AgentConfig", "EnvLimits", "MMPPState", "PrecisionPolicy",
    "PRECISION_POLICIES", "precision_policy", "SchedulerConfig",
    "ServiceConfig", "ServiceFunction", "SimConfig", "TorsoConfig",
    "SUPPORTED_OBJECTIVES", "SUPPORTED_OBSERVATIONS", "DROP_REASONS",
    "load_agent", "load_scheduler", "load_service", "load_sim",
    "get_resource_function", "register_resource_function",
]
