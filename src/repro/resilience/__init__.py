"""repro.resilience — deterministic fault injection, shard supervision and
recovery primitives.

See ``docs/resilience.md`` for the fault model, the recovery line in the
distributed reduction, and the serving degradation contract."""
from .faults import (  # noqa: F401
    SITES,
    CheckpointCorruption,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    TransientFault,
    WireCorruption,
    active_injector,
    backoff_delays,
    corrupt_payload,
    flip_bit,
    inject,
    retry_with_backoff,
)

__all__ = [
    "SITES",
    "CheckpointCorruption",
    "FaultInjector",
    "FaultPlan",
    "FaultSpec",
    "InjectedFault",
    "TransientFault",
    "WireCorruption",
    "active_injector",
    "backoff_delays",
    "corrupt_payload",
    "flip_bit",
    "inject",
    "retry_with_backoff",
]
