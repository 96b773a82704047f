"""repro.dist — sharding rules, compressed collectives, pipeline parallelism
and fault tolerance for the serving/training stack."""

from . import collectives, fault, pipeline, sharding  # noqa: F401
from .fault import FaultConfig, run_resilient  # noqa: F401
from .sharding import (  # noqa: F401
    PRESETS,
    TP_ROLES,
    active_tp,
    constrain_like_params,
    logical_axes_for,
    param_specs,
    shard,
    spec_for,
    tree_specs,
    use_rules,
    use_tp,
)
