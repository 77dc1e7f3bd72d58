from jax import make_mesh
from jax.sharding import AxisType

from repro.sharding.ctx import (
    CLIENTS_AXIS,
    axis_size,
    clients_sharding,
    current_mesh,
    replicated_sharding,
    set_mesh,
    shard,
    shard_residual,
    use_mesh,
)
from repro.sharding.rules import param_specs, spec_for_param

__all__ = [
    "CLIENTS_AXIS",
    "AxisType",
    "axis_size",
    "clients_sharding",
    "current_mesh",
    "make_mesh",
    "replicated_sharding",
    "set_mesh",
    "shard",
    "shard_residual",
    "use_mesh",
    "param_specs",
    "spec_for_param",
]
