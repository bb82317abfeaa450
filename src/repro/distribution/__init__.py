from repro.distribution.sharding import (
    batch_sharding,
    named,
    param_shardings,
    population_axes,
    population_sharding,
    replicated_sharding,
    spec_for_param,
)

__all__ = [
    "batch_sharding",
    "named",
    "param_shardings",
    "population_axes",
    "population_sharding",
    "replicated_sharding",
    "spec_for_param",
]
