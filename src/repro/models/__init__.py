"""Model zoo: the assigned architectures as composable JAX modules."""
from repro.models.base import (  # noqa: F401
    ArchConfig, ParamSpec, abstract_params, init_params, param_shardings,
)
from repro.models import registry  # noqa: F401
