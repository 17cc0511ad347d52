"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes (sharding propagated by XLA)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """(data, model) mesh over every device of this host; the data axis
    takes what the model axis leaves."""
    n = len(jax.devices())
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(f"model_parallel={model_parallel} does not divide "
                         f"the {n} devices of this host")
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
