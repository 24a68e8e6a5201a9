import jax
from jax.sharding import AxisType

from repro.distributed.sharding import (
    param_sharding,
    batch_sharding,
    lm_param_spec,
    dp_axes_of,
)
from repro.distributed.hlo import collective_bytes

__all__ = [
    "make_mesh",
    "param_sharding",
    "batch_sharding",
    "lm_param_spec",
    "dp_axes_of",
    "collective_bytes",
]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axis types: the sharding rules here rely
    on GSPMD propagation, and ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
