"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (elastic restarts onto shrunken worlds).  Axes are
    Auto: the models place values with `with_sharding_constraint`, which
    an Explicit axis (`jax.make_mesh`'s default) refuses."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — for examples/ and smoke runs."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))
