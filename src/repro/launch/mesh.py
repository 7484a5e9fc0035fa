"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state -- the dry-run must set
``xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_compat_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: ``jax.make_mesh``
    defaults to ``Explicit`` axes, which the solvers' ``shard_map`` and
    ``NamedSharding`` code does not use."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ("data", "model") / ("pod", "data", "model").  DP runs over
    ("pod", "data") so the inter-pod (DCI) traffic is gradient-reduction
    only; TP/SP/EP stay inside a pod on the fast ICI "model" axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_compat_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None) -> Mesh:
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return make_compat_mesh((data, model), ("data", "model"))
