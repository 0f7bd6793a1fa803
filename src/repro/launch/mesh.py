"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; callers (dryrun.py,
train.py) decide when devices are materialized. The production topology is
a TPU v5e pod of 16 x 16 = 256 chips; multi-pod doubles along a leading
"pod" axis (2 x 256 = 512 chips) reserved for DCN-tolerant data
parallelism.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes. The program steers placement
    with ``with_sharding_constraint`` and GSPMD, which ``Explicit`` axes
    (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (host/CPU) devices exist — used by
    tests and the local examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return make_mesh((data, model), ("data", "model"))
