"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. Shapes per the assignment:
(16, 16) = one v5e pod (256 chips), (2, 16, 16) = two pods over DCN.

All mesh construction goes through ``repro.compat.make_mesh`` (the single
door for version-sensitive JAX APIs).
"""

from __future__ import annotations

from repro.compat import AxisType, make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for CI on fake CPU devices."""
    return make_mesh((n_data, n_model), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))


def make_data_mesh(n: int):
    """1-D storage-tier mesh (graph engine tests/examples)."""
    return make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
