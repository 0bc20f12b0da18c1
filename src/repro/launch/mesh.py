"""Production mesh construction (spec: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def _auto(n: int) -> tuple:
    # Auto axes: the model code steers layouts with with_sharding_constraint,
    # which make_mesh's default Explicit axes refuse
    return (jax.sharding.AxisType.Auto,) * n


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def batch_spec(mesh) -> jax.sharding.PartitionSpec:
    return jax.sharding.PartitionSpec(data_axes(mesh))
