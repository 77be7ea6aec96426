"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis joins
'data' in the fsdp/dp logical axes (see repro.sharding.policy.AXIS_MAP).
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / reduced dry-runs)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))
