"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is pure
data parallelism over DCN.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax call.

The model tree shards through GSPMD (``with_sharding_constraint`` and
partitioner-chosen layouts), so every mesh here has ``Auto`` axes;
``jax.make_mesh`` would otherwise make them ``Explicit``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the host actually has (tests)."""
    return _auto_mesh((data, model), ("data", "model"))
