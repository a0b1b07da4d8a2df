"""Production mesh construction.

Single pod: 16x16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the ``pod``
axis carries pure data parallelism, which for ZO fine-tuning costs one
scalar all-reduce per forward (see DESIGN.md §3): the DCN between pods is
effectively idle, which is the property that lets LeZO scale to
arbitrarily many pods.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state; the dry-run sets
``xla_force_host_platform_device_count=512`` *before* any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules here are
    ``NamedSharding`` annotations that XLA propagates, not explicit
    sharding-in-types (``jax.make_mesh`` defaults to Explicit axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
