"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is an
outer data-parallel axis by default (cross-pod traffic = one gradient
all-reduce per step, the DCN-friendly choice) or a pipeline axis when
``ParallelConfig.pipeline_stages > 1``.

This is a FUNCTION (not a module constant) so importing never touches jax
device state — the dry-run driver sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` (``distributed.sharding.constrain``)
    refuses; every mesh in this repo is built here instead.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return make_mesh((data, model), ("data", "model"))
