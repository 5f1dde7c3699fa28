"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (one v5e pod's 16x16 torus).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is the
outer data-parallel ring (gradient/label reductions only -- the only
cross-pod traffic), 'model' stays intra-pod where ICI is fastest.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the step functions pin layouts with bare PartitionSpecs
    # (with_sharding_constraint), which Explicit axes refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    return _auto_mesh((n // model, model), ("data", "model"))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh ('pod' composes with 'data')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
