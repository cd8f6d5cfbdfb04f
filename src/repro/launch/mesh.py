"""The repo's one mesh constructor, plus the production mesh shapes.

Every mesh is built here with ``Auto`` axis types: since jax 0.9
``jax.make_mesh`` defaults to ``Explicit`` axes, which
``with_sharding_constraint`` in the RRS wire and the GSPMD-style
parameter specs reject. Functions (not module-level constants), so
importing never touches jax device state.

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips; the pod axis joins the worker axis of the robust
aggregation and shards the batch.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 4, model: int = 2, pod: int = 1):
    """Small mesh for CPU multi-device tests (host platform devices)."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
