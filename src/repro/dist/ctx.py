"""Ambient distributed context.

Model layers must not take a mesh argument (they are called from vmap /
scan bodies where threading one through would contaminate every
signature), so the active mesh lives in a trace-time context stack that
``train/step.py`` and the serve steps push via ``mesh_context``. Layers
then ask two questions lazily:

* ``axis_size(name)`` — how many shards along a mesh axis (1 when no
  mesh is active or the axis does not exist), e.g. to pad attention
  heads up to the tensor-parallel degree.
* ``constrain(x, *entries)`` — a best-effort
  ``with_sharding_constraint``: axis names absent from the mesh or not
  dividing the dimension degrade to UNCONSTRAINED instead of erroring,
  and the whole call is a no-op outside tracing or without a mesh, so
  single-device eager tests run the exact same layer code.

The stack is trace-time state only (pushed while jit traces the step
function); it is not part of the compiled computation.

This module also holds the robust-backward state consumed by
``robust_reduce.robust_dot`` (DESIGN.md §2): while a
``robust_backward(mesh, worker_axes, ...)`` context is active, the
layers' ``_dot`` routes matmuls through the custom-VJP robust dot.
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "U",
    "mesh_context",
    "current_mesh",
    "axis_size",
    "partitioned",
    "constrain",
    "RobustBackwardState",
    "push_robust_backward",
    "pop_robust_backward",
    "robust_backward_state",
]

U = P.UNCONSTRAINED  # per-dim "let GSPMD decide" sentinel

_MESH_STACK: list = []


@contextlib.contextmanager
def mesh_context(mesh):
    """Make ``mesh`` the ambient mesh for constrain()/axis_size()."""
    _MESH_STACK.append(mesh)
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def current_mesh():
    """The innermost active mesh, or None."""
    return _MESH_STACK[-1] if _MESH_STACK else None


def axis_size(name: str) -> int:
    """Size of mesh axis ``name`` in the ambient mesh (1 if absent)."""
    mesh = current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return int(mesh.shape[name])


def partitioned() -> bool:
    """True while the ambient mesh spans more than one device: the code
    being traced is partitioned by GSPMD, which cannot partition a
    Mosaic (Pallas TPU) kernel, so kernel call sites pick their jnp
    path there. Kernels under a mesh run inside ``shard_map`` instead
    (the RRS wire), where this context does not decide anything."""
    mesh = current_mesh()
    return mesh is not None and mesh.size > 1


def _clean_entry(mesh, entry, dim: int):
    """Validate one PartitionSpec entry against the mesh and dim size.

    Unknown axes and non-dividing products degrade to UNCONSTRAINED —
    callers state intent for the *production* mesh and smaller test
    meshes must not error.
    """
    if entry is U or entry is None:
        return entry
    names = entry if isinstance(entry, tuple) else (entry,)
    kept = tuple(a for a in names
                 if a in mesh.axis_names and int(mesh.shape[a]) > 1)
    if not kept:
        return U
    total = 1
    for a in kept:
        total *= int(mesh.shape[a])
    if dim % total:
        return U
    return kept if len(kept) > 1 else kept[0]


def constrain(x, *entries):
    """Best-effort with_sharding_constraint under the ambient mesh.

    ``entries`` has one element per dim of ``x``: an axis name, a tuple
    of axis names, None (replicate), or ``U`` (unconstrained). No-op
    when no mesh is active or when called eagerly (hints only matter to
    GSPMD during tracing).
    """
    mesh = current_mesh()
    if mesh is None or not isinstance(x, jax.core.Tracer):
        return x
    cleaned = [_clean_entry(mesh, e, d) for e, d in zip(entries, x.shape)]
    if all(e is U for e in cleaned):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*cleaned)))


# ---------------------------------------------------------------------------
# Robust-backward state (consumed by robust_reduce.robust_dot)
# ---------------------------------------------------------------------------

class RobustBackwardState(NamedTuple):
    """Active IB-RRS config: mesh + worker axes + the Estimator spec
    (``core.estimator.Estimator``) that ``robust_dot`` aggregates with."""

    mesh: object
    worker_axes: Tuple[str, ...]
    estimator: object


_RB_STACK: list = []


def push_robust_backward(state: RobustBackwardState) -> None:
    _RB_STACK.append(state)


def pop_robust_backward() -> RobustBackwardState:
    return _RB_STACK.pop()


def robust_backward_state() -> Optional[RobustBackwardState]:
    """Innermost active robust-backward config, or None."""
    return _RB_STACK[-1] if _RB_STACK else None
