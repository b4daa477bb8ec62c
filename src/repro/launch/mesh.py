"""Production mesh construction (a FUNCTION so importing never touches jax
device state — required by the dry-run's device-count override ordering).
Every mesh axis is ``Auto``: sharding is propagated by the compiler."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
    axis composes with data for batch sharding (pure DP across pods; the
    only cross-pod collective is the gradient all-reduce, DCN-friendly)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_solver_mesh(num_workers: int | None = None):
    """1-D mesh for the branching engine: one worker per device."""
    n = num_workers or len(jax.devices())
    return jax.make_mesh((n,), ("workers",), axis_types=(AxisType.Auto,))


def batch_axes_for(global_batch: int, mesh) -> tuple | None:
    """Largest prefix of (pod, data) that divides the global batch — decode
    shapes with batch 1 stay replicated, everything else shards."""
    names = [n for n in ("pod", "data") if n in mesh.axis_names]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    chosen = []
    div = 1
    for n in names:
        if global_batch % (div * sizes[n]) == 0:
            chosen.append(n)
            div *= sizes[n]
    return tuple(chosen) if chosen else None
