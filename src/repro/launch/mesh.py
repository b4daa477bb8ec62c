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
    """1-D mesh over every device JAX sees, axis ``chips``, for the
    branching engine: each device runs ``num_workers / devices`` virtual
    workers.  Raises ``ValueError`` when the device count does not divide
    ``num_workers``."""
    n = len(jax.devices())
    if num_workers is not None and (num_workers < n or num_workers % n):
        raise ValueError(
            f"num_workers={num_workers} cannot be split evenly over the "
            f"{n} devices of the mesh: use a multiple of {n}"
        )
    return jax.make_mesh((n,), ("chips",), axis_types=(AxisType.Auto,))


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
