"""The solver's device mesh (a FUNCTION so importing never touches jax
device state — required by the dry-run's device-count override ordering).
Every mesh axis is ``Auto``: sharding is propagated by the compiler."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


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

