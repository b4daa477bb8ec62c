"""Host spans and host-sync counters of the solve plane.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro:<name>``: while
a profiler trace is recorded it lands on the trace's host plane, on the
same clock as the device's operations, with the request's id as its
``request`` argument (every span of one solve or one service ticket
carries the same id).  Without a trace it costs about a microsecond and
records nothing; this module keeps no buffers.

The device side is named by ``jax.named_scope`` in the program itself
(``explore/pop``, ``reduce/.../sweep``, ``center``, ...): metadata on the
compiled operations, free at run time.

:class:`Fetches` counts the host's device-to-host fetches of one solve and
their bytes, taken from the fetched arrays' shapes (no extra sync).
"""

from __future__ import annotations

import dataclasses
import itertools

import jax

PREFIX = "repro:"

_request_ids = itertools.count(1)


def new_request_id() -> int:
    """A process-unique id tying one request's spans together."""
    return next(_request_ids)


def span(name: str, request: int):
    """Context manager: the host span ``repro:<name>`` of ``request``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, request=request)


def nbytes(tree) -> int:
    """Bytes of every array in ``tree``, from shapes and dtypes alone."""
    return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(tree))


@dataclasses.dataclass
class Fetches:
    """Device-to-host fetches: how many, and their bytes."""

    count: int = 0
    bytes: int = 0

    def add(self, n_bytes: int) -> None:
        """Count one fetch of ``n_bytes`` made elsewhere."""
        self.count += 1
        self.bytes += int(n_bytes)

    def get(self, tree):
        """``jax.device_get(tree)``, counted."""
        self.add(nbytes(tree))
        return jax.device_get(tree)
