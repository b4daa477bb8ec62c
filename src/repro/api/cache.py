"""The compiled-plane cache: warm repeat solves reuse executables.

The legacy engine rebuilt (and re-jitted) a chunk executable per ``solve``
call because the builders closed over the instance's ``ProblemData``.  The
parametric builders (:func:`repro.core.superstep.build_plane_fn` /
``build_batch_plane_fn``) take the instance tensors as call-time arguments,
so one jitted function serves every same-shape instance: a serving balancer
replaying the same (problem, W, B) plane all day compiles once.

:class:`PlaneCache` holds those parametric functions keyed by
``(kind, problem, config, pad_words, use_fpt, mesh)`` (a solo plane may be
sharded over a mesh of chips) and accounts warm/cold at SHAPE granularity:
a cache *miss* is the first time a shape signature ``(n, W, capacity[,
B])`` hits a plane (jax traces + compiles), a *hit* is every subsequent
same-shape call (executable reuse, no tracing).  The
ground-truth compile counter is ``repro.core.superstep.PLANE_TRACES``,
bumped by a host side effect that only runs while jax traces — tests assert
hits never trace.
"""

from __future__ import annotations

import dataclasses
import functools

from repro.core import superstep


@dataclasses.dataclass
class CacheStats:
    """Warm/cold accounting for one :class:`PlaneCache`.

    ``misses``/``hits`` count shape-level cold/warm calls; ``planes`` is the
    number of distinct parametric functions built; ``shapes`` the distinct
    shape signatures seen; ``bypasses`` counts solves that skipped the cache
    (none do: mesh-sharded planes are cached too); ``plane_traces``
    snapshots the global jax trace counter.
    """

    hits: int = 0
    misses: int = 0
    planes: int = 0
    shapes: int = 0
    bypasses: int = 0
    plane_traces: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class PlaneCache:
    """Parametric compiled planes, keyed by configuration; shared freely.

    A session owns one by default, but a cache may be passed to many
    sessions (and is what the legacy ``engine.solve`` shims share), so
    equal-config callers pool their executables.
    """

    def __init__(self):
        self._planes: dict = {}
        self._shapes: set = set()
        self.hits = 0
        self.misses = 0
        self.bypasses = 0

    # -- plane lookup ----------------------------------------------------------

    @staticmethod
    def _plane_key(
        kind: str, spec, cfg, pad: int, use_fpt: bool, mesh=None
    ) -> tuple:
        # key on the knobs the executable actually depends on, so configs
        # differing only in host-side knobs (max_rounds, sim latency, ...)
        # share planes
        knobs = (
            cfg.steps_per_round, cfg.lanes, cfg.policy, cfg.packed_status,
            cfg.skip_empty_transfer, cfg.transfer_impl, cfg.explore_impl,
            cfg.donate_k, cfg.chunk_rounds,
        )
        # a Mesh compares equal by its devices, their shape and axis names
        return (kind, spec, knobs, pad, use_fpt, mesh)

    def _get(self, kind: str, spec, cfg, pad: int, use_fpt: bool, mesh=None):
        key = self._plane_key(kind, spec, cfg, pad, use_fpt, mesh)
        plane = self._planes.get(key)
        if plane is None:
            if kind == "solo":
                build = functools.partial(superstep.build_plane_fn, mesh=mesh)
            else:
                build = superstep.build_batch_plane_fn
            plane = build(
                spec,
                steps_per_round=cfg.steps_per_round,
                lanes=cfg.lanes,
                policy_priority=cfg.policy_priority,
                transfer_pad_words=pad,
                packed_status=cfg.packed_status,
                skip_empty_transfer=cfg.skip_empty_transfer,
                transfer_impl=cfg.transfer_impl,
                explore_impl=cfg.explore_impl,
                donate_k=cfg.donate_k,
                chunk_rounds=cfg.chunk_rounds,
                use_fpt=use_fpt,
            )
            self._planes[key] = plane
        return plane

    def solo_plane(self, spec, cfg, pad: int, use_fpt: bool, mesh=None):
        """The parametric ``(data, state[, fpt_bound])`` solo runner, its
        workers sharded over ``mesh``'s chips when one is given."""
        return self._get("solo", spec, cfg, pad, use_fpt, mesh)

    def batch_plane(self, spec, cfg, pad: int, use_fpt: bool):
        """The parametric ``(datas, state, done[, fpt_bounds])`` runner."""
        return self._get("batch", spec, cfg, pad, use_fpt)

    # -- warm/cold accounting --------------------------------------------------

    def note(
        self, kind: str, spec, cfg, pad: int, use_fpt: bool, shape: tuple,
        mesh=None,
    ) -> bool:
        """Record one plane invocation's full signature (plane key + the
        shape tuple jax specializes on); True if it was warm."""
        key = (self._plane_key(kind, spec, cfg, pad, use_fpt, mesh), shape)
        warm = key in self._shapes
        if warm:
            self.hits += 1
        else:
            self.misses += 1
            self._shapes.add(key)
        return warm

    def stats(self) -> CacheStats:
        return CacheStats(
            hits=self.hits,
            misses=self.misses,
            planes=len(self._planes),
            shapes=len(self._shapes),
            bypasses=self.bypasses,
            plane_traces=superstep.PLANE_TRACES,
        )
