"""The unified result schema every backend returns.

Before this layer each engine had its own result type — ``EngineResult``
(SPMD), ``SimResult`` (both discrete-event simulators), bare tuples
(sequential reference) — so callers special-cased per backend.
:class:`SolveResult` is the one schema: the solution and the universally
meaningful counters are first-class fields, and everything
backend-specific rides in ``stats``.

``stats`` used to be an ad-hoc dict whose key set drifted per backend; it
is now the TYPED :class:`SolveStats` dataclass (with the service envelope
as a nested :class:`ServiceStats` and batch-plane occupancy as
:class:`LaneStats` on :class:`BatchSolveResult`).  The field sets are
pinned in ``tests/test_arch_guard.py`` — adding a counter is a deliberate,
reviewed schema change.  Legacy dict-style access (``r.stats["overflow"]``,
``.get``, ``in``) keeps working through a :class:`DeprecationWarning` shim;
read attributes (``r.stats.overflow``) instead.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import numpy as np


class _DictAccessShim:
    """Deprecation bridge: the pre-unification dict-style stats access
    (``stats["key"]`` / ``.get`` / ``in`` / ``.keys``) warns once per call
    site and delegates to the dataclass attributes."""

    def _names(self):
        return [f.name for f in dataclasses.fields(self)]

    def _warn(self):
        warnings.warn(
            f"dict-style access to {type(self).__name__} is deprecated and "
            f"will be removed in v1.0; read attributes instead "
            f"(e.g. r.stats.overflow_count, r.stats.service.deadline_hit)",
            DeprecationWarning,
            stacklevel=3,
        )

    def __getitem__(self, key):
        self._warn()
        if key in self._names():
            return getattr(self, key)
        raise KeyError(key)

    def get(self, key, default=None):
        self._warn()
        return getattr(self, key, default) if key in self._names() else default

    def __contains__(self, key):
        self._warn()
        return key in self._names()

    def keys(self):
        self._warn()
        return list(self._names())

    def items(self):
        self._warn()
        return [(name, getattr(self, name)) for name in self._names()]

    def to_dict(self) -> dict:
        """Plain-dict view (JSON-safe, no deprecation warning)."""
        return _jsonable(dataclasses.asdict(self))


@dataclasses.dataclass
class ServiceStats(_DictAccessShim):
    """The service envelope around one completed ticket (spmd service only):
    which lane/plane solved it, queue wait and lane residency (wall
    seconds), and whether its superstep deadline evicted it with an
    anytime result."""

    lane: int = -1
    plane: str = ""
    wait_s: float = 0.0
    residency_s: float = 0.0
    deadline_hit: bool = False
    # the wall-clock twin of deadline_hit: the request's deadline_s elapsed
    # (measured on the service's injected clock) before the solve finished
    wall_deadline_hit: bool = False
    # -- robustness (repro.faults): the self-healing ledger for THIS ticket ---
    # faults that hit the request (lane crash/stall windows), recoveries
    # (re-queue + bit-identical re-admission, cleared stall windows), times
    # its lane was quarantined, and extra payload-delivery attempts spent
    faults_injected: int = 0
    faults_recovered: int = 0
    lanes_quarantined: int = 0
    retries: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "ServiceStats":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls) if f.name in d})


@dataclasses.dataclass
class SolveStats(_DictAccessShim):
    """Every backend-specific counter, one typed superset schema.

    Fields a backend does not track stay at their zero defaults — the
    groups below document who writes what.  ``service`` is only populated
    for results delivered by a :class:`~repro.api.service.SolveService`.
    """

    # -- SPMD engine (collective-traffic accounting, EXPERIMENTS §Perf) -------
    overflow: bool = False
    overflow_count: int = 0
    control_bytes_per_round: int = 0
    transfer_rounds: int = 0
    transfer_bytes_total: int = 0
    transfer_bytes_per_round: float = 0.0
    # -- durability (spmd checkpoint/resume) ----------------------------------
    checkpoints_written: int = 0
    resumed_from: Optional[str] = None
    # -- hierarchical frontier memory (spmd, cfg.frontier_spill) --------------
    # cold-tier traffic: tasks evicted to the host store, tasks decoded and
    # re-admitted, and the store's peak encoded size in bytes.  With spill
    # enabled, overflow/overflow_count stay 0 (the no-drop guarantee).
    spilled_tasks: int = 0
    readmitted_tasks: int = 0
    cold_bytes_peak: int = 0
    # -- explore's reduction (spmd; 0 without one) ----------------------------
    # summed over workers: sweeps run by expanded lanes (each lane's last
    # sweep changes nothing), the lockstep loop's trips (per explore step,
    # the most sweeps of any lane of the worker), each rule's firings, and
    # the degree-2 candidates rule 3 examined (in each sweep where rules 1
    # and 2 do not fire: up to and including its vertex, else all)
    reduce_lane_sweeps: int = 0
    reduce_worker_sweeps: int = 0
    reduce_fires_rule1: int = 0
    reduce_fires_rule2: int = 0
    reduce_fires_rule3: int = 0
    reduce_rule3_probes: int = 0
    # -- data plane across chips (spmd) ---------------------------------------
    # tasks delivered to a worker on another chip of the mesh (0 on one chip)
    tasks_sent_remote: int = 0
    # -- host sync (spmd) -----------------------------------------------------
    # device-to-host fetches of the host loop while the instance was on the
    # plane (shared with its co-runners on a batched plane), and their bytes
    host_fetches: int = 0
    host_fetch_bytes: int = 0
    # -- discrete-event simulator backends ------------------------------------
    ticks: int = 0
    failed_requests: int = 0
    termination_cancelled: int = 0
    total_bytes: int = 0
    center_bytes: int = 0
    msg_count: dict = dataclasses.field(default_factory=dict)
    msg_bytes: dict = dataclasses.field(default_factory=dict)
    # -- sequential reference -------------------------------------------------
    pruned: int = 0
    solutions: int = 0
    max_depth: int = 0
    # -- service envelope (None outside SolveService) -------------------------
    service: Optional[ServiceStats] = None

    @classmethod
    def from_dict(cls, d: dict) -> "SolveStats":
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in known and k != "service"}
        service = d.get("service")
        if service is not None:
            kw["service"] = ServiceStats.from_dict(service)
        return cls(**kw)


@dataclasses.dataclass
class LaneStats(_DictAccessShim):
    """Batched-plane occupancy: ``chunk_calls`` (compiled chunk dispatches),
    ``lane_chunks`` (chunk_calls × plane width — paid lane slots),
    ``live_lane_chunks`` (slots that held an unfinished instance) and their
    ratio ``occupancy`` — the utilization a continuous-admission service
    raises over fixed batching (zeros where not tracked)."""

    chunk_calls: int = 0
    lane_chunks: int = 0
    live_lane_chunks: int = 0
    occupancy: float = 0.0


@dataclasses.dataclass
class SolveResult:
    """One instance solved by one backend.

    ``best_size`` is in the problem's EXTERNAL objective (``-1`` for an
    unsatisfiable FPT decision); ``rounds`` counts the backend's native
    progress unit (supersteps for spmd, simulator ticks for the two
    discrete-event backends, expanded nodes for sequential).
    """

    problem: str
    backend: str
    best_size: int
    best_sol: Optional[np.ndarray]
    found: bool
    wall_s: float
    rounds: int
    nodes_expanded: int
    tasks_transferred: int
    stats: SolveStats = dataclasses.field(default_factory=SolveStats)

    def to_dict(self) -> dict:
        """JSON-safe view (``best_sol`` as a list of packed u32 words)."""
        d = dataclasses.asdict(self)
        if self.best_sol is not None:
            d["best_sol"] = [int(w) for w in np.asarray(self.best_sol, np.uint32)]
        d["stats"] = _jsonable(d["stats"])
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SolveResult":
        """Inverse of :meth:`to_dict` (the service checkpoint round-trip)."""
        sol = d.get("best_sol")
        return cls(
            problem=d["problem"],
            backend=d["backend"],
            best_size=d["best_size"],
            best_sol=None if sol is None else np.asarray(sol, np.uint32),
            found=d["found"],
            wall_s=d["wall_s"],
            rounds=d["rounds"],
            nodes_expanded=d["nodes_expanded"],
            tasks_transferred=d["tasks_transferred"],
            stats=SolveStats.from_dict(d.get("stats") or {}),
        )


@dataclasses.dataclass
class BatchSolveResult:
    """Per-instance results of one batched solve; ``results[i]`` corresponds
    to ``graphs[i]`` (submission order survives bucketing/compaction).

    ``buckets`` is the packing record — one ``(W, n_max, [indices])`` triple
    per compiled bucket (empty for backends that solve instance-by-
    instance); ``compactions`` counts host-side batch compactions;
    ``lane_stats`` is the typed :class:`LaneStats` occupancy record.
    """

    problem: str
    backend: str
    results: list
    wall_s: float
    buckets: list = dataclasses.field(default_factory=list)
    compactions: int = 0
    lane_stats: LaneStats = dataclasses.field(default_factory=LaneStats)

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)


def _jsonable(obj):
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


# -- converters from the legacy per-engine schemas -----------------------------


def from_engine_result(r, *, problem: str, backend: str = "spmd") -> SolveResult:
    """Wrap a :class:`repro.core.engine.EngineResult`."""
    return SolveResult(
        problem=problem,
        backend=backend,
        best_size=r.best_size,
        best_sol=r.best_sol,
        found=r.best_sol is not None,
        wall_s=r.wall_s,
        rounds=r.rounds,
        nodes_expanded=r.nodes_expanded,
        tasks_transferred=r.tasks_transferred,
        stats=SolveStats(
            overflow=r.overflow,
            overflow_count=r.overflow_count,
            control_bytes_per_round=r.control_bytes_per_round,
            transfer_rounds=r.transfer_rounds,
            transfer_bytes_total=r.transfer_bytes_total,
            transfer_bytes_per_round=r.transfer_bytes_per_round,
            checkpoints_written=r.checkpoints_written,
            resumed_from=r.resumed_from,
            spilled_tasks=r.spilled_tasks,
            readmitted_tasks=r.readmitted_tasks,
            cold_bytes_peak=r.cold_bytes_peak,
            reduce_lane_sweeps=r.reduce_lane_sweeps,
            reduce_worker_sweeps=r.reduce_worker_sweeps,
            reduce_fires_rule1=r.reduce_fires_rule1,
            reduce_fires_rule2=r.reduce_fires_rule2,
            reduce_fires_rule3=r.reduce_fires_rule3,
            reduce_rule3_probes=r.reduce_rule3_probes,
            tasks_sent_remote=r.tasks_sent_remote,
            host_fetches=r.host_fetches,
            host_fetch_bytes=r.host_fetch_bytes,
        ),
    )


def from_sim_result(r, *, problem: str, backend: str, wall_s: float) -> SolveResult:
    """Wrap a :class:`repro.core.protocol_sim.SimResult` (both simulators)."""
    s = r.stats
    return SolveResult(
        problem=problem,
        backend=backend,
        best_size=r.best_size,
        best_sol=r.best_sol,
        found=r.best_sol is not None,
        wall_s=wall_s,
        rounds=r.ticks,
        nodes_expanded=s.nodes_expanded,
        tasks_transferred=s.tasks_transferred,
        stats=SolveStats(
            # host explorers keep unbounded Python frontiers: nothing to drop
            overflow_count=0,
            ticks=r.ticks,
            failed_requests=s.failed_requests,
            termination_cancelled=s.termination_cancelled,
            total_bytes=s.total_bytes,
            center_bytes=s.center_bytes,
            msg_count=dict(s.msg_count),
            msg_bytes=dict(s.msg_bytes),
        ),
    )


def from_sequential(best, sol, stats, *, problem: str, wall_s: float) -> SolveResult:
    """Wrap the sequential reference's ``(best, sol, SeqStats)`` triple."""
    return SolveResult(
        problem=problem,
        backend="sequential",
        best_size=best,
        best_sol=sol,
        found=sol is not None,
        wall_s=wall_s,
        rounds=stats.nodes,
        nodes_expanded=stats.nodes,
        tasks_transferred=0,
        stats=SolveStats(
            overflow_count=0,  # host recursion: no fixed-capacity pool
            pruned=stats.pruned,
            solutions=stats.solutions,
            max_depth=stats.max_depth,
        ),
    )
