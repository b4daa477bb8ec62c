"""Host driver for the SPMD branching engine.

NOTE (PR 4): the public entry points are now :class:`repro.api.SolverSession`
(+ :class:`repro.api.SolveConfig`); ``solve``/``solve_many`` below are thin
deprecated shims over the session drivers in :mod:`repro.api.backends`,
which reuse this module's helpers (startup scatter, batch state stacking,
result extraction) as their single source of truth.  The legacy result
types (``EngineResult``/``BatchResult``) and the elasticity API
(``snapshot``/``restore``/``resize``) live on here.

Responsibilities (the paper's startup/termination bookkeeping):

* **startup** (§3.5): expand the root on the host until ≥ P open tasks exist
  (BFS = the equitable split), order them by the Algorithm-7 waiting-list
  traversal, and scatter one task per worker (the paper's seed→waiting-list
  topology); overflow tasks (BFS can over-expand past P) are routed through
  the SAME Algorithm-7 permutation so the equitable topology is preserved;
* **rounds**: the solve loop is device-resident — ``build_plane_fn`` runs up
  to ``chunk_rounds`` supersteps per ``lax.while_loop`` on device, checking
  global quiescence (and, in FPT mode, the bound ``k``) on device; the host
  syncs ONE (done, ran) scalar pair per chunk instead of blocking on a
  ``device_get`` after every superstep (see EXPERIMENTS.md §Perf);
* **collect**: the center "knows which worker holds the best solution and
  fetches it only when the exploration has finished" (§3.1) — we argmin the
  per-worker local bests once, at the end; all stats (nodes, transfers,
  payload bytes) live in the carried ``WorkerState``, so collection is one
  host fetch;
* **elasticity / fault tolerance**: state is a plain pytree keyed only by
  (P, capacity, W).  ``snapshot``/``restore`` round-trip it through host
  memory; ``resize`` re-splits all pending tasks across a NEW worker count,
  which is how the engine survives losing (or gaining) devices mid-run.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.superstep import (
    REDUCE_COUNTERS,
    WorkerState,
    make_worker_state,
)
from repro.core.waiting_list import startup_assignment
from repro.graphs.bitgraph import BitGraph, n_words
from repro.problems import base as problems_base
from repro.problems.registry import DEFAULT_PROBLEM, get_problem


@dataclasses.dataclass
class EngineResult:
    best_size: int
    best_sol: Optional[np.ndarray]
    rounds: int
    nodes_expanded: int
    tasks_transferred: int
    wall_s: float
    overflow: bool
    # exact number of tasks lost to frontier saturation (summed over
    # workers) — 0 under engine-sized capacity; the loud twin of the bool
    overflow_count: int
    # collective-traffic accounting (bytes) for the roofline / paper §4.3.
    # Control plane is a static per-round budget; the data plane is counted
    # on device: `transfer_rounds` supersteps ran the transfer collective and
    # carried `transfer_bytes_total` bytes of task-record payload (sparse
    # path: exactly 4·rec_words·records_moved — zero on no-match rounds;
    # gather path: the full P·k record table per transfer round).  This is
    # INFORMATION payload — the nonzero rows of the collective operand —
    # not physical wire traffic: the sparse psum's static operand is still
    # (P, k, REC) per device (see EXPERIMENTS.md §Perf B/C).
    control_bytes_per_round: int
    transfer_rounds: int
    transfer_bytes_total: int
    transfer_bytes_per_round: float
    # durability: how many SolveCheckpoints this run wrote, and the
    # checkpoint path it restored from (None = started fresh).  Set by the
    # host drivers in repro.api.backends, not by result extraction.
    checkpoints_written: int = 0
    resumed_from: Optional[str] = None
    # hierarchical frontier memory (repro.core.spill): tasks evicted to /
    # re-admitted from the host cold tier, and its peak encoded size.  Set
    # by the host drivers when cfg.frontier_spill is on; with spill enabled
    # overflow/overflow_count stay 0 by construction (the no-drop
    # guarantee), so saturation shows up HERE instead.
    spilled_tasks: int = 0
    readmitted_tasks: int = 0
    cold_bytes_peak: int = 0
    # the explore reduction's work, summed over workers (WorkerState's
    # reduce_* counters; 0 without a reduction)
    reduce_lane_sweeps: int = 0
    reduce_worker_sweeps: int = 0
    reduce_fires_rule1: int = 0
    reduce_fires_rule2: int = 0
    reduce_fires_rule3: int = 0
    reduce_rule3_probes: int = 0
    # tasks delivered to a worker on another chip (0 on one chip)
    tasks_sent_remote: int = 0
    # device-to-host fetches of the host loop while this instance was on the
    # plane, and their bytes (repro.tracing.Fetches); set by the host drivers
    host_fetches: int = 0
    host_fetch_bytes: int = 0


def _scatter_startup(
    state: WorkerState, problem, g: BitGraph, num_workers: int, tasks=None
) -> WorkerState:
    """BFS-split the root into ~P tasks and place them per Algorithm 7 order.

    ``problem`` is the :class:`~repro.problems.base.BranchingProblem` whose
    host brancher drives the split.  Every task — including overflow beyond
    the first ``num_workers`` when the BFS split over-expands (``tasks`` may
    hold more than P records) — goes through the same ``order`` permutation,
    so task i lands on worker ``order[i mod P]``: the §3.5 equitable topology
    wraps instead of degrading to raw round-robin.
    """
    if tasks is None:
        tasks = problems_base.expand_frontier(problem, g, num_tasks=num_workers)
    order = startup_assignment(max_b=2, p=num_workers)  # 1-based worker ids
    masks = np.array(state.frontier.masks)
    sols = np.array(state.frontier.sols)
    depths = np.array(state.frontier.depths)
    active = np.array(state.frontier.active)
    for i, (mask, sol, depth) in enumerate(tasks):
        w = order[i % num_workers] - 1
        # next free slot on worker w
        slot = int(np.argmin(active[w]))
        assert not active[w, slot], "startup overflow"
        masks[w, slot] = mask
        sols[w, slot] = sol
        depths[w, slot] = depth
        active[w, slot] = True
    return state._replace(
        frontier=state.frontier._replace(
            masks=jnp.asarray(masks),
            sols=jnp.asarray(sols),
            depths=jnp.asarray(depths),
            active=jnp.asarray(active),
        )
    )


def solve(
    g: BitGraph,
    num_workers: int = 8,
    *,
    problem=DEFAULT_PROBLEM,
    steps_per_round: int = 32,
    lanes: int = 1,
    policy_priority: bool = True,
    codec: str = "optimized",
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    explore_impl: str = "fused",
    donate_k: int = 1,
    chunk_rounds: int = 16,
    mode: str = "bnb",
    k: Optional[int] = None,
    mesh=None,
    max_rounds: int = 200_000,
    capacity: Optional[int] = None,
    initial_state: Optional[WorkerState] = None,
    compact_threshold: float = 0.25,
) -> EngineResult:
    """DEPRECATED shim over :class:`repro.api.SolverSession` — solve one
    instance of ``problem`` with P workers (virtual or one-per-device).

    Prefer ``SolverSession(problem=..., config=SolveConfig(...)).solve(g)``:
    the session validates the knobs once, returns the unified result schema
    and caches compiled planes across solves.  This shim maps the legacy
    kwargs onto :class:`~repro.api.SolveConfig` (it now accepts the full
    knob superset — ``compact_threshold`` is accepted-and-inert here, fixing
    the historical solve/solve_many kwargs drift) and shares one
    process-wide plane cache, then returns the legacy ``EngineResult``.
    """
    warnings.warn(
        "engine.solve is deprecated and will be REMOVED in v1.0; use "
        "repro.api.SolverSession(...).solve (see the README migration "
        "table: 'Migrating from the legacy engine API')",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.api import backends as _api

    spec = get_problem(problem)
    cfg = _api.config_from_legacy(
        policy_priority=policy_priority,
        num_workers=num_workers,
        steps_per_round=steps_per_round,
        lanes=lanes,
        codec=codec,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        explore_impl=explore_impl,
        donate_k=donate_k,
        chunk_rounds=chunk_rounds,
        mode=mode,
        k=k,
        max_rounds=max_rounds,
        capacity=capacity,
        compact_threshold=compact_threshold,
    )
    return _api.solve_spmd(
        spec, g, cfg, _api.LEGACY_CACHE, initial_state=initial_state, mesh=mesh
    )


# -- the multi-instance solve plane --------------------------------------------


@dataclasses.dataclass
class BatchResult:
    """Per-instance results of one ``solve_many`` call.

    ``results[i]`` corresponds to ``graphs[i]`` (submission order is
    preserved across bucketing).  ``wall_s`` is the total wall time over all
    buckets; each ``EngineResult.wall_s`` inside is the amortized share
    (bucket wall / bucket size) — instances in a batch are not individually
    timeable.
    """

    results: list
    wall_s: float
    # packing record: one (W, n_max, [instance indices]) triple per bucket
    buckets: list
    compactions: int
    # plane occupancy counters (see api.result.BatchSolveResult.lane_stats)
    lane_stats: dict = dataclasses.field(default_factory=dict)


def _bucket_instances(graphs, by_n: bool = False) -> dict:
    """Group instance indices by packed width W = n_words(n).

    Instances sharing W pad to the bucket's max n with isolated (never
    in-mask) vertices — padding rows change no branching decision, so the
    padded trace is bit-identical to the solo one (tests assert this).
    Distinct W would change the task-record width, so it starts a new bucket
    (and a new compiled executable).

    ``by_n`` buckets by exact (W, n) instead: the basic codec's §4.3 payload
    pad is n·W words, so mixing n under one pad would skew the per-instance
    byte accounting that codec exists to measure.
    """
    buckets: dict = {}
    for i, g in enumerate(graphs):
        buckets.setdefault((g.W, g.n if by_n else None), []).append(i)
    return buckets


@functools.lru_cache(maxsize=None)
def _blank_state_builder(num_workers: int, cap: int, W: int):
    """Jitted per-shape blank (P, ...) state constructor: live-lane
    admission calls this once per swap-in, so the eager vmap's per-op
    dispatch would dominate the service's host loop."""
    return jax.jit(
        lambda best: jax.vmap(lambda _: make_worker_state(cap, W, best))(
            jnp.arange(num_workers)
        )
    )


def make_instance_state(
    problem, g, num_workers: int, cap: int, W: int, initial_best
) -> WorkerState:
    """One instance's (P, ...) worker state, initialized and §3.5-startup-
    scattered by exactly the solo-solve code path (:func:`make_worker_state`
    + :func:`_scatter_startup`) — one source of truth for the Algorithm-7
    placement, shared by solo solves, batch stacking, and live-lane
    admission (the service writes this state into a freed lane)."""
    state = _blank_state_builder(num_workers, cap, W)(jnp.int32(initial_best))
    return _scatter_startup(state, problem, g, num_workers)


def _make_batch_state(
    problem, graphs, num_workers: int, cap: int, W: int, initial_bests
) -> WorkerState:
    """(B, P, ...) stacked worker state: each instance initialized via
    :func:`make_instance_state`, then stacked."""
    per_instance = [
        make_instance_state(problem, g, num_workers, cap, W, initial_best)
        for g, initial_best in zip(graphs, initial_bests)
    ]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_instance)


def _extract_result(
    host_state: dict,
    lane: int,
    problem,
    g: BitGraph,
    rounds: int,
    wall_s: float,
    *,
    mode: str,
    k,
    num_workers: int,
    packed_status: bool,
) -> EngineResult:
    """Build one instance's EngineResult from a device-fetched batch state.

    ``best_size`` is reported in the problem's EXTERNAL objective
    (``external_value``); "found nothing acceptable" is exactly "the internal
    best never improved on the seed bound".
    """
    local_bests = host_state["local_best_val"][lane]
    wbest = int(np.argmin(local_bests))
    internal_best = int(local_bests[wbest])
    found = internal_best < problems_base.initial_bound(problem, g, mode, k)
    best_size = int(problem.external_value(internal_best))
    best_sol = host_state["best_sol"][lane][wbest]
    if not found:
        best_sol = None
        if mode == "fpt":
            best_size = -1
    # payload_words/transfer_rounds are replicated (derived from the shared
    # status table), so worker 0's view is the instance truth.
    payload_words = int(host_state["payload_words"][lane][0])
    transfer_rounds = int(host_state["transfer_rounds"][lane][0])
    return EngineResult(
        best_size=best_size,
        best_sol=best_sol,
        rounds=rounds,
        nodes_expanded=int(host_state["nodes_expanded"][lane].sum()),
        tasks_transferred=int(host_state["tasks_sent"][lane].sum()),
        tasks_sent_remote=int(host_state["tasks_sent_remote"][lane].sum()),
        wall_s=wall_s,
        overflow=bool(host_state["overflow"][lane].any()),
        overflow_count=int(host_state["dropped"][lane].sum()),
        control_bytes_per_round=4 * (1 if packed_status else 3) * num_workers,
        transfer_rounds=transfer_rounds,
        transfer_bytes_total=4 * payload_words,
        transfer_bytes_per_round=4 * payload_words / max(rounds, 1),
        **{name: int(host_state[name][lane].sum()) for name in REDUCE_COUNTERS},
    )


def _fetch_batch_state(state: WorkerState) -> dict:
    s = jax.device_get(state)
    return {
        "local_best_val": np.asarray(s.local_best_val),
        "best_sol": np.asarray(s.best_sol),
        "nodes_expanded": np.asarray(s.nodes_expanded),
        "tasks_sent": np.asarray(s.tasks_sent),
        "tasks_sent_remote": np.asarray(s.tasks_sent_remote),
        "overflow": np.asarray(s.frontier.overflow),
        "dropped": np.asarray(s.frontier.dropped),
        "transfer_rounds": np.asarray(s.transfer_rounds),
        "payload_words": np.asarray(s.payload_words),
        **{name: np.asarray(getattr(s, name)) for name in REDUCE_COUNTERS},
    }


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def solve_many(
    graphs,
    num_workers: int = 8,
    *,
    problem=DEFAULT_PROBLEM,
    steps_per_round: int = 32,
    lanes: int = 1,
    policy_priority: bool = True,
    codec: str = "optimized",
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    explore_impl: str = "fused",
    donate_k: int = 1,
    chunk_rounds: int = 16,
    mode: str = "bnb",
    k=None,
    mesh=None,
    max_rounds: int = 200_000,
    capacity: Optional[int] = None,
    compact_threshold: float = 0.25,
) -> BatchResult:
    """DEPRECATED shim over :class:`repro.api.SolverSession` — solve B
    independent instances of ``problem`` on ONE solve plane.

    Prefer ``SolverSession(...).solve_many(graphs)``.  This shim accepts the
    full legacy knob superset (``mesh`` is accepted for solve/solve_many
    parity but must stay ``None`` — the batched plane has no mesh path yet)
    and returns the legacy ``BatchResult``.

    The paper's center is cheap so one coordinator can drive huge worker
    pools; this extends the same amortization across *instances*: the batch
    shares a single compiled chunk executable, one host sync per chunk for
    the whole batch, and P workers per instance.  Per-instance
    ``best_size``/``best_sol`` are bit-identical to B solo ``solve`` calls
    (property-tested), because padding adds only isolated never-in-mask
    vertices and all collectives are bound to the worker axis.

    Packing: instances are bucketed by packed width ``W = n_words(n)`` and
    padded to the bucket's max n — one executable per (n_max, W) bucket.
    ``k`` (FPT mode) may be a single int or a per-instance sequence.

    Compaction: finished instances are frozen no-op lanes; when the live
    fraction of a bucket drops to ``compact_threshold`` or below, the batch
    is compacted to the next power of two above the live count (bounding
    recompiles to log2 B) and the finished lanes' results are collected
    early.  ``compact_threshold=0`` disables compaction.

    Capacity: one frontier size per bucket, ``4·n_max + 8·lanes`` — at least
    the solo solve's ``4·n + 8·lanes``.  The engine sizes capacity so
    overflow never fires (tests assert it), so the extra tail slots are
    behaviorally inert; a solo run that DID overflow (an engine-sizing bug)
    could drop tasks its batched lane keeps.  Pass ``capacity`` to pin an
    exact size.
    """
    warnings.warn(
        "engine.solve_many is deprecated and will be REMOVED in v1.0; use "
        "repro.api.SolverSession(...).solve_many (see the README migration "
        "table: 'Migrating from the legacy engine API')",
        DeprecationWarning,
        stacklevel=2,
    )
    if mesh is not None:
        raise ValueError(
            "solve_many has no mesh path yet (vmap virtual workers only); "
            "pass mesh=None"
        )
    from repro.api import backends as _api

    spec = get_problem(problem)
    cfg = _api.config_from_legacy(
        policy_priority=policy_priority,
        num_workers=num_workers,
        steps_per_round=steps_per_round,
        lanes=lanes,
        codec=codec,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        explore_impl=explore_impl,
        donate_k=donate_k,
        chunk_rounds=chunk_rounds,
        mode=mode,
        k=(tuple(k) if hasattr(k, "__len__") else k),
        max_rounds=max_rounds,
        capacity=capacity,
        compact_threshold=compact_threshold,
    )
    return _api.solve_many_spmd(spec, graphs, cfg, _api.LEGACY_CACHE)


# -- elasticity -----------------------------------------------------------------


def snapshot(state: WorkerState) -> dict:
    """Host-side checkpoint of the entire engine state."""
    return jax.tree.map(np.asarray, state._asdict())


def restore(snap: dict) -> WorkerState:
    return WorkerState(**jax.tree.map(jnp.asarray, snap))


def resize(state: WorkerState, new_num_workers: int) -> WorkerState:
    """Re-split all pending tasks over a different worker count (elastic
    scale-up/down or failed-node recovery — any device count works because
    tasks are self-contained records over the original instance)."""
    masks = np.array(state.frontier.masks)
    sols = np.array(state.frontier.sols)
    depths = np.array(state.frontier.depths)
    active = np.array(state.frontier.active)
    P_old, cap, W = masks.shape[0], masks.shape[1], masks.shape[2]

    tasks = [
        (masks[w, s], sols[w, s], depths[w, s])
        for w in range(P_old)
        for s in range(cap)
        if active[w, s]
    ]
    best = int(np.asarray(state.local_best_val).min())
    bw = int(np.argmin(np.asarray(state.local_best_val)))
    new = jax.vmap(lambda _: make_worker_state(cap, W, best))(
        jnp.arange(new_num_workers)
    )
    nm = np.array(new.frontier.masks)
    ns = np.array(new.frontier.sols)
    nd = np.array(new.frontier.depths)
    na = np.array(new.frontier.active)
    for i, (m, s, d) in enumerate(tasks):
        w = i % new_num_workers
        slot = i // new_num_workers
        assert slot < cap, "resize: capacity too small for pending tasks"
        nm[w, slot], ns[w, slot], nd[w, slot], na[w, slot] = m, s, d, True
    sol = np.asarray(state.best_sol)[bw]
    return new._replace(
        frontier=new.frontier._replace(
            masks=jnp.asarray(nm),
            sols=jnp.asarray(ns),
            depths=jnp.asarray(nd),
            active=jnp.asarray(na),
        ),
        best_sol=jnp.broadcast_to(jnp.asarray(sol), new.best_sol.shape),
        local_best_val=jnp.full((new_num_workers,), best, jnp.int32),
    )
