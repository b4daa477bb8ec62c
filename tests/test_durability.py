"""Durable solve plane: kill at ANY chunk boundary, resume, and the final
result is bit-identical to the uninterrupted run.

The engine carries its whole trajectory on device (frontier records, bounds,
stat counters, the round-robin donor salt in ``WorkerState.rounds``) and the
host loop holds only a rounds counter — so a checkpoint written at a
host-sync boundary plus that counter IS the full state.  These tests pin the
contract end-to-end for every plane: solo, fpt, the batched solve_many plane
(across a compaction), and an occupied live :class:`SolveService`.

Bit-identity covers result fields and device-carried stats.  Explicitly
OUTSIDE the contract: ``wall_s`` (wall clock) and the durability bookkeeping
itself (``checkpoints_written``, ``resumed_from``), which legitimately
differ between a resumed and an uninterrupted run.
"""

import os

import numpy as np
import pytest

from repro.api import (
    PlaneCache,
    SolveConfig,
    SolverSession,
    SolveService,
)
from repro.core import superstep
from repro.graphs.generators import erdos_renyi
from repro.problems.registry import get_problem

# checkpoint at EVERY host-sync boundary: one round per chunk, tiny rounds
CFG = dict(num_workers=4, steps_per_round=2, chunk_rounds=1, checkpoint_every=1)

PROBLEMS = ["vertex_cover", "max_clique", "mis"]


def _edge_p(problem, p):
    """The edge density for ``problem`` where vertex cover uses ``p``: MIS
    searches sparse graphs for hundreds of rounds, so its instances are
    denser, which keeps every resume loop a few dozen chunks long."""
    return 0.6 if problem == "mis" else p


def _assert_same(a, b):
    """Bit-identity modulo wall-clock and durability bookkeeping."""
    assert a.best_size == b.best_size
    assert a.found == b.found
    assert a.rounds == b.rounds
    assert a.nodes_expanded == b.nodes_expanded
    assert a.tasks_transferred == b.tasks_transferred
    assert a.stats.transfer_rounds == b.stats.transfer_rounds
    assert a.stats.transfer_bytes_total == b.stats.transfer_bytes_total
    assert a.stats.overflow_count == b.stats.overflow_count
    assert (a.best_sol is None) == (b.best_sol is None)
    if a.best_sol is not None:
        assert (np.asarray(a.best_sol) == np.asarray(b.best_sol)).all()


def _steps(d):
    return sorted(
        int(p[5:]) for p in os.listdir(d)
        if p.startswith("step_") and not p.endswith(".tmp")
    )


def _fpt_miss_k(problem, g):
    """An fpt target the graph misses, so the whole tree is searched:
    vertex cover asks for 20 under its optimum of 23, max clique and MIS
    for one past their optimum."""
    if problem == "vertex_cover":
        return 20
    return get_problem(problem).sequential(g)[0] + 1


@pytest.mark.parametrize("problem", PROBLEMS)
@pytest.mark.parametrize("mode", ["bnb", "fpt"])
def test_solo_resume_bit_identical_at_every_boundary(tmp_path, mode, problem):
    g = erdos_renyi(34, _edge_p(problem, 0.25), seed=3)
    mode_kw = dict(mode="fpt", k=_fpt_miss_k(problem, g)) if mode == "fpt" else {}
    cfg = SolveConfig(**CFG, **mode_kw)
    cache = PlaneCache()
    base = SolverSession(problem, config=cfg, cache=cache).solve(g)
    assert base.rounds > 3  # the run really spans several chunk boundaries
    if mode == "fpt":
        assert not base.found  # a miss: the whole tree was searched

    d = str(tmp_path / "ck")
    r = SolverSession(problem, config=cfg, cache=cache).solve(
        g, checkpoint_dir=d
    )
    _assert_same(r, base)
    steps = _steps(d)
    assert r.stats.checkpoints_written == len(steps) > 0

    traces_before = superstep.PLANE_TRACES
    for s in steps:  # a kill after ANY chunk is resumable
        rr = SolverSession.resume(
            os.path.join(d, f"step_{s}"), cache=cache, checkpoint_dir=None
        )
        _assert_same(rr, base)
        assert rr.stats.resumed_from
    # resuming into the warm plane cache compiles NOTHING new
    assert superstep.PLANE_TRACES == traces_before


@pytest.mark.parametrize("problem", PROBLEMS)
def test_solve_many_resume_bit_identical_across_compaction(tmp_path, problem):
    sizes = [(20, 1), (30, 2), (34, 3), (18, 4), (33, 5), (26, 6)]
    gs = [erdos_renyi(n, _edge_p(problem, 0.3), seed=s) for n, s in sizes]
    cfg = SolveConfig(**CFG)
    cache = PlaneCache()
    session = SolverSession(problem, config=cfg, cache=cache)
    base = session.solve_many(gs)
    assert base.compactions >= 1  # the batch really crosses a compaction

    d = str(tmp_path / "ck")
    r = session.solve_many(gs, checkpoint_dir=d)
    for a, b in zip(r.results, base.results):
        _assert_same(a, b)
    steps = _steps(d)
    assert steps

    traces_before = superstep.PLANE_TRACES
    for s in steps:
        rr = SolverSession.resume(
            os.path.join(d, f"step_{s}"), cache=cache, checkpoint_dir=None
        )
        assert len(rr.results) == len(base.results)
        for a, b in zip(rr.results, base.results):
            _assert_same(a, b)
        # host-side plane accounting resumes too, not just results
        assert rr.compactions == base.compactions
        assert rr.lane_stats.chunk_calls == base.lane_stats.chunk_calls
    assert superstep.PLANE_TRACES == traces_before


@pytest.mark.parametrize("problem", PROBLEMS)
def test_occupied_service_restores_and_finishes_every_ticket(tmp_path, problem):
    sizes = [(20, 1), (30, 2), (34, 3), (18, 4), (33, 5), (26, 6), (24, 7)]
    gs = [erdos_renyi(n, _edge_p(problem, 0.3), seed=s) for n, s in sizes]
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, service_lanes=3
    )
    cache = PlaneCache()

    svc = SolveService(problem, cfg, cache=cache)
    tickets = [svc.submit(g) for g in gs]
    svc.drain()
    base = {t: svc.result(t) for t in tickets}

    # occupy the plane: live lanes AND a pending queue at checkpoint time
    svc = SolveService(problem, cfg, cache=cache)
    tickets = [svc.submit(g) for g in gs]
    done_before = []
    for _ in range(4):
        done_before.extend(svc.step())
    d = str(tmp_path / "ck")
    svc.checkpoint(d)
    assert svc.tickets()  # still occupied — this checkpoint holds live lanes

    traces_before = superstep.PLANE_TRACES
    svc2 = SolveService.restore(d, cache=cache)
    assert svc2.tickets() == svc.tickets()
    svc2.drain()
    for t in tickets:
        _assert_same(svc2.result(t), base[t])
    assert superstep.PLANE_TRACES == traces_before
    # tickets finished before the kill came back from the checkpoint too
    assert set(done_before) <= set(base)


def test_auto_checkpoint_from_config_and_stats_fields(tmp_path):
    """checkpoint_dir in the CONFIG (not the call) also checkpoints, and the
    durability bookkeeping lands in the typed stats."""
    g = erdos_renyi(30, 0.25, seed=3)
    d = str(tmp_path / "ck")
    cfg = SolveConfig(**CFG, checkpoint_dir=d)
    r = SolverSession(config=cfg).solve(g)
    assert r.stats.checkpoints_written == len(_steps(d)) > 0
    assert r.stats.resumed_from is None

    rr = SolverSession.resume(d, checkpoint_dir=None)
    _assert_same(rr, r)
    assert rr.stats.resumed_from == d
    assert rr.stats.checkpoints_written == 0
