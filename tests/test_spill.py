"""Hierarchical frontier memory (``repro.core.spill``): the no-drop claims.

The contract, on every plane that can saturate (solo bnb, solo fpt,
``solve_many`` lanes, the live service):

1. **No task is ever dropped** — with ``frontier_spill=True`` a frontier
   driven past its high-water mark reports ``overflow=False`` /
   ``overflow_count=0`` and non-zero ``spilled_tasks``; the cold tier is
   fully drained back (``readmitted_tasks == spilled_tasks`` for a solve
   run to optimality).
2. **The optimum is unchanged** — the spilled solve lands on the SAME
   best value as the same instance solved with engine-sized (never
   saturating) capacity.
3. **Determinism** — spill/readmit decisions are host-side, stable-sorted
   and A7-ordered: running the same saturated solve twice is identical,
   counters included.
4. **Durability** — the cold tier rides SolveCheckpoints; a resume
   mid-spill finishes bit-identically, counters included.

Watermark resolution is pinned separately: the high mark must leave one
chunk's worth of growth headroom, and an impossible (capacity, chunk
shape) pair fails loudly at solve start, not silently mid-solve.
"""

import os

import numpy as np
import pytest

from repro.api import PlaneCache, SolveConfig, SolveService, SolverSession
from repro.core.encoding import make_codec
from repro.core.spill import (
    BAND_WIDTH,
    FrontierSpiller,
    chunk_headroom,
    resolve_watermarks,
)
from repro.graphs.generators import erdos_renyi
from repro.problems.registry import get_problem

# a shape that saturates: n=40 VC explores ~150 nodes with hot peaks ~15
# per worker, so capacity 16 with a one-chunk headroom of 7 spills
_SAT = dict(num_workers=4, steps_per_round=2, chunk_rounds=2, capacity=16)

PROBLEMS = ["vertex_cover", "max_clique", "mis"]
# instances whose frontier saturates _SAT, per problem.  A clique search
# only runs deep on dense graphs and an MIS search on sparse ones, so those
# two take n=20 at p=0.9 and p=0.1 (the first is the solo instance).
_SAT_GRAPHS = {
    "vertex_cover": [(40, 0.28, 0), (40, 0.28, 1), (40, 0.28, 2)],
    "max_clique": [(20, 0.9, 5), (20, 0.9, 3), (20, 0.9, 4)],
    "mis": [(20, 0.1, 3), (20, 0.1, 0), (20, 0.1, 1)],
}


def _sat_graphs(problem):
    return [erdos_renyi(*spec) for spec in _SAT_GRAPHS[problem]]


# one warm plane cache for the whole module: the cases re-solve the same
# shapes under the same configs and need not recompile each time
_CACHE = PlaneCache()


def _cfg(**over):
    return SolveConfig(**{**_SAT, **over})


def _solve(g, cfg, problem="vertex_cover"):
    return SolverSession(problem, config=cfg, cache=_CACHE).solve(g)


# -- 1. watermark resolution ---------------------------------------------------


def test_chunk_headroom_arithmetic():
    assert (
        chunk_headroom(chunk_rounds=2, steps_per_round=2, lanes=1, donate_k=1)
        == 2 * (2 * 1 + 1) + 1
    )


def test_resolve_watermarks_clamps_to_headroom():
    low, high = resolve_watermarks(
        16, (0.5, 0.9), chunk_rounds=2, steps_per_round=2, lanes=1, donate_k=1
    )
    # high = min(int(0.9*16)=14, 16-7=9) = 9; low = min(int(0.5*16)=8, 8)
    assert (low, high) == (8, 9)
    assert 1 <= low < high


def test_resolve_watermarks_impossible_capacity_fails_loudly():
    with pytest.raises(ValueError, match="headroom"):
        resolve_watermarks(
            8,
            (0.5, 0.9),
            chunk_rounds=16,
            steps_per_round=32,
            lanes=1,
            donate_k=1,
        )


def test_undersized_capacity_fails_at_solve_start():
    g = erdos_renyi(18, 0.35, 2)
    cfg = SolveConfig(num_workers=4, steps_per_round=8, capacity=2,
                      frontier_spill=True)
    with pytest.raises(ValueError, match="headroom"):
        _solve(g, cfg)


def test_mesh_path_is_gated():
    g = erdos_renyi(18, 0.35, 2)
    with pytest.raises(ValueError, match="mesh"):
        _solve(g, _cfg(frontier_spill=True, use_mesh=True))


def test_config_validates_watermarks_and_codec():
    with pytest.raises(ValueError, match="spill_watermarks"):
        SolveConfig(spill_watermarks=(0.9, 0.5))
    with pytest.raises(ValueError, match="codec"):
        SolveConfig(spill_codec="zstd")


# -- 2. saturation property: no drop, same optimum, deterministic --------------


@pytest.mark.parametrize("problem", PROBLEMS)
def test_solo_saturated_matches_unsaturated_and_is_deterministic(problem):
    g = _sat_graphs(problem)[0]
    big = _solve(g, _cfg(capacity=None), problem)
    a = _solve(g, _cfg(frontier_spill=True), problem)
    b = _solve(g, _cfg(frontier_spill=True), problem)

    assert a.stats.spilled_tasks > 0  # the shape really saturates
    assert a.stats.readmitted_tasks == a.stats.spilled_tasks
    assert a.stats.cold_bytes_peak > 0
    assert not a.stats.overflow and a.stats.overflow_count == 0
    # same optimum VALUE with a VALID witness — spill changes exploration
    # order, so the (equally optimal) witness may differ from big-capacity
    assert a.best_size == big.best_size
    assert get_problem(problem).verify(g, np.asarray(a.best_sol))
    assert int(np.unpackbits(np.asarray(a.best_sol).view(np.uint8)).sum()) == a.best_size

    # run-to-run: everything identical, counters included
    assert a.best_size == b.best_size
    assert (np.asarray(a.best_sol) == np.asarray(b.best_sol)).all()
    assert a.rounds == b.rounds and a.nodes_expanded == b.nodes_expanded
    assert (
        a.stats.spilled_tasks,
        a.stats.readmitted_tasks,
        a.stats.cold_bytes_peak,
    ) == (
        b.stats.spilled_tasks,
        b.stats.readmitted_tasks,
        b.stats.cold_bytes_peak,
    )


@pytest.mark.parametrize("problem", PROBLEMS)
def test_solo_fpt_saturated_matches_unsaturated(problem):
    g = _sat_graphs(problem)[0]
    opt = get_problem(problem).sequential(g)[0]
    # feasible decision at the optimum (29 for the vertex cover instance):
    # spill must not change the witness
    sat_big = _solve(g, _cfg(capacity=None, mode="fpt", k=opt), problem)
    sat = _solve(g, _cfg(frontier_spill=True, mode="fpt", k=opt), problem)
    assert sat.found and sat_big.found
    assert sat.best_size == sat_big.best_size
    assert sat.stats.spilled_tasks > 0
    # infeasible decision (vertex cover 20, the others one past their
    # optimum): the WHOLE tree must drain through the cold tier before the
    # engine may answer "no"
    miss = 20 if problem == "vertex_cover" else opt + 1
    unsat = _solve(g, _cfg(frontier_spill=True, mode="fpt", k=miss), problem)
    assert not unsat.found and not unsat.stats.overflow


@pytest.mark.parametrize("problem", PROBLEMS)
def test_solve_many_saturated_lanes_match_unsaturated(problem):
    gs = _sat_graphs(problem) + [erdos_renyi(18, 0.35, 2)]
    big = SolverSession(
        problem, config=_cfg(capacity=None), cache=_CACHE
    ).solve_many(gs)
    spl = SolverSession(
        problem, config=_cfg(frontier_spill=True), cache=_CACHE
    ).solve_many(gs)
    for a, b in zip(big.results, spl.results):
        assert b.best_size == a.best_size
        assert not b.stats.overflow and b.stats.overflow_count == 0
        assert b.stats.readmitted_tasks == b.stats.spilled_tasks
    assert sum(r.stats.spilled_tasks for r in spl.results) > 0


@pytest.mark.parametrize("problem", PROBLEMS)
def test_service_saturated_lanes_match_solve_many(problem):
    gs = _sat_graphs(problem)
    ref = SolverSession(
        problem, config=_cfg(frontier_spill=True), cache=_CACHE
    ).solve_many(gs)
    svc = SolveService(
        problem, _cfg(frontier_spill=True, service_lanes=2), cache=_CACHE
    )
    tix = [svc.submit(g) for g in gs]
    svc.drain()
    for t, want in zip(tix, ref.results):
        got = svc.result(t)
        assert got.best_size == want.best_size
        assert got.stats.spilled_tasks == want.stats.spilled_tasks
        assert not got.stats.overflow


# -- 3. durability: the cold tier rides checkpoints ----------------------------


@pytest.mark.parametrize("problem", PROBLEMS)
def test_checkpoint_resume_mid_spill_is_bit_identical(tmp_path, problem):
    g = _sat_graphs(problem)[0]
    ck = os.path.join(str(tmp_path), "ck")
    cfg = _cfg(frontier_spill=True, checkpoint_dir=ck, checkpoint_every=1)
    full = _solve(g, cfg, problem)
    assert full.stats.spilled_tasks > 0

    # stop mid-solve (cold backlog checkpointed), then resume to the end
    part = _solve(g, cfg.replace(max_rounds=6), problem)
    assert part.rounds == 6
    res = _solve(g, cfg.replace(resume_from=ck), problem)
    assert res.stats.resumed_from is not None
    assert res.best_size == full.best_size
    assert (np.asarray(res.best_sol) == np.asarray(full.best_sol)).all()
    assert res.stats.spilled_tasks == full.stats.spilled_tasks
    assert res.stats.readmitted_tasks == full.stats.readmitted_tasks


@pytest.mark.parametrize("problem", PROBLEMS)
def test_service_restore_rebuilds_spillers(tmp_path, problem):
    gs = _sat_graphs(problem)
    cfg = _cfg(frontier_spill=True, service_lanes=2)
    ref = SolveService(problem, cfg, cache=_CACHE)
    tix = [ref.submit(g) for g in gs]
    ref.drain()
    want = {t: ref.result(t) for t in tix}

    svc = SolveService(problem, cfg, cache=_CACHE)
    tix2 = [svc.submit(g) for g in gs]
    svc.step()
    svc.step()
    ck = os.path.join(str(tmp_path), "sck")
    svc.checkpoint(ck)
    back = SolveService.restore(ck, cache=_CACHE)
    back.drain()
    for t, t2 in zip(tix, tix2):
        got = back.result(t2)
        assert got.best_size == want[t].best_size
        assert got.stats.spilled_tasks == want[t].stats.spilled_tasks


# -- 4. spiller unit behaviour -------------------------------------------------


def _unit_spiller(codec_name="optimized", n=12, P=4, cap=32, graph=None):
    codec = make_codec(codec_name, n)
    return FrontierSpiller(
        codec,
        P,
        cap,
        (0.25, 0.75),
        chunk_rounds=1,
        steps_per_round=2,
        lanes=1,
        donate_k=1,
        graph=graph,
    )


def _full_pool(P=4, CAP=32, W=1, per_worker=30):
    """A (P, CAP, ...) host pool with ``per_worker`` distinct active tasks
    per worker, depths spanning several bands."""
    masks = np.zeros((P, CAP, W), np.uint32)
    sols = np.zeros((P, CAP, W), np.uint32)
    depths = np.zeros((P, CAP), np.int32)
    active = np.zeros((P, CAP), bool)
    for w in range(P):
        for s in range(per_worker):
            masks[w, s] = w * CAP + s + 1
            depths[w, s] = (w * per_worker + s) % 24  # 3 depth bands
            active[w, s] = True
    return masks, sols, depths, active


def _pool_keys(masks, depths, active):
    return sorted(
        (int(masks[w, s, 0]), int(depths[w, s]))
        for w, s in zip(*np.nonzero(active))
    )


def test_pump_host_conserves_tasks_and_respects_watermarks():
    sp = _unit_spiller()  # cap 32 -> high 24, low 8
    assert (sp.low, sp.high) == (8, 24)
    masks, sols, depths, active = _full_pool()
    before = _pool_keys(masks, depths, active)
    assert sp.pump_host(masks, sols, depths, active)
    counts = active.sum(axis=1)
    # every worker spilled down to low; all workers AT low -> no refill
    assert (counts == sp.low).all()
    assert sp.spilled_total == 4 * (30 - sp.low) == sp.cold_tasks
    assert sp.readmitted_total == 0
    # survivors are the deepest tasks: everything cold is shallower than
    # (or band-equal to) what stayed hot, per worker
    for w in range(4):
        deepest_cold = max(b for b in sp._bands[w])
        assert depths[w][active[w]].min() // BAND_WIDTH >= deepest_cold - 1

    # drain everything back through repeated empty pools: the cold tier
    # must conserve the task multiset exactly (no drop, no duplication)
    recovered = _pool_keys(masks, depths, active)
    while sp.cold_tasks:
        m2 = np.zeros_like(masks)
        s2 = np.zeros_like(sols)
        d2 = np.zeros_like(depths)
        a2 = np.zeros_like(active)
        assert sp.pump_host(m2, s2, d2, a2)
        recovered += _pool_keys(m2, d2, a2)
    assert sorted(recovered) == before
    assert sp.readmitted_total == sp.spilled_total


def test_spiller_flat_roundtrip_rebands_by_depth():
    sp = _unit_spiller()
    masks, sols, depths, active = _full_pool()
    sp.pump_host(masks, sols, depths, active)
    assert sp.cold_tasks > 0

    flat = sp.to_flat("s")
    assert FrontierSpiller.present_in(flat, "s")
    assert not FrontierSpiller.present_in(flat, "other")
    sp2 = _unit_spiller()
    sp2.load_flat(flat, "s")
    assert sp2.cold_tasks == sp.cold_tasks
    assert sp2.spilled_total == sp.spilled_total
    assert sp2.cold_bytes_peak == sp.cold_bytes_peak
    flat2 = sp2.to_flat("s")
    assert flat2.keys() == flat.keys()
    for k in flat:
        assert (np.asarray(flat2[k]) == np.asarray(flat[k])).all()
    # bands are keyed by depth // BAND_WIDTH, rebuilt exactly
    for w in range(4):
        assert sorted(sp2._bands[w]) == sorted(sp._bands[w])


def test_basic_spill_codec_requires_graph():
    with pytest.raises(ValueError, match="graph"):
        _unit_spiller("basic")
    g = erdos_renyi(12, 0.4, 5)
    sp = _unit_spiller("basic", graph=g)
    assert sp.codec.record_words == 12 * sp.codec.W + 2 * sp.codec.W + 1


@pytest.mark.parametrize("problem", PROBLEMS)
def test_basic_spill_codec_end_to_end(problem):
    g = _sat_graphs(problem)[0]
    big = _solve(g, _cfg(capacity=None), problem)
    r = _solve(g, _cfg(frontier_spill=True, spill_codec="basic"), problem)
    assert r.best_size == big.best_size
    assert r.stats.spilled_tasks > 0 and not r.stats.overflow
    # basic records are (n+2)W+1 words: the cold tier is accordingly fatter
    opt = _solve(g, _cfg(frontier_spill=True), problem)
    assert r.stats.cold_bytes_peak > opt.stats.cold_bytes_peak
