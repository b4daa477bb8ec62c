"""The continuous-batching ``SolveService``: live lane lifecycle guarantees.

Four claims from the continuous-admission refactor:

1. **Bit-identity through the live plane** — a request admitted into a
   service lane (even mid-stream, into a lane freed by another instance)
   produces the SAME result — branching decisions AND counters — as its
   solo ``SolverSession.solve``, pinned against ``tests/golden_vc.json``
   (including the basic codec's byte accounting and fpt mode).
2. **Zero-retrace admission** — admitting into a freed lane is pure data
   movement: ``superstep.PLANE_TRACES`` does not move after the first
   drain, no matter how many instances churn through.
3. **Streaming lifecycle** — easy instances complete and stream out while
   hard lanemates keep solving (out-of-order completion); ``result()`` on
   a not-ready ticket raises; overflow/deadline/occupancy accounting
   propagates into the streamed results.
4. **Deterministic scheduling** — priority/deadline admission order and
   per-tenant lane caps are pure functions of the submit sequence.
"""

import json
import pathlib

import numpy as np
import pytest

from repro.api import (
    PlaneCache,
    SolveConfig,
    SolveService,
    SolverSession,
    solve_stream_session,
)
from repro.api.backends import config_from_legacy
from repro.api.service import LaneScheduler, SolveRequest
from repro.core import superstep
from repro.graphs.generators import erdos_renyi
from repro.problems.registry import get_problem
from repro.problems.sequential import (
    solve_sequential,
    solve_sequential_max_clique,
)

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden_vc.json").read_text()
)
PROBLEMS = ["vertex_cover", "max_clique", "mis"]


def _minimizes(problem) -> bool:
    return get_problem(problem).objective.startswith("minimize")


def _check_golden(r, want: dict):
    got = {
        "best_size": int(r.best_size),
        "best_sol": [int(w) for w in np.asarray(r.best_sol, np.uint32)],
        "rounds": int(r.rounds),
        "nodes_expanded": int(r.nodes_expanded),
        "tasks_transferred": int(r.tasks_transferred),
        "transfer_rounds": int(r.stats.transfer_rounds),
        "transfer_bytes_total": int(r.stats.transfer_bytes_total),
        "overflow": bool(r.stats.overflow),
    }
    assert got == want


# -- 1. bit-identity: the live plane vs the solo goldens -----------------------


@pytest.mark.parametrize("label", sorted(GOLDEN["solo"]))
def test_service_result_bit_identical_to_solo_golden(label):
    """The golden instance solves in a lane NEXT TO another live instance
    and still reproduces its solo trajectory exactly — the frozen-lane
    select means lanemates can never perturb each other."""
    case = GOLDEN["solo"][label]
    gkw = case["graph"]
    g = erdos_renyi(gkw["n"], gkw["p"], gkw["seed"])
    cfg = config_from_legacy(**case["solve_kw"]).replace(service_lanes=2)
    svc = SolveService("vertex_cover", cfg)
    g_mate = erdos_renyi(gkw["n"], gkw["p"], gkw["seed"] + 77)
    ticket = svc.submit(g)
    lanemate = svc.submit(g_mate)
    svc.drain()
    r = svc.result(ticket)
    assert (r.problem, r.backend, r.found) == ("vertex_cover", "spmd", True)
    _check_golden(r, case["result"])
    # the lanemate is a real solve too, not a casualty of the golden's lane
    assert svc.result(lanemate).best_size == solve_sequential(g_mate)[0]
    assert svc.idle() and not svc.ready(ticket)  # result() pops


def test_service_fpt_bit_identical_to_golden():
    case = GOLDEN["fpt"]
    gkw = case["graph"]
    g = erdos_renyi(gkw["n"], gkw["p"], gkw["seed"])
    cfg = SolveConfig(
        num_workers=4, mode="fpt", k=case["k"], service_lanes=2
    )
    svc = SolveService("vertex_cover", cfg)
    t = svc.submit(g)  # k defaults from the config in fpt mode
    svc.drain()
    _check_golden(svc.result(t), case["result"])


@pytest.mark.parametrize("problem", PROBLEMS)
def test_service_churn_matches_solo_across_sizes(problem):
    """A stream wider than the lane count: every instance that churns
    through a reused lane matches its solo solve bit-for-bit (best, rounds,
    counters), across mixed sizes within the W bucket."""
    cfg = SolveConfig(num_workers=4, steps_per_round=8, service_lanes=2)
    sizes = [18, 26, 22, 30, 20, 24]
    gs = [erdos_renyi(n, 0.3, 200 + i) for i, n in enumerate(sizes)]
    svc = SolveService(problem, cfg)
    tickets = [svc.submit(g) for g in gs]
    svc.drain()
    sess = SolverSession(problem=problem, config=cfg)
    for t, g in zip(tickets, gs):
        r, solo = svc.result(t), sess.solve(g)
        assert r.best_size == solo.best_size
        assert r.rounds == solo.rounds
        assert r.nodes_expanded == solo.nodes_expanded
        assert r.tasks_transferred == solo.tasks_transferred
        assert r.stats.transfer_bytes_total == solo.stats.transfer_bytes_total
        assert (np.asarray(r.best_sol) == np.asarray(solo.best_sol)).all()


# -- 2. zero-retrace admission into freed lanes --------------------------------


@pytest.mark.parametrize("problem", PROBLEMS)
def test_admission_into_freed_lanes_traces_nothing(problem):
    cfg = SolveConfig(num_workers=4, steps_per_round=8, service_lanes=2)
    svc = SolveService(problem, cfg)
    wave1 = [svc.submit(erdos_renyi(20, 0.3, s)) for s in range(2)]
    svc.drain()  # compiles the plane (first wave)
    traces0 = superstep.PLANE_TRACES
    wave2 = [svc.submit(erdos_renyi(24, 0.3, 10 + s)) for s in range(4)]
    svc.drain()
    assert superstep.PLANE_TRACES == traces0, (
        "admitting into freed lanes must be pure data movement — a plane "
        "re-trace means the live-plane shape contract broke"
    )
    for t in wave1 + wave2:
        assert svc.ready(t)
    stats = svc.stats()
    assert stats["completed"] == 6 and stats["planes"] == 1
    assert 0.0 < stats["occupancy"] <= 1.0


# -- 3. streaming lifecycle ----------------------------------------------------


@pytest.mark.parametrize("problem", PROBLEMS)
def test_out_of_order_completion_streams_early_finishers(problem):
    """An easy instance submitted AFTER a hard one completes first and its
    result is poppable while the hard lane keeps solving."""
    sequential = get_problem(problem).sequential
    cfg = SolveConfig(
        num_workers=2, steps_per_round=2, chunk_rounds=1, service_lanes=2,
        admission="fifo",
    )
    svc = SolveService(problem, cfg)
    hard = svc.submit(erdos_renyi(30, 0.5, 3))
    easy = svc.submit(erdos_renyi(8, 0.3, 4))
    completed, steps = [], 0
    while not svc.ready(easy):
        completed.extend(svc.step())
        steps += 1
        assert steps < 200
    assert completed[0] == easy
    if not svc.ready(hard):  # the point: easy streamed out mid-solve
        assert svc.status()["planes"]["(1, None)"]["tickets"] == [hard]
    r_easy = svc.result(easy)
    assert r_easy.best_size == sequential(erdos_renyi(8, 0.3, 4))[0]
    svc.drain()
    assert svc.result(hard).best_size == sequential(
        erdos_renyi(30, 0.5, 3)
    )[0]


def test_result_before_completion_raises_keyerror():
    svc = SolveService(
        "vertex_cover", SolveConfig(num_workers=2, service_lanes=2)
    )
    t = svc.submit(erdos_renyi(12, 0.3, 0))
    assert not svc.ready(t)
    with pytest.raises(KeyError):
        svc.result(t)  # still queued — step()/drain() first
    with pytest.raises(KeyError):
        svc.result(999)  # unknown ticket
    svc.drain()
    assert svc.ready(t) and svc.result(t).found


# an instance per problem that overruns capacity 6; a clique search only
# runs deep enough on a dense graph
_STARVING = {
    "vertex_cover": (26, 0.3, 0),
    "max_clique": (26, 0.9, 0),
    "mis": (26, 0.3, 0),
}


@pytest.mark.parametrize("problem", PROBLEMS)
def test_overflow_count_propagates_into_streamed_results(problem):
    """A capacity-starved config overflows identically through the service
    and the solo path — the live plane does not hide dropped work."""
    cfg = SolveConfig(
        num_workers=2, steps_per_round=4, capacity=6, service_lanes=2
    )
    g = erdos_renyi(*_STARVING[problem])
    solo = SolverSession(problem=problem, config=cfg).solve(g)
    assert solo.stats.overflow_count > 0  # the config really starves
    svc = SolveService(problem, cfg)
    t = svc.submit(g)
    svc.drain()
    r = svc.result(t)
    assert r.stats.overflow_count == solo.stats.overflow_count
    assert r.stats.overflow and r.best_size == solo.best_size


@pytest.mark.parametrize("problem", PROBLEMS)
def test_deadline_evicts_with_anytime_result(problem):
    cfg = SolveConfig(
        num_workers=2, steps_per_round=2, chunk_rounds=1, service_lanes=2
    )
    g = erdos_renyi(32, 0.5, 7)
    svc = SolveService(problem, cfg)
    t = svc.submit(g, deadline=1)
    svc.drain()
    r = svc.result(t)
    assert r.stats.service.deadline_hit is True
    assert r.rounds == 1  # stopped at the budget, not at optimality
    assert svc.stats()["evicted"] == 1
    # the anytime answer is a valid-but-possibly-loose bound vs full solve
    full = SolverSession(problem=problem, config=cfg).solve(g)
    if _minimizes(problem):
        assert r.best_size >= full.best_size
    else:
        assert r.best_size <= full.best_size
    # a finished (non-evicted) lane never reports a deadline hit
    svc2 = SolveService(problem, cfg)
    t2 = svc2.submit(erdos_renyi(12, 0.3, 1), deadline=500)
    svc2.drain()
    assert svc2.result(t2).stats.service.deadline_hit is False


def test_wall_deadline_evicts_on_injected_clock():
    """``deadline_s`` is a WALL budget on the service's injectable clock:
    blowing it between steps evicts with an anytime result flagged
    ``wall_deadline_hit`` (and NOT ``deadline_hit`` — that stays the
    superstep-budget flag).  No ``time.time()`` in traced code: advancing
    the fake clock is the only stimulus."""

    class FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    clk = FakeClock()
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=2, service_lanes=2
    )
    svc = SolveService("vertex_cover", cfg, clock=clk)
    g = erdos_renyi(40, 0.28, 0)
    t = svc.submit(g, deadline_s=5.0)
    svc.step()  # still within budget: not evicted
    assert t not in [*svc._results]
    clk.t = 10.0  # budget blown between steps
    assert svc.step() == [t]
    r = svc.result(t)
    assert r.stats.service.wall_deadline_hit is True
    assert r.stats.service.deadline_hit is False
    assert r.found  # anytime incumbent, valid but possibly loose
    full = SolverSession(problem="vertex_cover", config=cfg).solve(g)
    assert r.best_size >= full.best_size
    assert svc.stats()["evicted"] == 1

    # a solve finishing before its wall budget never reports the hit
    svc2 = SolveService("vertex_cover", cfg, clock=FakeClock())
    t2 = svc2.submit(erdos_renyi(12, 0.3, 1), deadline_s=100.0)
    svc2.drain()
    s2 = svc2.result(t2).stats.service
    assert s2.wall_deadline_hit is False and s2.deadline_hit is False


def test_wall_deadline_survives_checkpoint_restore(tmp_path):
    """``deadline_s`` rides the request metadata through checkpoint():
    a restored service still enforces the original wall budget."""

    class FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, service_lanes=2
    )
    svc = SolveService("vertex_cover", cfg, clock=FakeClock())
    t = svc.submit(erdos_renyi(40, 0.28, 0), deadline_s=5.0)
    svc.step()
    svc.checkpoint(str(tmp_path / "ck"))
    back = SolveService.restore(str(tmp_path / "ck"))
    req = next(
        r
        for p in back._planes.values()
        for r in p.requests
        if r is not None
    )
    assert req.deadline_s == 5.0


def test_submit_validation():
    svc = SolveService(
        "vertex_cover", SolveConfig(num_workers=2, service_lanes=2)
    )
    with pytest.raises(ValueError, match="fpt"):
        svc.submit(erdos_renyi(10, 0.3, 0), k=3)  # k needs mode='fpt'
    with pytest.raises(ValueError, match="deadline"):
        svc.submit(erdos_renyi(10, 0.3, 0), deadline=0)
    with pytest.raises(ValueError, match="servable"):
        SolveService(
            "vertex_cover", SolveConfig(num_workers=2, use_mesh=True)
        )


# -- 4. deterministic scheduling -----------------------------------------------


def test_priority_admission_order_is_deterministic():
    sched = LaneScheduler("priority")
    reqs = [
        SolveRequest(ticket=0, g=None, priority=0),
        SolveRequest(ticket=1, g=None, priority=5, deadline=9),
        SolveRequest(ticket=2, g=None, priority=5, deadline=3),
        SolveRequest(ticket=3, g=None, priority=5),  # no deadline: last of the 5s
        SolveRequest(ticket=4, g=None, priority=1),
    ]
    for r in reqs:
        sched.push(r)
    assert [r.ticket for r in sched.ordered()] == [2, 1, 3, 4, 0]
    # fifo ignores all of that
    fifo = LaneScheduler("fifo")
    for r in reversed(reqs):
        fifo.push(r)
    assert [r.ticket for r in fifo.ordered()] == [0, 1, 2, 3, 4]


def test_tenant_cap_skips_without_starving():
    """tenant_max_lanes=1: tenant a's second request waits even though a
    lane is free, tenant b overtakes into it, and a2 still completes."""
    cfg = SolveConfig(
        num_workers=2, steps_per_round=2, chunk_rounds=1, service_lanes=2,
        admission="fifo", tenant_max_lanes=1,
    )
    # hard enough to outlive the first chunk, so occupancy is observable
    svc = SolveService("vertex_cover", cfg)
    a1 = svc.submit(erdos_renyi(30, 0.5, 0), tenant="a")
    a2 = svc.submit(erdos_renyi(30, 0.5, 1), tenant="a")
    b1 = svc.submit(erdos_renyi(30, 0.5, 2), tenant="b")
    svc.step()
    st = svc.status()
    lanes = st["planes"]["(1, None)"]
    assert lanes["occupied"] == 2
    assert lanes["tickets"] == sorted([a1, b1])  # a2 skipped, b1 overtook
    assert st["queued"] == 1
    svc.drain()
    for t in (a1, a2, b1):
        assert svc.ready(t)


@pytest.mark.parametrize("problem", PROBLEMS)
def test_fpt_per_request_k_overrides_config(problem):
    g = erdos_renyi(20, 0.3, 2)
    want, _, _ = get_problem(problem).sequential(g)
    cfg = SolveConfig(num_workers=4, mode="fpt", k=want, service_lanes=2)
    svc = SolveService(problem, cfg)
    t_yes = svc.submit(g)  # config k == optimum: found
    # per-request k one past the optimum: infeasible
    t_no = svc.submit(g, k=want - 1 if _minimizes(problem) else want + 1)
    svc.drain()
    assert svc.result(t_yes).found is True
    assert svc.result(t_no).found is False


# -- 5. the continuous path under solve_stream_session -------------------------


def test_solve_stream_session_mixed_problem_churn():
    """A mixed-problem stream wider than the lane count routes through one
    continuous service per problem (shared cache), preserves submission
    order, matches the sequential references and keeps one plane per
    problem (no per-wave re-compiles)."""
    sizes = [16, 18, 14, 20, 16, 18, 14, 20]
    probs = ["vertex_cover", "max_clique"] * 4
    gs = [erdos_renyi(n, 0.35, 40 + i) for i, n in enumerate(sizes)]
    cache = PlaneCache()
    out = solve_stream_session(
        gs, batch_size=2, problem=probs, cache=cache,
        config=SolveConfig(num_workers=4, steps_per_round=8),
    )
    assert [r.problem for r in out] == probs
    for g, r in zip(gs, out):
        ref = (
            solve_sequential if r.problem == "vertex_cover"
            else solve_sequential_max_clique
        )
        assert r.best_size == ref(g)[0]
    assert cache.stats().planes == 2  # one live plane per problem, reused
