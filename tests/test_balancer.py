"""Semi-centralized serving balancer: the paper's guarantees, restated."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.balancer import (
    BalancerState,
    RequestBatch,
    SolveBatcher,
    rebalance,
    simulate,
    solve_stream,
)


class _FakeGraph:
    """Just enough of a BitGraph for the admission logic (n, W)."""

    def __init__(self, n):
        self.n = n
        self.W = (n + 31) // 32


def test_rebalance_moves_heaviest_to_neediest():
    reps = [
        RequestBatch(4, [], [10, 99, 5]),  # donor with queue
        RequestBatch(4, [], []),  # starving replica
    ]
    state = BalancerState(reps)
    moved = rebalance(state)
    assert moved == 1
    assert 99 in reps[1].queued_work  # heaviest request moved (§3.4 priority)


def test_failure_free_matching():
    """A matched receiver ALWAYS gets a request: donors must have a queue."""
    reps = [RequestBatch(4, [1], []), RequestBatch(4, [], [])]
    state = BalancerState(reps)
    moved = rebalance(state)
    assert moved == 0  # nobody has queued work -> no (failing) match attempted


@settings(max_examples=30, deadline=None)
@given(
    st.lists(st.integers(1, 64), min_size=4, max_size=60),
    st.integers(2, 8),
)
def test_work_conservation(works, replicas):
    """No request is lost or duplicated across rebalancing rounds."""
    reps = [RequestBatch(4, [], []) for _ in range(replicas)]
    reps[0].queued_work = list(works)
    state = BalancerState(reps)
    for _ in range(5):
        rebalance(state)
        total = sorted(
            w for r in reps for w in (r.active_work + r.queued_work)
        )
        assert total == sorted(works)


def test_solve_batcher_buckets_and_fills():
    """Requests bucket by packed width W (the solve plane's packing rule)
    and full planes drain largest-work-first (the balancer's admit order)."""
    b = SolveBatcher(batch_size=2)
    tickets = [b.submit(_FakeGraph(n)) for n in (20, 40, 22, 44, 24)]
    batches = b.ready_batches()
    # W=1 bucket had 3 queued: the largest two (24, 22) form the full plane
    assert [sorted(g.n for g in b.take(batch)) for batch in batches] == [
        [22, 24],
        [40, 44],
    ]
    # the leftover partial plane only drains on flush
    rest = b.flush()
    assert [[g.n for g in b.take(batch)] for batch in rest] == [[20]]
    assert sorted(s for batch in batches + rest for s in batch) == tickets
    assert b.graphs == {}  # take() evicted everything the stream solved


def test_batcher_status_surfaces_vacant_lanes_of_partial_buckets():
    """A partially-filled bucket reports its unfilled lanes as vacant —
    no placeholder ticket ever pads a plane lane."""
    b = SolveBatcher(batch_size=4)
    for n in (20, 22, 24):
        b.submit(_FakeGraph(n))
    assert b.status() == {
        ("vertex_cover", 1): {"queued": 3, "admitted": 0, "vacant": 4}
    }
    batches = b.flush()  # 3 requests into a 4-lane plane: 1 lane vacant
    assert [len(batch) for batch in batches] == [3]
    assert b.status() == {
        ("vertex_cover", 1): {"queued": 0, "admitted": 0, "vacant": 4}
    }
    # exactly the real instances come back — no padded placeholder result
    assert sorted(g.n for g in b.take(batches[0])) == [20, 22, 24]


def test_batcher_take_rejects_undrained_tickets():
    """take() on a still-queued ticket would leave a stale queue entry to
    drain later with no instance behind it, so it must refuse."""
    b = SolveBatcher(batch_size=2)
    t1 = b.submit(_FakeGraph(20))
    with pytest.raises(ValueError, match=f"{t1}"):
        b.take([t1])  # never drained
    t2 = b.submit(_FakeGraph(22))
    (batch,) = b.ready_batches()
    with pytest.raises(ValueError, match="not in any drained batch"):
        b.take([t1, t2, 99])  # 99 unknown -> still an error, batch intact
    assert sorted(g.n for g in b.take(batch)) == [20, 22]
    with pytest.raises(ValueError):
        b.take(batch)  # double-take: already evicted


def test_solve_stream_returns_submission_order():
    gs = [_FakeGraph(n) for n in (20, 40, 22, 24, 44, 26, 28)]
    seen = []

    def fake_solver(batch, **kw):
        assert len({g.W for g in batch}) == 1  # never mixes buckets
        seen.append([g.n for g in batch])
        return [g.n * 100 for g in batch]

    out = solve_stream(gs, 2, solver=fake_solver)
    assert out == [g.n * 100 for g in gs]
    assert all(len(batch) <= 2 for batch in seen)


def test_buckets_key_on_problem_and_width():
    """Same W, different problem -> different planes: a solve batch compiles
    ONE problem's brancher, so the batcher must never mix problems."""
    b = SolveBatcher(batch_size=2)
    t_vc = [b.submit(_FakeGraph(n), "vertex_cover") for n in (20, 22)]
    t_cl = [b.submit(_FakeGraph(n), "max_clique") for n in (21, 23)]
    batches = b.ready_batches()
    assert len(batches) == 2
    probs = sorted(b.problem_of(batch[0]) for batch in batches)
    assert probs == ["max_clique", "vertex_cover"]
    for batch in batches:
        assert len({b.problem_of(t) for t in batch}) == 1
    assert sorted(t for batch in batches for t in batch) == sorted(t_vc + t_cl)


def test_solve_stream_mixed_problems():
    """A mixed request stream splits per problem and each batch's solver
    call carries its own problem name."""
    gs = [_FakeGraph(n) for n in (20, 21, 22, 23)]
    probs = ["vertex_cover", "mis", "vertex_cover", "mis"]
    calls = []

    def fake_solver(batch, problem=None, **kw):
        calls.append((problem, [g.n for g in batch]))
        return [f"{problem}:{g.n}" for g in batch]

    out = solve_stream(gs, 2, solver=fake_solver, problem=probs)
    assert out == [f"{p}:{g.n}" for p, g in zip(probs, gs)]
    assert sorted(p for p, _ in calls) == ["mis", "vertex_cover"]


def test_balancing_reduces_makespan():
    works = list(np.random.default_rng(0).integers(8, 128, 48))
    off = simulate(8, 4, works, balance=False)
    on = simulate(8, 4, works, balance=True)
    assert on["rounds"] < off["rounds"]
    assert on["idle_slot_steps"] < off["idle_slot_steps"]
    # control plane: two integers per replica per round (paper goal #2)
    assert on["control_ints_per_round"] == 16
