"""The SPMD superstep engine vs the sequential ground truth (+ elasticity)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core.superstep import build_superstep_fn, make_worker_state
from repro.graphs.bitgraph import n_words
from repro.graphs.generators import erdos_renyi
from repro.problems.base import make_data
from repro.problems.registry import get_problem
from repro.problems.sequential import solve_sequential

VC = get_problem("vertex_cover")
PROBLEMS = ["vertex_cover", "max_clique", "mis"]


@pytest.mark.parametrize("problem", PROBLEMS)
@pytest.mark.parametrize("policy", [True, False])
@pytest.mark.parametrize("codec", ["optimized", "basic"])
def test_matches_sequential(policy, codec, problem):
    spec = get_problem(problem)
    g = erdos_renyi(40, 0.28, 0)
    want, _, _ = spec.sequential(g)
    r = E.solve(
        g, num_workers=6, steps_per_round=8,
        policy_priority=policy, codec=codec, problem=problem,
    )
    assert r.best_size == want
    assert spec.verify(g, r.best_sol)
    assert not r.overflow


@pytest.mark.parametrize("problem", PROBLEMS)
def test_lanes(problem):
    g = erdos_renyi(44, 0.25, 4)
    want, _, _ = get_problem(problem).sequential(g)
    r = E.solve(g, num_workers=4, steps_per_round=4, lanes=4, problem=problem)
    assert r.best_size == want
    assert not r.overflow


@pytest.mark.parametrize("problem", PROBLEMS)
def test_fpt_mode(problem):
    spec = get_problem(problem)
    g = erdos_renyi(34, 0.3, 9)
    opt, _, _ = spec.sequential(g)
    r = E.solve(g, num_workers=4, mode="fpt", k=opt, problem=problem)
    # a decision at the optimum is met by an optimal witness, no better
    assert r.best_size == opt
    assert spec.verify(g, r.best_sol)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_random_graphs_property(seed):
    g = erdos_renyi(30, 0.22, seed)
    want, _, _ = solve_sequential(g)
    r = E.solve(g, num_workers=5, steps_per_round=8)
    assert r.best_size == want
    assert not r.overflow


def test_snapshot_restore_resize():
    """Fault tolerance: checkpoint mid-run, restart on a DIFFERENT worker
    count, still optimal (elastic re-meshing of the frontier)."""
    g = erdos_renyi(46, 0.25, 2)
    want, _, _ = solve_sequential(g)
    W = n_words(g.n)
    cap = 4 * g.n + 8
    state = jax.vmap(lambda _: make_worker_state(cap, W, g.n + 1))(jnp.arange(8))
    state = E._scatter_startup(state, VC, g, 8)
    data = make_data(VC, g)
    fn = build_superstep_fn(VC, data, num_workers=8, steps_per_round=4, lanes=1)
    for _ in range(3):
        state, done = fn(state)
    snap = E.snapshot(state)  # "node failure" here
    resized = E.resize(E.restore(snap), 5)
    r = E.solve(g, num_workers=5, steps_per_round=8, initial_state=resized)
    assert r.best_size == want


@pytest.mark.parametrize("problem", PROBLEMS)
def test_transfer_accounting(problem):
    g = erdos_renyi(40, 0.28, 0)
    W = n_words(g.n)
    rec_opt = 2 * W + 1
    rec_bas = (g.n + 2) * W + 1
    kw = dict(num_workers=4, problem=problem)
    # gather: every transfer round moves the full P-row record table
    r_opt = E.solve(g, codec="optimized", transfer_impl="gather", **kw)
    r_bas = E.solve(g, codec="basic", transfer_impl="gather", **kw)
    assert r_opt.transfer_bytes_total == 4 * rec_opt * 4 * r_opt.transfer_rounds
    assert r_bas.transfer_bytes_total == 4 * rec_bas * 4 * r_bas.transfer_rounds
    # sparse: payload == exactly the records that matched (paper: the donated
    # task is the sole payload), regardless of P
    r_sp = E.solve(g, codec="optimized", transfer_impl="sparse", **kw)
    assert r_sp.transfer_bytes_total == 4 * rec_opt * r_sp.tasks_transferred
    assert r_sp.transfer_bytes_total < r_opt.transfer_bytes_total
    # rounds that ran no transfer move zero payload on either path
    assert r_sp.transfer_rounds <= r_sp.rounds
    # the paper's point: control plane is O(P) integers regardless of codec —
    # ONE packed i32 per worker by default, three with packed_status=False
    assert r_opt.control_bytes_per_round == r_bas.control_bytes_per_round == 16
    r_unpacked = E.solve(g, packed_status=False, **kw)
    assert r_unpacked.control_bytes_per_round == 48


@pytest.mark.parametrize("problem", PROBLEMS)
def test_chunked_loop_matches_per_round(problem):
    """K supersteps per host sync must be bit-identical to per-round syncs."""
    g = erdos_renyi(40, 0.28, 0)
    want, _, _ = get_problem(problem).sequential(g)
    kw = dict(num_workers=6, steps_per_round=8, problem=problem)
    r1 = E.solve(g, chunk_rounds=1, **kw)
    rk = E.solve(g, chunk_rounds=32, **kw)
    assert r1.best_size == rk.best_size == want
    assert (r1.best_sol == rk.best_sol).all()
    assert r1.rounds == rk.rounds
    assert r1.nodes_expanded == rk.nodes_expanded


@pytest.mark.parametrize("problem", PROBLEMS)
def test_multi_task_donation(problem):
    g = erdos_renyi(44, 0.25, 4)
    want, _, _ = get_problem(problem).sequential(g)
    kw = dict(num_workers=8, steps_per_round=4, problem=problem)
    r1 = E.solve(g, donate_k=1, **kw)
    r4 = E.solve(g, donate_k=4, **kw)
    assert r1.best_size == r4.best_size == want
    assert not r4.overflow
    # single-task donation ships exactly one record per match...
    assert r1.tasks_transferred >= r1.transfer_rounds
    # ...while k=4 actually exploits the batch (deep donors ship > 1/match)
    assert r4.tasks_transferred > r4.transfer_rounds
    assert (
        r4.tasks_transferred / max(r4.transfer_rounds, 1)
        > r1.tasks_transferred / max(r1.transfer_rounds, 1)
    )


def test_scatter_startup_overflow_uses_waiting_list_order():
    """Regression: overflow tasks (i >= P when BFS over-expands) must follow
    the same Algorithm-7 permutation as the first P, not raw i mod P."""
    from repro.core.waiting_list import startup_assignment
    from repro.problems.sequential import expand_frontier

    g = erdos_renyi(40, 0.28, 0)
    P = 6
    W = n_words(g.n)
    tasks = expand_frontier(g, num_tasks=2 * P + 3)  # BFS over-expansion
    assert len(tasks) > P
    state = jax.vmap(lambda _: make_worker_state(40, W, g.n + 1))(jnp.arange(P))
    placed = E._scatter_startup(state, VC, g, P, tasks=tasks)
    order = startup_assignment(max_b=2, p=P)
    want_counts = np.zeros(P, np.int64)
    for i in range(len(tasks)):
        want_counts[order[i % P] - 1] += 1
    active = np.asarray(placed.frontier.active)
    got_counts = active.sum(axis=1)
    assert (got_counts == want_counts).all()
    # every BFS task landed somewhere, none lost or duplicated
    placed_recs = sorted(
        np.asarray(placed.frontier.masks)[w, s].tobytes()
        + np.asarray(placed.frontier.sols)[w, s].tobytes()
        for w in range(P)
        for s in range(active.shape[1])
        if active[w, s]
    )
    want_recs = sorted(m.tobytes() + s.tobytes() for m, s, _ in tasks)
    assert placed_recs == want_recs
