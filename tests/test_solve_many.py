"""The batched multi-instance solve plane vs B independent solo solves.

`solve_many` is an amortization, not an approximation: per-instance
`best_size`/`best_sol` (and the deterministic stats) must be bit-identical
to running `engine.solve` once per instance, across padding, bucketing and
host-side batch compaction — and donation must never cross the instance
axis.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import engine as E
from repro.core.frontier import Frontier
from repro.core.superstep import (
    WorkerState,
    build_batch_superstep_fn,
)
from repro.graphs.generators import erdos_renyi
from repro.problems.registry import get_problem
from repro.problems.sequential import solve_sequential
from repro.problems.vertex_cover import VCProblem

PROBLEMS = ["vertex_cover", "max_clique", "mis"]


def _assert_matches_solo(graphs, batch, **solve_kw):
    for g, b in zip(graphs, batch.results):
        s = E.solve(g, **solve_kw)
        assert s.best_size == b.best_size
        same_sol = (s.best_sol is None and b.best_sol is None) or (
            (s.best_sol == b.best_sol).all()
        )
        assert same_sol
        assert s.rounds == b.rounds
        assert s.nodes_expanded == b.nodes_expanded
        assert s.tasks_transferred == b.tasks_transferred
        assert s.transfer_bytes_total == b.transfer_bytes_total
        assert not b.overflow


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10_000))
def test_batch_matches_singles_property(seed):
    """B mixed-size random instances, padded onto one plane: bit-identical
    results and stats vs B solo solves (the padding path is always hit —
    sizes differ within the bucket)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(10, 27, size=3)
    graphs = [
        erdos_renyi(int(n), 0.3, int(s))
        for n, s in zip(sizes, rng.integers(0, 1000, size=3))
    ]
    kw = dict(num_workers=4, steps_per_round=4)
    batch = E.solve_many(graphs, **kw)
    _assert_matches_solo(graphs, batch, **kw)
    for g, b in zip(graphs, batch.results):
        want, _, _ = solve_sequential(g)
        assert b.best_size == want


@pytest.mark.parametrize("problem", PROBLEMS)
def test_mixed_word_buckets_preserve_order(problem):
    """Instances with different packed widths W split into separate buckets;
    results still come back in submission order."""
    graphs = [
        erdos_renyi(40, 0.28, 0),  # W=2
        erdos_renyi(20, 0.3, 1),  # W=1
        erdos_renyi(36, 0.28, 2),  # W=2 (padded to 40 in its bucket)
        erdos_renyi(14, 0.3, 3),  # W=1 (padded to 20)
    ]
    kw = dict(num_workers=4, steps_per_round=8, problem=problem)
    batch = E.solve_many(graphs, **kw)
    assert sorted(W for W, _, _ in batch.buckets) == [1, 2]
    assert sorted(i for _, _, idxs in batch.buckets for i in idxs) == [0, 1, 2, 3]
    _assert_matches_solo(graphs, batch, **kw)


@pytest.mark.parametrize("problem", PROBLEMS)
def test_compaction_bit_identical(problem):
    """Early-exit compaction (finished lanes dropped, batch re-packed to a
    smaller executable) must not perturb the surviving instances."""
    graphs = [erdos_renyi(12, 0.3, s) for s in range(6)] + [
        erdos_renyi(30, 0.25, 0),
        erdos_renyi(30, 0.28, 6),
    ]
    kw = dict(num_workers=4, steps_per_round=1, chunk_rounds=1, problem=problem)
    batch = E.solve_many(graphs, compact_threshold=0.5, **kw)
    assert batch.compactions > 0
    _assert_matches_solo(graphs, batch, **kw)


def test_basic_codec_buckets_by_exact_n():
    """codec="basic" pads records by n·W words, so mixed n must split into
    exact-(W, n) buckets — per-instance payload accounting stays identical
    to the solo run."""
    graphs = [erdos_renyi(24, 0.3, 1), erdos_renyi(20, 0.3, 2)]
    kw = dict(num_workers=4, steps_per_round=4, codec="basic")
    batch = E.solve_many(graphs, **kw)
    assert len(batch.buckets) == 2  # same W, different n
    _assert_matches_solo(graphs, batch, **kw)


@pytest.mark.parametrize("problem", PROBLEMS)
def test_fpt_mode_per_instance_bounds(problem):
    spec = get_problem(problem)
    graphs = [erdos_renyi(24, 0.3, 1), erdos_renyi(20, 0.3, 2)]
    opts = [spec.sequential(g)[0] for g in graphs]
    # per-instance k: first solvable at its optimum, second unsatisfiable
    # one past its optimum
    past = -1 if spec.objective.startswith("minimize") else 1
    ks = [opts[0], opts[1] + past]
    batch = E.solve_many(graphs, num_workers=4, mode="fpt", k=ks, problem=problem)
    # a decision at the optimum is met by an optimal witness, no better
    assert batch.results[0].best_size == opts[0]
    assert batch.results[1].best_size == -1
    assert batch.results[1].best_sol is None


def _hand_built_batch(masks_spec, P=4, cap=8, W=1, n=16):
    """(B, P, cap) worker state with explicit frontier contents and a
    matching (trivial) batched problem.  masks_spec[b] = list of
    (worker, mask, depth)."""
    B = len(masks_spec)
    masks = np.zeros((B, P, cap, W), np.uint32)
    sols = np.zeros((B, P, cap, W), np.uint32)
    depths = np.zeros((B, P, cap), np.int32)
    active = np.zeros((B, P, cap), bool)
    slot = np.zeros((B, P), np.int64)
    for b, spec in enumerate(masks_spec):
        for w, mask, depth in spec:
            s = slot[b, w]
            masks[b, w, s, 0] = mask
            depths[b, w, s] = depth
            active[b, w, s] = True
            slot[b, w] += 1
    z = jnp.zeros((B, P), jnp.int32)
    state = WorkerState(
        frontier=Frontier(
            masks=jnp.asarray(masks),
            sols=jnp.asarray(sols),
            depths=jnp.asarray(depths),
            active=jnp.asarray(active),
            overflow=jnp.zeros((B, P), bool),
            dropped=jnp.zeros((B, P), jnp.int32),
        ),
        best_val=jnp.full((B, P), 99, jnp.int32),
        local_best_val=jnp.full((B, P), 99, jnp.int32),
        best_sol=jnp.zeros((B, P, W), jnp.uint32),
        nodes_expanded=z,
        tasks_sent=z,
        tasks_recv=z,
        rounds=z,
        transfer_rounds=z,
        payload_words=z,
        reduce_lane_sweeps=z,
        reduce_worker_sweeps=z,
        reduce_fires_rule1=z,
        reduce_fires_rule2=z,
        reduce_fires_rule3=z,
        reduce_rule3_probes=z,
        tasks_sent_remote=z,
    )
    v = np.arange(n, dtype=np.int32)
    problems = VCProblem(
        n=jnp.full((B,), n, jnp.int32),
        adj=jnp.zeros((B, n, W), jnp.uint32),
        word_idx=jnp.asarray(v // 32),
        bit_idx=jnp.asarray((v % 32).astype(np.uint32)),
    )
    return state, problems


@pytest.mark.parametrize("problem", PROBLEMS)
def test_donation_never_crosses_instance_axis(problem):
    """Instance 0 has idle workers but NO donor; instance 1 has a donor.
    The rebalance must stay inside each instance: instance 0 receives
    nothing even though instance 1's donor has spare tasks."""
    state, problems = _hand_built_batch(
        [
            # pending=1 -> neither idle nor donor; workers 1-3 idle
            [(0, 0xAAAA, 5)],
            # worker 0 donates its shallowest (0x7, depth 1) inside inst 1
            [(0, 0x1, 3), (0, 0x3, 2), (0, 0x7, 1)],
        ]
    )
    fn = build_batch_superstep_fn(
        get_problem(problem), problems, steps_per_round=0, lanes=1
    )
    new, done = fn(state)
    assert not bool(done[0]) and not bool(done[1])

    # instance 0: untouched — no transfer in, no tasks lost
    assert int(np.asarray(new.tasks_recv)[0].sum()) == 0
    assert int(np.asarray(new.tasks_sent)[0].sum()) == 0
    act0 = np.asarray(new.frontier.active)[0]
    assert act0.sum() == 1
    masks0 = np.asarray(new.frontier.masks)[0][act0]
    assert set(masks0[:, 0].tolist()) == {0xAAAA}

    # instance 1: exactly one intra-instance donation (shallowest record)
    assert int(np.asarray(new.tasks_sent)[1].sum()) == 1
    assert int(np.asarray(new.tasks_recv)[1].sum()) == 1
    act1 = np.asarray(new.frontier.active)[1]
    assert act1.sum() == 3  # moved, not duplicated or lost
    masks1 = np.asarray(new.frontier.masks)[1][act1]
    assert sorted(masks1[:, 0].tolist()) == [0x1, 0x3, 0x7]
    recv_worker = np.asarray(new.tasks_recv)[1].argmax()
    assert recv_worker != 0
    got = np.asarray(new.frontier.masks)[1, recv_worker][
        np.asarray(new.frontier.active)[1, recv_worker]
    ]
    assert got[:, 0].tolist() == [0x7]


@pytest.mark.parametrize("problem", PROBLEMS)
def test_per_instance_quiescence(problem):
    """An empty instance is done immediately; a live one in the same batch
    keeps its pending work — done is a per-instance vector."""
    state, problems = _hand_built_batch([[], [(0, 0x1, 0), (1, 0x3, 1)]])
    fn = build_batch_superstep_fn(
        get_problem(problem), problems, steps_per_round=0, lanes=1
    )
    _, done = fn(state)
    assert bool(done[0]) and not bool(done[1])
