"""The fused exploration plane: bit-identical to the reference, by contract.

Four guarantees from the fused-plane PR:

1. **Golden bit-identity** — ``explore_impl="fused"`` reproduces the pinned
   pre-fused vertex-cover goldens exactly (solo, fpt, solve_many incl.
   padding + compaction), and ``"reference"`` still does too: the knob
   switches implementations, never the search.
2. **Cross-problem identity** — max-clique and MIS full results (best,
   sol, rounds, nodes, transfers) agree between the two impls on random
   graphs, solo and batched.
3. **Expansion-level identity** — per problem, the hand-fused
   ``expand_tasks`` matches the composed per-task callables on random
   task batches (every engine-consumed field), and the composed default
   itself matches the callables it wraps — so third-party plugins without
   a fused impl are covered too.
4. **Cheap frontier pop** — ``pop_deepest_cheap`` is state- and
   lane-identical to the reference ``top_k`` pop on random frontiers.
"""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import SolveConfig, SolverSession
from repro.api.backends import config_from_legacy
from repro.core.frontier import make_frontier, pop_deepest, pop_deepest_cheap, push_many
from repro.graphs.generators import erdos_renyi
from repro.problems import base as B
from repro.problems.registry import get_problem

GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden_vc.json").read_text()
)

IMPLS = ("fused", "reference")


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


def _session(legacy_kw: dict, impl: str, **extra) -> SolverSession:
    return SolverSession(
        problem="vertex_cover",
        config=config_from_legacy(**legacy_kw, **extra).replace(
            explore_impl=impl
        ),
    )


# -- 1. both impls against the pinned pre-fused goldens ------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("label", sorted(GOLDEN["solo"]))
def test_solo_golden_bit_identical(impl, label):
    case = GOLDEN["solo"][label]
    gkw = case["graph"]
    g = erdos_renyi(gkw["n"], gkw["p"], gkw["seed"])
    r = _session(case["solve_kw"], impl).solve(g)
    _check_golden(r, case["result"])


@pytest.mark.parametrize("impl", IMPLS)
def test_fpt_golden_bit_identical(impl):
    case = GOLDEN["fpt"]
    gkw = case["graph"]
    g = erdos_renyi(gkw["n"], gkw["p"], gkw["seed"])
    r = _session({"num_workers": 4}, impl, mode="fpt", k=case["k"]).solve(g)
    _check_golden(r, case["result"])


@pytest.mark.parametrize("impl", IMPLS)
def test_solve_many_golden_bit_identical(impl):
    """The batched plane under both impls, including the padding (mixed n in
    one W bucket) and host-side compaction paths."""
    case = GOLDEN["many"]
    graphs = [
        erdos_renyi(n, case["p"], case["seed0"] + i)
        for i, n in enumerate(case["sizes"])
    ]
    batch = _session(case["solve_kw"], impl).solve_many(graphs)
    assert batch.compactions == case["compactions"]
    assert [[W, n_max, idxs] for W, n_max, idxs in batch.buckets] == case["buckets"]
    for r, want in zip(batch.results, case["results"]):
        _check_golden(r, want)


# -- 2. clique / MIS: fused == reference on full results -----------------------


def _result_key(r):
    return (
        r.best_size,
        tuple(int(w) for w in np.asarray(r.best_sol, np.uint32)),
        r.rounds,
        r.nodes_expanded,
        r.tasks_transferred,
        int(r.stats.overflow_count),
    )


@pytest.mark.parametrize("problem", ["max_clique", "mis"])
def test_clique_mis_fused_matches_reference_solo_and_fpt(problem):
    for seed in (0, 1, 2):
        g = erdos_renyi(16, 0.4, seed)
        keys = {}
        for impl in IMPLS:
            cfg = SolveConfig(
                num_workers=4, steps_per_round=8, explore_impl=impl
            )
            keys[impl] = _result_key(
                SolverSession(problem=problem, config=cfg).solve(g)
            )
        assert keys["fused"] == keys["reference"], (problem, seed)
    # decision mode too (the fpt early-exit runs through the same plane)
    g = erdos_renyi(16, 0.45, 11)
    keys = {}
    for impl in IMPLS:
        cfg = SolveConfig(
            num_workers=4, mode="fpt", k=3, explore_impl=impl
        )
        r = SolverSession(problem=problem, config=cfg).solve(g)
        keys[impl] = (r.best_size, r.rounds, r.nodes_expanded)
    assert keys["fused"] == keys["reference"]


@pytest.mark.parametrize("problem", ["max_clique", "mis"])
def test_clique_mis_fused_matches_reference_solve_many(problem):
    """Mixed sizes in one W bucket -> the padding AND compaction paths run
    under both impls; results must agree lane for lane."""
    sizes = [14, 10, 16, 12]
    graphs = [erdos_renyi(n, 0.4, 3 + i) for i, n in enumerate(sizes)]
    batches = {}
    for impl in IMPLS:
        cfg = SolveConfig(
            num_workers=4, steps_per_round=4, compact_threshold=0.6,
            explore_impl=impl,
        )
        batches[impl] = SolverSession(problem=problem, config=cfg).solve_many(
            graphs
        )
    assert batches["fused"].compactions == batches["reference"].compactions
    for a, b in zip(batches["fused"].results, batches["reference"].results):
        assert _result_key(a) == _result_key(b)


def test_plugin_without_fused_impl_runs_on_composed_default():
    """A problem that ships NO hand-fused expand_tasks must still run under
    explore_impl="fused" (composed default) and match the reference."""
    bare = dataclasses.replace(get_problem("max_clique"), expand_tasks=None)
    g = erdos_renyi(15, 0.4, 5)
    keys = {}
    for impl in IMPLS:
        cfg = SolveConfig(num_workers=4, steps_per_round=8, explore_impl=impl)
        keys[impl] = _result_key(
            SolverSession(problem=bare, config=cfg).solve(g)
        )
    assert keys["fused"] == keys["reference"]


# -- 3. expansion-level identity on random task batches ------------------------


def _random_task_batch(n, W, L, seed):
    """Random (masks, sols) with the engine invariant mask ∩ sol = ∅."""
    rng = np.random.default_rng(seed)
    masks = rng.integers(0, 2**32, size=(L, W), dtype=np.uint32)
    sols = rng.integers(0, 2**32, size=(L, W), dtype=np.uint32)
    rem = n % 32
    if rem:
        top = np.uint32((1 << rem) - 1)
        masks[:, -1] &= top
        sols[:, -1] &= top
    sols &= ~masks  # disjoint, like every reachable engine task
    # include an empty-mask (terminal) lane so that path is exercised
    masks[0] = 0
    return jnp.asarray(masks), jnp.asarray(sols)


@pytest.mark.parametrize("problem", ["vertex_cover", "max_clique", "mis"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_hand_fused_expand_matches_composed(problem, seed):
    """Every engine-consumed ExpandResult field agrees between the hand-
    fused one-pass impl and the composed per-task callables: task bounds and
    the branch step on every lane, child bounds on non-terminal lanes (the
    only lanes whose child bounds the engine reads)."""
    spec = get_problem(problem)
    assert spec.expand_tasks is not None
    g = erdos_renyi(21, 0.35, 100 + seed)
    data = B.make_data(spec, g)
    masks, sols = _random_task_batch(g.n, g.W, 6, seed)
    fused = spec.expand_tasks(data, masks, sols)
    composed = B.compose_expand_tasks(spec)(data, masks, sols)
    assert (fused.bound == composed.bound).all()
    for name in composed.step._fields:
        assert (
            getattr(fused.step, name) == getattr(composed.step, name)
        ).all(), name
    live = ~np.asarray(composed.step.is_terminal)
    assert (np.asarray(fused.left_bound)[live]
            == np.asarray(composed.left_bound)[live]).all()
    assert (np.asarray(fused.right_bound)[live]
            == np.asarray(composed.right_bound)[live]).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_composed_default_matches_per_task_callables(seed):
    """The composed default IS the per-task callables: property-checked over
    random graphs/batches for a problem picked by the seed."""
    rng = np.random.default_rng(seed)
    spec = get_problem(
        ("vertex_cover", "max_clique", "mis")[int(rng.integers(3))]
    )
    n = int(rng.integers(8, 40))
    g = erdos_renyi(n, float(rng.uniform(0.1, 0.5)), seed)
    data = B.make_data(spec, g)
    L = int(rng.integers(1, 5))
    masks, sols = _random_task_batch(g.n, g.W, L, seed + 1)
    ex = B.compose_expand_tasks(spec)(data, masks, sols)
    for i in range(L):
        m, s = masks[i], sols[i]
        assert int(ex.bound[i]) == int(spec.task_bound(data, m, s))
        step = spec.branch_once(data, m, s)
        assert (ex.step.left_mask[i] == step.left_mask).all()
        assert (ex.step.right_sol[i] == step.right_sol).all()
        assert bool(ex.step.is_terminal[i]) == bool(step.is_terminal)
        assert int(ex.left_bound[i]) == int(
            spec.child_bound(data, step.left_mask, step.left_sol)
        )
        assert int(ex.right_bound[i]) == int(
            spec.child_bound(data, step.right_mask, step.right_sol)
        )


def test_overflow_count_surfaces_in_solve_result():
    """Frontier saturation reaches the public result schema: an undersized
    capacity reports the exact number of dropped tasks (and the bool flag);
    engine-sized capacity stays at zero."""
    g = erdos_renyi(18, 0.35, 2)
    ok = SolverSession(
        problem="vertex_cover",
        config=SolveConfig(num_workers=4, steps_per_round=8),
    ).solve(g)
    assert ok.stats.overflow_count == 0 and not ok.stats.overflow
    starved = SolverSession(
        problem="vertex_cover",
        config=SolveConfig(num_workers=4, steps_per_round=8, capacity=2),
    ).solve(g)
    assert starved.stats.overflow
    assert starved.stats.overflow_count > 0


# -- 4. cheap frontier pop == reference top_k pop ------------------------------


@settings(max_examples=40, deadline=None)
@given(
    st.lists(st.integers(0, 60), min_size=0, max_size=24),
    st.integers(1, 4),
)
def test_pop_deepest_cheap_matches_top_k(depths, count):
    """Same valid lanes (tasks, order, flags) and same post-pop active set,
    for every frontier content and lane count."""
    W = 2
    f = make_frontier(32, W)
    if depths:
        k = len(depths)
        f = push_many(
            f,
            jnp.tile(jnp.arange(1, k + 1, dtype=jnp.uint32)[:, None], (1, W)),
            jnp.zeros((k, W), jnp.uint32),
            jnp.asarray(depths, jnp.int32),
            jnp.ones((k,), bool),
        )
    ref = pop_deepest(f, count)
    cheap = pop_deepest_cheap(f, count)
    assert (ref[0].active == cheap[0].active).all()
    rv, cv = np.asarray(ref[4]), np.asarray(cheap[4])
    assert (rv == cv).all()
    for a, b in zip(ref[1:4], cheap[1:4]):
        assert (np.asarray(a)[rv] == np.asarray(b)[rv]).all()
