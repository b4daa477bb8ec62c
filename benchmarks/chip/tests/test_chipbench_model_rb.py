"""The model RB generator: deterministic per seed, and its planted cover is
the optimum (checked against the solver's sequential branch and bound and
the reference's MILP)."""

import math
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))

from benchmarks.chip import reference  # noqa: E402
from benchmarks.chip.traffic import model_rb  # noqa: E402


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 11])
def test_deterministic_per_seed(seed):
    a, b = model_rb.model_rb(10, seed), model_rb.model_rb(10, seed)
    assert np.array_equal(a["edges"], b["edges"])
    assert a["optimum"] == b["optimum"]
    assert not np.array_equal(a["edges"], model_rb.model_rb(10, seed + 1)["edges"])


def test_published_frb30_15_shape():
    inst = model_rb.model_rb(30, 7)
    d = 15
    assert inst["n"] == 450 and inst["optimum"] == 420
    # at the phase transition r = 0.8 / ln(4/3): 284 constraints of
    # round(p d^2) = 56 pairs on top of one clique per variable
    r = model_rb.threshold_r(0.8, 0.25)
    assert r == pytest.approx(0.8 / math.log(4 / 3))
    m, t = round(r * 30 * math.log(30)), round(0.25 * d * d)
    assert (m, t) == (284, 56)
    cliques = 30 * d * (d - 1) // 2
    assert cliques < len(inst["edges"]) <= cliques + m * t
    # the published frb30-15-1 has 17,827 edges; draws land within 2%
    assert abs(len(inst["edges"]) - 17827) < 0.02 * 17827


@pytest.mark.parametrize("n_vars,seed", [(6, 1), (8, 2), (10, 3), (10, 4)])
def test_planted_cover_is_the_optimum(n_vars, seed):
    from repro.graphs.bitgraph import BitGraph
    from repro.problems.sequential import solve_sequential

    inst = model_rb.model_rb(n_vars, seed)
    n, edges = inst["n"], inst["edges"]
    # the planted set is independent, so its complement is a cover
    planted = np.zeros(n, bool)
    planted[inst["planted"]] = True
    assert reference.edges_inside(edges, planted) == 0
    assert reference.uncovered_edges(edges, ~planted) == 0
    best, _, _ = solve_sequential(BitGraph.from_edges(n, edges.tolist()))
    assert best == inst["optimum"] == n - n_vars
    assert reference.min_vertex_cover(n, edges) == inst["optimum"]


def test_cell_instance_is_fixed_and_seed_orders_its_edges():
    params = {"n_vars": 10, "alpha": 0.8, "r": model_rb.threshold_r(0.8, 0.25),
              "p": 0.25, "instance_seed": 3}
    a, b = model_rb.make(params, 1), model_rb.make(params, 2**31 + 9)
    assert not np.array_equal(a["edges"], b["edges"])
    assert {tuple(e) for e in a["edges"].tolist()} == {tuple(e) for e in b["edges"].tolist()}
    assert np.array_equal(model_rb.make(params, 1)["edges"], a["edges"])


@pytest.mark.parametrize("seed", [5, 6, 2**31 + 13])
def test_reference_expansion_matches_the_program(seed):
    """The plain reference's node expansion says what the program's fused
    ``expand_tasks`` says, on random tasks of a model RB graph (the CPU runs
    the kernel's jnp twin)."""
    import jax
    import jax.numpy as jnp

    from repro.graphs.bitgraph import BitGraph
    from repro.problems import base, vertex_cover

    inst = model_rb.model_rb(10, seed)
    n, edges = inst["n"], inst["edges"]
    g = BitGraph.from_edges(n, edges.tolist())
    adj = reference.dense(n, edges)
    rng = np.random.default_rng(seed)
    # sound tasks: a random remaining graph, the rest of each removed
    # vertex's edges covered
    masks = rng.random((16, n)) < rng.uniform(0.05, 0.6, (16, 1))
    sols = np.zeros_like(masks)
    for i in range(16):
        u, v = edges[:, 0], edges[:, 1]
        loose = ~(masks[i, u] & masks[i, v])
        sols[i, np.where(masks[i, u[loose]], v[loose], u[loose])] = True
    assert reference.bad_tasks(adj, masks, sols) == 0
    pm = np.stack([reference.pack(m, g.W) for m in masks])
    ps = np.stack([reference.pack(s, g.W) for s in sols])
    ex = jax.device_get(jax.jit(vertex_cover.expand_tasks)(
        base.make_data(vertex_cover.SPEC, g), jnp.asarray(pm), jnp.asarray(ps)
    ))
    fired = np.zeros(3, int)
    for i in range(16):
        ref = reference.expand(adj, masks[i], sols[i])
        fired += ref["fired"]
        assert int(ex.bound[i]) == ref["bound"]
        assert bool(ex.step.is_terminal[i]) == ref["terminal"]
        if ref["terminal"]:
            assert np.array_equal(reference.unpack(ex.step.terminal_sol[i], n), ref["sol"])
            continue
        for k in ("left_mask", "left_sol", "right_mask", "right_sol"):
            assert np.array_equal(reference.unpack(getattr(ex.step, k)[i], n), ref[k]), k
        assert int(ex.left_bound[i]) == ref["left_bound"]
        assert int(ex.right_bound[i]) == ref["right_bound"]
    assert fired.sum() > 0  # the draws exercise the reduction
