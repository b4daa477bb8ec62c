"""Model RB instances (Xu & Li), the BHOSLIB construction of hard vertex cover.

A CSP with ``n_vars`` variables, each with domain ``d = round(n_vars**alpha)``;
``m = round(r * n_vars * ln n_vars)`` binary constraints, each on two distinct
random variables and forbidding ``round(p * d**2)`` value pairs.  A planted
assignment is never forbidden ("forced" instances).  The graph has one vertex
per (variable, value): the values of one variable form a clique, and each
forbidden pair is an edge.  An independent set holds at most one vertex per
variable, and the planted assignment is one of size ``n_vars``, so the
minimum vertex cover is ``N - n_vars`` with ``N = n_vars * d``: the optimum
is known by construction.

BHOSLIB builds its instances at the phase transition of model RB,
``r = -alpha / ln(1 - p)``: its frb30-15 family is ``alpha = 0.8, p = 0.25,
r = 0.8 / ln(4/3) (about 2.78)`` at ``n_vars = 30`` (d = 15, N = 450,
m = 284 constraints; the published frb30-15-1 has 17,827 edges).
"""

from __future__ import annotations

import math

import numpy as np


def threshold_r(alpha: float, p: float) -> float:
    """The ratio of constraints at model RB's phase transition."""
    return -alpha / math.log(1 - p)


def model_rb(n_vars: int, seed: int, *, alpha: float = 0.8, r: float | None = None,
             p: float = 0.25) -> dict:
    """One forced model RB instance: ``{"n", "edges", "optimum", "planted"}``.
    ``r`` defaults to the phase transition, as BHOSLIB's.

    ``edges`` is an (E, 2) int64 array of distinct pairs ``u < v``;
    ``optimum`` the minimum vertex cover size; ``planted`` the planted
    independent set's vertices.
    """
    if r is None:
        r = threshold_r(alpha, p)
    d = round(n_vars ** alpha)
    m = round(r * n_vars * math.log(n_vars))
    t = round(p * d * d)
    rng = np.random.default_rng(seed)
    planted = rng.integers(0, d, size=n_vars)
    edges = set()
    for x in range(n_vars):  # one clique per variable
        for a in range(d):
            for b in range(a + 1, d):
                edges.add((x * d + a, x * d + b))
    for _ in range(m):
        x, y = rng.choice(n_vars, size=2, replace=False)
        allowed = planted[x] * d + planted[y]  # the planted pair stays
        pool = np.delete(np.arange(d * d), allowed)
        for pair in rng.choice(pool, size=t, replace=False):
            u, v = x * d + pair // d, y * d + pair % d
            edges.add((min(u, v), max(u, v)))
    return {
        "n": n_vars * d,
        "edges": np.array(sorted(edges), dtype=np.int64),
        "optimum": n_vars * d - n_vars,
        "planted": np.arange(n_vars) * d + planted,
    }


def make(params: dict, seed: int) -> dict:
    """The cell's instance: the model RB draw of ``params["instance_seed"]``,
    its edge list in an order drawn from the run's seed.  Every seed gets the
    same graph, so the same work: a call's time depends on the instance's
    search trajectory, which differs by a fifth from draw to draw."""
    inst = model_rb(
        params["n_vars"], params["instance_seed"],
        alpha=params["alpha"], r=params["r"], p=params["p"],
    )
    order = np.random.default_rng(seed).permutation(len(inst["edges"]))
    return {**inst, "edges": inst["edges"][order]}
