"""Vertex-cover plugin: the paper's own workload on the generic solve plane.

This is the jit/vmap-compatible twin of the host reference in
:mod:`repro.problems.sequential`.  Every function operates on tasks in the
paper's *optimized encoding* (§4.3): packed ``uint32[W]`` masks over the
ORIGINAL vertex set; the adjacency bitset ``adj (n, W)`` is loaded once per
worker and never re-serialized.  The packed-bitset primitives themselves are
problem-agnostic and live in :mod:`repro.problems.base` (re-exported here
for compatibility).

All control flow is `jax.lax` (while_loop / select) so the ops compose into
the SPMD superstep engine (`repro.core.superstep`) and into the Pallas
bitset kernels (`repro.kernels.bitset_ops`, which accelerates `degrees`).
Semantics match the host reference exactly (tests assert equality):
`reduce_instance` fires the same rule on the same lowest qualifying vertex
as `sequential.reduce_sweep`, sweep for sweep, and ends in the same masks.

``SPEC`` at the bottom is the :class:`~repro.problems.base.BranchingProblem`
plugin registered as ``"vertex_cover"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.problems import sequential
from repro.problems.base import (  # noqa: F401  (re-exported public API)
    WORD_BITS,
    BranchingProblem,
    BranchStep,
    ExpandResult,
    ProblemData,
    ReduceWork,
    degrees,
    degrees_batch,
    edge_count,
    in_mask,
    pack_bits,
    popcount,
    single_bit,
    unpack_bits,
)

# the pre-plugin names, kept for callers and tests
VCProblem = ProblemData
BranchResult = BranchStep


def make_problem(adj, n: int) -> ProblemData:
    v = jnp.arange(adj.shape[0], dtype=jnp.int32)
    return ProblemData(
        n=jnp.int32(n),
        adj=jnp.asarray(adj, dtype=jnp.uint32),
        word_idx=v // WORD_BITS,
        bit_idx=(v % WORD_BITS).astype(jnp.uint32),
    )


def lower_bound(deg: jnp.ndarray) -> jnp.ndarray:
    """ceil(E / maxdeg): each cover vertex covers at most maxdeg edges."""
    maxdeg = jnp.maximum(deg.max(), 0)
    E = edge_count(deg)
    return jnp.where(maxdeg > 0, -(-E // jnp.maximum(maxdeg, 1)), 0).astype(jnp.int32)


# -- reduction rules (paper §4.1, Chen-Kanj-Jia) -------------------------------


def _first_vertex(cond: jnp.ndarray, n_total: int) -> jnp.ndarray:
    """Lowest vertex index satisfying ``cond``; n_total if none."""
    idx = jnp.where(cond, jnp.arange(n_total, dtype=jnp.int32), jnp.int32(n_total))
    return idx.min()


def _first_neighbour(rows: jnp.ndarray, n_total: int | None = None) -> jnp.ndarray:
    """Lowest set bit of each packed (rows, W) row, word by word: (rows,)
    int32, ``n_total`` (the graph's vertices; by default one row each) for
    an empty row."""
    n_total = rows.shape[0] if n_total is None else n_total
    W = rows.shape[1]
    low = rows & (~rows + jnp.uint32(1))  # each word's lowest set bit alone
    bit = WORD_BITS - 1 - jax.lax.clz(low).astype(jnp.int32)
    first = jnp.arange(W, dtype=jnp.int32) * WORD_BITS + bit
    return jnp.where(rows != 0, first, n_total).min(axis=1)


def _ends_adjacent(adj: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Whether the two neighbours of each row of ``rows`` (packed neighbour
    rows inside the mask) are adjacent, (rows,) bool; meaningful for rows of
    exactly two neighbours.  The first neighbour's row of the shared ``adj``
    meets such a row in the other neighbour's bit alone, if at all (graphs
    have no self-loops).  One row lookup a row: no n x n table."""
    fc = jnp.clip(_first_neighbour(rows, adj.shape[0]), 0, adj.shape[0] - 1)
    return (adj[fc] & rows).any(axis=-1)


# Rule 3 tests its degree-2 candidates this many at a time, lowest first.
RULE3_CHUNK = 16


def _ranks(bits: jnp.ndarray, W: int):
    """Each vertex's rank among the set bits of an (n,) bool vector (how many
    set bits lie below it), (n,) int32, and their count; from packed words,
    with no n-long prefix sum."""
    words = pack_bits(bits, W)
    per_word = jax.lax.population_count(words).astype(jnp.int32)  # (W,)
    w = jnp.arange(W)
    before = jnp.where(w[None, :] < w[:, None], per_word[None, :], 0).sum(axis=1)
    below = (jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32)) - jnp.uint32(1)
    within = jax.lax.population_count(words[:, None] & below[None, :]).astype(jnp.int32)
    rank = (before[:, None] + within).reshape(-1)[: bits.shape[-1]]
    return rank, per_word.sum()


def _probe(problem: ProblemData, mask, cand, rank, start):
    """The lowest of the candidates ranked ``start`` to ``start + K - 1``
    whose two neighbours are adjacent (() int32, n if none), and the next
    chunk's first rank: 2 K row lookups."""
    n_total = problem.adj.shape[0]
    K = min(RULE3_CHUNK, n_total)
    v = jnp.arange(n_total, dtype=jnp.int32)
    want = start + jnp.arange(K, dtype=jnp.int32)
    u = jnp.where(cand[None, :] & (rank[None, :] == want[:, None]), v[None, :], n_total).min(axis=1)
    rows = problem.adj[jnp.minimum(u, n_total - 1)] & mask  # (K, W)
    hit = (u < n_total) & _ends_adjacent(problem.adj, rows)
    return jnp.where(hit, u, n_total).min(), start + K


def _sweep(problem: ProblemData, mask, sol_mask, active):
    """One reduction sweep.  Returns (mask, sol_mask, rule, probes): the rule
    that fired (1, 2 or 3; () int32), 0 when none did, and the degree-2
    candidates rule 3 examined, in index order up to and including its
    vertex (all of them if none qualifies; 0 where rule 1 or 2 fires or the
    lane is not ``active``).  The ``sweep`` scope holds the code that runs
    once a sweep; rule 3's further chunks run in a loop of their own."""
    n_total, W = problem.adj.shape
    with jax.named_scope("sweep"):
        deg = degrees(problem, mask)
        inside = deg >= 0

        # Rule 1: drop all isolated vertices at once (removals never conflict).
        iso = inside & (deg == 0)
        any_iso = iso.any()
        mask_r1 = mask & ~pack_bits(iso, W)

        # Rule 2: one degree-1 vertex per sweep (batching could over-add on
        # isolated edges where both endpoints have degree 1).
        u2 = _first_vertex(inside & (deg == 1), n_total)
        has_u2 = u2 < n_total
        u2c = jnp.minimum(u2, n_total - 1)
        nb2 = problem.adj[u2c] & mask
        sol_r2 = sol_mask | nb2
        mask_r2 = mask & ~(nb2 | single_bit(u2c, W))

        # Rule 3: first degree-2 vertex whose two neighbours are adjacent.
        # Only a lane where rules 1 and 2 do not fire offers candidates (a
        # lane done with its loop none, or it would hold the lockstep),
        # probed a chunk at a time until every lane has its vertex or runs
        # out.
        cand = inside & (deg == 2) & ~any_iso & ~has_u2 & active
        rank, count = _ranks(cand, W)
        first = _probe(problem, mask, cand, rank, jnp.int32(0))
    with jax.named_scope("rule3_chunks"):
        u3, _ = jax.lax.while_loop(
            lambda c: (c[0] >= n_total) & (c[1] < count),
            lambda c: _probe(problem, mask, cand, rank, c[1]),
            first,
        )
    with jax.named_scope("sweep"):
        has_u3 = u3 < n_total
        u3c = jnp.minimum(u3, n_total - 1)
        probes = jnp.where(has_u3, rank[u3c] + 1, count)
        nb3 = problem.adj[u3c] & mask
        sol_r3 = sol_mask | nb3
        mask_r3 = mask & ~(nb3 | single_bit(u3c, W))

        # Priority: rule 1 > rule 2 > rule 3 (mirrors the host reference).
        new_mask = jnp.where(any_iso, mask_r1, jnp.where(has_u2, mask_r2, jnp.where(has_u3, mask_r3, mask)))
        new_sol = jnp.where(any_iso, sol_mask, jnp.where(has_u2, sol_r2, jnp.where(has_u3, sol_r3, sol_mask)))
        rule = jnp.where(any_iso, 1, jnp.where(has_u2, 2, jnp.where(has_u3, 3, 0)))
    return new_mask, new_sol, rule.astype(jnp.int32), probes


def _reduce_step(problem: ProblemData, mask, sol_mask):
    """One reduction sweep as :func:`sequential.reduce_sweep` gives it:
    (mask, sol_mask, rule)."""
    return _sweep(problem, mask, sol_mask, True)[:3]


def _reduce_counted(problem: ProblemData, mask, sol_mask):
    """Apply rules 1-3 to fixpoint (bounded while_loop); returns (mask,
    sol_mask, :class:`ReduceWork`): the sweeps the loop ran, its last one
    (which changes nothing) included, the (3,) firings of each rule and
    rule 3's probes.
    Every firing removes a vertex, so the bound of n + 1 sweeps never cuts
    the loop short and sweeps == fires.sum() + 1."""

    def cond(state):
        _, _, changed, it, _, _ = state
        return changed & (it < problem.adj.shape[0] + 1)

    def body(state):
        m, s, changed, it, fires, probes = state
        m2, s2, rule, p = _sweep(problem, m, s, changed)
        fires = fires + (jnp.arange(1, 4) == rule).astype(jnp.int32)
        return (m2, s2, rule > 0, it + 1, fires, probes + p)

    # initial `changed` is derived from mask (always True) so its varying-
    # manual-axes match the body output under shard_map (see JAX scan-vma).
    changed0 = popcount(mask) >= 0
    with jax.named_scope("reduce"):
        mask, sol_mask, _, sweeps, fires, probes = jax.lax.while_loop(
            cond, body,
            (mask, sol_mask, changed0, jnp.int32(0), jnp.zeros(3, jnp.int32), jnp.int32(0)),
        )
    return mask, sol_mask, ReduceWork(sweeps=sweeps, fires=fires, rule3_probes=probes)


def reduce_instance(problem: ProblemData, mask, sol_mask):
    """Apply rules 1-3 to fixpoint (bounded while_loop)."""
    mask, sol_mask, _ = _reduce_counted(problem, mask, sol_mask)
    return mask, sol_mask


_REDUCE_INSTANCE = reduce_instance


def _reduce_lanes(problem: ProblemData, masks, sols):
    """:func:`reduce_instance` over an (L, W) lane batch, with each lane's
    :class:`ReduceWork`.  ``reduce_instance`` stays the module's override
    point: a replaced one (a variant, or a planted fault) runs as it is,
    and its work is not counted (None)."""
    if reduce_instance is not _REDUCE_INSTANCE:
        rmasks, rsols = jax.vmap(
            lambda m, s: reduce_instance(problem, m, s)
        )(masks, sols)
        return rmasks, rsols, None
    return jax.vmap(lambda m, s: _reduce_counted(problem, m, s))(masks, sols)


# -- branching (paper Algorithm 8 lines 7-11) ----------------------------------


def branch_once(problem: ProblemData, mask, sol_mask) -> BranchStep:
    """Reduce, then branch on a maximum-degree vertex u:
    left = (G-u, S+{u}), right = (G-N[u], S+N(u)).  Matches Alg. 8/9."""
    W = problem.adj.shape[1]
    mask, sol_mask = reduce_instance(problem, mask, sol_mask)
    deg = degrees(problem, mask)
    maxdeg = deg.max()
    is_terminal = maxdeg <= 0
    u = jnp.argmax(deg).astype(jnp.int32)
    u_bit = single_bit(u, W)
    nb = problem.adj[u] & mask
    return BranchStep(
        left_mask=mask & ~u_bit,
        left_sol=sol_mask | u_bit,
        right_mask=mask & ~(nb | u_bit),
        right_sol=sol_mask | nb,
        is_terminal=is_terminal,
        terminal_sol=sol_mask,
        terminal_value=popcount(sol_mask),
    )


def task_bound(problem: ProblemData, mask, sol_mask) -> jnp.ndarray:
    """|S| + ceil(E/maxdeg): admissible lower bound on the final cover."""
    return popcount(sol_mask) + lower_bound(degrees(problem, mask))


def expand_tasks(problem: ProblemData, masks, sols) -> ExpandResult:
    """One-pass fused expansion of an (L, W) lane batch (Alg. 8 hot path).

    The per-task path computes two full degree panels per lane (task_bound
    on the raw mask, branch_once on the reduced mask) through separate
    vmapped calls, then popcounts both children's covers from scratch.
    Here each panel is ONE batched ``degrees_batch`` over all lanes (the
    Pallas kernel on TPU), the pivot and bound read the same panel, and the
    child bounds are arithmetic on it — ``|S|+1`` for the take-u child and
    ``|S| + deg[u]`` for the take-N(u) child (u and its neighbours live in
    the reduced mask, disjoint from the cover, so the popcounts are exact).
    Terminal lanes carry placeholder child bounds (never consumed — see
    :class:`ExpandResult`); all consumed values are bit-identical to the
    composed per-task callables (property-tested).
    """
    W = problem.adj.shape[1]
    with jax.named_scope("degrees"):
        deg0 = degrees_batch(problem, masks)  # (L, n)
        bound = popcount(sols) + jax.vmap(lower_bound)(deg0)  # (L,)
    rmasks, rsols, work = _reduce_lanes(problem, masks, sols)
    with jax.named_scope("degrees"):
        deg = degrees_batch(problem, rmasks)  # (L, n)
    with jax.named_scope("pivot"):
        maxdeg = deg.max(axis=1)  # also == deg[u], so it feeds the right bound
        u = jnp.argmax(deg, axis=1).astype(jnp.int32)
        u_bit = jax.vmap(lambda v: single_bit(v, W))(u)
        nb = problem.adj[u] & rmasks
        pc_rsol = popcount(rsols)  # (L,)
        step = BranchStep(
            left_mask=rmasks & ~u_bit,
            left_sol=rsols | u_bit,
            right_mask=rmasks & ~(nb | u_bit),
            right_sol=rsols | nb,
            is_terminal=maxdeg <= 0,
            terminal_sol=rsols,
            terminal_value=pc_rsol,
        )
    return ExpandResult(
        bound=bound,
        step=step,
        left_bound=pc_rsol + 1,
        right_bound=pc_rsol + maxdeg,
        work=work,
    )


def child_bound(problem: ProblemData, mask, sol_mask) -> jnp.ndarray:
    """Cheap birth-time bound: the partial cover can only grow."""
    return popcount(sol_mask)


@functools.partial(jax.jit, static_argnames=("n",))
def verify_cover(adj, sol_mask, n: int) -> jnp.ndarray:
    """True iff sol_mask covers every edge (device-side checker)."""
    problem = make_problem(adj, n)
    inc = in_mask(problem, sol_mask)  # (n,)
    # edges with neither endpoint in the cover
    uncovered_rows = adj & ~sol_mask[None, :]
    cnt = popcount(uncovered_rows)
    return (jnp.where(inc, 0, cnt).sum() == 0)


def _host_task_bound(g, mask, sol_mask) -> int:
    """|S| + ceil(E/maxdeg) — the host twin of :func:`task_bound`."""
    from repro.graphs.bitgraph import popcount_rows

    return int(popcount_rows(sol_mask)) + sequential.lower_bound(g, mask)


def _host_child_bound(g, mask, sol_mask) -> int:
    from repro.graphs.bitgraph import popcount_rows

    return int(popcount_rows(sol_mask))


SPEC = BranchingProblem(
    name="vertex_cover",
    objective="minimize |cover|",
    branch_once=branch_once,
    task_bound=task_bound,
    child_bound=child_bound,
    expand_tasks=expand_tasks,
    bnb_bound=lambda g: g.n + 1,
    branch_once_host=sequential.branch_once,
    sequential=sequential.solve_sequential,
    verify=sequential.verify_cover,
    host_task_bound=_host_task_bound,
    host_child_bound=_host_child_bound,
    host_terminal_value=_host_child_bound,  # a leaf's cover size is |S|
)
