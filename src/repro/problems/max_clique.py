"""Max-clique plugin: a native candidate-set brancher on the generic plane.

Task state (paper-optimized encoding, unchanged layout): ``mask`` is the
candidate set P (vertices adjacent to everything already picked), ``sol`` is
the clique R being grown.  One expansion branches on a maximum-degree
candidate u — either u joins (candidates shrink to P ∩ N(u)) or u is
discarded — and a task is terminal when P is empty.

The engine minimizes, so the internal objective is ``-|R|``; the admissible
bound ``-(|R| + |P|)`` (every candidate could, at best, join) prunes both
popped tasks and freshly-born children.  ``external_value`` flips the sign
back for reporting.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.problems import sequential
from repro.problems.base import (
    BranchingProblem,
    BranchStep,
    ExpandResult,
    ProblemData,
    degrees,
    expand_stats_batch,
    popcount,
    single_bit,
)


def branch_once(data: ProblemData, mask, sol) -> BranchStep:
    """Branch on a maximum-degree candidate (degree within P, ties lowest)."""
    W = data.adj.shape[1]
    deg = degrees(data, mask)
    u = jnp.argmax(deg).astype(jnp.int32)
    u_bit = single_bit(u, W)
    nb = data.adj[u] & mask
    return BranchStep(
        left_mask=nb,  # u joins: only its neighbours stay candidates
        left_sol=sol | u_bit,
        right_mask=mask & ~u_bit,  # u discarded
        right_sol=sol,
        is_terminal=popcount(mask) == 0,
        terminal_sol=sol,
        terminal_value=-popcount(sol),
    )


def bound(data: ProblemData, mask, sol) -> jnp.ndarray:
    """-(|R| + |P|): no completion can beat adding every candidate."""
    return -(popcount(sol) + popcount(mask))


def expand_tasks(data: ProblemData, masks, sols) -> ExpandResult:
    """One-pass fused expansion of an (L, W) lane batch.

    The per-task path reads every packed word five times (task_bound's two
    popcounts, branch_once's degrees + two popcounts, child_bound's four);
    here ONE ``expand_stats_batch`` panel (Pallas kernel on TPU) yields
    degrees + |P| + |R| for the whole batch, and the child bounds become
    arithmetic on known quantities instead of fresh popcounts:

    * ``|left_sol| = |R| + 1`` — the pivot u is a candidate (u ∈ P, P∩R=∅);
    * ``|left_mask| = |N(u)∩P| = deg[u]`` — degrees already computed it;
    * ``|right_mask| = |P| - 1``, ``|right_sol| = |R|``.

    On terminal lanes (P empty) the pivot is arbitrary, so the child bounds
    are not the composed values there — the engine never consumes child
    bounds of terminal lanes (see :class:`ExpandResult`); every consumed
    quantity is bit-identical to the composed path (property-tested).
    """
    W = data.adj.shape[1]
    with jax.named_scope("degrees"):
        deg, pc_mask, pc_sol = expand_stats_batch(data, masks, sols)  # (L,n),(L,),(L,)
        task_bound_v = -(pc_sol + pc_mask)
    with jax.named_scope("pivot"):
        u = jnp.argmax(deg, axis=1).astype(jnp.int32)  # (L,)
        deg_u = deg.max(axis=1)  # == deg[u] (the argmax row max), one reduce
        u_bit = jax.vmap(lambda v: single_bit(v, W))(u)  # (L, W)
        nb = data.adj[u] & masks  # (L, W)
        step = BranchStep(
            left_mask=nb,
            left_sol=sols | u_bit,
            right_mask=masks & ~u_bit,
            right_sol=sols,
            is_terminal=pc_mask == 0,
            terminal_sol=sols,
            terminal_value=-pc_sol,
        )
    return ExpandResult(
        bound=task_bound_v,
        step=step,
        left_bound=-(pc_sol + 1 + deg_u),
        right_bound=-(pc_sol + pc_mask - 1),
    )


def host_bound(g, mask, sol_mask) -> int:
    """Host twin of :func:`bound`: -(|R| + |P|) over packed host bitsets."""
    from repro.graphs.bitgraph import popcount_rows

    return -int(popcount_rows(sol_mask) + popcount_rows(mask))


def host_terminal_value(g, mask, sol_mask) -> int:
    from repro.graphs.bitgraph import popcount_rows

    return -int(popcount_rows(sol_mask))


SPEC = BranchingProblem(
    name="max_clique",
    objective="maximize |clique|",
    branch_once=branch_once,
    task_bound=bound,
    child_bound=bound,
    expand_tasks=expand_tasks,
    bnb_bound=lambda g: 1,  # just worse than the empty clique (value 0)
    external_value=lambda v: -v,
    fpt_target=lambda k: -k,
    branch_once_host=sequential.branch_once_clique,
    sequential=sequential.solve_sequential_max_clique,
    verify=sequential.verify_clique,
    host_task_bound=host_bound,
    host_child_bound=host_bound,
    host_terminal_value=host_terminal_value,
)
