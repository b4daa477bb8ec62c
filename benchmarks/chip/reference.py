"""The plain reference: answers and search tasks checked from the edge list
the traffic made.

Nothing here imports the solver.  Graphs are the generator's (n, edges)
pairs; answers and tasks are the solver's packed uint32 words, unpacked
LSB-first as its documented encoding says.

A vertex-cover search task is a pair (mask, sol): the vertices of the graph
that remain, and the partial cover chosen so far.  :func:`expand` is one
node expansion written out plainly: the task's lower bound, the reduction
rules of Chen, Kanj and Jia applied to a fixpoint (rule 1 drops every
isolated vertex; else rule 2 covers the neighbour of the lowest-numbered
degree-1 vertex; else rule 3 covers both neighbours of the lowest-numbered
degree-2 vertex whose neighbours are adjacent), then the branch on the
lowest-numbered vertex of maximum degree: take it, or take its neighbours.
"""

from __future__ import annotations

import numpy as np


def pack(chosen: np.ndarray, W: int) -> np.ndarray:
    """Boolean vertex set -> W packed LSB-first uint32 words."""
    bits = np.zeros(W * 32, np.uint64)
    bits[:len(chosen)] = chosen
    return (bits.reshape(W, 32) << np.arange(32, dtype=np.uint64)).sum(axis=1).astype(np.uint32)


def unpack(words, n: int) -> np.ndarray:
    """Packed LSB-first uint32 words -> boolean vertex set of length n
    (the last axis holds the words)."""
    words = np.asarray(words, dtype=np.uint32)
    bits = (words[..., :, None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    return bits.reshape(*words.shape[:-1], 32 * words.shape[-1])[..., :n].astype(bool)


def uncovered_edges(edges: np.ndarray, chosen: np.ndarray) -> int:
    """Edges with neither endpoint in the cover."""
    if len(edges) == 0:
        return 0
    return int((~chosen[edges[:, 0]] & ~chosen[edges[:, 1]]).sum())


def edges_inside(edges: np.ndarray, chosen: np.ndarray) -> int:
    """Edges with both endpoints in the set."""
    if len(edges) == 0:
        return 0
    return int((chosen[edges[:, 0]] & chosen[edges[:, 1]]).sum())


def dense(n: int, edges: np.ndarray) -> np.ndarray:
    a = np.zeros((n, n), dtype=bool)
    if len(edges):
        a[edges[:, 0], edges[:, 1]] = True
        a[edges[:, 1], edges[:, 0]] = True
    return a


def _count(masks: np.ndarray, adj: np.ndarray) -> np.ndarray:
    """(T, n) boolean rows times the adjacency, exact in float32 (counts
    stay far below 2**24)."""
    return (masks.astype(np.float32) @ adj.astype(np.float32)).astype(np.int64)


def degrees(adj: np.ndarray, masks: np.ndarray) -> np.ndarray:
    """(T, n) boolean masks -> (T, n) degrees in the induced subgraphs,
    -1 for a vertex outside its mask."""
    return np.where(masks, _count(masks, adj), -1)


def bad_tasks(adj: np.ndarray, masks: np.ndarray, sols: np.ndarray,
              block: int = 4096) -> int:
    """Tasks (rows of boolean masks and sols) that are no sound search
    state: the partial cover meets the remaining graph, or an edge is
    neither covered nor left inside the remaining graph (the edges among
    the vertices outside the cover are more than those inside the mask)."""
    bad = 0
    for i in range(0, len(masks), block):
        m, s = masks[i:i + block], sols[i:i + block]
        free = ~s
        free_edges = (_count(free, adj) * free).sum(axis=1)
        mask_edges = (_count(m, adj) * m).sum(axis=1)
        bad += int(((m & s).any(axis=1) | (free_edges != mask_edges)).sum())
    return bad


def _lower_bound(deg: np.ndarray) -> int:
    """ceil(E / maxdeg): each cover vertex covers at most maxdeg edges."""
    maxdeg = max(int(deg.max()), 0)
    if maxdeg == 0:
        return 0
    edges = int(np.maximum(deg, 0).sum()) // 2
    return -(-edges // maxdeg)


def reduce(adj: np.ndarray, mask: np.ndarray, sol: np.ndarray) -> tuple:
    """Rules 1-3 to a fixpoint: (mask, sol, rules fired as [r1, r2, r3])."""
    mask, sol = mask.copy(), sol.copy()
    fired = [0, 0, 0]
    while True:
        nbs = adj & mask  # row v: v's neighbours that remain
        deg = np.where(mask, nbs.sum(axis=1), -1)
        if (deg == 0).any():
            mask &= deg != 0
            fired[0] += 1
            continue
        ones = np.flatnonzero(deg == 1)
        if len(ones):
            u = ones[0]
            sol |= nbs[u]
            mask &= ~nbs[u]
            mask[u] = False
            fired[1] += 1
            continue
        for u in np.flatnonzero(deg == 2):
            a, b = np.flatnonzero(nbs[u])
            if adj[a, b]:
                sol |= nbs[u]
                mask &= ~nbs[u]
                mask[u] = False
                fired[2] += 1
                break
        else:
            return mask, sol, fired


def expand(adj: np.ndarray, mask: np.ndarray, sol: np.ndarray) -> dict:
    """One node expansion of the task (mask, sol), as boolean vertex sets:
    ``bound``, ``terminal``, and the reduced task (``sol`` of a terminal) or
    the two children with their bounds; ``fired`` counts the rules."""
    bound = int(sol.sum()) + _lower_bound(degrees(adj, mask[None])[0])
    rmask, rsol, fired = reduce(adj, mask, sol)
    deg = degrees(adj, rmask[None])[0]
    out = {"bound": bound, "terminal": bool(deg.max() <= 0), "fired": fired}
    if out["terminal"]:
        out["sol"] = rsol
        return out
    u = int(np.argmax(deg))
    nb = adj[u] & rmask
    take_u = np.zeros_like(rmask)
    take_u[u] = True
    out.update(
        left_mask=rmask & ~take_u, left_sol=rsol | take_u,
        right_mask=rmask & ~(nb | take_u), right_sol=rsol | nb,
        left_bound=int(rsol.sum()) + 1, right_bound=int(rsol.sum() + deg[u]),
    )
    return out


def min_vertex_cover(n: int, edges: np.ndarray) -> int:
    """Exact minimum vertex cover by integer programming."""
    from scipy.optimize import Bounds, LinearConstraint, milp
    from scipy.sparse import coo_matrix

    if len(edges) == 0:
        return 0
    rows = np.repeat(np.arange(len(edges)), 2)
    a = coo_matrix((np.ones(2 * len(edges)), (rows, edges.reshape(-1))),
                   shape=(len(edges), n))
    res = milp(
        c=np.ones(n),
        constraints=LinearConstraint(a, lb=1, ub=np.inf),
        integrality=np.ones(n),
        bounds=Bounds(0, 1),
    )
    if not res.success:
        raise RuntimeError(f"MILP failed: {res.message}")
    return int(round(res.fun))
