"""The ``BranchingProblem`` plugin protocol: the framework/problem split.

The paper's pitch (and GemPBA's) is that a sequential branching algorithm
becomes a massively parallel one by changing only a few lines: the
coordination machinery — supersteps, the replicated center, the data plane,
batching, serving — is problem-generic, and a *problem* is a small plugin.
This module defines that contract; :mod:`repro.core` depends only on it
(never on a concrete problem), and :mod:`repro.problems.registry` maps names
to plugins.

A problem supplies:

* **packed-state layout** — every task is ``(mask, sol, depth)`` over packed
  ``uint32[W]`` bitsets of the ORIGINAL vertex set (the paper's optimized
  encoding, §4.3).  The per-instance device tensors live in a shared
  :class:`ProblemData` pytree; ``host_adj`` defines which adjacency view the
  branching runs on (e.g. MIS branches on the complement graph).
* **device fns** — ``branch_once`` (one node expansion -> a
  :class:`BranchStep`), ``task_bound``/``child_bound`` (admissible bounds for
  pruning).  All jit/vmap-compatible, all over ``(data, mask, sol)``.
* **objective adapter** — the engine always MINIMIZES an int32 *internal*
  value; maximization problems negate (``external_value`` converts back).
  ``bnb_bound(g)`` is the "worse than any real solution" seed;
  ``fpt_target(k)`` the internal decision threshold.
* **host plumbing** — ``branch_once_host`` drives the §3.5 startup split,
  ``sequential`` is the ground-truth reference, ``verify`` checks solutions.
* **codec record layout** — ``record_fields`` names the words a task record
  carries on the wire (see :mod:`repro.core.encoding`).

See ``problems/mis.py`` for the whole contract implemented in ~40 lines
(README "Adding a new problem").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.bitgraph import mask_full

WORD_BITS = 32


class ProblemData(NamedTuple):
    """Static per-instance device tensors (replicated on every worker).

    ``adj`` is the BRANCHING graph's packed adjacency — the problem's
    ``host_adj`` decides what that is (original graph, complement, ...).
    Batched instances add a leading axis on ``n``/``adj`` only
    (:data:`DATA_IN_AXES`); ``word_idx``/``bit_idx`` are shared bit maps.
    """

    n: jnp.ndarray  # () int32 -- number of (real, unpadded) vertices
    adj: jnp.ndarray  # (n, W) uint32 packed adjacency
    word_idx: jnp.ndarray  # (n,) int32 -- v // 32
    bit_idx: jnp.ndarray  # (n,) uint32 -- v % 32


# vmap axis spec for batched ProblemData: per-instance n/adj, shared bit maps
DATA_IN_AXES = ProblemData(n=0, adj=0, word_idx=None, bit_idx=None)


class BranchStep(NamedTuple):
    """One node expansion: two children plus terminal detection.

    ``terminal_value`` is the INTERNAL objective value (minimization sense)
    of the completed solution when ``is_terminal``.
    """

    left_mask: jnp.ndarray
    left_sol: jnp.ndarray
    right_mask: jnp.ndarray
    right_sol: jnp.ndarray
    is_terminal: jnp.ndarray  # () bool
    terminal_sol: jnp.ndarray  # (W,) uint32
    terminal_value: jnp.ndarray  # () int32


class ExpandResult(NamedTuple):
    """One-pass batched expansion of L popped tasks (the fused hot path).

    Everything :func:`~repro.core.superstep._explore_one_round` needs from a
    task batch in one call: the pre-expansion bound (== ``task_bound`` per
    lane), the batched :class:`BranchStep` (every field gains a leading lane
    axis), and the two children's birth-time bounds (== ``child_bound`` on
    the left/right child per lane).  Child bounds are only consumed for
    non-terminal, non-pruned lanes, so a fused implementation may return
    arbitrary values on lanes where ``step.is_terminal`` holds.
    """

    bound: jnp.ndarray  # (L,) int32 -- task_bound per lane
    step: BranchStep  # batched: every field has a leading (L,) axis
    left_bound: jnp.ndarray  # (L,) int32 -- child_bound of the left child
    right_bound: jnp.ndarray  # (L,) int32 -- child_bound of the right child
    # per-lane work of the plugin's reduction, counted on device; None for a
    # plugin without one (the engine's reduction counters then stay 0)
    work: Optional["ReduceWork"] = None


class ReduceWork(NamedTuple):
    """What a reduction to fixpoint did on each lane of a batch: the sweeps
    its loop ran (the last one changes nothing), how often each of up to
    three rules fired, and the candidates its third rule examined.  Read
    by the engine's ``reduce_*`` counters."""

    sweeps: jnp.ndarray  # (L,) int32
    fires: jnp.ndarray  # (L, 3) int32 -- firings of rules 1, 2, 3
    rule3_probes: jnp.ndarray  # (L,) int32


# -- packed-bitset primitives (problem-agnostic device ops) --------------------


def popcount(words: jnp.ndarray) -> jnp.ndarray:
    """Popcount summed over the trailing word axis -> int32."""
    return jax.lax.population_count(words).astype(jnp.int32).sum(axis=-1)


def unpack_bits(words: jnp.ndarray, n: int) -> jnp.ndarray:
    """(..., W) uint32 -> (..., n) bool."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1)[..., :n].astype(bool)


def pack_bits(bits: jnp.ndarray, W: int) -> jnp.ndarray:
    """(..., n) bool -> (..., W) uint32 (LSB-first)."""
    n = bits.shape[-1]
    pad = W * WORD_BITS - n
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((*bits.shape[:-1], pad), dtype=bool)], axis=-1
        )
    b = bits.reshape(*bits.shape[:-1], W, WORD_BITS).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return (b * weights).sum(axis=-1).astype(jnp.uint32)


def single_bit(v: jnp.ndarray, W: int) -> jnp.ndarray:
    """Packed mask with only bit ``v`` set (v: () int32)."""
    word = v // WORD_BITS
    bit = (v % WORD_BITS).astype(jnp.uint32)
    return jnp.where(
        jnp.arange(W) == word, jnp.uint32(1) << bit, jnp.uint32(0)
    ).astype(jnp.uint32)


def in_mask(data: ProblemData, mask: jnp.ndarray) -> jnp.ndarray:
    """(n,) bool: vertex v inside the packed mask."""
    return ((mask[data.word_idx] >> data.bit_idx) & 1).astype(bool)


def degrees(data: ProblemData, mask: jnp.ndarray) -> jnp.ndarray:
    """Induced-subgraph degrees on the branching graph; -1 outside the mask.

    This is the branching hot spot the Pallas kernel accelerates (one AND +
    popcount per adjacency row per task).
    """
    deg = popcount(data.adj & mask[None, :])
    return jnp.where(in_mask(data, mask), deg, jnp.int32(-1))


def degrees_batch(data: ProblemData, masks: jnp.ndarray) -> jnp.ndarray:
    """(L, W) task masks -> (L, n) degrees, kernel-accelerated when native.

    The fused ``expand_tasks`` implementations route their whole lane batch
    through ONE degrees computation; on a TPU runtime this dispatches to the
    Pallas ``bitset_ops`` kernel (native Mosaic), elsewhere it stays on the
    identical jnp math (same values bit-for-bit — the kernel suite asserts
    equality).  Imported lazily so the reference explore path never touches
    :mod:`repro.kernels` (arch-guarded: CPU-only installs stay Pallas-free).
    """
    from repro.kernels.bitset_ops.ops import degrees_auto

    return degrees_auto(data.adj, masks)


def expand_stats_batch(data: ProblemData, masks: jnp.ndarray, sols: jnp.ndarray):
    """(L, W) masks/sols -> (deg (L, n), pc_mask (L,), pc_sol (L,)).

    The fused expand panel (degrees + both popcounts) in one pass; Pallas
    ``batched_expand_stats`` when the runtime lowers it natively, identical
    jnp math elsewhere.  Lazy import, same arch rule as
    :func:`degrees_batch`.
    """
    from repro.kernels.bitset_ops.ops import expand_stats_auto

    return expand_stats_auto(data.adj, masks, sols)


def edge_count(deg: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(deg, 0).sum() // 2


# -- the plugin contract --------------------------------------------------------

# Default on-the-wire task record: the frontier's native (mask, sol, depth)
# row.  Widths are symbolic: "W" -> packed words, "n*W" -> adjacency payload,
# int -> literal word count.  Resolved by repro.core.encoding, which is the
# single consumer: a problem's schema MUST start with this native triple
# (the frontier owns those fields); any fields after it ride as zero-filled
# extra payload words that the codecs and the SPMD data plane (via the
# codec's pad_words) actually carry, so wire-byte accounting stays exact.
RECORD_FIELDS = (("mask", "W"), ("sol", "W"), ("depth", 1))


@dataclasses.dataclass(frozen=True)
class BranchingProblem:
    """A branching problem plugged into the generic solve plane.

    Device callables are pure jnp functions over ``(data, mask, sol)``; the
    engine vmaps them across lanes and instances.  Host callables operate on
    :class:`~repro.graphs.bitgraph.BitGraph` instances.
    """

    name: str
    objective: str  # human-readable, e.g. "minimize |cover|"

    # device: one expansion; admissible internal-value bounds for pruning.
    # task_bound gates expansion of a popped task (may be expensive);
    # child_bound gates pushing a freshly-created child (must be cheap).
    branch_once: Callable[[ProblemData, Any, Any], BranchStep]
    task_bound: Callable[[ProblemData, Any, Any], Any]
    child_bound: Callable[[ProblemData, Any, Any], Any]

    # objective adapter (engine minimizes internal int32 values)
    bnb_bound: Callable[[Any], int]  # internal value worse than any solution

    # optional fused hot path: (data, masks (L, W), sols (L, W)) ->
    # ExpandResult computing bound + branch + child bounds in ONE pass over
    # the lane batch (shared popcounts/degrees, batched kernels).  Must be
    # bit-identical to the composed per-task callables on every lane the
    # engine consumes; None -> the engine composes the three callables
    # (:func:`compose_expand_tasks`), so third-party plugins need not
    # provide one to run under ``explore_impl="fused"``.
    expand_tasks: Optional[Callable[[ProblemData, Any, Any], ExpandResult]] = None
    external_value: Callable[[int], int] = staticmethod(lambda v: v)
    fpt_target: Callable[[int], int] = staticmethod(lambda k: k)

    # host plumbing
    host_adj: Callable[[Any], np.ndarray] = staticmethod(lambda g: g.adj)
    host_view: Callable[[Any], Any] = staticmethod(lambda g: g)
    # (view, mask, sol) -> (children, terminal) for the startup BFS split
    branch_once_host: Optional[Callable] = None
    sequential: Optional[Callable] = None  # ground-truth reference solver
    verify: Optional[Callable] = None  # (g, sol_mask) -> bool

    # host-side twins of task_bound/child_bound plus the terminal objective,
    # all in the INTERNAL (minimization) sense over (view, mask, sol_mask) —
    # these are what make a problem runnable on the discrete-event simulator
    # backends (protocol_sim / centralized), which explore on the host.
    host_task_bound: Optional[Callable] = None  # admissible pre-expansion bound
    host_child_bound: Optional[Callable] = None  # cheap bound at task birth
    host_terminal_value: Optional[Callable] = None  # internal value of a leaf

    # codec record layout (see repro.core.encoding)
    record_fields: tuple = RECORD_FIELDS


def compose_expand_tasks(problem: BranchingProblem) -> Callable:
    """The default batched expansion: the three per-task callables, vmapped.

    This is exactly what the reference explore path computes per round —
    ``task_bound`` on the popped batch, ``branch_once``, then ``child_bound``
    on both children — packaged behind the :class:`ExpandResult` signature.
    Problems without a hand-fused ``expand_tasks`` run on this under
    ``explore_impl="fused"`` and are trivially bit-identical to the
    reference path (property-tested in ``tests/test_explore_fused.py``).
    """

    def expand(data: ProblemData, masks, sols) -> ExpandResult:
        bound = jax.vmap(lambda m, s: problem.task_bound(data, m, s))(masks, sols)
        step = jax.vmap(lambda m, s: problem.branch_once(data, m, s))(masks, sols)
        left = jax.vmap(lambda m, s: problem.child_bound(data, m, s))(
            step.left_mask, step.left_sol
        )
        right = jax.vmap(lambda m, s: problem.child_bound(data, m, s))(
            step.right_mask, step.right_sol
        )
        return ExpandResult(bound=bound, step=step, left_bound=left, right_bound=right)

    return expand


def resolve_expand(problem: BranchingProblem) -> Callable:
    """The fused plane's batched expansion for ``problem``: its hand-fused
    ``expand_tasks`` when it ships one, else the composed default."""
    if problem.expand_tasks is not None:
        return problem.expand_tasks
    return compose_expand_tasks(problem)


def require_host_bounds(problem: BranchingProblem) -> BranchingProblem:
    """Assert a problem carries the host-side exploration callables the
    simulator backends need; raises a ``ValueError`` naming what's missing
    (the same fail-helpfully pattern as the registries)."""
    missing = [
        field
        for field in (
            "branch_once_host",
            "host_task_bound",
            "host_child_bound",
            "host_terminal_value",
        )
        if getattr(problem, field) is None
    ]
    if missing:
        raise ValueError(
            f"problem {problem.name!r} cannot run on a host simulator "
            f"backend: missing {', '.join(missing)} (see BranchingProblem)"
        )
    return problem


def initial_bound(problem: BranchingProblem, g, mode: str, k) -> int:
    """The engine's seed internal best: "worse than any acceptable solution".

    bnb: the problem's worst-case bound.  fpt: one worse than the decision
    target, so the bound prunes everything that cannot reach ``k`` and
    ``best < initial`` means the decision succeeded.
    """
    if mode == "fpt":
        if k is None:
            raise ValueError("fpt mode requires k")
        return int(problem.fpt_target(k)) + 1
    return int(problem.bnb_bound(g))


def make_data(problem: BranchingProblem, g) -> ProblemData:
    """Per-instance device tensors from a host graph (solo solve path)."""
    adj = np.asarray(problem.host_adj(g), dtype=np.uint32)
    v = np.arange(adj.shape[0], dtype=np.int32)
    return ProblemData(
        n=jnp.int32(g.n),
        adj=jnp.asarray(adj),
        word_idx=jnp.asarray(v // WORD_BITS),
        bit_idx=jnp.asarray((v % WORD_BITS).astype(np.uint32)),
    )


def make_batch_data(
    problem: BranchingProblem, graphs, n_max: int, W: int
) -> ProblemData:
    """Pack B same-width instances into padded (B, n_max, W) device tensors.

    Padding rows are zero (isolated, never-in-mask vertices), so they change
    no branching decision for any problem whose initial mask covers only the
    real vertices — the batched trace stays bit-identical to the solo one.
    """
    B = len(graphs)
    adj = np.zeros((B, n_max, W), np.uint32)
    for b, g in enumerate(graphs):
        adj[b, : g.n, :] = np.asarray(problem.host_adj(g), np.uint32)
    v = np.arange(n_max, dtype=np.int32)
    return ProblemData(
        n=jnp.asarray(np.array([g.n for g in graphs], np.int32)),
        adj=jnp.asarray(adj),
        word_idx=jnp.asarray(v // WORD_BITS),
        bit_idx=jnp.asarray((v % WORD_BITS).astype(np.uint32)),
    )


def slice_instances(data: ProblemData, sel) -> ProblemData:
    """Select instances along the batch axis (host-side compaction)."""
    return data._replace(n=data.n[sel], adj=data.adj[sel])


def make_blank_batch_data(num_lanes: int, n_max: int, W: int) -> ProblemData:
    """An all-vacant batched :class:`ProblemData` for a live plane: zero
    adjacency and n=0 per lane (inert under the frozen-lane select —
    admission overwrites a lane's slice via :func:`write_instance`)."""
    v = np.arange(n_max, dtype=np.int32)
    return ProblemData(
        n=jnp.zeros((num_lanes,), jnp.int32),
        adj=jnp.zeros((num_lanes, n_max, W), jnp.uint32),
        word_idx=jnp.asarray(v // WORD_BITS),
        bit_idx=jnp.asarray((v % WORD_BITS).astype(np.uint32)),
    )


# jitted lane write (one executable per (B, n_max, W) shape — live-plane
# admission calls this once per swap-in, where eager scatters add up)
@jax.jit
def _write_lane_dev(n, adj, lane, n_val, adj_block):
    return n.at[lane].set(n_val), adj.at[lane].set(adj_block)


def write_instance(
    data: ProblemData, lane: int, problem: BranchingProblem, g
) -> ProblemData:
    """Write one instance into lane ``lane`` of a batched ``data`` (live-
    plane admission).  Rows past ``g.n`` are zeroed (isolated, never-in-mask
    vertices — exactly :func:`make_batch_data`'s padding rule, so the
    admitted instance's trace is bit-identical to its solo solve).  Pure
    data writes: shapes are unchanged, the compiled plane is reused as-is.
    """
    n_max, W = data.adj.shape[1], data.adj.shape[2]
    if g.n > n_max or g.W > W:
        raise ValueError(
            f"instance (n={g.n}, W={g.W}) exceeds the live plane's "
            f"(n_max={n_max}, W={W}) packing"
        )
    adj = np.zeros((n_max, W), np.uint32)
    adj[: g.n, : g.W] = np.asarray(problem.host_adj(g), np.uint32)
    new_n, new_adj = _write_lane_dev(
        data.n, data.adj, jnp.int32(lane), jnp.int32(g.n), jnp.asarray(adj)
    )
    return data._replace(n=new_n, adj=new_adj)


def expand_frontier(
    problem: BranchingProblem,
    g,
    num_tasks: int,
    max_nodes: int = 10_000,
):
    """Startup-phase breadth-first split (paper §3.5), problem-generic:
    expand the root until at least ``num_tasks`` open tasks exist.  Returns
    ``[(mask, sol_mask, depth)]``.

    Terminal nodes encountered during the split are kept (they carry
    candidate solutions and must not be lost).  The traversal order matches
    the pre-plugin vertex-cover implementation exactly: pop the shallowest
    open task, append children in the plugin's order.
    """
    view = problem.host_view(g)
    frontier = [(mask_full(g.n), np.zeros(g.W, dtype=np.uint32), 0)]
    terminals = []
    nodes = 0
    while (
        len(frontier) + len(terminals) < num_tasks
        and frontier
        and nodes < max_nodes
    ):
        # expand the shallowest open task (BFS == equitable split)
        idx = min(range(len(frontier)), key=lambda i: frontier[i][2])
        mask, sol_mask, depth = frontier.pop(idx)
        nodes += 1
        children, terminal = problem.branch_once_host(view, mask, sol_mask)
        if terminal is not None:
            terminals.append((terminal[0], terminal[1], depth))
            continue
        for cmask, csol in children:
            frontier.append((cmask, csol, depth + 1))
    return frontier + terminals
