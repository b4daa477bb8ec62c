"""SPMD superstep engine: the TPU adaptation of the semi-centralized strategy.

One superstep =

  1. **explore** — each worker expands up to ``lanes`` of its deepest tasks
     for ``steps_per_round`` rounds (the paper's exploration threads);
  2. **control plane** — each worker contributes THREE integers
     (pending count, shallowest pending depth, local best value) to an
     all-gather: this is the paper's "every message is a single integer"
     budget, and the gathered (P, 3) table is the entire center state;
  3. **replicated center** — every worker deterministically computes the same
     idle→donor matching from the table (`getNextWorkingNode` over RUNNING
     workers; priority = shallowest pending task, or round-robin "random");
  4. **data plane** — matched donors pop up to ``donate_k`` of their
     *shallowest* tasks (Alg. 6, batched) and the fixed-size records move to
     the idle worker.  Two implementations (§Perf in EXPERIMENTS.md):

       ``transfer_impl="sparse"`` (default) — each donor scatters its record
       block into a zero (P, k, REC) buffer addressed by ``send_to`` and ONE
       ``psum`` delivers it; rows for unmatched workers are zero, so the
       payload actually carrying tasks scales with ``n_match`` (and the
       whole collective is skipped on match-free rounds — zero bytes);

       ``transfer_impl="gather"`` — the all-gather + select reference path
       kept for A/B benchmarking: every transfer round moves the full
       (P, k, REC) table regardless of how few records matched;
  5. **best-value broadcast** — global best = min over workers (the paper's
     ``bestval_update`` verify-then-broadcast collapses to one pmin).

Failure-free guarantee (paper §3.1): the matcher only pairs an idle worker
with a donor whose ``pending >= 2``, donors keep at least one task
(``donated = min(k, pending - 1)``), and in BSP the transfer completes inside
the same superstep — a matched idle worker ALWAYS receives a task, no retries.

Termination (paper §3.3): transfers cannot straddle a superstep boundary, so
``psum(pending) == 0`` after the transfer phase is exact quiescence — the
sent/ack counting and timeout safety mechanisms of the MPI implementation are
subsumed by the BSP barrier.

The same function runs for every worker under ``jax.vmap`` (P virtual
workers on one device) or, on a mesh of chips, under ``shard_map`` over the
chips with each chip's P / chips workers vmapped inside it.
``build_plane_fn`` wraps either in a device-resident ``lax.while_loop`` that
runs up to K supersteps per host sync, checking quiescence (and the FPT
bound) on device — the host only syncs once per chunk, so round latency is
hardware-bound, not host-dispatch-bound.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.frontier import (
    Frontier,
    make_frontier,
    pending_per_worker,
    pop_deepest,
    pop_deepest_cheap,
    pop_k_shallowest,
    push_many,
)
from repro.problems.base import (
    DATA_IN_AXES,
    BranchingProblem,
    ProblemData,
    compose_expand_tasks,
    resolve_expand,
)

# explore-phase implementations (§Perf, EXPERIMENTS.md §F):
#   "reference" — per-task callables (task_bound / branch_once / child_bound
#                 as three separate vmapped calls) + full-capacity top_k pop;
#                 no repro.kernels dependency (arch-guarded), the bit-exact
#                 baseline kept for A/B and goldens;
#   "fused"     — the problem's one-pass batched expand_tasks (hand-fused
#                 impls share degrees/popcounts and ride the Pallas bitset
#                 kernel on TPU; other plugins get the composed default) +
#                 the cheap depth-major frontier pop.  Bit-identical to the
#                 reference by contract (golden- and property-tested).
# These tuples are THE registries for the two hot-path knobs —
# SolveConfig._validate imports them, so the engine and the config can never
# disagree about what is valid.
EXPLORE_IMPLS = ("fused", "reference")
TRANSFER_IMPLS = ("sparse", "gather")


def _shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes check off: the chunked
    runner's ``lax.while_loop`` carries the worker state sliced from the
    sharded input (typed as varying over the chip axis), and the
    pmin/psum that update scalars such as ``best_val`` return values the
    checker types as replicated, so the loop carry would change type.  Kept
    local so :mod:`repro.core` stays launch-independent."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# The worker axis is one name under vmap (every worker on one device), or
# the pair (chips, workers) on a mesh: the mesh's chip axis outside each
# chip's vmapped block of workers, so that worker w of P sits on chip
# w // (P / chips).  A collective over the pair runs inside each chip first
# (a local reduction under vmap), then once across the chips.


def _axes(axis_name) -> tuple:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def _all_gather(x, axis_name):
    """Every worker's ``x``, stacked in worker order."""
    for name in reversed(_axes(axis_name)):
        x = jax.lax.all_gather(x, name)
    if isinstance(axis_name, str):
        return x
    return x.reshape((-1,) + x.shape[2:])


def _over_workers(collective, x, axis_name):
    """``collective`` (``psum``, ``pmin``) of ``x`` over every worker."""
    for name in reversed(_axes(axis_name)):
        x = collective(x, name)
    return x


def _worker_index(axis_name):
    if isinstance(axis_name, str):
        return jax.lax.axis_index(axis_name)
    chips, workers = axis_name
    return (
        jax.lax.axis_index(chips) * jax.lax.axis_size(workers)
        + jax.lax.axis_index(workers)
    )


class WorkerState(NamedTuple):
    frontier: Frontier
    best_val: jnp.ndarray  # () int32 -- global best seen (paper: global_bestval)
    local_best_val: jnp.ndarray  # () int32 -- best found by THIS worker
    best_sol: jnp.ndarray  # (W,) uint32 -- the cover achieving local_best_val
    nodes_expanded: jnp.ndarray  # () int32
    tasks_sent: jnp.ndarray  # () int32
    tasks_recv: jnp.ndarray  # () int32
    rounds: jnp.ndarray  # () int32
    # collective-traffic accounting, carried ON DEVICE so the chunked runner
    # never has to sync for stats (replicated: same value on every worker)
    transfer_rounds: jnp.ndarray  # () int32 -- rounds that ran the data plane
    payload_words: jnp.ndarray  # () int32 -- u32 words moved by the data plane
    # the explore reduction's work (ExpandResult.work; 0 for a plugin
    # without a reduction and on the reference explore path).  Counters,
    # not trajectory: a checkpoint without them loads them as 0.
    reduce_lane_sweeps: jnp.ndarray  # () int32 -- sweeps of expanded lanes
    reduce_worker_sweeps: jnp.ndarray  # () int32 -- per step, max over lanes
    reduce_fires_rule1: jnp.ndarray  # () int32
    reduce_fires_rule2: jnp.ndarray  # () int32
    reduce_fires_rule3: jnp.ndarray  # () int32
    reduce_rule3_probes: jnp.ndarray  # () int32 -- candidates rule 3 examined
    # tasks this worker donated to a worker on another chip (0 on one chip)
    tasks_sent_remote: jnp.ndarray  # () int32

    @property
    def overflow_count(self) -> jnp.ndarray:
        """Tasks this worker lost to frontier saturation (cumulative () int32
        stat, owned by ``frontier.dropped`` — push_many maintains it).  0
        under engine-sized capacity; surfaced per instance as
        ``SolveResult.stats["overflow_count"]``."""
        return self.frontier.dropped


def make_worker_state(capacity: int, W: int, initial_best: int) -> WorkerState:
    z = jnp.int32(0)
    return WorkerState(
        frontier=make_frontier(capacity, W),
        best_val=jnp.int32(initial_best),
        local_best_val=jnp.int32(initial_best),
        best_sol=jnp.zeros((W,), jnp.uint32),
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


# the reduction's counters by name: results sum them over workers
REDUCE_COUNTERS = (
    "reduce_lane_sweeps",
    "reduce_worker_sweeps",
    "reduce_fires_rule1",
    "reduce_fires_rule2",
    "reduce_fires_rule3",
    "reduce_rule3_probes",
)
# counters a checkpoint may lack (they are not trajectory): loaded as 0
LATE_COUNTERS = REDUCE_COUNTERS + ("tasks_sent_remote",)


# -- phase 1: exploration ------------------------------------------------------


@jax.named_scope("explore")
def _explore_one_round(
    problem: BranchingProblem,
    data: ProblemData,
    state: WorkerState,
    lanes: int,
    explore_impl: str = "reference",
):
    """Pop up to ``lanes`` deepest tasks, expand each, push children.

    Problem-generic: the plugin supplies ``task_bound`` (admissible bound on
    the internal objective, gates expansion), ``branch_once`` (one node
    expansion -> :class:`BranchStep`) and ``child_bound`` (cheap birth-time
    prune).  The engine always minimizes internal values.

    ``explore_impl`` picks the hot-path implementation (:data:`EXPLORE_IMPLS`):
    the reference path sweeps the lane batch once per callable (plus a
    full-capacity top_k pop); the fused path pops via the cheap depth-major
    selection and expands through the plugin's one-pass ``expand_tasks``.
    Both produce bit-identical states.

    Its operations carry the scopes ``explore/pop``, ``explore/expand`` and
    ``explore/push`` (the best update sits in ``explore`` alone).
    """
    with jax.named_scope("pop"):
        if explore_impl == "fused":
            f, masks, sols, depths, valid = pop_deepest_cheap(state.frontier, lanes)
            expand = resolve_expand(problem)
        else:
            f, masks, sols, depths, valid = pop_deepest(state.frontier, lanes)
            # ALWAYS the composed per-task callables — one source of truth
            # with the fused path's default, so the two can never desynchronize
            expand = compose_expand_tasks(problem)
    with jax.named_scope("expand"):
        ex = expand(data, masks, sols)
    bounds, res = ex.bound, ex.step
    left_bound, right_bound = ex.left_bound, ex.right_bound

    not_pruned = valid & (bounds < state.best_val)

    # terminal candidates -> best update (paper: handleSolution + bestval)
    term = not_pruned & res.is_terminal & (res.terminal_value < state.best_val)
    term_val = jnp.where(term, res.terminal_value, jnp.int32(1 << 30))
    li = jnp.argmin(term_val)
    found_val = term_val[li]  # 1<<30 when no lane found a terminal
    # local best only improves with terminals THIS worker found (its stored
    # solution must actually achieve local_best_val); the global view may also
    # shrink via the pmin in the communication phase.
    new_sol = jnp.where(
        found_val < state.local_best_val, res.terminal_sol[li], state.best_sol
    )
    new_local = jnp.minimum(state.local_best_val, found_val)
    new_best = jnp.minimum(state.best_val, found_val)

    # children push: [left_0..left_L, right_0..right_L], pruned-at-birth when
    # the cheap bound says they cannot beat best (host reference does the same).
    expandable = not_pruned & ~res.is_terminal
    cdepth = depths + 1
    lvalid = expandable & (left_bound < new_best)
    rvalid = expandable & (right_bound < new_best)
    all_masks = jnp.concatenate([res.left_mask, res.right_mask], axis=0)
    all_sols = jnp.concatenate([res.left_sol, res.right_sol], axis=0)
    all_depths = jnp.concatenate([cdepth, cdepth], axis=0)
    all_valid = jnp.concatenate([lvalid, rvalid], axis=0)
    with jax.named_scope("push"):
        f = push_many(f, all_masks, all_sols, all_depths, all_valid)

    state = state._replace(
        frontier=f,
        best_val=new_best,
        local_best_val=new_local,
        best_sol=new_sol,
        nodes_expanded=state.nodes_expanded + valid.sum().astype(jnp.int32),
    )
    if ex.work is None:
        return state
    # the lanes sweep in lockstep: the worker's loop runs as often as its
    # slowest lane, popped or not
    sweeps = jnp.where(valid, ex.work.sweeps, 0).sum().astype(jnp.int32)
    fires = jnp.where(valid[:, None], ex.work.fires, 0).sum(axis=0).astype(jnp.int32)
    probes = jnp.where(valid, ex.work.rule3_probes, 0).sum().astype(jnp.int32)
    return state._replace(
        reduce_lane_sweeps=state.reduce_lane_sweeps + sweeps,
        reduce_worker_sweeps=state.reduce_worker_sweeps
        + ex.work.sweeps.max().astype(jnp.int32),
        reduce_fires_rule1=state.reduce_fires_rule1 + fires[0],
        reduce_fires_rule2=state.reduce_fires_rule2 + fires[1],
        reduce_fires_rule3=state.reduce_fires_rule3 + fires[2],
        reduce_rule3_probes=state.reduce_rule3_probes + probes,
    )


def explore_phase(
    problem: BranchingProblem,
    data: ProblemData,
    state: WorkerState,
    steps: int,
    lanes: int,
    explore_impl: str = "reference",
) -> WorkerState:
    def body(_, s):
        return _explore_one_round(problem, data, s, lanes, explore_impl)

    return jax.lax.fori_loop(0, steps, body, state)


# -- phase 3: the replicated center -------------------------------------------


def match_idle_to_donors(
    pending: jnp.ndarray,  # (P,) int32
    top_depth: jnp.ndarray,  # (P,) int32 (BIG_DEPTH when empty)
    policy_priority: bool,
    round_idx: jnp.ndarray,  # () int32 -- salt for the round-robin policy
):
    """The center's `getNextWorkingNode`, replicated: every worker computes
    the same matching from the same (P,) status vectors.

    Returns (send_to, recv_from): per-worker partner index or -1.
    Donors need pending >= 2 (donate one, keep one — failure-free).
    'priority' ranks donors by shallowest pending depth (heaviest task,
    paper §3.2 metadata policy); 'random' becomes a round-salted round-robin
    (deterministic — required for SPMD replication — but unbiased over time).
    """
    P = pending.shape[0]
    idx = jnp.arange(P, dtype=jnp.int32)
    idle = pending == 0
    donor = pending >= 2

    # rank idle workers 0..n_idle-1 in index order
    idle_rank = jnp.where(idle, jnp.cumsum(idle.astype(jnp.int32)) - 1, -1)

    # order donors: priority -> by (top_depth, idx); round-robin -> by
    # ((idx + salt) mod P, idx) which rotates who donates first each round.
    if policy_priority:
        donor_key = top_depth * P + idx
    else:
        donor_key = (idx + round_idx) % P
    donor_key = jnp.where(donor, donor_key, jnp.int32(1 << 30))
    donor_order = jnp.argsort(donor_key)  # donors first, in key order
    donor_rank = jnp.zeros((P,), jnp.int32).at[donor_order].set(idx)
    donor_rank = jnp.where(donor, donor_rank, -1)

    # donor with rank k serves idle with rank k
    n_idle = idle.sum()
    n_donor = donor.sum()
    n_match = jnp.minimum(n_idle, n_donor)

    # send_to[w] = idle worker with rank donor_rank[w] (if matched)
    idle_by_rank = jnp.zeros((P,), jnp.int32).at[
        jnp.where(idle, idle_rank, P)
    ].set(idx, mode="drop")
    send_to = jnp.where(
        donor & (donor_rank < n_match), idle_by_rank[jnp.clip(donor_rank, 0, P - 1)], -1
    )
    donor_by_rank = jnp.zeros((P,), jnp.int32).at[
        jnp.where(donor, donor_rank, P)
    ].set(idx, mode="drop")
    recv_from = jnp.where(
        idle & (idle_rank < n_match), donor_by_rank[jnp.clip(idle_rank, 0, P - 1)], -1
    )
    return send_to, recv_from


# -- the full superstep ---------------------------------------------------------


def superstep(
    problem: BranchingProblem,
    data: ProblemData,
    state: WorkerState,
    *,
    axis_name,
    steps_per_round: int,
    lanes: int,
    policy_priority: bool = True,
    transfer_pad_words: int = 0,
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    donate_k: int = 1,
    explore_impl: str = "reference",
):
    """One BSP round for a single worker (replicated via vmap/shard_map).

    ``axis_name`` names the worker axis: one vmap axis, or (chips, workers)
    on a mesh (see :func:`_all_gather`).

    ``transfer_pad_words`` emulates the paper's *basic* encoding (§4.3): the
    task record is padded by n·W words of (redundant) adjacency payload so the
    collective moves the same bytes the MPI version would — used by the
    encoding benchmark; 0 = optimized encoding.

    §Perf knobs (EXPERIMENTS.md):
      packed_status       — (pending, top_depth) bit-packed into ONE i32 per
                            worker (+ a scalar pmin for the bound) instead of
                            a 3-int row: the control-plane gather shrinks 3x.
      skip_empty_transfer — the data-plane collective runs under a cond that
                            every worker evaluates identically from the
                            replicated table; rounds with no match move ZERO
                            payload.
      transfer_impl       — "sparse": donors scatter their record block into a
                            zero (P, k, REC) buffer by ``send_to`` and one
                            psum delivers it (payload records == matches);
                            "gather": all-gather + select reference path
                            (payload == the full P·k record table).
      donate_k            — a matched donor sends up to ``donate_k`` of its
                            shallowest tasks (always keeping one), filling a
                            starved worker in one rebalance round.
      explore_impl        — "fused": one-pass batched expansion + cheap
                            depth-major frontier pop; "reference": per-task
                            callables + full-capacity top_k.  Bit-identical
                            traces (see :data:`EXPLORE_IMPLS`).

    Its operations carry the scopes ``explore/...`` (see
    :func:`_explore_one_round`), ``center`` (status gather, best pmin,
    matching), ``transfer`` (the data plane) and ``termination``.

    Returns (state, done) where done is the exact global quiescence flag.
    """
    if transfer_impl not in TRANSFER_IMPLS:
        raise ValueError(
            f"unknown transfer_impl: {transfer_impl!r}; "
            f"valid: {', '.join(TRANSFER_IMPLS)}"
        )
    if explore_impl not in EXPLORE_IMPLS:
        raise ValueError(
            f"unknown explore_impl: {explore_impl!r}; "
            f"valid: {', '.join(EXPLORE_IMPLS)}"
        )
    if donate_k < 1:
        # a matched donor must ship at least one task, or the failure-free
        # guarantee (a matched idle worker ALWAYS receives work) breaks
        raise ValueError(f"donate_k must be >= 1, got {donate_k}")
    W = state.best_sol.shape[0]
    # the frontier's native task record: (mask, sol, depth) — problem-
    # independent by construction (every plugin uses the packed-state layout)
    rec_words = 2 * W + 1 + transfer_pad_words

    # 1. explore
    state = explore_phase(
        problem, data, state, steps_per_round, lanes, explore_impl
    )

    with jax.named_scope("center"):
        # 2. control plane through the "center" + 5. best-value broadcast
        pending = state.frontier.pending()
        top_depth = state.frontier.top_priority_depth()
        if packed_status:
            # one i32 per worker: pending (15b) | clamped depth (16b)
            word = (jnp.clip(pending, 0, 0x7FFF) << 16) | jnp.clip(
                top_depth, 0, 0xFFFF
            )
            table_w = _all_gather(word, axis_name)  # (P,)
            pend_t = table_w >> 16
            depth_t = table_w & 0xFFFF
            global_best = _over_workers(
                jax.lax.pmin,
                jnp.minimum(state.local_best_val, state.best_val),
                axis_name,
            )
        else:
            my_status = jnp.stack([pending, top_depth, state.local_best_val])
            table = _all_gather(my_status, axis_name)  # (P, 3)
            pend_t, depth_t = table[:, 0], table[:, 1]
            global_best = jnp.minimum(table[:, 2].min(), state.best_val)
        state = state._replace(best_val=global_best)

        # 3. replicated center matching
        P = pend_t.shape[0]
        me = _worker_index(axis_name).astype(jnp.int32)
        send_to, recv_from = match_idle_to_donors(
            pend_t, depth_t, policy_priority, state.rounds
        )
        n_match = (send_to >= 0).sum()
        # records each donor actually ships (>=1 when matched: pending >= 2);
        # replicated, so donor AND receiver count the block identically.
        n_don = jnp.where(
            send_to >= 0,
            jnp.minimum(jnp.int32(donate_k), pend_t - 1),
            jnp.int32(0),
        )  # (P,)

    # 4. data plane: donor pops its shallowest block; record row =
    #    (mask, sol, depth[, pad])
    def do_transfer(state):
        f2, d_masks, d_sols, d_depths, d_valid = pop_k_shallowest(
            state.frontier, donate_k, limit=n_don[me]
        )
        record = jnp.concatenate(
            [d_masks, d_sols, d_depths[:, None].astype(jnp.uint32)], axis=1
        )
        if transfer_pad_words:
            record = jnp.concatenate(
                [record, jnp.zeros((donate_k, transfer_pad_words), jnp.uint32)],
                axis=1,
            )
        record = jnp.where(d_valid[:, None], record, jnp.uint32(0))

        my_src = recv_from[me]
        i_recv = my_src >= 0
        if transfer_impl == "gather":
            # reference path: all-gather the full record table (indexed by
            # DONOR), select my donor's block
            all_records = _all_gather(record, axis_name)  # (P, k, REC)
            got = all_records[jnp.clip(my_src, 0, P - 1)]  # (k, REC)
            moved_words = jnp.int32(P * donate_k * rec_words)
        else:
            # sparse path: scatter my block into the row my RECEIVER owns;
            # one psum delivers every matched block at once (unmatched rows
            # stay zero — the payload is exactly the matched records), and
            # each receiver reads its own row.
            buf = jnp.zeros((P, donate_k, rec_words), jnp.uint32)
            tgt = jnp.where(send_to[me] >= 0, send_to[me], jnp.int32(P))
            buf = buf.at[tgt].set(record, mode="drop")
            delivered = _over_workers(jax.lax.psum, buf, axis_name)  # (P, k, REC)
            got = delivered[me]  # (k, REC)
            moved_words = n_don.sum() * rec_words
        recv_valid = i_recv & (
            jnp.arange(donate_k) < n_don[jnp.clip(my_src, 0, P - 1)]
        )
        new_frontier = push_many(
            f2,
            got[:, :W],
            got[:, W : 2 * W],
            got[:, 2 * W].astype(jnp.int32),
            recv_valid,
        )
        state = state._replace(
            frontier=new_frontier,
            tasks_sent=state.tasks_sent + n_don[me],
            tasks_recv=state.tasks_recv + recv_valid.sum().astype(jnp.int32),
            transfer_rounds=state.transfer_rounds + 1,
            payload_words=state.payload_words + moved_words,
        )
        if isinstance(axis_name, str):  # one chip: nothing leaves it
            return state
        # an unmatched donor has send_to -1 and ships nothing (n_don 0)
        per_chip = P // jax.lax.axis_size(axis_name[0])
        remote = jnp.where(send_to[me] // per_chip != me // per_chip, n_don[me], 0)
        return state._replace(tasks_sent_remote=state.tasks_sent_remote + remote)

    with jax.named_scope("transfer"):
        if skip_empty_transfer:
            # n_match derives from the replicated table: every worker takes
            # the same branch, so the collective inside the cond is safe.
            state = jax.lax.cond(n_match > 0, do_transfer, lambda s: s, state)
        else:
            state = do_transfer(state)
    state = state._replace(rounds=state.rounds + 1)

    # exact termination: nothing pending anywhere after the transfer phase
    with jax.named_scope("termination"):
        total_pending = _over_workers(
            jax.lax.psum, state.frontier.pending(), axis_name
        )
        done = total_pending == 0
    return state, done


def build_superstep_fn(
    problem: BranchingProblem,
    data: ProblemData,
    *,
    num_workers: int,
    steps_per_round: int,
    lanes: int,
    policy_priority: bool = True,
    transfer_pad_words: int = 0,
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    donate_k: int = 1,
    explore_impl: str = "reference",
    axis_name: str = "workers",
):
    """Return a jitted ``state -> (state, done)`` over stacked (P, ...) state:
    one superstep of P virtual workers, vmapped on one device.

    One host sync per superstep — solve loops run :func:`build_plane_fn`;
    this remains the single-round entry point for tests/benchmarks.
    """
    step = functools.partial(
        superstep,
        problem,
        data,
        axis_name=axis_name,
        steps_per_round=steps_per_round,
        lanes=lanes,
        policy_priority=policy_priority,
        transfer_pad_words=transfer_pad_words,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        donate_k=donate_k,
        explore_impl=explore_impl,
    )
    vstep = jax.vmap(step, axis_name=axis_name)

    def run(state):
        state, done = vstep(state)
        return state, done.all()

    return jax.jit(run)


# -- parametric compiled planes ------------------------------------------------
#
# The builders below close over NOTHING instance-specific: `ProblemData` (and
# the FPT bound) are call-time arguments of the returned jitted function, so
# ONE executable serves every same-shape instance — the session-level
# compiled-plane cache (repro.api) keys these functions by configuration and
# lets jax's own trace cache specialize per (n, W, capacity) shape.  A warm
# repeat solve therefore re-traces nothing.
#
# `PLANE_TRACES` counts actual traces: it is bumped by a host side effect
# inside the traced body, which only runs when jax (re)traces — tests and the
# session's cache_stats() use it as the ground-truth compile counter.

PLANE_TRACES = 0


def _count_plane_trace() -> None:
    global PLANE_TRACES
    PLANE_TRACES += 1


def build_plane_fn(
    problem: BranchingProblem,
    *,
    steps_per_round: int,
    lanes: int,
    policy_priority: bool = True,
    transfer_pad_words: int = 0,
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    donate_k: int = 1,
    explore_impl: str = "reference",
    chunk_rounds: int = 16,
    use_fpt: bool = False,
    axis_name: str = "workers",
    mesh=None,
):
    """Parametric solo chunk runner.

    Returns a jitted ``(data, state) -> (state, done, ran, hot)`` — or, with
    ``use_fpt``, ``(data, state, fpt_bound) -> ...`` where ``fpt_bound`` is
    the () int32 INTERNAL decision target.  ``state`` is the (P, ...)
    stacked worker state; ``done`` is exact global quiescence (or the FPT
    bound reached); ``ran`` the supersteps executed (< ``chunk_rounds`` only
    when the run finished mid-chunk); ``hot`` the (P,) int32 per-worker
    pending count after the chunk — the spill pump's eviction trigger,
    computed on device so the host decides whether to pump from scalars it
    already fetched.  Up to ``chunk_rounds`` supersteps run inside ONE
    ``lax.while_loop`` on device, so the host syncs once per chunk.  The
    instance tensors are arguments, so the function serves every same-shape
    instance without re-tracing.

    mesh=None: the P workers are virtual, vmapped on one device.  A 1-D
    ``mesh`` (see :func:`repro.launch.mesh.make_solver_mesh`): the state's
    worker axis is sharded over the mesh's chips, P / chips workers on each,
    ``data`` replicated; each chip runs the while_loop over its block of
    workers, vmapped, and every collective of the superstep spans the chips
    and the workers together.  Both give bit-identical results.
    """
    if chunk_rounds < 1:
        # 0 would return (state, done=False, ran=0) forever: the caller's
        # progress counter never advances and its solve loop cannot exit
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    step = functools.partial(
        superstep,
        problem,
        steps_per_round=steps_per_round,
        lanes=lanes,
        policy_priority=policy_priority,
        transfer_pad_words=transfer_pad_words,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        donate_k=donate_k,
        explore_impl=explore_impl,
    )

    def cond(carry):
        _, done, i = carry
        return jnp.logical_not(done) & (i < chunk_rounds)

    def chunk(data, state, fpt_bound, axes):
        vstep = jax.vmap(
            lambda s: step(data, s, axis_name=axes), axis_name=axis_name
        )

        def body(carry):
            state, _, i = carry
            state, done = vstep(state)
            done = done.all()
            if use_fpt:
                # best_val is the global min after the pmin: replicated
                done = done | (state.best_val.min() <= fpt_bound)
            return state, done, i + 1

        state, done, i = jax.lax.while_loop(
            cond, body, (state, jnp.bool_(False), jnp.int32(0))
        )
        return state, done, i, pending_per_worker(state.frontier)

    if mesh is None:

        def _run(data, state, fpt_bound):
            _count_plane_trace()
            return chunk(data, state, fpt_bound, axis_name)

    else:
        from jax.sharding import PartitionSpec as P

        (chips,) = mesh.axis_names
        axes = (chips, axis_name)
        bound_spec = (P(),) if use_fpt else ()
        per_chip = _shard_map(
            lambda data, state, *bound: chunk(
                data, state, bound[0] if use_fpt else None, axes
            ),
            mesh=mesh,
            in_specs=(P(), P(chips)) + bound_spec,
            out_specs=(P(chips), P(), P(), P(chips)),
        )

        def _run(data, state, fpt_bound):
            _count_plane_trace()
            return per_chip(data, state, *([fpt_bound] if use_fpt else []))

    if use_fpt:
        return jax.jit(_run)
    return jax.jit(lambda data, state: _run(data, state, None))


def build_batch_plane_fn(
    problem: BranchingProblem,
    *,
    steps_per_round: int,
    lanes: int,
    policy_priority: bool = True,
    transfer_pad_words: int = 0,
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    donate_k: int = 1,
    explore_impl: str = "reference",
    chunk_rounds: int = 16,
    use_fpt: bool = False,
    axis_name: str = "workers",
):
    """Parametric batch chunk runner over (B, P, ...) stacked state.

    Returns a jitted ``(datas, state, done) -> (state, done, rounds_delta,
    ran, hot)`` — with ``use_fpt``, an extra trailing ``fpt_bounds`` (B,)
    int32 argument.  ``hot`` is the (B, P) int32 per-lane, per-worker
    pending count after the chunk (the spill pump's trigger, see
    :func:`build_plane_fn`).  Same contract as
    :func:`build_batch_chunk_fn`, but the batched
    instance tensors are call-time arguments: host-side compaction can
    reslice and keep calling the SAME function, and a later batch with
    previously-seen shapes reuses the executable outright.
    """
    if chunk_rounds < 1:
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    step = functools.partial(
        superstep,
        problem,
        axis_name=axis_name,
        steps_per_round=steps_per_round,
        lanes=lanes,
        policy_priority=policy_priority,
        transfer_pad_words=transfer_pad_words,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        donate_k=donate_k,
        explore_impl=explore_impl,
    )

    def one_instance(data, state):
        state, done = jax.vmap(
            lambda s: step(data, s), axis_name=axis_name
        )(state)
        return state, done.all()

    bstep = jax.vmap(one_instance, in_axes=(DATA_IN_AXES, 0))

    def cond(carry):
        _, done, _, i = carry
        return jnp.logical_not(done.all()) & (i < chunk_rounds)

    def _run(datas, state, done, fpt_bounds):
        _count_plane_trace()

        def body(carry):
            state, done, rounds_delta, i = carry
            new_state, step_done = bstep(datas, state)
            # freeze finished lanes (see build_batch_chunk_fn)
            state = jax.tree.map(
                lambda old, new: jnp.where(_expand_like(done, new), old, new),
                state,
                new_state,
            )
            new_done = done | step_done
            if use_fpt:
                new_done = new_done | (state.best_val[:, 0] <= fpt_bounds)
            rounds_delta = rounds_delta + jnp.where(done, 0, 1).astype(jnp.int32)
            return state, new_done, rounds_delta, i + 1

        B = done.shape[0]
        state, done, rounds_delta, i = jax.lax.while_loop(
            cond, body, (state, done, jnp.zeros((B,), jnp.int32), jnp.int32(0))
        )
        return state, done, rounds_delta, i, pending_per_worker(state.frontier)

    if use_fpt:
        return jax.jit(_run)
    return jax.jit(lambda datas, state, done: _run(datas, state, done, None))


# -- the instance axis ---------------------------------------------------------
#
# `solve_many` stacks B independent instances in front of the worker axis:
# state leaves become (B, P, ...) and the problem data gains per-instance
# leaves (adj (B, n, W), n (B,)) while word_idx/bit_idx stay shared
# (`problems.base.DATA_IN_AXES`).  The collectives inside `superstep` are
# bound to the WORKER axis name, so vmapping the whole worker-mapped step
# over an unnamed instance axis keeps every all-gather / psum / pmin confined
# to one instance: donation cannot cross the instance axis by construction
# (tested in tests/test_solve_many.py).


def _expand_like(flags: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (B,) flag vector against a (B, ...) state leaf."""
    return flags.reshape(flags.shape + (1,) * (leaf.ndim - 1))


# -- the lane lifecycle --------------------------------------------------------
#
# A *lane* is one instance slot of the batched plane: worker-state leaves
# (B, P, ...) plus the per-lane control scalars.  `LaneState` makes the
# lifecycle explicit so a batch is no longer an all-or-nothing unit of work:
# the host can step the plane one chunk at a time (`step_lanes`), slice a
# finished lane's state out (`lane_slice`), retire it (`lane_retire`) and
# swap a NEW instance into the freed slot (`lane_swap_in`) — all data-only
# writes against the parametric batch plane, so a long-lived "live" plane
# admits work forever without re-tracing.  Both the run-to-completion
# `solve_many` driver and the continuous solve service (repro.api.service)
# are built from these four verbs.


class LaneState(NamedTuple):
    """Per-lane lifecycle state of a live batched plane.

    ``worker``  — (B, P, ...) stacked :class:`WorkerState` (the plane state);
    ``done``    — (B,) bool: quiescent/FPT-finished OR vacant (frozen no-op);
    ``tag``     — (B,) host int32: the occupant's instance tag, -1 = vacant.
                  Kept as a numpy array: tags are pure host bookkeeping (the
                  plane never reads them) and the scheduler consults them
                  every chunk, so a device round-trip per lookup would be
                  wasted;
    ``rounds``  — (B,) int32: supersteps run by the CURRENT occupant (reset
                  on swap-in).
    """

    worker: WorkerState
    done: jnp.ndarray
    tag: object  # (B,) np.int32 — host-side, see class docstring
    rounds: jnp.ndarray

    @property
    def num_lanes(self) -> int:
        return self.done.shape[0]

    def occupied(self):
        """(B,) host bool — lanes holding a (possibly finished) instance."""
        return np.asarray(self.tag) >= 0


def make_vacant_lanes(
    num_lanes: int, num_workers: int, capacity: int, W: int
) -> LaneState:
    """An all-vacant live plane: every lane is a frozen no-op (``done``)
    until an instance is swapped in."""
    one = jax.vmap(lambda _: make_worker_state(capacity, W, 0))(
        jnp.arange(num_workers)
    )
    worker = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_lanes,) + x.shape), one
    )
    return LaneState(
        worker=worker,
        done=jnp.ones((num_lanes,), bool),
        tag=np.full((num_lanes,), -1, np.int32),
        rounds=jnp.zeros((num_lanes,), jnp.int32),
    )


def lane_slice(lanes: LaneState, lane: int) -> WorkerState:
    """One lane's (P, ...) worker state, sliced out for result extraction."""
    return jax.tree.map(lambda x: x[lane], lanes.worker)


# the admission write, jitted: one fused executable per lane-state shape
# instead of ~15 eager scatter dispatches per swap-in (`lane` is a traced
# scalar, so every lane index shares the executable)
@jax.jit
def _swap_in_dev(worker_full, worker_one, done, rounds, lane):
    return (
        jax.tree.map(
            lambda full, one: full.at[lane].set(one), worker_full, worker_one
        ),
        done.at[lane].set(False),
        rounds.at[lane].set(0),
    )


def lane_swap_in(
    lanes: LaneState, lane: int, worker: WorkerState, tag: int
) -> LaneState:
    """Admit a freshly startup-scattered instance into ``lane``.

    ``worker`` is a solo (P, ...) state (same shapes as one lane).  The lane
    un-freezes (``done`` False), its round counter resets, and its tag
    records the occupant.  Pure data writes — the compiled plane is reused
    as-is, no re-trace (asserted via ``PLANE_TRACES`` in tests).
    """
    new_tag = np.asarray(lanes.tag).copy()
    new_tag[lane] = tag
    new_worker, new_done, new_rounds = _swap_in_dev(
        lanes.worker, worker, lanes.done, lanes.rounds, jnp.int32(lane)
    )
    return LaneState(
        worker=new_worker, done=new_done, tag=new_tag, rounds=new_rounds
    )


# the stall write-back, jitted like _swap_in_dev: restore one lane's worker
# state AND its done/rounds flags exactly as sliced (no swap-in resets).
# Used to freeze a stalled lane across a chunk — the plane steps it, then
# the snapshot is written back so the lane observably made no progress —
# without touching the compiled plane (traced lane index, shared executable).
@jax.jit
def _write_back_dev(worker_full, worker_one, done_full, done_one,
                    rounds_full, rounds_one, lane):
    return (
        jax.tree.map(
            lambda full, one: full.at[lane].set(one), worker_full, worker_one
        ),
        done_full.at[lane].set(done_one),
        rounds_full.at[lane].set(rounds_one),
    )


def lane_write_back(
    lanes: LaneState, lane: int, worker: WorkerState, done, rounds
) -> LaneState:
    """Overwrite one lane with a previously sliced snapshot: the (P, ...)
    ``worker`` state plus the exact ``done`` flag and ``rounds`` counter
    (contrast :func:`lane_swap_in`, which resets both).  The tag is
    untouched — the occupant never changed."""
    new_worker, new_done, new_rounds = _write_back_dev(
        lanes.worker, worker, lanes.done, jnp.asarray(done, bool),
        lanes.rounds, jnp.asarray(rounds, jnp.int32), jnp.int32(lane)
    )
    return lanes._replace(
        worker=new_worker, done=new_done, rounds=new_rounds
    )


_retire_dev = jax.jit(lambda done, lane: done.at[lane].set(True))
_resume_dev = jax.jit(lambda done, lane: done.at[lane].set(False))


def lane_resume(lanes: LaneState, lane: int) -> LaneState:
    """Un-freeze a quiescent lane WITHOUT touching its occupant: the spill
    pump re-admitted cold tasks into its frontier, so the "done" verdict the
    plane reached no longer holds and the lane must keep stepping."""
    return lanes._replace(done=_resume_dev(lanes.done, jnp.int32(lane)))


def lane_retire(lanes: LaneState, lane: int) -> LaneState:
    """Mark a lane vacant (after collecting its result, or on deadline
    eviction): frozen no-op until the next swap-in.  The stale worker state
    is inert — admission overwrites every leaf."""
    new_tag = np.asarray(lanes.tag).copy()
    new_tag[lane] = -1
    return lanes._replace(
        done=_retire_dev(lanes.done, jnp.int32(lane)), tag=new_tag
    )


def slice_lanes(lanes: LaneState, sel) -> LaneState:
    """Select/reorder lanes (host-side batch compaction): every leaf —
    device and host alike — is indexed by ``sel`` along the lane axis."""
    return jax.tree.map(lambda x: x[sel], lanes)


def step_lanes(plane, datas, lanes: LaneState, fpt_bounds=None):
    """One resumable plane step: run up to ``chunk_rounds`` supersteps of a
    :func:`build_batch_plane_fn` executable over the live lanes.

    Finished and vacant lanes are frozen inside the plane (their state and
    per-occupant stats stay bit-identical to a solo run); ``rounds``
    accumulates each occupant's actual supersteps.  Returns ``(lanes, ran,
    hot)`` where ``ran`` is the chunk's superstep count (0 when every lane
    was already done — the plane's while_loop exits immediately) and ``hot``
    is the (B, P) per-worker pending count (the spill-pump trigger).
    """
    if fpt_bounds is not None:
        worker, done, delta, ran, hot = plane(
            datas, lanes.worker, lanes.done, fpt_bounds
        )
    else:
        worker, done, delta, ran, hot = plane(datas, lanes.worker, lanes.done)
    return (
        lanes._replace(worker=worker, done=done, rounds=lanes.rounds + delta),
        ran,
        hot,
    )


# -- checkpoint (de)serialization ----------------------------------------------
#
# The engine carries its ENTIRE trajectory state on device (frontier task
# records, bounds, stats counters, the round-robin donor salt in `rounds`),
# so a checkpoint is exactly these named arrays — flat stable names, one per
# leaf, consumed by repro.checkpoint.solve.  Explicit field-by-field code
# (not a generic tree flatten) so a schema change here is a visible,
# reviewed change to the checkpoint format.


def worker_state_to_flat(state: WorkerState, prefix: str = "worker") -> dict:
    """A (possibly batched) :class:`WorkerState` as named host arrays."""
    host = jax.device_get(state)
    flat = {
        f"{prefix}.frontier.{name}": np.asarray(leaf)
        for name, leaf in host.frontier._asdict().items()
    }
    for name, leaf in host._asdict().items():
        if name != "frontier":
            flat[f"{prefix}.{name}"] = np.asarray(leaf)
    return flat


def worker_state_from_flat(flat: dict, prefix: str = "worker") -> WorkerState:
    frontier = Frontier(
        **{
            name: jnp.asarray(flat[f"{prefix}.frontier.{name}"])
            for name in Frontier._fields
        }
    )
    rest = {
        name: jnp.asarray(flat[f"{prefix}.{name}"])
        for name in WorkerState._fields
        if name != "frontier" and f"{prefix}.{name}" in flat
    }
    for name in LATE_COUNTERS:  # a checkpoint written before them
        rest.setdefault(name, jnp.zeros_like(rest["nodes_expanded"]))
    return WorkerState(frontier=frontier, **rest)


def lane_state_to_flat(lanes: LaneState, prefix: str = "lanes") -> dict:
    flat = worker_state_to_flat(lanes.worker, f"{prefix}.worker")
    flat[f"{prefix}.done"] = np.asarray(jax.device_get(lanes.done))
    flat[f"{prefix}.tag"] = np.asarray(lanes.tag, np.int32)
    flat[f"{prefix}.rounds"] = np.asarray(jax.device_get(lanes.rounds))
    return flat


def lane_state_from_flat(flat: dict, prefix: str = "lanes") -> LaneState:
    return LaneState(
        worker=worker_state_from_flat(flat, f"{prefix}.worker"),
        done=jnp.asarray(flat[f"{prefix}.done"]),
        tag=np.asarray(flat[f"{prefix}.tag"], np.int32),
        rounds=jnp.asarray(flat[f"{prefix}.rounds"]),
    )


def build_batch_superstep_fn(
    problem: BranchingProblem,
    datas: ProblemData,
    *,
    steps_per_round: int,
    lanes: int,
    policy_priority: bool = True,
    transfer_pad_words: int = 0,
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    donate_k: int = 1,
    explore_impl: str = "reference",
    axis_name: str = "workers",
):
    """Jitted ``state -> (state, done)`` over (B, P, ...) stacked state.

    ``datas`` is a batched :class:`ProblemData` (leading instance axis on
    ``n``/``adj``; ``word_idx``/``bit_idx`` shared).  ``done`` is (B,) bool —
    exact PER-INSTANCE quiescence.  One superstep always runs for every
    instance (no freezing); use :func:`build_batch_chunk_fn` for solve loops,
    which masks finished instances into no-op lanes.
    """
    step = functools.partial(
        superstep,
        problem,
        axis_name=axis_name,
        steps_per_round=steps_per_round,
        lanes=lanes,
        policy_priority=policy_priority,
        transfer_pad_words=transfer_pad_words,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        donate_k=donate_k,
        explore_impl=explore_impl,
    )

    def one_instance(data, state):
        state, done = jax.vmap(
            lambda s: step(data, s), axis_name=axis_name
        )(state)
        return state, done.all()

    bstep = jax.vmap(one_instance, in_axes=(DATA_IN_AXES, 0))

    def run(state):
        return bstep(datas, state)

    return jax.jit(run)


def build_batch_chunk_fn(
    problem: BranchingProblem,
    datas: ProblemData,
    *,
    steps_per_round: int,
    lanes: int,
    policy_priority: bool = True,
    transfer_pad_words: int = 0,
    packed_status: bool = True,
    skip_empty_transfer: bool = True,
    transfer_impl: str = "sparse",
    donate_k: int = 1,
    explore_impl: str = "reference",
    chunk_rounds: int = 16,
    fpt_bounds: Optional[jnp.ndarray] = None,
    axis_name: str = "workers",
):
    """Device-resident multi-round runner over a batch of instances.

    Returns a jitted ``(state, done) -> (state, done, rounds_delta, ran)``:

    * ``state``        (B, P, ...) stacked worker state;
    * ``done``         (B,) bool carried ACROSS chunks — instances that
      finished (quiescent, or FPT bound hit when ``fpt_bounds`` (B,) int32 is
      given; bounds are INTERNAL targets, ``problem.fpt_target(k)``) become
      no-op lanes: their state is frozen by a select, so stats
      stay bit-identical to a solo run while live instances keep stepping;
    * ``rounds_delta`` (B,) int32 supersteps each instance actually ran this
      chunk (0 for already-finished lanes);
    * ``ran``          () int32 supersteps the chunk executed (max over
      instances) — the host's ``max_rounds`` progress counter;
    * ``hot``          (B, P) int32 per-worker pending counts (the spill
      pump's trigger, see :func:`build_plane_fn`).

    The while_loop exits when EVERY instance is done or after
    ``chunk_rounds`` supersteps, so one straggler instance never forces the
    finished majority through extra host syncs — and the host can compact
    the batch between chunks (see ``engine.solve_many``).
    """
    plane = build_batch_plane_fn(
        problem,
        steps_per_round=steps_per_round,
        lanes=lanes,
        policy_priority=policy_priority,
        transfer_pad_words=transfer_pad_words,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        donate_k=donate_k,
        explore_impl=explore_impl,
        chunk_rounds=chunk_rounds,
        use_fpt=(fpt_bounds is not None),
        axis_name=axis_name,
    )
    if fpt_bounds is not None:
        bounds = jnp.asarray(fpt_bounds, jnp.int32)
        return lambda state, done: plane(datas, state, done, bounds)
    return lambda state, done: plane(datas, state, done)
