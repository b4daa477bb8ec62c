"""Closed loop of anytime calls on one hard vertex-cover instance.

Set-up makes the seed's instance, opens a ``SolverSession`` with the
configuration's pinned ``SolveConfig`` (``max_rounds = chunk_rounds``: each
``solve`` call is one device chunk and ends in its host sync), and warms
every program the window runs with one call on an edgeless graph of the same
size, which has the same shapes and finishes in one superstep.

The window calls ``session.solve(g)`` back to back: it starts calls until
``seconds`` have passed and ends when the last one returns, so it holds
whole calls only.  ``nodes_per_s`` is every node those calls expanded over
the whole window.  Each call repeats the same deterministic work.  The
solver state that each call ends with, which its host sync fetches, is kept
(a reference to the device arrays, nothing copied) for the check.

The check, against the generator's edge list and the plain reference:

* the answers: every call has one, it covers every edge, its size is its
  popcount, and every call returned the same;
* the frontier: no task was dropped, and every task left in the last
  call's frontiers is a sound search state;
* the center and the data plane: the tasks that workers sent add up to the
  tasks that workers received;
* explore: on tasks drawn by the seed from those frontiers (the root task
  fills up a short draw), the program's degree panel at the timed lane
  batch, and the program's node expansion (reduction, bound, branch) at the
  same batch, against :mod:`benchmarks.chip.reference`;
* the lanes were kept busy (no more than the cell's limit of the
  configured lane steps went idle).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.harness import Check


@dataclasses.dataclass
class Window:
    metrics: dict
    attempted: int
    failed: int
    calls: list  # (start_s, end_s, SolveResult) per call
    traced_calls: list  # the calls inside the profiler trace
    kernel_shape: tuple  # (tasks, n, W) of one expand kernel call
    info: dict  # printed beside the checks, compared with nothing
    last_state: object = None  # the last call's final solver state (on device)
    session: object = None
    graph: object = None
    compiles_in_window: int = 0
    trace: object = None


@dataclasses.dataclass
class State:
    g: object
    session: object
    cfg: object
    final_states: list  # the newest call's final state
    restore: object  # puts the program's own fetch back


def solve_config(ctx):
    from repro.api import SolveConfig

    return SolveConfig.from_dict(ctx.config["solve_config"])


def instance(ctx) -> dict:
    return ctx.traffic.make({**ctx.config["instance"], **ctx.cell["params"]}, ctx.seed)


def setup(ctx) -> State:
    from repro.api import SolverSession
    from repro.core import engine
    from repro.graphs.bitgraph import BitGraph

    with ctx.spans("make_instance"):
        inst = instance(ctx)
        g = BitGraph.from_edges(inst["n"], inst["edges"].tolist())
    cfg = solve_config(ctx)
    session = SolverSession(problem=ctx.config["problem"], config=cfg)

    # keep the state that each call's host sync fetches; the fetch itself is
    # the program's own, unchanged
    final_states = []
    fetch = engine._fetch_batch_state

    def fetch_and_keep(state):
        final_states[:] = [state]
        return fetch(state)

    engine._fetch_batch_state = fetch_and_keep
    with ctx.spans("warm_up"):
        session.solve(BitGraph.from_edges(inst["n"], []))
    return State(g=g, session=session, cfg=cfg, final_states=final_states,
                 restore=lambda: setattr(engine, "_fetch_batch_state", fetch))


def window(ctx, st: State) -> Window:
    calls = []

    def run_until(t0, until_s):
        while not calls or calls[-1][1] < until_s:
            with ctx.spans("solve_call"):
                c0 = time.perf_counter()
                r = st.session.solve(st.g)
                c1 = time.perf_counter()
            calls.append((c0 - t0, c1 - t0, r))

    try:
        # a traced run records its first trace_seconds of calls (at least one)
        with ctx.profiled():
            t0 = time.perf_counter()
            run_until(t0, ctx.cell["trace_seconds"] if ctx.trace else ctx.seconds)
        traced = len(calls) if ctx.trace else 0
        run_until(t0, ctx.seconds)
    finally:
        st.restore()
    span = calls[-1][1]
    nodes = sum(r.nodes_expanded for _, _, r in calls)
    return Window(
        metrics={"nodes_per_s": nodes / span},
        attempted=len(calls),
        failed=sum(r.best_sol is None for _, _, r in calls),
        calls=calls,
        traced_calls=calls[:traced],
        kernel_shape=(st.cfg.num_workers * st.cfg.lanes, st.g.n, st.g.W),
        info={"calls": len(calls), "window_s": span,
              "call_s": [c1 - c0 for c0, c1, _ in calls]},
        last_state=st.final_states[-1],
        session=st.session,
        graph=st.g,
    )


def _signature(r) -> tuple:
    sol = None if r.best_sol is None else tuple(np.asarray(r.best_sol).tolist())
    return (r.best_size, sol, r.rounds, r.nodes_expanded, r.tasks_transferred,
            r.stats.overflow_count, r.stats.transfer_bytes_total)


def _answers(win: Window, n: int, edges: np.ndarray) -> list:
    no_answer = uncovered = mismatch = 0
    for _, _, r in win.calls:
        if r.best_sol is None:
            no_answer += 1
            continue
        chosen = reference.unpack(r.best_sol, n)
        uncovered = max(uncovered, reference.uncovered_edges(edges, chosen))
        mismatch = max(mismatch, abs(int(chosen.sum()) - r.best_size))
    first = _signature(win.calls[0][2])
    return [
        Check("no_answer", no_answer, 0),
        Check("uncovered_edges", uncovered, 0),
        Check("size_vs_popcount", mismatch, 0),
        Check("calls_disagree", sum(_signature(r) != first for _, _, r in win.calls[1:]), 0),
    ]


def _draw_tasks(ctx, state, n: int, count: int) -> tuple:
    """``count`` tasks (packed masks, packed sols) drawn by the seed from the
    frontiers' live tasks, the root task filling up a short draw; and every
    live task, for the soundness check."""
    f = state.frontier
    live = np.asarray(f.active)[0]  # (workers, capacity)
    masks = np.asarray(f.masks)[0][live]
    sols = np.asarray(f.sols)[0][live]
    pick = np.random.default_rng([ctx.seed, 1]).permutation(len(masks))[:count]
    W = masks.shape[1]
    fill = count - len(pick)
    root = reference.pack(np.ones(n, bool), W)
    draw_m = np.concatenate([masks[pick], np.tile(root, (fill, 1))])
    draw_s = np.concatenate([sols[pick], np.zeros((fill, W), np.uint32)])
    return draw_m, draw_s, masks, sols


def _explore(ctx, win: Window, n: int, edges: np.ndarray) -> list:
    import jax
    import jax.numpy as jnp

    from repro.kernels.bitset_ops import ops
    from repro.problems import base

    spec = win.session.problem
    state = jax.device_get(win.last_state)
    tasks_sent = int(np.asarray(state.tasks_sent).sum())
    tasks_recv = int(np.asarray(state.tasks_recv).sum())
    draw_m, draw_s, live_m, live_s = _draw_tasks(ctx, state, n, win.kernel_shape[0])
    adj = reference.dense(n, edges)
    bad = reference.bad_tasks(
        adj, reference.unpack(live_m, n), reference.unpack(live_s, n)
    )

    # the program at the timed lane batch: its degree panel and one expansion
    # (fresh functions, so that each run traces what the program is now)
    data = base.make_data(spec, win.graph)
    deg = np.asarray(jax.jit(lambda a, m: ops.degrees_auto(a, m))(
        data.adj, jnp.asarray(draw_m)
    ))
    ex = jax.device_get(jax.jit(lambda d, m, s: spec.expand_tasks(d, m, s))(
        data, jnp.asarray(draw_m), jnp.asarray(draw_s)
    ))
    bm, bs = reference.unpack(draw_m, n), reference.unpack(draw_s, n)
    deg_bad = int((deg[:, :n] != reference.degrees(adj, bm)).sum())
    expand_bad, fired = 0, np.zeros(3, int)
    for i in range(len(bm)):
        ref = reference.expand(adj, bm[i], bs[i])
        fired += ref["fired"]
        expand_bad += not _same_expansion(ex, i, ref, n)
    win.info.update(live_tasks=len(live_m), rules_fired=fired.tolist(),
                    tasks_sent=tasks_sent, tasks_recv=tasks_recv)
    return [
        Check("frontier_unsound", bad, 0),
        Check("sent_minus_received", abs(tasks_sent - tasks_recv), 0),
        Check("degrees_mismatch", deg_bad, 0),
        Check("expand_mismatch", expand_bad, 0),
    ]


def _same_expansion(ex, i: int, ref: dict, n: int) -> bool:
    """Whether lane ``i`` of the program's expansion says what the reference
    says (the children of a terminal lane are placeholders, not compared)."""
    step = ex.step
    terminal = bool(step.is_terminal[i])
    if int(ex.bound[i]) != ref["bound"] or terminal != ref["terminal"]:
        return False
    if terminal:
        return np.array_equal(reference.unpack(step.terminal_sol[i], n), ref["sol"])
    return all(
        np.array_equal(reference.unpack(getattr(step, k)[i], n), ref[k])
        for k in ("left_mask", "left_sol", "right_mask", "right_sol")
    ) and (int(ex.left_bound[i]), int(ex.right_bound[i])) == (
        ref["left_bound"], ref["right_bound"]
    )


def check(ctx, win: Window) -> list:
    inst = instance(ctx)
    n, edges = inst["n"], inst["edges"]
    cfg = solve_config(ctx)
    lane_steps = cfg.num_workers * cfg.lanes * cfg.steps_per_round
    dropped = max(r.stats.overflow_count for _, _, r in win.calls)
    idle_pct = max(
        100.0 * (1 - r.nodes_expanded / (r.rounds * lane_steps))
        for _, _, r in win.calls
    )
    return _answers(win, n, edges) + [
        Check("dropped_tasks", dropped, 0),
    ] + _explore(ctx, win, n, edges) + [
        Check("lane_idle_pct", idle_pct, ctx.cell["limits"]["lane_idle_pct"]),
    ]
