"""The solve plane's own tracing: named device scopes, host spans, and the
reduction and host-sync counters.

The counters are checked against what they count (a host loop of the
reduction's sweep, the shapes of the fetched arrays), the scopes against
the compiled plane's metadata, the spans against a CPU profiler trace.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.api import SolveConfig, SolverSession
from repro.api.service import SolveService
from repro.core import engine, superstep
from repro.graphs.generators import erdos_renyi
from repro.problems import base
from repro.problems import vertex_cover as vc
from repro.problems.registry import get_problem

CFG = dict(num_workers=4, lanes=4, steps_per_round=4, chunk_rounds=2)
REDUCE = superstep.REDUCE_COUNTERS


def _tasks(n, W, count, seed):
    rng = np.random.default_rng(seed)
    masks = np.zeros((count, W), np.uint32)
    for i in range(count):
        keep = rng.random(n) < 0.7
        masks[i] = np.asarray(base.pack_bits(jnp.asarray(keep), W))
    return jnp.asarray(masks), jnp.zeros((count, W), jnp.uint32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_counted_sweeps_and_fires_match_a_host_loop(seed):
    g = erdos_renyi(30, 0.08, seed)
    data = vc.make_problem(g.adj, g.n)
    masks, sols = _tasks(g.n, g.W, 6, seed)
    work = vc.expand_tasks(data, masks, sols).work
    step = jax.jit(lambda m, s: vc._reduce_step(data, m, s))
    fired_any = 0
    for i in range(masks.shape[0]):
        m, s, sweeps, fires = masks[i], sols[i], 0, [0, 0, 0]
        while True:
            m, s, rule = step(m, s)
            sweeps += 1
            if int(rule) == 0:
                break
            fires[int(rule) - 1] += 1
        assert int(work.sweeps[i]) == sweeps
        assert np.asarray(work.fires[i]).tolist() == fires
        fired_any += sum(fires)
    assert fired_any > 0  # the draw exercises the rules


def test_fires_plus_nodes_equal_lane_sweeps_and_plugins_without_a_reduction():
    g = erdos_renyi(40, 0.15, 3)
    r = SolverSession(problem="vertex_cover", config=SolveConfig(**CFG)).solve(g)
    st = r.stats
    fires = st.reduce_fires_rule1 + st.reduce_fires_rule2 + st.reduce_fires_rule3
    # every sweep of an expanded lane fires one rule, but its last
    assert fires > 0 and fires + r.nodes_expanded == st.reduce_lane_sweeps
    assert CFG["lanes"] * st.reduce_worker_sweeps >= st.reduce_lane_sweeps
    # the reference explore path and a plugin without a reduction count none
    ref = SolverSession(
        problem="vertex_cover", config=SolveConfig(**CFG, explore_impl="reference")
    ).solve(g)
    clique = SolverSession(problem="max_clique", config=SolveConfig(**CFG)).solve(g)
    for res in (ref, clique):
        assert all(getattr(res.stats, name) == 0 for name in REDUCE)
    assert ref.best_size == r.best_size and ref.nodes_expanded == r.nodes_expanded


def test_batched_plane_counts_what_the_solo_plane_counts():
    graphs = [erdos_renyi(24, 0.2, s) for s in (5, 6)]
    session = SolverSession(problem="vertex_cover", config=SolveConfig(**CFG))
    batch = session.solve_many(graphs)
    for g, r in zip(graphs, batch.results):
        solo = session.solve(g)
        for name in REDUCE:
            assert getattr(r.stats, name) == getattr(solo.stats, name), name
        assert r.stats.host_fetches > 0 and r.stats.host_fetch_bytes > 0


def test_fetch_bytes_are_the_shape_arithmetic():
    g = erdos_renyi(40, 0.15, 3)
    cfg = SolveConfig(**CFG, capacity=64)
    r = SolverSession(problem="vertex_cover", config=cfg).solve(g)
    P = cfg.num_workers
    state = jax.eval_shape(
        lambda: jax.vmap(lambda _: superstep.make_worker_state(64, g.W, 0))(
            jnp.arange(P)
        )
    )
    state_bytes = sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(state)
    )
    chunks = r.stats.host_fetches - 1  # one (done, ran, hot) fetch a chunk
    assert chunks == -(-r.rounds // cfg.chunk_rounds)
    # done (bool), ran (int32), hot ((P,) int32); then the whole state
    assert r.stats.host_fetch_bytes == chunks * (1 + 4 + 4 * P) + state_bytes


def test_service_counts_fetches_per_ticket_and_in_total():
    svc = SolveService("vertex_cover", config=SolveConfig(**CFG, service_lanes=2))
    tickets = [svc.submit(erdos_renyi(24, 0.2, s)) for s in (1, 2, 3)]
    svc.drain()
    results = [svc.result(t) for t in tickets]
    assert all(r.stats.host_fetches > 0 for r in results)
    stats = svc.stats()
    assert stats["host_fetch_bytes"] >= max(r.stats.host_fetch_bytes for r in results)
    assert all(r.stats.reduce_lane_sweeps > 0 for r in results)


SCOPES = {
    "vertex_cover": ("explore", "pop", "expand", "push", "degrees", "reduce",
                     "sweep", "pivot", "center", "transfer", "termination"),
    "max_clique": ("explore", "pop", "expand", "push", "degrees", "pivot",
                   "center", "transfer", "termination"),
}


@pytest.mark.parametrize("problem", sorted(SCOPES))
def test_compiled_solo_plane_carries_every_scope(problem):
    spec = get_problem(problem)
    g = erdos_renyi(40, 0.15, 0)
    plane = superstep.build_plane_fn(
        spec, steps_per_round=2, lanes=4, explore_impl="fused", chunk_rounds=2
    )
    state = jax.vmap(lambda _: superstep.make_worker_state(64, g.W, 0))(
        jnp.arange(4)
    )
    text = plane.lower(base.make_data(spec, g), state).compile().as_text()
    components = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for part in op_name.split("/"):
            components.add(re.sub(r"^(\w+\()+|\)+$", "", part))
    missing = [s for s in SCOPES[problem] if s not in components]
    assert not missing, missing


def test_profiler_trace_holds_the_solve_spans_under_one_request(tmp_path):
    from jax.profiler import ProfileData

    session = SolverSession(problem="vertex_cover", config=SolveConfig(**CFG))
    g = erdos_renyi(30, 0.15, 1)
    session.solve(g)  # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        session.solve(g)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    spans = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats).get("request"))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if e.name.startswith(tracing.PREFIX)
    ]
    names = {name for name, *_ in spans}
    assert {"repro:solve", "repro:solve.startup", "repro:solve.chunk",
            "repro:solve.fetch_state", "repro:solve.extract"} <= names
    (root,) = [s for s in spans if s[0] == "repro:solve"]
    for _, start, end, _ in spans:
        assert root[1] <= start and end <= root[2]
    assert len({str(req) for *_, req in spans}) == 1


def test_checkpoint_round_trips_the_counters_and_loads_old_ones_as_zero():
    g = erdos_renyi(30, 0.15, 1)
    spec = get_problem("vertex_cover")
    plane = superstep.build_plane_fn(
        spec, steps_per_round=2, lanes=2, explore_impl="fused", chunk_rounds=2
    )
    state = jax.vmap(lambda _: superstep.make_worker_state(64, g.W, g.n + 1))(
        jnp.arange(2)
    )
    state = engine._scatter_startup(state, spec, g, 2)
    state = plane(base.make_data(spec, g), state)[0]
    assert int(state.reduce_lane_sweeps.sum()) > 0
    assert int(state.reduce_rule3_probes.sum()) > 0
    flat = superstep.worker_state_to_flat(state)
    back = superstep.worker_state_from_flat(flat)
    for name in superstep.LATE_COUNTERS:
        assert (np.asarray(getattr(back, name)) == np.asarray(getattr(state, name))).all()
    old = {k: v for k, v in flat.items() if k.split(".", 1)[1] not in superstep.LATE_COUNTERS}
    loaded = superstep.worker_state_from_flat(old)
    for name in superstep.LATE_COUNTERS:
        leaf = np.asarray(getattr(loaded, name))
        assert leaf.shape == np.asarray(state.nodes_expanded).shape and not leaf.any()
    assert (np.asarray(loaded.nodes_expanded) == np.asarray(state.nodes_expanded)).all()


def test_fetches_count_calls_and_shape_bytes():
    f = tracing.Fetches()
    got = f.get((jnp.zeros((3, 2), jnp.int32), jnp.ones((), bool)))
    f.add(10)
    assert f.count == 2 and f.bytes == 3 * 2 * 4 + 1 + 10
    assert np.asarray(got[0]).shape == (3, 2)
    assert tracing.nbytes({"a": jnp.zeros((5,), jnp.uint32)}) == 20
