"""The readers of the program's scopes, spans and counters: on synthetic
HLO text and traces, and on a small CPU run of the anytime cell (whose
compiled plane and counters are real; its times are not read)."""

import dataclasses
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, scopes, trace  # noqa: E402

# the anytime cell at a small size (as in test_chipbench_harness.py)
SMALL_SOLVE = {"num_workers": 4, "lanes": 2, "steps_per_round": 8,
               "chunk_rounds": 2, "max_rounds": 2}

HLO = """HloModule jit__lambda, entry_computation_layout={()->()}

%fused_computation.1 (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  ROOT %and.1 = u32[8]{0} and(%p, %p), metadata={op_name="jit(f)/explore/expand/vmap(reduce)/while/body/sweep/and"}
}

%body.2 (arg: (u32[8])) -> (u32[8]) {
  %arg = (u32[8]{0}) parameter(0)
  %gte = u32[8]{0} get-tuple-element(%arg), index=0
  %fusion.7 = u32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/explore/expand/vmap(reduce)/while"}
  %copy.3 = u32[8]{0} copy(%fusion.7)
  ROOT %tuple.1 = (u32[8]{0}) tuple(%copy.3)
}

%cond.2 (arg: (u32[8])) -> pred[] {
  %arg.1 = (u32[8]{0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

%branch.5 (x: u32[8]) -> u32[8] {
  %x = u32[8]{0} parameter(0)
  ROOT %copy.9 = u32[8]{0} copy(%x)
}

ENTRY %main.9 (a: u32[8]) -> u32[8] {
  %a = u32[8]{0} parameter(0)
  %add.4 = u32[8]{0} add(%a, %a), metadata={op_name="jit(f)/explore/pop/add"}
  %t = (u32[8]{0}) tuple(%add.4)
  %while.1 = (u32[8]{0}) while(%t), condition=%cond.2, body=%body.2, metadata={op_name="jit(f)/explore/expand/vmap(reduce)/while"}
  %i = s32[] constant(0)
  %conditional.1 = u32[8]{0} conditional(%i, %a), branch_computations={%branch.5}, metadata={op_name="jit(f)/vmap(transfer)/cond"}
  ROOT %mul.2 = u32[8]{0} multiply(%a, %a), metadata={op_name="jit(f)/vmap(termination)/mul;jit(f)/center/mul"}
}
"""


def test_scope_paths_take_wrappers_off():
    assert scopes.scope_path("jit(<lambda>)/vmap(vmap(reduce))/while/body/sweep/and") == (
        "<lambda>", "reduce", "while", "body", "sweep", "and")
    p = scopes.scope_path("jit(f)/explore/pop/reduce_max;explore/push/add")
    assert scopes.has_scope(p, "explore/pop") and not scopes.has_scope(p, "explore/push")
    assert not scopes.has_scope(("reduce_sum",), "reduce")


def test_hlo_scopes_roots_callers_and_merged_names():
    paths = scopes.hlo_scopes(HLO)
    assert scopes.module_name(HLO) == "jit__lambda"
    # a fusion takes its root's op_name; a copy without one, its while's
    assert scopes.has_scope(paths["%fusion.7"], "sweep")
    assert scopes.has_scope(paths["%copy.3"], "explore/expand/reduce")
    assert not scopes.has_scope(paths["%copy.3"], "sweep")
    assert scopes.has_scope(paths["%copy.9"], "transfer")
    assert scopes.has_scope(paths["%add.4"], "explore/pop")
    assert scopes.has_scope(paths["%mul.2"], "termination")
    assert paths["%a"] == ()


def _raw():
    # one device; two programs named jit__lambda: the plane (id 7, 0..100)
    # and a small one (id 9, 200..210) that reuses instruction names
    return scopes.Raw(
        modules={0: [("jit__lambda(7)", 0, 100), ("jit__lambda(9)", 200, 210),
                     ("jit_other(3)", 300, 400)]},
        ops={0: [("%add.4", 0, 10), ("%fusion.7", 10, 20), ("%copy.3", 20, 30),
                 ("%fusion.7", 30, 40), ("%copy.3", 40, 50), ("%copy.9", 50, 55),
                 ("%mul.2", 55, 60), ("%fusion.7", 200, 210), ("%add.4", 300, 400)]},
        spans=[("repro:solve", 0, 120, "3"), ("repro:solve.fetch_state", 100, 118, "3")],
    )


def _plane():
    return scopes.Plane(
        ops=scopes.plane_ops(_raw(), "jit__lambda"),
        paths=scopes.hlo_scopes(HLO), window=(0, 200),
    )


def test_plane_ops_keep_the_busiest_program_only():
    ops = scopes.plane_ops(_raw(), "jit__lambda")[0]
    assert [s for _, s, _ in ops] == [0, 10, 20, 30, 40, 50, 55]


def test_scope_times_and_executions():
    plane = _plane()
    assert plane.scoped()
    assert plane.time_s("reduce") == pytest.approx(40e-9)
    assert plane.time_s("sweep") == pytest.approx(20e-9)
    assert plane.time_s("explore/pop") == pytest.approx(10e-9)
    assert plane.executions("sweep") == 2.0
    assert plane.executions("nothing") == 0.0
    parts = scopes.breakdown(plane, busy_s=60e-9)
    assert parts["named_share_of_busy"] == pytest.approx(1.0)


def _synthetic_trace():
    # device busy 0..60, 100..101, 118..130 and 140..200 (133 ns) of a
    # 0..200 window; the harness's spans
    return trace.Trace(
        ops={0: [("%add.4", 0, 60), ("%a", 100, 101), ("%b", 118, 130),
                 ("%c", 140, 200)]},
        spans=[("window", 0, 200), ("solve_call", 0, 125)],
        window=(0, 200),
    )


def test_idle_gaps_labelled_by_the_programs_spans():
    tr = _synthetic_trace()
    gaps = dict(scopes.labelled_idle_gaps(tr, _raw().spans))
    # gaps 60..100 (midpoint in repro:solve), 101..118 (in its fetch),
    # 130..140 (in no span)
    assert gaps == {
        "idle: repro:solve": pytest.approx(40e-9),
        "idle: repro:solve.fetch_state": pytest.approx(17e-9),
        "idle: no span": pytest.approx(10e-9),
    }
    assert scopes.repro_idle_share(list(gaps.items())) == pytest.approx(57 / 67)
    # the harness's own labelling is untouched
    assert dict(trace.idle_gaps(tr)) == {
        "idle: solve_call": pytest.approx(57e-9),
        "idle: no span": pytest.approx(10e-9),
    }


@dataclasses.dataclass
class _Stats:
    reduce_lane_sweeps: int = 0
    reduce_worker_sweeps: int = 0
    host_fetch_bytes: int = 0


@dataclasses.dataclass
class _Result:
    nodes_expanded: int
    rounds: int
    stats: object


def _ctx():
    return harness.Ctx(
        workload="vc_rb_anytime", cell={}, seed=1, seconds=1.0, trace=True,
        spans=harness.Spans(), traffic=None,
        config={"solve_config": {"lanes": 4, "steps_per_round": 2}},
    )


def _win(stats, plane):
    calls = [(0.0, 1.0, _Result(10, 3, stats)), (1.0, 2.0, _Result(10, 3, stats))]
    win = type("Win", (), {})()
    win.calls, win.traced_calls, win.trace = calls, calls[:1], _synthetic_trace()
    win.scoped_plane = plane
    return win


NEW = ["reduce_share.explore", "frontier_us_per_node", "reduce_sweeps_per_step.explore",
       "reduce_lane_use.explore", "fetch_bytes_per_call.host_sync"]


def _read(name, win):
    return harness.load_plugin("layer_metrics", name).read(_ctx(), win, {"kind": "TPU v5 lite"})


def test_new_readers_on_a_synthetic_trace():
    win = _win(_Stats(30, 10, 1000), _plane())
    assert _read("reduce_share.explore", win) == pytest.approx(100 * 40 / 133)
    assert _read("frontier_us_per_node", win) == pytest.approx(1e6 * 10e-9 / 10)
    # two sweeps over one traced call of 3 supersteps x 2 steps
    assert _read("reduce_sweeps_per_step.explore", win) == pytest.approx(2 / 6)
    assert _read("reduce_lane_use.explore", win) == pytest.approx(100 * 60 / (4 * 20))
    assert _read("fetch_bytes_per_call.host_sync", win) == pytest.approx(1000)


def test_new_readers_read_nothing_where_the_program_has_nothing():
    """A program without the scopes and counters (its results' stats lack
    the fields) gives no value, and raises nothing."""
    win = _win(type("Old", (), {})(), None)
    assert all(_read(name, win) is None for name in NEW)


def test_existing_readers_read_the_same_beside_the_programs_spans():
    existing = [m["name"] for m in harness.benchmark()["per_layer"] if m["name"] not in NEW]
    win = _win(_Stats(), None)
    win.kernel_shape = (8, 16, 1)
    before = {name: _read(name, win) for name in existing}
    scopes.labelled_idle_gaps(win.trace, _raw().spans)
    win.trace = dataclasses.replace(
        win.trace, spans=win.trace.spans + [(n, s, e) for n, s, e, _ in _raw().spans]
    )
    assert {name: _read(name, win) for name in existing} == before


@pytest.fixture(scope="module")
def cpu_window():
    overrides = {"config": {"instance": {"n_vars": 14},
                            "solve_config": SMALL_SOLVE}}
    _, _, win = harness.run(
        "vc_rb_anytime", 2**31 + 9, 0.5, False, t_start=time.perf_counter(),
        require_chip=False, overrides=overrides,
    )
    return win


def test_the_compiled_plane_of_a_run_names_its_scopes(cpu_window):
    hlo = scopes._plane_hlo(cpu_window)
    paths = scopes.hlo_scopes(hlo)
    for scope in scopes.BREAKDOWN + ("sweep",):
        assert any(scopes.has_scope(p, scope) for p in paths.values()), scope


def test_counter_readers_on_a_run(cpu_window):
    import jax

    ctx = _ctx()
    ctx.config = {"solve_config": SMALL_SOLVE}
    use = harness.load_plugin("layer_metrics", "reduce_lane_use.explore").read(
        ctx, cpu_window, {})
    assert 0 < use <= 100
    per_call = harness.load_plugin(
        "layer_metrics", "fetch_bytes_per_call.host_sync").read(ctx, cpu_window, {})
    # one chunk a call: its (done, ran, hot) scalars, then the whole state
    P = ctx.config["solve_config"]["num_workers"]
    state = jax.tree.map(lambda x: x[0], cpu_window.last_state)
    whole = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    assert per_call == 1 + 4 + 4 * P + whole
