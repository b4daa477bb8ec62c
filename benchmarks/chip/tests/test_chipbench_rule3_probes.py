"""The reader of rule 3's probe counter: on synthetic stats, on stats of a
program without the counter, and on a small CPU run of the anytime cell
(whose counters are real)."""

import dataclasses
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402

METRIC = "rule3_probes_per_sweep.explore"
# the anytime cell at a small size (as in test_chipbench_harness.py)
SMALL = {"config": {"instance": {"n_vars": 14},
                    "solve_config": {"num_workers": 4, "lanes": 2, "steps_per_round": 8,
                                     "chunk_rounds": 2, "max_rounds": 2}}}


@dataclasses.dataclass
class _Stats:
    reduce_lane_sweeps: int = 0
    reduce_rule3_probes: int = 0


def _read(stats):
    win = type("Win", (), {})()
    win.calls = [(0.0, 1.0, type("R", (), {"stats": s})()) for s in stats]
    return harness.load_plugin("layer_metrics", METRIC).read(None, win, {})


def test_reads_probes_over_lane_sweeps():
    assert _read([_Stats(20, 30), _Stats(20, 10)]) == pytest.approx(1.0)
    assert _read([_Stats(20, 0)]) == 0.0


def test_reads_nothing_where_the_program_has_no_counter():
    old = type("Old", (), {"reduce_lane_sweeps": 20})()
    assert _read([old]) is None
    assert _read([_Stats()]) is None
    assert _read([]) is None


def test_the_cell_lists_the_metric():
    bench = harness.benchmark()
    names = {m["name"] for m in harness.metrics_for(bench["per_layer"], "vc_rb_anytime")}
    assert METRIC in names


def test_reads_a_run():
    _, _, win = harness.run(
        "vc_rb_anytime", 2**31 + 17, 0.5, False, t_start=time.perf_counter(),
        require_chip=False, overrides=SMALL,
    )
    value = harness.load_plugin("layer_metrics", METRIC).read(None, win, {})
    assert value is not None and 0 < value
    assert all(r.stats.reduce_rule3_probes > 0 for _, _, r in win.calls)
