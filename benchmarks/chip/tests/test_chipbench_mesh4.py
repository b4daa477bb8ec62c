"""The four-chip cell ``vc_rb_mesh4_anytime`` on four virtual CPU devices,
and its two readers on synthetic four-device traces.

The cell runs through the harness at a small size in a child process (the
CPU backend's device count is fixed when JAX starts): every check reads 0
(``lane_idle_pct`` under its small-size limit), and each call of the window
is bit-identical to the same ``SolveConfig`` solved with every worker
vmapped on one device.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, trace  # noqa: E402

CELL = "vc_rb_mesh4_anytime"
# two workers of two lanes on each of four devices
SMALL = {
    "config": {
        "instance": {"n_vars": 14},
        "solve_config": {"num_workers": 8, "lanes": 2, "steps_per_round": 8,
                         "chunk_rounds": 2, "max_rounds": 2},
    },
    "cell": {"limits": {"lane_idle_pct": 10.0}},
}

CHILD = """
import json, sys, time
sys.path.insert(0, sys.argv[1])
import jax
from benchmarks.chip import harness
from repro.api import SolverSession

cell, small, seed = sys.argv[2], json.loads(sys.argv[3]), int(sys.argv[4])
result, checks, win = harness.run(
    cell, seed, 0.5, False, t_start=time.perf_counter(), require_chip=False,
    overrides=small,
)
signature = harness.load_plugin("drivers", "anytime")._signature
one = SolverSession(
    problem=win.session.problem, config=win.session.config.replace(use_mesh=False)
).solve(win.graph)
print(json.dumps({
    "devices": len(jax.devices()),
    "count": result["device"]["count"],
    "checks": {c.name: [c.value, c.limit] for c in checks},
    "metrics": {k: v["value"] for k, v in result["metrics"].items()},
    "mesh_vs_one_device": max(
        sum(a != b for a, b in zip(signature(r), signature(one)))
        for _, _, r in win.calls
    ),
    "calls": len(win.calls),
    "remote": [r.stats.tasks_sent_remote for _, _, r in win.calls],
    "moved": [r.tasks_transferred for _, _, r in win.calls],
}))
"""


def test_cell_runs_on_four_devices_and_matches_one_device():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip(),
    )
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), CELL, json.dumps(SMALL),
         str(2**31 + 7)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["devices"] == 4 and out["count"] == 4
    for name, (value, limit) in out["checks"].items():
        assert value <= limit, (name, value, limit)
        if name != "lane_idle_pct":
            assert value == 0, (name, value)
    assert out["mesh_vs_one_device"] == 0
    assert out["calls"] >= 1 and out["metrics"]["nodes_per_s"] > 0
    assert all(0 <= r <= m for r, m in zip(out["remote"], out["moved"]))


def test_cell_entries_name_the_mesh_configuration():
    bench = harness.benchmark()
    entry = harness.cell_entry(bench, CELL)
    config = harness.load_json("configs", entry["config"])
    assert entry["chips"] == config["chips"] == 4
    sc = config["solve_config"]
    assert sc["use_mesh"] and sc["num_workers"] % entry["chips"] == 0
    # each chip holds the whole-chip share of the one-chip configuration
    one = harness.load_json("configs", "vc_rb_1chip")
    assert sc["num_workers"] // entry["chips"] == one["solve_config"]["num_workers"]
    assert config["instance"] == one["instance"]
    names = {m["name"] for m in harness.metrics_for(bench["per_layer"], CELL)}
    assert names == {"collective_share.mesh", "chip_busy_spread.mesh"}


def _four_chips() -> trace.Trace:
    # window 0..100 ns; chip d computes 0..(60 + 10 d) and then waits in one
    # all-reduce until 90; a fusion that reads the all-reduce's result is no
    # collective, nor is anything outside the window
    ops, labels = {}, {}
    for d in range(4):
        end = 60 + 10 * d
        ops[d] = [
            ("%fusion.1", 0, end),
            ("%psum.16", end, 90),
            ("%compare_select_fusion.4", 90, 95),
            ("%all-reduce.7", 150, 160),
        ]
    labels["%psum.16"] = "%psum.16 = u32[128,1,31]{2,1,0:T(1,128)S(1)} all-reduce(%bitcast.4)"
    labels["%compare_select_fusion.4"] = (
        "%compare_select_fusion.4 = s32[128]{0} fusion(%psum.16), kind=kLoop"
    )
    labels["%fusion.1"] = "%fusion.1 = s32[128]{0} fusion(%x), kind=kLoop"
    return trace.Trace(ops=ops, spans=[("window", 0, 100)], window=(0, 100),
                       labels=labels)


def _read(metric, t):
    win = type("Win", (), {"trace": t})()
    return harness.load_plugin("layer_metrics", metric).read(None, win, {})


def test_collective_share_on_a_synthetic_trace():
    t = _four_chips()
    # collectives 30 + 20 + 10 + 0 ns over 4 chips, busy 95 ns on each
    assert _read("collective_share.mesh", t) == pytest.approx(100 * 15 / 95)
    one = trace.Trace(ops={0: [("%fusion.1", 0, 50)]}, spans=[], window=(0, 100),
                      labels={})
    assert _read("collective_share.mesh", one) is None


def test_chip_busy_spread_on_a_synthetic_trace():
    t = _four_chips()
    assert _read("chip_busy_spread.mesh", t) == pytest.approx(0.0)
    # chip 3 idles from 40 to 90: busy 45 of the others' 95
    t.ops[3] = [("%fusion.1", 0, 40), ("%psum.16", 90, 95)]
    assert _read("chip_busy_spread.mesh", t) == pytest.approx(100 * 50 / 95)
    one = trace.Trace(ops={0: [("%fusion.1", 0, 50)]}, spans=[], window=(0, 100),
                      labels={})
    assert _read("chip_busy_spread.mesh", one) is None
