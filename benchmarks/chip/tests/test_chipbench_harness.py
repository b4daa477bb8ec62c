"""The harness's files and its runs on the CPU at a small size.

Every workload and configuration file loads and names what exists; every
metric of ``BENCHMARK.json`` has its reader; ``bench.py`` refuses to run
without a TPU; and every cell's driver, run through the harness at a small
size, comes out correct on sound runs and not correct under each planted
fault and under the cell's control.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import control, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = sorted(p.stem for p in (harness.HERE / "workloads").glob("*.json"))

# small sizes of each driver, for the CPU (where a start-up split of a few
# tasks over 4 x 2 lanes idles more of two rounds than of the cell's 16)
SMALL = {
    "anytime": {
        "config": {
            "instance": {"n_vars": 14},
            "solve_config": {"num_workers": 4, "lanes": 2, "steps_per_round": 8,
                             "chunk_rounds": 2, "max_rounds": 2},
        },
        "cell": {"limits": {"lane_idle_pct": 10.0}},
    },
}
# each fault a driver can have, and the number compared that catches it
FAULTS = {"anytime": {
    "state_unchanged": "no_answer",
    "half_batch": "lane_idle_pct",
    "answer_altered": "size_vs_popcount",
    "degrees_off_by_one": "degrees_mismatch",
    "reduction_skipped": "expand_mismatch",
}}
CONTROL_CAUGHT_BY = {"anytime": "dropped_tasks"}
SECONDS = {"anytime": 0.5}


def driver(cell):
    return harness.load_json("workloads", cell)["driver"]


def small(cell):
    return SMALL[driver(cell)]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_name_what_exists(cell):
    from repro.api import SolveConfig

    bench = BENCH
    entry = harness.cell_entry(bench, cell)
    wl = harness.load_json("workloads", cell)
    assert wl["config"] == entry["config"] and wl["traffic"] == entry["traffic"]
    cfg = harness.load_json("configs", wl["config"])
    assert cfg["name"] == wl["config"]
    SolveConfig.from_dict(cfg["solve_config"])
    assert hasattr(harness.load_plugin("traffic", wl["traffic"]), "make")
    driver = harness.load_plugin("drivers", wl["driver"])
    for fn in ("setup", "window", "check"):
        assert callable(getattr(driver, fn))
    e2e = harness.metrics_for(bench["end_to_end"], cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    per_layer = harness.metrics_for(bench["per_layer"], cell, names)
    assert per_layer
    for m in per_layer:
        assert callable(harness.load_plugin("layer_metrics", m["name"]).read)


def test_every_listed_config_and_cell_has_its_files():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert harness.load_json("configs", c["name"])["name"] == c["name"]
    assert {w["name"] for w in BENCH["workloads"]} == set(CELLS)


@pytest.mark.parametrize("env", [{}, {"REPRO_PALLAS_INTERPRET": "1"}])
def test_bench_refuses_without_a_tpu(env):
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )
    assert proc.returncode != 0
    assert "refused" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    row = control.readings(
        cell, 2**31 + 5, SECONDS[driver(cell)],
        require_chip=False, overrides=small(cell),
    )
    assert row["correct"], row
    assert all(v > 0 for v in row["metrics"].values()), row


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    row = control.readings(
        cell, 7, SECONDS[driver(cell)],
        kind="control", require_chip=False, overrides=small(cell),
    )
    assert not row["correct"], row
    assert row["checks"][CONTROL_CAUGHT_BY[driver(cell)]] > 0, row


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS
    for f in FAULTS[driver(c)]
])
def test_planted_fault_is_not_correct(cell, fault):
    row = control.readings(
        cell, 11, SECONDS[driver(cell)],
        kind=fault, require_chip=False, overrides=small(cell),
    )
    assert not row["correct"], row
    caught_by = FAULTS[driver(cell)][fault]
    limits = small(cell).get("cell", {}).get("limits", {})
    assert row["checks"][caught_by] > limits.get(caught_by, 0), row
