"""The mesh path (the workers sharded over the devices under ``shard_map``)
solves bit-identically to the vmap path (the same workers, virtual, on one
device), and takes its plane from the session's cache as the vmap path does.

The mesh needs several devices, and the CPU backend's device count is fixed
when JAX starts, so each case runs in a child process with four virtual CPU
devices.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"

CHILD = """
import json, sys
import jax
from repro.api import SolveConfig, SolverSession
from repro.graphs.generators import erdos_renyi

problem, n, p, lanes = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), int(sys.argv[4])
assert len(jax.devices()) == 4, jax.devices()
g = erdos_renyi(n, p, 3)
out = {}
for use_mesh in (False, True):
    session = SolverSession(
        problem=problem,
        config=SolveConfig(num_workers=4, lanes=lanes, use_mesh=use_mesh),
    )
    r = session.solve(g)
    out["mesh" if use_mesh else "vmap"] = dict(
        best_size=r.best_size,
        best_sol=[int(w) for w in r.best_sol],
        rounds=r.rounds,
        nodes_expanded=r.nodes_expanded,
        tasks_transferred=r.tasks_transferred,
        transfer_bytes_total=r.stats.transfer_bytes_total,
    )
    out["bypasses_" + ("mesh" if use_mesh else "vmap")] = (
        session.cache_stats()["bypasses"]
    )
    out["remote_" + ("mesh" if use_mesh else "vmap")] = r.stats.tasks_sent_remote
print(json.dumps(out))
"""


@pytest.mark.parametrize(
    "problem,n,p,lanes",
    [("vertex_cover", 40, 0.2, 1), ("max_clique", 48, 0.3, 4)],
)
def test_mesh_solve_is_bit_identical_to_vmap(problem, n, p, lanes):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip(),
        PYTHONPATH=str(SRC),
    )
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, problem, str(n), str(p), str(lanes)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    # both solves took their plane from the cache; the mesh solve really
    # ran sharded (one worker per device: every task it moved crossed chips)
    assert out["bypasses_vmap"] == 0 and out["bypasses_mesh"] == 0
    assert out["remote_vmap"] == 0
    assert out["remote_mesh"] == out["mesh"]["tasks_transferred"]
    assert out["mesh"] == out["vmap"]
