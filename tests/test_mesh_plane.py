"""The cached mesh plane: a solo solve whose workers are sharded over the
chips of a mesh, several virtual workers on each chip.

Against the same solve with every worker vmapped on one device it must give
bit-identical results, count the tasks that crossed chips (none on one
device), take its plane from the session's ``PlaneCache`` (a second solve
traces nothing) and refuse a worker count the chips do not divide.  The
mesh needs several devices and the CPU backend's device count is fixed when
JAX starts, so each case runs in a child process with four virtual CPU
devices.
"""

import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import superstep
from repro.graphs.generators import erdos_renyi
from repro.launch.mesh import make_solver_mesh
from repro.problems import base
from repro.problems.registry import get_problem

ROOT = pathlib.Path(__file__).resolve().parent.parent
CHIPS = 4

CHILD = """
import json, sys
sys.path.insert(0, sys.argv[1])
from repro.api import SolveConfig, SolverSession
from repro.core import superstep
from repro.graphs.bitgraph import BitGraph
from repro.graphs.generators import erdos_renyi
from benchmarks.chip.traffic.model_rb import model_rb

problem, graph, workers, lanes = sys.argv[2], sys.argv[3], int(sys.argv[4]), int(sys.argv[5])
kind, *a = graph.split(":")
if kind == "rb":
    inst = model_rb(int(a[0]), int(a[1]))
    g = BitGraph.from_edges(inst["n"], inst["edges"].tolist())
else:
    g = erdos_renyi(int(a[0]), float(a[1]), int(a[2]))

def solve(use_mesh):
    session = SolverSession(problem=problem, config=SolveConfig(
        num_workers=workers, lanes=lanes, steps_per_round=4, use_mesh=use_mesh,
    ))
    try:
        r = session.solve(g)
    except ValueError as e:
        return {"error": str(e)}
    traces = superstep.PLANE_TRACES
    again = session.solve(g)
    return dict(
        result=[r.best_size, [int(w) for w in r.best_sol], r.rounds,
                r.nodes_expanded, r.tasks_transferred, r.stats.overflow_count,
                r.stats.transfer_bytes_total],
        again_same=again.nodes_expanded == r.nodes_expanded,
        remote=r.stats.tasks_sent_remote,
        traces_again=superstep.PLANE_TRACES - traces,
        cache=session.cache_stats(),
    )

print(json.dumps({"vmap": solve(False), "mesh": solve(True)}))
"""


def _run_child(problem, graph, workers, lanes):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={CHIPS}"
        ).strip(),
        PYTHONPATH=str(ROOT / "src"),
    )
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), problem, graph, str(workers),
         str(lanes)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize(
    "problem,graph,per_chip,lanes",
    [
        ("vertex_cover", "rb:8:1", 1, 1),
        ("vertex_cover", "rb:10:2", 2, 4),
        ("vertex_cover", "gnp:40:0.2:3", 4, 1),
        ("vertex_cover", "gnp:44:0.15:1", 2, 1),
        ("max_clique", "gnp:48:0.3:3", 1, 4),
        ("max_clique", "gnp:48:0.3:3", 4, 1),
        ("mis", "gnp:40:0.4:3", 1, 4),
        ("mis", "gnp:40:0.4:3", 2, 1),
    ],
)
def test_mesh_plane_is_bit_identical_cached_and_counts_remote_tasks(
    problem, graph, per_chip, lanes
):
    out = _run_child(problem, graph, CHIPS * per_chip, lanes)
    vmap, mesh = out["vmap"], out["mesh"]
    assert mesh["result"] == vmap["result"]
    # every case is a proof that moves tasks; on the mesh some cross chips
    assert vmap["result"][4] > 0
    assert vmap["remote"] == 0 and mesh["remote"] > 0
    assert mesh["remote"] <= mesh["result"][4]
    for side in (vmap, mesh):
        # a second same-shape solve reuses the cached plane
        assert side["again_same"] and side["traces_again"] == 0
        assert side["cache"]["bypasses"] == 0
        assert side["cache"]["planes"] == 1 and side["cache"]["hits"] == 1


def test_mesh_refuses_workers_the_chips_do_not_divide():
    out = _run_child("vertex_cover", "gnp:20:0.2:1", 6, 1)
    assert "result" in out["vmap"]
    assert "cannot be split evenly" in out["mesh"]["error"]


def test_one_device_mesh_plane_carries_every_solo_scope():
    spec = get_problem("vertex_cover")
    g = erdos_renyi(40, 0.15, 0)
    mesh = make_solver_mesh(4)  # every device JAX sees here: one CPU
    plane = superstep.build_plane_fn(
        spec, steps_per_round=2, lanes=4, explore_impl="fused", chunk_rounds=2,
        mesh=mesh,
    )
    state = jax.vmap(lambda _: superstep.make_worker_state(64, g.W, 0))(
        jnp.arange(4)
    )
    text = plane.lower(base.make_data(spec, g), state).compile().as_text()
    components = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        for part in op_name.split("/"):
            components.add(re.sub(r"^(\w+\()+|\)+$", "", part))
    scopes = ("explore", "pop", "expand", "degrees", "reduce", "sweep",
              "pivot", "push", "center", "transfer", "termination")
    assert not [s for s in scopes if s not in components]
