"""Quickstart: the paper's solver on one graph, three engines, on CPU.

  PYTHONPATH=src python examples/quickstart.py
"""

import sys

sys.path.insert(0, "src")

from repro.api import SolveConfig, SolverSession
from repro.graphs.generators import erdos_renyi
from repro.problems.sequential import verify_cover


def main():
    # the paper's workload, minimum vertex cover, on three engines: one
    # façade over every engine, pick a backend, get one result schema
    g = erdos_renyi(50, 4 / 49, seed=7)
    print(f"graph: n={g.n} m={g.num_edges}")
    cfg = SolveConfig(num_workers=6, steps_per_round=16)

    seq = SolverSession(backend="sequential", config=cfg).solve(g)
    print(f"sequential:        mvc={seq.best_size} "
          f"({seq.nodes_expanded} nodes)")

    sim = SolverSession(backend="protocol_sim", config=cfg).solve(g)
    print(
        f"semi-centralized:  mvc={sim.best_size} "
        f"(async protocol sim, {sim.tasks_transferred} transfers, "
        f"{sim.stats.failed_requests} failed requests)"
    )

    r = SolverSession(backend="spmd", config=cfg).solve(g)
    ok = r.best_size == seq.best_size and verify_cover(g, r.best_sol)
    print(
        f"SPMD engine:       mvc={r.best_size} "
        f"({r.rounds} supersteps, {r.tasks_transferred} transfers, "
        f"verified={ok})"
    )


if __name__ == "__main__":
    main()
