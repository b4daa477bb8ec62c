"""SPMD superstep engine: throughput + collective-traffic budget.

Three sections (EXPERIMENTS.md §Perf):

  budget   expansion/transfer accounting per worker count and matching
           policy (the TPU-adaptation counterpart of Table 1);
  chunked  supersteps/sec, K-round device-resident stepping (one host sync
           per ``lax.while_loop`` chunk) vs the per-round host loop
           (blocking ``device_get(done)`` every round) at P=64 virtual
           workers.  Reported for pure *coordination rounds*
           (steps_per_round=0: all-gather + replicated matching + transfer,
           i.e. the per-round coordination cost the paper says caps
           scaling) and for compute-carrying rounds (steps_per_round=1);
  transfer gather vs sparse data-plane A/B on the DIMACS-style sample from
           examples/solve_dimacs.py: identical best_size/best_sol, payload
           bytes per round, zero-byte no-match rounds.
"""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from repro.api import SolveConfig, SolverSession
from repro.core import engine as E  # startup-scatter helper for chunked_ab
from repro.core.superstep import (
    build_plane_fn,
    build_superstep_fn,
    make_worker_state,
)
from repro.graphs.bitgraph import n_words
from repro.graphs.generators import erdos_renyi, p_hat_like
from repro.problems.base import make_data
from repro.problems.registry import get_problem
from repro.problems.sequential import solve_sequential


def budget_rows():
    g = erdos_renyi(48, 0.25, 2)
    want, _, _ = solve_sequential(g)
    rows = []
    for p in (2, 4, 8):
        for policy in ("priority", "random"):
            r = SolverSession(config=SolveConfig(
                num_workers=p, steps_per_round=8, policy=policy
            )).solve(g)
            assert r.best_size == want
            rows.append(
                dict(
                    workers=p,
                    policy="priority" if policy == "priority" else "round_robin",
                    rounds=r.rounds,
                    nodes=r.nodes_expanded,
                    transfers=r.tasks_transferred,
                    nodes_per_round=round(r.nodes_expanded / r.rounds, 1),
                    control_B_per_round=r.stats.control_bytes_per_round,
                    transfer_B_per_round=round(
                        r.stats.transfer_bytes_per_round, 1
                    ),
                )
            )
    return rows


def _median_rate(fn, reps=3):
    return statistics.median(fn() for _ in range(reps))


def chunked_ab(P=64, K=32, R=96, n=32, seed=1):
    """supersteps/sec: per-round host loop vs K-round device-resident."""
    g = erdos_renyi(n, 0.3, seed)
    W = n_words(g.n)
    cap = 4 * g.n + 8
    spec = get_problem("vertex_cover")
    data = make_data(spec, g)
    s0 = jax.vmap(lambda _: make_worker_state(cap, W, g.n + 1))(jnp.arange(P))
    s0 = E._scatter_startup(s0, spec, g, P)
    out = []
    for spr, label in ((0, "coordination (steps_per_round=0)"),
                       (1, "compute round (steps_per_round=1)")):
        step_fn = build_superstep_fn(
            spec, data, num_workers=P, steps_per_round=spr, lanes=1
        )
        plane = build_plane_fn(
            spec, steps_per_round=spr, lanes=1, chunk_rounds=K
        )
        chunk_fn = lambda s: plane(data, s)  # noqa: E731
        # compile
        _, d = step_fn(s0)
        jax.device_get(d)
        jax.device_get(chunk_fn(s0)[2])

        def host_rate():
            s, t0 = s0, time.perf_counter()
            for _ in range(R):
                s, d = step_fn(s)
                jax.device_get(d)  # the seed's per-round blocking sync
            return R / (time.perf_counter() - t0)

        def device_rate():
            s, t0, ran_tot = s0, time.perf_counter(), 0
            while ran_tot < R:
                s, d, ran, _hot = chunk_fn(s)
                d, ran = jax.device_get((d, ran))
                ran_tot += int(ran)
                if bool(d):
                    break
            return ran_tot / (time.perf_counter() - t0)

        h = _median_rate(host_rate)
        v = _median_rate(device_rate)
        out.append(
            dict(
                mode=label, workers=P, chunk_rounds=K,
                host_steps_per_s=round(h, 1),
                device_steps_per_s=round(v, 1),
                speedup=round(v / h, 2),
            )
        )
    return out


def transfer_ab():
    """gather vs sparse on the solve_dimacs.py sample: identical results,
    payload ∝ matches for sparse (zero on no-match rounds)."""
    g = p_hat_like(60, 0.4, seed=0)
    out = []
    results = {}
    for impl in ("gather", "sparse"):
        r = SolverSession(config=SolveConfig(
            num_workers=8, steps_per_round=16, transfer_impl=impl
        )).solve(g)
        results[impl] = r
        rec_words = 2 * n_words(g.n) + 1
        out.append(
            dict(
                impl=impl,
                best=r.best_size,
                rounds=r.rounds,
                transfer_rounds=r.stats.transfer_rounds,
                tasks_moved=r.tasks_transferred,
                payload_B_total=r.stats.transfer_bytes_total,
                payload_B_per_round=round(
                    r.stats.transfer_bytes_per_round, 1
                ),
                record_B=4 * rec_words,
            )
        )
    a, b = results["gather"], results["sparse"]
    assert a.best_size == b.best_size and (a.best_sol == b.best_sol).all(), (
        "transfer paths diverged"
    )
    # sparse payload is exactly the matched records; no-match rounds are free
    rec_words = 2 * n_words(g.n) + 1
    assert b.stats.transfer_bytes_total == 4 * rec_words * b.tasks_transferred
    return out


def _print_csv(rows):
    keys = list(rows[0].keys())
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r[k]) for k in keys))


def run(csv=True):
    sections = {
        "budget": budget_rows(),
        "chunked": chunked_ab(),
        "transfer": transfer_ab(),
    }
    if csv:
        for name, rows in sections.items():
            print(f"# {name}")
            _print_csv(rows)
    return sections


if __name__ == "__main__":
    run()
