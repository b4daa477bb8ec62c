#!/usr/bin/env python3
"""Smoke test of the solver's main path on a TPU chip.

Runs in one process and starts none.  It drives the solver through its
public entry points (``SolverSession`` and ``SolveService``) at real width
and checks every answer against the host's sequential reference solver.

One chip (the default), three phases:

  kernel   the fused expand kernel, compiled natively, on random task masks
           of a G(200, p) graph: degrees and popcounts bit-identical to
           numpy on the host.
  solo     ``SolverSession.solve`` proves the minimum vertex cover of a
           generated G(200, p) graph (W = 7 packed words) with 32 workers
           and 8 lanes each, so the fused plane expands through the Pallas
           bitset kernel.  Before the solve it compiles that plane and
           counts its ``tpu_custom_call`` ops, and fails if there are none.
  service  one ``SolveService`` per problem, sharing a plane cache, answers
           nine requests that mix vertex_cover, max_clique and mis at
           n = 200..224; each answer must equal the same instance's solo
           solve and the sequential optimum.

``--chips 4`` runs only the mesh phase: the ``use_mesh`` solve with one
worker per chip, compared with the vmap solve of the same configuration on
one chip (``best_size``, ``best_sol`` and ``rounds`` bit-identical).

The last line of standard output is one JSON object naming the device.  The
script exits non-zero, and prints no such line, when JAX finds no TPU, when
``REPRO_PALLAS_INTERPRET`` is set, or when any phase fails.

Usage:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# (n, edge probability, seed) of the solo phase's vertex-cover instance
SOLO = (200, 0.025, 7)
# the service's request mix: (problem, n, edge probability, seeds)
SERVICE = (
    ("vertex_cover", 200, 0.02, (7, 8, 9)),
    ("max_clique", 224, 0.1, (1, 2, 3)),
    ("mis", 208, 0.9, (1, 2, 3)),
)
# the mesh phase's vertex-cover instance
MESH = (200, 0.025, 2)
# task masks in the kernel phase's direct check: eight sublane tiles
KERNEL_TASKS = 64
# a solve that reaches this many supersteps was cut, not proved
MAX_ROUNDS = 200_000


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"[chip_smoke] FAIL: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def popcount(words) -> int:
    return sum(bin(int(w)).count("1") for w in words)


def check_optimal(spec, g, r, what: str) -> None:
    """``r`` is a proved optimum: the search ran to quiescence with no task
    dropped, the answer is feasible and its size is the sequential
    reference's."""
    check(r.rounds < MAX_ROUNDS, f"{what}: stopped by max_rounds")
    check(r.stats.overflow_count == 0, f"{what}: frontier dropped tasks")
    check(spec.verify(g, r.best_sol), f"{what}: answer fails {spec.name} verify")
    check(
        popcount(r.best_sol) == r.best_size,
        f"{what}: best_sol has {popcount(r.best_sol)} vertices, "
        f"best_size says {r.best_size}",
    )
    seq_best = spec.sequential(g)[0]
    check(
        r.best_size == seq_best,
        f"{what}: best_size {r.best_size} != sequential optimum {seq_best}",
    )


def same(a, b, what: str) -> None:
    """Bit-identical results: answer, trajectory and counters."""
    import numpy as np

    for field in ("best_size", "rounds", "nodes_expanded", "tasks_transferred"):
        check(
            getattr(a, field) == getattr(b, field),
            f"{what}: {field} {getattr(a, field)} != {getattr(b, field)}",
        )
    check(
        np.array_equal(np.asarray(a.best_sol), np.asarray(b.best_sol)),
        f"{what}: best_sol differs",
    )


def compiled_solo_plane(session, g):
    """The solo plane ``session.solve(g)`` runs, lowered for the shapes of
    ``g`` and compiled (the persistent cache then serves the solve)."""
    import jax
    import jax.numpy as jnp

    from repro.core.encoding import make_codec
    from repro.core.superstep import make_worker_state
    from repro.problems.base import make_data

    cfg, spec = session.config, session.problem
    pad = make_codec(cfg.codec, g.n, problem=spec).pad_words
    plane = session.cache.solo_plane(spec, cfg, pad, False)
    state = jax.eval_shape(
        lambda: jax.vmap(
            lambda _: make_worker_state(cfg.capacity, g.W, 0)
        )(jnp.arange(cfg.num_workers))
    )
    return plane.lower(make_data(spec, g), state).compile()


def kernel_phase() -> None:
    """The fused expand kernel, native on the chip, against numpy on the
    host: bit-identical degrees and popcounts for random task masks."""
    import jax
    import numpy as np

    from repro.graphs.bitgraph import pack_masks, unpack_mask
    from repro.graphs.generators import erdos_renyi
    from repro.kernels.bitset_ops.kernel import batched_expand_stats

    n, p, seed = SOLO
    g = erdos_renyi(n, 4 * p, seed)
    rng = np.random.default_rng(seed)
    masks = pack_masks(rng.random((KERNEL_TASKS, n)) < 0.5)
    sols = pack_masks(rng.random((KERNEL_TASKS, n)) < 0.3)
    deg, pc = jax.device_get(
        batched_expand_stats(g.adj, masks, sols, interpret=False)
    )
    want = np.bitwise_count(g.adj[None] & masks[:, None]).sum(-1)
    inside = unpack_mask(masks, n)
    check(
        np.array_equal(deg, np.where(inside, want.astype(np.int64), -1)),
        "kernel: degree panel differs from numpy",
    )
    check(
        np.array_equal(pc[:, 0], np.bitwise_count(masks).sum(-1))
        and np.array_equal(pc[:, 1], np.bitwise_count(sols).sum(-1)),
        "kernel: popcounts differ from numpy",
    )
    log(
        f"kernel: batched_expand_stats n={n} W={g.W} tasks={KERNEL_TASKS} "
        f"bit-identical to numpy: PASS"
    )


def solo_phase() -> None:
    from repro.api import SolveConfig, SolverSession
    from repro.graphs.generators import erdos_renyi

    n, p, seed = SOLO
    g = erdos_renyi(n, p, seed)
    cfg = SolveConfig(
        num_workers=32, lanes=8, capacity=1024, max_rounds=MAX_ROUNDS
    )
    session = SolverSession(problem="vertex_cover", config=cfg)

    t0 = time.perf_counter()
    compiled = compiled_solo_plane(session, g)
    setup_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    log(
        f"solo: compiled plane n={g.n} W={g.W} workers={cfg.num_workers} "
        f"lanes={cfg.lanes} in {setup_s:.3f}s (set-up); "
        f"tpu_custom_call ops={kernels}"
    )
    check(kernels > 0, "the compiled solo plane holds no Pallas kernel")

    t0 = time.perf_counter()
    r = session.solve(g)  # ends in device_get of the final state
    wall_s = time.perf_counter() - t0
    log(
        f"solo: vertex_cover n={g.n} m={g.num_edges} best={r.best_size} "
        f"rounds={r.rounds} nodes={r.nodes_expanded} "
        f"wall={wall_s:.3f}s (solve loop {r.wall_s:.3f}s)"
    )
    check_optimal(session.problem, g, r, "solo")
    log("solo: PASS (proved optimum equals the sequential reference)")


def service_phase() -> None:
    from repro.api import PlaneCache, SolveConfig, SolverSession
    from repro.graphs.generators import erdos_renyi

    cfg = SolveConfig(
        num_workers=8, lanes=8, capacity=1024, service_lanes=4,
        max_rounds=MAX_ROUNDS,
    )
    cache = PlaneCache()
    sessions, services, requests = {}, {}, []
    for problem, n, p, seeds in SERVICE:
        sessions[problem] = SolverSession(
            problem=problem, config=cfg, cache=cache
        )
        services[problem] = sessions[problem].serve()
        for seed in seeds:
            g = erdos_renyi(n, p, seed)
            requests.append((problem, g, services[problem].submit(g)))

    t0 = time.perf_counter()
    for svc in services.values():
        svc.drain()
    wall_s = time.perf_counter() - t0
    results = [(pb, g, services[pb].result(t)) for pb, g, t in requests]
    log(
        f"service: {len(results)} requests "
        f"({', '.join(sorted(services))}) answered in {wall_s:.3f}s, "
        f"compile included; nodes="
        f"{sum(r.nodes_expanded for _, _, r in results)}"
    )
    for i, (problem, g, r) in enumerate(results):
        what = f"service request {i} ({problem} n={g.n})"
        solo = sessions[problem].solve(g)
        same(r, solo, what)
        check_optimal(sessions[problem].problem, g, r, what)
        log(
            f"service: request {i} {problem} n={g.n} best={r.best_size} "
            f"rounds={r.rounds} nodes={r.nodes_expanded} equals its solo "
            f"solve and the sequential optimum"
        )
    log("service: PASS")


def mesh_phase() -> None:
    import jax

    from repro.api import SolveConfig, SolverSession
    from repro.graphs.generators import erdos_renyi

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, have "
          f"{len(jax.devices())}")
    n, p, seed = MESH
    g = erdos_renyi(n, p, seed)
    cfg = SolveConfig(
        num_workers=4, lanes=8, capacity=1024, max_rounds=MAX_ROUNDS,
        use_mesh=True,
    )
    out = {}
    for name, c in (("mesh", cfg), ("vmap", cfg.replace(use_mesh=False))):
        session = SolverSession(problem="vertex_cover", config=c)
        t0 = time.perf_counter()
        r = session.solve(g)
        wall_s = time.perf_counter() - t0
        log(
            f"mesh: {name} solve n={g.n} workers={c.num_workers} "
            f"best={r.best_size} rounds={r.rounds} nodes={r.nodes_expanded} "
            f"wall={wall_s:.3f}s, compile included"
        )
        out[name] = r
    check(
        session.cache_stats()["bypasses"] == 0, "vmap solve took the mesh path"
    )
    same(out["mesh"], out["vmap"], "mesh vs vmap")
    check(
        out["mesh"].stats.transfer_bytes_total
        == out["vmap"].stats.transfer_bytes_total,
        "mesh vs vmap: transfer_bytes_total differs",
    )
    check_optimal(session.problem, g, out["mesh"], "mesh")
    log("mesh: PASS (use_mesh on 4 chips is bit-identical to vmap on one)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = only the use_mesh phase across four chips",
    )
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        fail("REPRO_PALLAS_INTERPRET is set; the smoke runs native kernels")
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no solver package at {src}; run from a checkout of the repo")
    sys.path.insert(0, src)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"JAX found no TPU (platform {dev.platform})")
    cache_dir = enable_compile_cache()
    log(
        f"device: {dev.device_kind} x{len(devices)} (platform {dev.platform}, "
        f"jax {jax.__version__}); compile cache {cache_dir}"
    )

    if args.chips == 4:
        mesh_phase()
    else:
        kernel_phase()
        solo_phase()
        service_phase()
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
