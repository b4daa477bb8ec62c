"""Branching-problem solver driver — any registry problem, any backend,
one config.

  --problem NAME     which branching problem (vertex_cover, max_clique, mis;
                     see repro.problems.registry)
  --engine spmd         the TPU-adapted superstep engine (vmap of P virtual
                        workers on one device; with --use-mesh, P / devices
                        of them on each device JAX sees)
  --engine protocol_sim the faithful asynchronous MPI-protocol simulator
                        (alias: protocol)
  --engine centralized  the fully-centralized baseline (Abu-Khzam 2006;
                        alias: central)
  --engine sequential   the problem's sequential reference (alias: seq)

All engines run behind one :class:`repro.api.SolverSession`, so every
combination of backend x problem with host plumbing works (e.g.
``--engine protocol_sim --problem max_clique``) and results arrive in the
unified :class:`repro.api.SolveResult` schema.

Config: every tuning knob is a :class:`repro.api.SolveConfig` field.
``--config cfg.json`` loads a base config, explicit CLI flags override it,
and ``--dump-config out.json`` writes the EFFECTIVE config next to the
results (``-`` prints it) — the solve is reproducible from that file.

Multi-instance mode (the batched solve plane): pass several DIMACS files
and/or ``--batch B`` to pack B instances onto one plane — one compiled
executable and one host sync per chunk for the whole batch.

Usage:
  PYTHONPATH=src python -m repro.launch.solve --graph gnp --n 60 --p 0.1 \
      --engine spmd --workers 8
  PYTHONPATH=src python -m repro.launch.solve --graph gnp --n 40 \
      --problem max_clique --engine protocol_sim --workers 8
  PYTHONPATH=src python -m repro.launch.solve --graph gnp --n 40 --batch 16
  PYTHONPATH=src python -m repro.launch.solve --config cfg.json --workers 4 \
      --dump-config effective.json
"""

from __future__ import annotations

import argparse
import sys

from repro.graphs.generators import erdos_renyi, p_hat_like, parse_dimacs
from repro.launch.compile_cache import enable_compile_cache


def build_graph(args, seed=None):
    seed = args.seed if seed is None else seed
    if args.graph == "gnp":
        return erdos_renyi(args.n, args.p if args.p else 4.0 / (args.n - 1), seed)
    if args.graph == "phat":
        return p_hat_like(args.n, args.density, seed)
    if args.graph == "dimacs":
        with open(args.file) as f:
            return parse_dimacs(f.read())
    raise ValueError(args.graph)


def build_graphs(args):
    """The multi-instance work list: every --files entry, plus --batch
    generated instances (consecutive seeds).  Empty unless one of those
    multi-instance flags was used."""
    graphs, labels = [], []
    for path in args.files or []:
        with open(path) as f:
            graphs.append(parse_dimacs(f.read()))
        labels.append(path)
    if args.batch is not None:
        if args.batch < 1:
            raise SystemExit("--batch must be >= 1")
        if args.graph == "dimacs":
            raise SystemExit("--batch needs a generated graph (gnp/phat)")
        for b in range(args.batch):
            graphs.append(build_graph(args, seed=args.seed + b))
            labels.append(f"{args.graph}-n{args.n}-seed{args.seed + b}")
    return graphs, labels


# CLI flag dest -> SolveConfig field.  These flags default to SUPPRESS so
# only EXPLICIT flags override a --config file (load -> override -> dump).
CONFIG_FLAGS = {
    "workers": "num_workers",
    "codec": "codec",
    "policy": "policy",
    "steps_per_round": "steps_per_round",
    "lanes": "lanes",
    "transfer": "transfer_impl",
    "explore": "explore_impl",
    "donate_k": "donate_k",
    "chunk_rounds": "chunk_rounds",
    "use_mesh": "use_mesh",
    "mode": "mode",
    "k": "k",
    "latency": "latency",
    "checkpoint_dir": "checkpoint_dir",
    "checkpoint_every": "checkpoint_every",
    "capacity": "capacity",
    "spill": "frontier_spill",
    "spill_codec": "spill_codec",
}


def effective_config(args):
    """--config base (or defaults), overridden by explicit CLI flags."""
    from repro.api import SolveConfig

    base = SolveConfig.load(args.config) if args.config else SolveConfig()
    provided = {
        CONFIG_FLAGS[dest]: value
        for dest, value in vars(args).items()
        if dest in CONFIG_FLAGS
    }
    return base.replace(**provided) if provided else base


def resume_solve(args):
    """--resume DIR: rebuild the session FROM the checkpoint (problem,
    config, graphs all live in it) and run to completion.  Explicit CLI
    flags act as config overrides; the fingerprint check refuses any that
    would change the solve trajectory."""
    from repro.api import BatchSolveResult, SolverSession

    overrides = {
        CONFIG_FLAGS[dest]: value
        for dest, value in vars(args).items()
        if dest in CONFIG_FLAGS
    }
    res = SolverSession.resume(args.resume, **overrides)
    if isinstance(res, BatchSolveResult):
        for i, r in enumerate(res.results):
            print(f"[solve]   instance {i}: best={r.best_size} "
                  f"rounds={r.rounds} nodes={r.nodes_expanded}")
        print(f"[solve] resumed batch from {args.resume}: "
              f"{len(res.results)} instances in {res.wall_s:.2f}s")
    else:
        print(f"[solve] resumed from {args.resume}: best={res.best_size} "
              f"rounds={res.rounds} nodes={res.nodes_expanded} "
              f"wall={res.wall_s:.2f}s")


def main():
    S = argparse.SUPPRESS
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="gnp", choices=["gnp", "phat", "dimacs"])
    ap.add_argument("--n", type=int, default=60)
    ap.add_argument("--p", type=float, default=0.0)
    ap.add_argument("--density", type=float, default=0.4)
    ap.add_argument("--file", default=None)
    ap.add_argument("--files", nargs="+", default=None,
                    help="several DIMACS files -> one solve_many batch")
    ap.add_argument("--batch", type=int, default=None,
                    help="generate B instances (seeds seed..seed+B-1) and "
                         "solve them on one batched plane (B=1 still uses "
                         "the batched engine)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--engine", default="spmd",
        help="backend: spmd, protocol_sim (protocol), centralized "
             "(central), sequential (seq)",
    )
    ap.add_argument("--problem", default="vertex_cover",
                    help="branching problem from the registry "
                         "(vertex_cover, max_clique, mis, ...)")
    ap.add_argument("--config", default=None,
                    help="JSON SolveConfig to start from; explicit CLI "
                         "flags override it")
    ap.add_argument("--dump-config", default=None, metavar="PATH",
                    help="write the EFFECTIVE config as JSON ('-' prints) "
                         "and still run the solve")
    # -- SolveConfig knobs (SUPPRESS default = "not explicitly provided") ----
    ap.add_argument("--workers", type=int, default=S)
    ap.add_argument("--codec", default=S,
                    help="task codec: optimized (n-bit masks) or basic "
                         "(adjacency payload, §4.3)")
    ap.add_argument("--policy", default=S, choices=["priority", "random"])
    ap.add_argument("--steps-per-round", type=int, default=S)
    ap.add_argument("--lanes", type=int, default=S)
    ap.add_argument("--transfer", default=S, choices=["sparse", "gather"],
                    help="data-plane impl (sparse=masked psum, gather=all-gather)")
    ap.add_argument("--explore", default=S, choices=["fused", "reference"],
                    help="explore hot path (fused=one-pass expand + cheap "
                         "pop, reference=per-task callables + top_k)")
    ap.add_argument("--donate-k", type=int, default=S,
                    help="max tasks a matched donor ships per round")
    ap.add_argument("--chunk-rounds", type=int, default=S,
                    help="supersteps per host sync (device-resident loop)")
    ap.add_argument("--use-mesh", action="store_true", default=S,
                    help="one worker per jax device (shard_map)")
    ap.add_argument("--mode", default=S, choices=["bnb", "fpt"])
    ap.add_argument("--k", type=int, default=S)
    ap.add_argument("--latency", type=int, default=S,
                    help="simulator message latency in ticks")
    ap.add_argument("--checkpoint-dir", default=S, metavar="DIR",
                    help="write a resumable SolveCheckpoint every "
                         "--checkpoint-every chunks (spmd)")
    ap.add_argument("--checkpoint-every", type=int, default=S,
                    help="chunks between checkpoint writes (default 8)")
    ap.add_argument("--capacity", type=int, default=S,
                    help="hot frontier slots per worker "
                         "(default: engine-sized 4n + 8*lanes)")
    ap.add_argument("--spill", action="store_true", default=S,
                    help="hierarchical frontier memory: evict past the "
                         "high-water mark to a codec-compressed host cold "
                         "tier instead of dropping tasks (spmd)")
    ap.add_argument("--spill-codec", default=S,
                    choices=["optimized", "basic"],
                    help="record encoding for the cold tier (default: "
                         "optimized, 2W+1 words/task)")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume a checkpointed solve (dir or step_N subdir); "
                         "problem/config/graphs come from the checkpoint, "
                         "explicit flags override non-trajectory knobs")
    ap.add_argument("--chaos", type=int, default=None, metavar="N",
                    help="deterministic fault injection (spmd): fire N "
                         "random faults from repro.faults (lane crashes, "
                         "stalls, payload corruption, checkpoint I/O "
                         "errors) and self-heal — results stay "
                         "bit-identical to a fault-free run")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the --chaos fault plan (default 0)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.resume:
        resume_solve(args)
        return

    # one validation pass: config knobs, problem and backend names all fail
    # with the list of valid values, not a deep KeyError
    from repro.api import SolverSession, get_backend
    from repro.problems.registry import get_problem

    try:
        cfg = effective_config(args)
        spec = get_problem(args.problem)
        backend = get_backend(args.engine)
    except ValueError as e:
        raise SystemExit(f"error: {e}")

    if args.dump_config:
        if args.dump_config == "-":
            sys.stdout.write(cfg.to_json())
        else:
            cfg.save(args.dump_config)
            print(f"[solve] effective config -> {args.dump_config}")

    session = SolverSession(problem=spec, backend=backend, config=cfg)

    injector = None
    if args.chaos is not None:
        if backend.name != "spmd":
            raise SystemExit("--chaos needs the spmd engine")
        from repro.faults import FaultInjector, FaultPlan

        plan = FaultPlan.random(
            args.chaos_seed, n_events=args.chaos, lanes=cfg.lanes
        )
        injector = FaultInjector(plan)
        print(f"[solve] chaos: {args.chaos} seeded fault(s) "
              f"(seed {args.chaos_seed}): {plan.counts()}")
    extra = {"injector": injector} if injector is not None else {}

    batch_graphs, batch_labels = build_graphs(args)
    if batch_graphs:
        if cfg.use_mesh:
            raise SystemExit(
                "multi-instance mode has no mesh path yet (vmap virtual "
                "workers only) — drop --use-mesh"
            )
        print(f"[solve] batch of {len(batch_graphs)} instances "
              f"[{spec.name}] on {backend.name}, "
              f"workers/instance={cfg.num_workers}")
        res = session.solve_many(batch_graphs, **extra)
        for label, r in zip(batch_labels, res.results):
            print(f"[solve]   {label}: best={r.best_size} rounds={r.rounds} "
                  f"nodes={r.nodes_expanded} transfers={r.tasks_transferred}")
        print(f"[solve] batch done: {len(batch_graphs)} instances in "
              f"{res.wall_s:.2f}s "
              f"({len(batch_graphs) / max(res.wall_s, 1e-9):.2f} inst/s), "
              f"{len(res.buckets)} bucket(s), {res.compactions} "
              f"compaction(s); cache: {session.cache_stats()}")
        if injector is not None:
            print(f"[solve] chaos report: {injector.report()}")
        return

    g = build_graph(args)
    print(f"[solve] graph n={g.n} m={g.num_edges} engine={backend.name} "
          f"problem={spec.name}")
    r = session.solve(g, **extra)
    line = (f"[solve] best={r.best_size} rounds={r.rounds} "
            f"nodes={r.nodes_expanded} transfers={r.tasks_transferred} "
            f"wall={r.wall_s:.2f}s")
    s = r.stats
    if backend.name == "spmd":
        line += (f" overflow={s.overflow} "
                 f"control_B/round={s.control_bytes_per_round} "
                 f"transfer_B/round={s.transfer_bytes_per_round:.1f} "
                 f"(total {s.transfer_bytes_total}B over "
                 f"{s.transfer_rounds} transfer rounds, "
                 f"{cfg.transfer_impl})")
        if s.checkpoints_written:
            line += f" checkpoints={s.checkpoints_written}"
        if s.spilled_tasks:
            line += (f" spilled={s.spilled_tasks} "
                     f"readmitted={s.readmitted_tasks} "
                     f"cold_peak={s.cold_bytes_peak}B")
    elif backend.name in ("protocol_sim", "centralized"):
        line += (f" bytes={s.total_bytes}"
                 + (f" (center {s.center_bytes})"
                    f" failed_requests={s.failed_requests}"
                    if backend.name == "protocol_sim" else ""))
    print(line)
    if injector is not None:
        print(f"[solve] chaos report: {injector.report()}")


if __name__ == "__main__":
    main()
