"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

  speedup           Fig. 4 / Table 1 (semi vs central x encodings)
  encoding_bytes    §4.3 serialization sizes
  protocol_stats    §3 message accounting (failed requests == 0)
  engine_throughput TPU-adapted engine rounds/transfers budget
  batch_throughput  multi-instance solve plane vs sequential loop
  clique_smoke      max-clique on the generic plane vs sequential reference
  session_warm      cold-vs-warm SolverSession (compiled-plane cache gate)
  explore_throughput fused vs reference exploration plane, nodes/sec (gated)
  serve_load        continuous-admission service vs fixed batching (gated)
  spill_throughput  hierarchical frontier memory: no-drop + wall gate
  chaos_smoke       seeded fault schedule: bit-identical self-healing gate
  balancer_bench    beyond-paper serving balancer

Usage:  PYTHONPATH=src python -m benchmarks.run [--smoke] [name ...]

The kill-and-resume durability gate is not in this list: its solves run in
child processes, and a chip belongs to one process at a time, so it runs as
its own command (``python -m benchmarks.resume_smoke``), never after this
process has used JAX.

``--smoke`` runs shrunken versions of the smoke-capable benchmarks (the
default name set becomes SMOKE_DEFAULT) and records every dict a benchmark
returns in benchmarks/out/BENCH_smoke.json — the per-PR perf trajectory the
CI bench-smoke job uploads as an artifact and ``benchmarks.check_regression``
compares against the committed ``benchmarks/baseline.json``.  Every recorded entry is tagged with the
branching problem it exercised (``problem``; vertex_cover unless the
benchmark says otherwise).
"""

import argparse
import inspect
import json
import os
import sys
import time

from benchmarks import (
    balancer_bench,
    batch_throughput,
    chaos_smoke,
    clique_smoke,
    encoding_bytes,
    engine_throughput,
    explore_throughput,
    protocol_stats,
    serve_load,
    session_warm,
    speedup,
    spill_throughput,
)
from repro.launch.compile_cache import enable_compile_cache

ALL = {
    "encoding_bytes": encoding_bytes,
    "protocol_stats": protocol_stats,
    "engine_throughput": engine_throughput,
    "batch_throughput": batch_throughput,
    "clique_smoke": clique_smoke,
    "session_warm": session_warm,
    "explore_throughput": explore_throughput,
    "serve_load": serve_load,
    "spill_throughput": spill_throughput,
    "chaos_smoke": chaos_smoke,
    "balancer_bench": balancer_bench,
    "speedup": speedup,
}

# kept fast enough for a per-PR CI job; full runs remain opt-in by name
SMOKE_DEFAULT = (
    "encoding_bytes", "batch_throughput", "clique_smoke", "session_warm",
    "explore_throughput", "serve_load", "spill_throughput", "chaos_smoke",
)

# generated artifacts live under benchmarks/out/ (gitignored); only the
# reviewed baseline.json is committed
OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
SMOKE_JSON = os.path.join(OUT_DIR, "BENCH_smoke.json")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("names", nargs="*", help="benchmarks to run (default: all)")
    ap.add_argument(
        "--smoke",
        action="store_true",
        help=f"shrunken sizes; record results in {SMOKE_JSON}",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = args.names or (
        list(SMOKE_DEFAULT) if args.smoke else list(ALL)
    )
    unknown = [n for n in names if n not in ALL]
    if unknown:
        print(
            f"unknown benchmark(s): {', '.join(unknown)}\n"
            f"available: {', '.join(sorted(ALL))}",
            file=sys.stderr,
        )
        raise SystemExit(2)

    recorded = {}
    for name in names:
        run_fn = ALL[name].run
        kwargs = (
            {"smoke": True}
            if args.smoke and "smoke" in inspect.signature(run_fn).parameters
            else {}
        )
        print(f"== {name} ==")
        t0 = time.perf_counter()
        out = run_fn(**kwargs)
        elapsed = time.perf_counter() - t0
        print(f"-- {name} done in {elapsed:.1f}s\n", flush=True)
        if isinstance(out, dict):
            entry = dict(out, elapsed_s=round(elapsed, 1))
            # every BENCH_smoke.json entry names the problem it exercised
            entry.setdefault("problem", "vertex_cover")
            recorded[name] = entry

    if args.smoke:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(SMOKE_JSON, "w") as f:
            json.dump({"smoke": True, "benchmarks": recorded}, f, indent=2)
            f.write("\n")
        print(f"wrote {SMOKE_JSON} ({', '.join(recorded) or 'no dict results'})")


if __name__ == "__main__":
    main()
