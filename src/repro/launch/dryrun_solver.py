import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the PAPER'S OWN technique at production scale: the SPMD
superstep engine lowered with one worker per device on a 512-chip mesh
(the solo plane of :func:`repro.core.superstep.build_plane_fn`, sharded).

Reports the roofline terms of :mod:`repro.launch.analysis` for the baseline engine
(3-int status rows, unconditional record all-gather — the straight port of
the protocol), the optimized control plane (bit-packed 1-int status + pmin
bound, data plane skipped on match-free rounds) and the sparse data plane
(masked-psum transfer: payload rows carry only matched records) — §Perf
cell C of EXPERIMENTS.md.  ``--chunked`` lowers the K-round device-resident
runner instead of a one-round chunk (the shape the production launcher
runs: one host sync per chunk).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun_solver [--n 1024] [--out f.json]
"""

import argparse
import json

import jax
import jax.numpy as jnp

from repro.core.superstep import build_plane_fn, make_worker_state
from repro.graphs.bitgraph import n_words
from repro.graphs.generators import erdos_renyi
from repro.launch.analysis import collective_bytes, roofline
from repro.problems.base import make_data
from repro.problems.registry import get_problem


def lower_engine(n: int, workers: int, *, packed_status, skip_empty_transfer,
                 transfer_impl="gather", steps_per_round=32, lanes=1,
                 codec_pad=0, chunked=False, chunk_rounds=16,
                 problem="vertex_cover"):
    mesh = jax.make_mesh(
        (workers,), ("chips",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    g = erdos_renyi(n, 4.0 / (n - 1), 0)
    spec = get_problem(problem)
    data = make_data(spec, g)
    W = n_words(n)
    cap = 4 * n + 8 * lanes
    fn = build_plane_fn(
        spec,
        steps_per_round=steps_per_round,
        lanes=lanes,
        transfer_pad_words=codec_pad,
        packed_status=packed_status,
        skip_empty_transfer=skip_empty_transfer,
        transfer_impl=transfer_impl,
        chunk_rounds=chunk_rounds if chunked else 1,
        mesh=mesh,
    )
    state = jax.eval_shape(
        lambda: jax.vmap(lambda _: make_worker_state(cap, W, n + 1))(
            jnp.arange(workers)
        )
    )
    lowered = fn.lower(data, state)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    flops = float(cost.get("flops", 0.0))
    rl = roofline(flops, float(cost.get("bytes accessed", 0.0)), coll["total"])
    return {
        "n": n,
        "workers": workers,
        "packed_status": packed_status,
        "skip_empty_transfer": skip_empty_transfer,
        "transfer_impl": transfer_impl,
        "chunked": chunked,
        "flops_per_dev": flops,
        "collectives": {k: v for k, v in coll.items() if k != "counts"},
        "collective_counts": coll["counts"],
        "temp_b": int(getattr(mem, "temp_size_in_bytes", 0)),
        "roofline": rl,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--workers", type=int, default=512)
    ap.add_argument("--out", default=None)
    ap.add_argument("--chunked", action="store_true",
                    help="lower the K-round device-resident runner")
    ap.add_argument("--chunk-rounds", type=int, default=16)
    args = ap.parse_args()
    results = []
    for packed, skip, impl, label in [
        (False, False, "gather", "baseline (3-int status, unconditional gather)"),
        (True, False, "gather", "packed status word"),
        (True, True, "gather", "packed + skip-empty-transfer"),
        (True, True, "sparse", "packed + skip-empty + sparse psum transfer"),
    ]:
        r = lower_engine(
            args.n, args.workers, packed_status=packed,
            skip_empty_transfer=skip, transfer_impl=impl,
            chunked=args.chunked, chunk_rounds=args.chunk_rounds,
        )
        r["label"] = label
        results.append(r)
        c = r["collectives"]
        print(
            f"{label:>50s}: coll_total={c['total']/2**10:.1f}KiB "
            f"(ag={c['all-gather']/2**10:.1f} ar={c['all-reduce']/2**10:.1f}) "
            f"counts={r['collective_counts']} temp={r['temp_b']/2**20:.1f}MiB",
            flush=True,
        )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
