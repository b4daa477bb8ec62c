#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

Usage, from the root of a checkout:

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Either way the last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, last, ``checks``: each number
compared beside its limit), and the check lines end standard error.  Without
a TPU, with fewer chips than the cell asks for, or with
``REPRO_PALLAS_INTERPRET`` set, it prints no result and exits non-zero.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import harness

    result, checks, _ = harness.run(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START,
    )
    for c in checks:
        print(
            f"[check] {c.name} = {c.value} (limit {c.limit}) "
            f"{'ok' if c.ok else 'FAIL'}",
            file=sys.stderr,
        )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
