#!/usr/bin/env python3
"""Readings that set a cell's limits: sound runs, the control, planted faults.

In one process on the chip, at the cell's own size: the program as
configured on each seed (the lower readings), the cell's control (its
workload file's ``control`` overrides: the program with a path switched on
that breaks a guarantee the configuration states), and each fault of
``faults.py`` that the cell can have.  Prints one JSON line per run with the
numbers compared, then a summary line per kind: the largest reading of each
number over the runs, and whether every run of that kind came out correct.

Usage, from the root of a checkout, on the chip:

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 [--sound-seeds 4 5 ...] [--faults half_batch ...]

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def readings(workload: str, seed: int, seconds: float, *, kind: str = "sound",
             require_chip: bool = True, overrides: dict | None = None) -> dict:
    """One run of ``kind`` (``sound``, ``control`` or a fault's name):
    its numbers compared and whether it came out correct."""
    from benchmarks.chip import faults, harness

    over = dict(overrides or {})
    plant = None
    if kind == "control":
        control = harness.load_json("workloads", workload)["control"]
        for part, value in control.items():
            over[part] = {**over.get(part, {}), **value}
    elif kind != "sound":
        plant = faults.PLANTS[kind]
    t0 = time.perf_counter()
    with plant() if plant else contextlib.nullcontext():
        try:
            result, checks, _ = harness.run(
                workload, seed, seconds, False, t_start=t0,
                require_chip=require_chip, overrides=over,
            )
        except Exception as e:  # a crashed run has failed its check
            return {"kind": kind, "seed": seed, "correct": False,
                    "crashed": f"{type(e).__name__}: {e}"}
    return {
        "kind": kind, "seed": seed, "correct": result["correct"],
        "checks": {c.name: c.value for c in checks},
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "wall_s": time.perf_counter() - t0,
    }


def summarize(rows: list) -> dict:
    out = {}
    for row in rows:
        s = out.setdefault(row["kind"], {"runs": 0, "all_correct": True, "max": {}})
        s["runs"] += 1
        s["all_correct"] &= row["correct"]
        for name, value in row.get("checks", {}).items():
            s["max"][name] = max(s["max"].get(name, value), value)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True,
                    help="seeds of the control and the faults")
    ap.add_argument("--sound-seeds", type=int, nargs="*", default=[],
                    help="seeds of the sound runs")
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    rows = []
    plan = [("sound", s) for s in args.sound_seeds]
    plan += [("control", s) for s in args.seeds]
    plan += [(f, s) for f in args.faults for s in args.seeds]
    for kind, seed in plan:
        row = readings(args.workload, seed, args.seconds, kind=kind)
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"summary": summarize(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
