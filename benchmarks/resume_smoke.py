"""Kill-and-resume smoke: a real SIGKILL mid-solve, then a bit-identical
resume.

Every solve runs in a child process, one at a time, and the parent never
initializes a JAX backend: a chip belongs to one process, so a parent that
had touched JAX would hold it while its children wait.  A ``baseline`` child
solves the instance uninterrupted; a ``solve`` child runs the same
checkpointed solve (``checkpoint_every=1`` so every chunk boundary is
durable) and is SIGKILLed as soon as checkpoints appear on disk; a
``resume`` child restores the survivors via :meth:`SolverSession.resume`.
The parent asserts the resumed result is bit-identical to the baseline
(modulo wall-clock and the durability counters, which are outside the
contract).

A double-kill cycle then SIGKILLs the RECOVERY itself: a ``recover`` child
resumes from the survivors while continuing to checkpoint into the same
directory, is killed again once a newer generation is durable, and the
final resume must still be bit-identical — checkpoints written by a
recovering process are as good as any other.

A further kill cycle runs the same contract MID-SPILL: a saturating
``frontier_spill`` solve whose checkpoints carry a non-empty cold tier —
the resumed solve must land bit-identically INCLUDING the spill counters
(``spilled_tasks`` / ``readmitted_tasks``), proving the host cold tier
survives a SIGKILL at any chunk boundary.

Also records the §H durability overheads for EXPERIMENTS.md /
benchmarks/out/RESUME_smoke.json: checkpoint write cost (checkpointed vs
plain solve wall, both on a warm plane cache inside the baseline child),
on-disk checkpoint size, and resume latency (the resume child's solve call,
compile included).

Usage:
  PYTHONPATH=src python -m benchmarks.resume_smoke           # full
  PYTHONPATH=src python -m benchmarks.resume_smoke --smoke   # CI sizes
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
RESUME_JSON = os.path.join(OUT_DIR, "RESUME_smoke.json")

# the one deterministic workload every process builds (seeded generator);
# the spill variant pins a saturating capacity so checkpoints mid-solve
# carry a non-empty cold tier
def _workload(smoke: bool, spill: bool = False, deep: bool = False):
    from repro.api import SolveConfig
    from repro.graphs.generators import erdos_renyi

    if deep:
        # the double-kill cycle wants many chunks REMAINING after the first
        # kill, so the recovery child demonstrably writes new generations
        # before it too is killed
        g = erdos_renyi(44, 0.25, seed=5)
        cfg = SolveConfig(
            num_workers=4, steps_per_round=2, chunk_rounds=1,
            checkpoint_every=1,
        )
        return g, cfg
    if spill:
        g = erdos_renyi(40, 0.28, seed=0)
        cfg = SolveConfig(
            num_workers=4, steps_per_round=2, chunk_rounds=2, capacity=16,
            frontier_spill=True, checkpoint_every=1,
        )
        return g, cfg
    n = 36 if smoke else 40
    g = erdos_renyi(n, 0.25, seed=3)
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, checkpoint_every=1
    )
    return g, cfg


def _baseline(smoke: bool) -> dict:
    """The uninterrupted solves of all three workloads, plus the checkpoint
    write cost of the main one (warm plane cache, so plain-vs-checkpointed
    walls compare steady-state write cost, not a compile against a hit)."""
    from repro.api import PlaneCache, SolverSession
    from repro.checkpoint.store import latest_step

    g, cfg = _workload(smoke)
    cache = PlaneCache()
    SolverSession(config=cfg, cache=cache).solve(g)
    t0 = time.perf_counter()
    base = SolverSession(config=cfg, cache=cache).solve(g)
    plain_wall = time.perf_counter() - t0

    d_cost = tempfile.mkdtemp(prefix="resume_smoke_cost_")
    try:
        t0 = time.perf_counter()
        ck_run = SolverSession(config=cfg, cache=cache).solve(
            g, checkpoint_dir=d_cost
        )
        ckpt_wall = time.perf_counter() - t0
        ckpt_bytes = _dir_bytes(
            os.path.join(d_cost, f"step_{latest_step(d_cost)}")
        )
    finally:
        shutil.rmtree(d_cost, ignore_errors=True)

    g_dp, cfg_dp = _workload(smoke, deep=True)
    g_sp, cfg_sp = _workload(smoke, spill=True)
    return dict(
        main=base.to_dict(),
        deep=SolverSession(config=cfg_dp, cache=cache).solve(g_dp).to_dict(),
        spill=SolverSession(config=cfg_sp, cache=cache).solve(g_sp).to_dict(),
        n=g.n,
        plain_wall_s=plain_wall,
        checkpointed_wall_s=ckpt_wall,
        checkpoints_written=ck_run.stats.checkpoints_written,
        checkpoint_bytes=ckpt_bytes,
    )


def _child(role: str, ckpt_dir: str, smoke: bool, spill: bool, deep: bool,
           out: str) -> None:
    from repro.api import SolverSession
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = None
    if role == "baseline":
        result = _baseline(smoke)
    elif role == "solve":
        g, cfg = _workload(smoke, spill, deep)
        SolverSession(config=cfg).solve(g, checkpoint_dir=ckpt_dir)
    elif role == "recover":
        # resume from the survivors AND keep checkpointing into the same
        # directory — so the parent can SIGKILL it again mid-recovery
        SolverSession.resume(ckpt_dir, checkpoint_dir=ckpt_dir)
    else:  # "resume": the final, uninterrupted restore
        t0 = time.perf_counter()
        r = SolverSession.resume(ckpt_dir, checkpoint_dir=None)
        result = dict(r.to_dict(), resume_wall_s=time.perf_counter() - t0)
    if out is not None:
        with open(out, "w") as f:
            json.dump(result, f)


def _argv(role: str, ckpt_dir: str, smoke: bool, spill: bool = False,
          deep: bool = False, out: str | None = None) -> list:
    return (
        [sys.executable, "-m", "benchmarks.resume_smoke", "--child", role,
         "--dir", ckpt_dir]
        + (["--smoke"] if smoke else [])
        + (["--spill"] if spill else [])
        + (["--deep"] if deep else [])
        + (["--out", out] if out else [])
    )


def _env() -> dict:
    return {**os.environ, "PYTHONPATH": "src"}


def _run_child(role: str, ckpt_dir: str, smoke: bool, **kw) -> dict:
    """Run one child to completion and return the JSON it wrote."""
    out = f"{ckpt_dir}.{role}.json"
    subprocess.run(
        _argv(role, ckpt_dir, smoke, out=out, **kw), env=_env(), check=True
    )
    with open(out) as f:
        return json.load(f)


def _kill_when(proc, ready, poll_s: float, what: str) -> bool:
    """SIGKILL ``proc`` once ``ready()`` holds; False if it exited first."""
    deadline = time.time() + 300
    while time.time() < deadline:
        if ready():
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            return True
        if proc.poll() is not None:
            return False
        time.sleep(poll_s)
    proc.kill()
    proc.wait()
    raise RuntimeError(f"{what} within 300s")


def _dir_bytes(d: str) -> int:
    total = 0
    for root, _, files in os.walk(d):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


def _kill_and_resume(smoke: bool, spill: bool = False):
    """Launch the checkpointing child, SIGKILL it at the first durable
    step, resume from the survivors in a fresh child.  Returns
    (resumed_result, killed_at_step, killed_mid_solve)."""
    from repro.checkpoint.store import latest_step  # reads dirs; no backend

    d = tempfile.mkdtemp(prefix="resume_smoke_kill_")
    try:
        ckpt = os.path.join(d, "ckpt")
        proc = subprocess.Popen(
            _argv("solve", ckpt, smoke, spill=spill), env=_env()
        )
        killed_mid_solve = _kill_when(
            proc, lambda: latest_step(ckpt) is not None, 0.05,
            "child produced no checkpoint",
        )
        step = latest_step(ckpt)
        assert step is not None, "no checkpoint survived the kill"
        resumed = _run_child("resume", ckpt, smoke)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return resumed, step, killed_mid_solve


def _kill_mid_recovery(smoke: bool):
    """The double-kill cycle: SIGKILL the first child at its first durable
    step, then launch a RECOVERY child (it resumes from the survivors while
    continuing to checkpoint into the same directory) and SIGKILL that one
    too once it has written a newer generation — the final resume must
    still land bit-identically.  Returns (resumed_result, first_kill_step,
    recovery_kill_step, recovery_killed_mid_solve)."""
    from repro.checkpoint.store import latest_step  # reads dirs; no backend

    d = tempfile.mkdtemp(prefix="resume_smoke_kill2_")
    try:
        ckpt = os.path.join(d, "ckpt")
        proc = subprocess.Popen(
            _argv("solve", ckpt, smoke, deep=True), env=_env()
        )
        _kill_when(
            proc, lambda: latest_step(ckpt) is not None, 0.002,
            "child produced no checkpoint",
        )
        step1 = latest_step(ckpt)
        assert step1 is not None, "no checkpoint survived the first kill"

        # recovery child: resumes from step1 and keeps checkpointing; kill
        # it again as soon as a NEWER generation is durable (mid-recovery).
        # If the remaining work finishes before that, the cycle degrades to
        # a plain resume — recorded, not failed.
        proc = subprocess.Popen(
            _argv("recover", ckpt, smoke, deep=True), env=_env()
        )
        killed_mid_recovery = _kill_when(
            proc,
            lambda: (latest_step(ckpt) or -1) > step1,
            0.002,
            "recovery child made no progress",
        )
        step2 = latest_step(ckpt)
        assert step2 is not None and step2 >= step1
        resumed = _run_child("resume", ckpt, smoke)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return resumed, step1, step2, killed_mid_recovery


def _same(got: dict, want: dict, keys) -> None:
    for key in keys:
        assert got[key] == want[key], (key, got[key], want[key])


def run(smoke: bool = False) -> dict:
    d = tempfile.mkdtemp(prefix="resume_smoke_base_")
    try:
        baseline = _run_child("baseline", os.path.join(d, "base"), smoke)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    base = baseline["main"]
    plain_wall = baseline["plain_wall_s"]
    ckpt_wall = baseline["checkpointed_wall_s"]

    resumed, step, killed_mid_solve = _kill_and_resume(smoke)
    resume_wall = resumed["resume_wall_s"]

    # bit-identity vs the uninterrupted baseline (wall_s and the durability
    # counters are explicitly outside the contract)
    _same(resumed, base, (
        "best_size", "rounds", "nodes_expanded", "tasks_transferred",
        "best_sol",
    ))
    _same(resumed["stats"], base["stats"], ("transfer_bytes_total",))

    # double-kill cycle: SIGKILL the solve, then SIGKILL the recovery
    # itself mid-checkpoint — the second-generation survivors must still
    # resume bit-identically (checkpoints are valid at EVERY boundary,
    # including ones written by a recovering process)
    res2, kill1_step, kill2_step, killed_mid_recovery = _kill_mid_recovery(
        smoke
    )
    _same(res2, baseline["deep"], (
        "best_size", "rounds", "nodes_expanded", "best_sol",
    ))

    # second cycle: SIGKILL with a live cold tier (frontier_spill on a
    # saturating capacity) — resume must replay the spill pump exactly
    base_sp = baseline["spill"]
    assert base_sp["stats"]["spilled_tasks"] > 0, (
        "spill workload no longer saturates — retune _workload(spill=True)"
    )
    res_sp, sp_step, sp_killed = _kill_and_resume(smoke, spill=True)
    _same(res_sp, base_sp, (
        "best_size", "rounds", "nodes_expanded", "best_sol",
    ))
    _same(res_sp["stats"], base_sp["stats"], (
        "spilled_tasks", "readmitted_tasks",
    ))
    assert res_sp["stats"]["overflow_count"] == 0
    assert not res_sp["stats"]["overflow"]

    out = dict(
        n=baseline["n"],
        rounds=int(base["rounds"]),
        killed_mid_solve=killed_mid_solve,
        killed_at_step=int(step),
        resumed_best=int(resumed["best_size"]),
        bit_identical=True,
        plain_wall_s=round(plain_wall, 3),
        checkpointed_wall_s=round(ckpt_wall, 3),
        checkpoint_overhead_pct=round(
            100.0 * (ckpt_wall - plain_wall) / max(plain_wall, 1e-9), 1
        ),
        checkpoints_written=int(baseline["checkpoints_written"]),
        checkpoint_bytes=int(baseline["checkpoint_bytes"]),
        resume_wall_s=round(resume_wall, 3),
        recovery_first_kill_step=int(kill1_step),
        recovery_second_kill_step=int(kill2_step),
        killed_mid_recovery=killed_mid_recovery,
        recovery_bit_identical=True,
        spill_killed_at_step=int(sp_step),
        spill_killed_mid_solve=sp_killed,
        spill_resumed_best=int(res_sp["best_size"]),
        spill_spilled_tasks=int(res_sp["stats"]["spilled_tasks"]),
        spill_readmitted_tasks=int(res_sp["stats"]["readmitted_tasks"]),
        spill_bit_identical=True,
    )
    print(
        f"kill-and-resume: SIGKILL at step {step} "
        f"({'mid-solve' if killed_mid_solve else 'after finish'}), resume "
        f"bit-identical (best={out['resumed_best']}, rounds={out['rounds']}); "
        f"checkpoint {out['checkpoint_bytes']}B, write overhead "
        f"{out['checkpoint_overhead_pct']}% at every-chunk cadence, resume "
        f"{out['resume_wall_s']}s"
    )
    second = (
        f"SIGKILL the recovery at step {kill2_step}"
        if killed_mid_recovery
        else "recovery finished before a second kill landed"
    )
    print(
        f"mid-recovery kill: SIGKILL at step {kill1_step}, then {second}; "
        f"final resume bit-identical"
    )
    print(
        f"mid-spill kill: SIGKILL at step {sp_step} with a live cold tier, "
        f"resume bit-identical (best={out['spill_resumed_best']}, "
        f"{out['spill_spilled_tasks']} spilled / "
        f"{out['spill_readmitted_tasks']} readmitted, 0 dropped)"
    )
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(RESUME_JSON, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {RESUME_JSON}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.resume_smoke")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument(
        "--child", default=None, help=argparse.SUPPRESS,
        choices=("baseline", "solve", "recover", "resume"),
    )
    ap.add_argument("--dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--spill", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--deep", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        _child(
            args.child, args.dir, args.smoke, args.spill, args.deep, args.out
        )
    else:
        run(args.smoke)


if __name__ == "__main__":
    main()
