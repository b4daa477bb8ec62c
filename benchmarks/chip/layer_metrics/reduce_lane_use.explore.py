"""Share of the reduction's lane sweeps that were a popped lane's own, in
percent, over the window's calls, from the solver's ``SolveStats``
counters: ``reduce_lane_sweeps`` (each expanded lane's sweeps, its last,
unchanging one included) over lanes x ``reduce_worker_sweeps`` (each
worker's lockstep loop trips: per explore step, its slowest lane's
sweeps).  Exact from run to run.  The device runs all workers' lanes in
one lockstep loop, so its own lane use is at most this."""


def read(ctx, win, device):
    stats = [r.stats for _, _, r in win.calls]
    lane = sum(getattr(s, "reduce_lane_sweeps", 0) for s in stats)
    worker = sum(getattr(s, "reduce_worker_sweeps", 0) for s in stats)
    lanes = int(ctx.config["solve_config"]["lanes"])
    if not lane or not worker:
        return None
    return 100.0 * lane / (lanes * worker)
