"""Sweeps of explore's reduction loop per explore step, on the device, over
the traced calls: how often the body under the program's ``sweep`` scope
ran (its operations' events over its distinct instructions), over the
explore steps of those calls (supersteps x steps_per_round).  Every lane of
the batch sweeps in lockstep, so this is the slowest lane's count, step by
step."""

from benchmarks.chip import scopes


def read(ctx, win, device):
    plane = scopes.of(ctx, win)
    steps = sum(r.rounds for _, _, r in win.traced_calls) * int(
        ctx.config["solve_config"]["steps_per_round"]
    )
    if plane is None or not steps:
        return None
    sweeps = plane.executions("sweep")
    return sweeps / steps if sweeps else None
