"""How unevenly the chips were kept busy over the traced calls, in percent:
100 x (max - min) / max of each chip's busy seconds (the union of its
operation intervals inside the window).  None with fewer than two chips in
the trace."""

from benchmarks.chip import trace


def read(ctx, win, device):
    lo, hi = win.trace.window
    busy = [
        sum(e - s for s, e in trace.union([(s, e) for _, s, e in evs], lo, hi))
        for evs in win.trace.ops.values()
    ]
    if len(busy) < 2 or max(busy) == 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
