"""Device idle share of the search cells' traced calls: 1 - (union of the
device operation intervals) / traced window, in percent."""

from benchmarks.chip import trace


def read(ctx, win, device):
    return 100.0 * trace.idle_share(win.trace)
