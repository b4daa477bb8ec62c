"""Share of busy device time spent in explore's reduction, in percent: the
device time of the traced plane's operations under the program's
``reduce`` scope (``reduce_instance``'s loop, its sweeps and the layout
copies the compiler added inside it), over the union of every device
operation's intervals in the traced window."""

from benchmarks.chip import scopes, trace


def read(ctx, win, device):
    plane = scopes.of(ctx, win)
    if plane is None:
        return None
    seconds, busy = plane.time_s("reduce"), trace.busy_s(win.trace)
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy
