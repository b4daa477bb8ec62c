"""Device microseconds of the frontier per node expanded, over the traced
calls: the traced plane's operations under the program's ``explore/pop``
and ``explore/push`` scopes (the depth-major pop of each lane's task and
the push of its children), over the nodes those calls expanded."""

from benchmarks.chip import scopes


def read(ctx, win, device):
    plane = scopes.of(ctx, win)
    nodes = sum(r.nodes_expanded for _, _, r in win.traced_calls)
    if plane is None or not nodes:
        return None
    seconds = plane.time_s("explore/pop") + plane.time_s("explore/push")
    return 1e6 * seconds / nodes if seconds else None
