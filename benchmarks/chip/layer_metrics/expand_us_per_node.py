"""Device microseconds of the bitset expand kernels per node expanded, over
the traced calls: the summed durations of the kernels' events over the
nodes those calls expanded."""

from benchmarks.chip import trace
from benchmarks.chip.roofline import is_expand_kernel


def read(ctx, win, device):
    seconds, events = trace.op_time_s(win.trace, is_expand_kernel)
    nodes = sum(r.nodes_expanded for _, _, r in win.traced_calls)
    if not events or not nodes:
        return None
    return 1e6 * seconds / nodes
