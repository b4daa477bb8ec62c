"""Device microseconds of the XLA operations (every device operation but the
bitset kernels) per node expanded, over the traced calls.

On the solo plane these are explore's reduction (``reduce_instance``'s
per-lane neighbour tables), its pivot and child construction, and the
frontier's pop and push, with the center's and the transfer's few small
operations; the program names none of them apart yet."""

from benchmarks.chip import trace
from benchmarks.chip.roofline import is_expand_kernel


def read(ctx, win, device):
    seconds, events = trace.op_time_s(win.trace, lambda name: not is_expand_kernel(name))
    nodes = sum(r.nodes_expanded for _, _, r in win.traced_calls)
    if not events or not nodes:
        return None
    return 1e6 * seconds / nodes
