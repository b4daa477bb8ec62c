"""The expand degree kernel's share of its HBM roofline, in percent: the
least bytes each ``batched_degrees`` call must move (computed from its
shapes by ``benchmarks/chip/roofline.py``) at the chip's peak HBM
bandwidth, over the measured kernel time.  Only the bandwidth bound is
used: the published v5e table has no integer vector peak."""

from benchmarks.chip import roofline, trace


def read(ctx, win, device):
    seconds, events = trace.op_time_s(win.trace, roofline.is_degrees_kernel)
    if not events or not seconds:
        return None
    per_chip = events / len(win.trace.ops)
    total = per_chip * roofline.degrees_bytes(*win.kernel_shape)
    return 100.0 * roofline.hbm_roofline_share(total, seconds, device["kind"])
