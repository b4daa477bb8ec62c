"""Profiler trace capture and its reduction to device times.

A traced run records JAX's profiler trace of its window into a directory,
and :func:`load` reduces the ``.xplane.pb`` file to plain intervals: the
operations that ran on each device, and the harness's own host spans
(``bench:<name>`` annotations) on the same clock.  Everything else here is
arithmetic on those intervals, kept apart from JAX so that it is tested on
synthetic traces.

Device planes are the profiler's ``/device:TPU:<i>`` planes; their ``XLA
Ops`` line holds one event per operation executed, named by the operation's
HLO text (``%fusion.339 = pred[...] fusion(...)``).  An operation is known
by its instruction name (``%fusion.339``); control-flow containers
(``while``, ``conditional``, ``call``) span the operations they run and are
left out.  Host spans come from the ``/host:CPU`` plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?$")
LABEL_CHARS = 120  # how much of an operation's HLO text names it in a breakdown
# collective operations by their HLO instruction names
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
)


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds on the profiler's clock.

    ``ops``: device id -> list of (instruction name, start, end);
    ``spans``: list of (name, start, end) of the harness's host spans,
    without the prefix; ``window``: (start, end) of the ``window`` span;
    ``labels``: instruction name -> the start of its HLO text.
    """

    ops: dict
    spans: list
    window: tuple
    labels: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def load(directory: str, devices=None) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``.

    ``devices``: the device ids in use (None = every TPU plane found).
    Raises ``ValueError`` when the trace has no device operations or no
    ``window`` span.
    """
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise ValueError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(files[-1])
    ops, spans, labels = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = ops.setdefault(dev, [])
                for e in line.events:
                    name = e.name.split(" = ", 1)[0].strip()
                    if CONTAINER.match(name):
                        continue
                    labels.setdefault(name, e.name[:LABEL_CHARS])
                    evs.append(
                        (name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((
                            e.name[len(SPAN_PREFIX):],
                            int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                        ))
    windows = [s for s in spans if s[0] == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    if not any(ops.values()):
        raise ValueError("the trace holds no device operation")
    return Trace(ops=ops, spans=spans, window=windows[-1][1:], labels=labels)


def union(intervals, lo: int, hi: int) -> list:
    """Merged (start, end) intervals clipped to [lo, hi], in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran on a device, averaged over the
    devices in the trace."""
    lo, hi = trace.window
    per_dev = [
        sum(e - s for s, e in union([(s, e) for _, s, e in evs], lo, hi))
        for evs in trace.ops.values()
    ]
    return sum(per_dev) / len(per_dev) / 1e9


def idle_share(trace: Trace) -> float:
    """1 - busy / window, as a fraction."""
    return 1.0 - busy_s(trace) / trace.window_s


def op_time_s(trace: Trace, match) -> tuple:
    """(seconds, events) of the operations whose name ``match`` accepts,
    summed over devices and divided by their number (per-chip time), and
    the total count of such events."""
    lo, hi = trace.window
    total, count = 0, 0
    for evs in trace.ops.values():
        for name, s, e in evs:
            if match(name) and e > lo and s < hi:
                total += min(e, hi) - max(s, lo)
                count += 1
    return total / len(trace.ops) / 1e9, count


def collective_time_s(trace: Trace) -> float:
    return op_time_s(trace, lambda n: bool(COLLECTIVE.search(n)))[0]


def top_ops(trace: Trace, k: int = 10) -> list:
    """The ``k`` operations that took the most device time, as [label,
    seconds per chip]; the label is the start of the operation's HLO text."""
    lo, hi = trace.window
    acc: dict = {}
    for evs in trace.ops.values():
        for name, s, e in evs:
            if e > lo and s < hi:
                acc[name] = acc.get(name, 0) + min(e, hi) - max(s, lo)
    n = len(trace.ops)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[trace.labels.get(name, name), ns / n / 1e9] for name, ns in ranked]


def _host_label(trace: Trace, t: int) -> str:
    """The innermost harness span (shortest) that covers time ``t``."""
    covering = [
        (e - s, name) for name, s, e in trace.spans
        if s <= t < e and name != "window"
    ]
    return min(covering)[1] if covering else "no span"


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """Idle device time grouped by what the harness was doing: for each gap
    between busy intervals of the first device, the innermost host span at
    its midpoint names it; returns the ``k`` largest groups as
    [label, seconds]."""
    lo, hi = trace.window
    dev = min(trace.ops)
    busy = union([(s, e) for _, s, e in trace.ops[dev]], lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    acc: dict = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            label = "idle: " + _host_label(trace, (s + e) // 2)
            acc[label] = acc.get(label, 0) + e - s
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[label, ns / 1e9] for label, ns in ranked]
