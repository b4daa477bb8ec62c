"""Share of the chips' busy time spent in collective operations, in
percent: 100 x (collective seconds per chip) / (busy seconds per chip) over
the traced calls.  A collective waits for the slowest chip, so this share
holds the round barrier's cost as well as the bytes moved.

An operation is a collective by its HLO opcode, read from the start of its
HLO text (``%psum.16 = u32[128,1,31]{...} all-reduce(...)``: JAX names the
instruction after the primitive, XLA's opcode says what runs); where that
text is cut before the opcode, by its instruction name.  None when the
trace holds no collective (a plane on one device)."""

import re

from benchmarks.chip import trace

OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9\-]*)\(")
COLLECTIVE_OPCODE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$"
)
COLLECTIVE_NAME = re.compile(r"^%?(psum|pmin|pmax)\b")


def is_collective(label: str) -> bool:
    m = OPCODE.search(label)
    if m:
        return bool(COLLECTIVE_OPCODE.match(m.group(1)))
    name = label.split(" = ", 1)[0]
    return bool(trace.COLLECTIVE.search(name) or COLLECTIVE_NAME.match(name))


def read(ctx, win, device):
    labels = win.trace.labels
    seconds, _ = trace.op_time_s(
        win.trace, lambda name: is_collective(labels.get(name, name))
    )
    if seconds == 0:
        return None
    return 100.0 * seconds / trace.busy_s(win.trace)
