"""The chip's published peaks and the least bytes each bitset kernel moves.

The bitset kernels do integer popcounts on the vector unit; the v5e's
published table gives no integer vector peak, so their roofline is the HBM
bandwidth bound alone: the least bytes a call must move, over the peak
bandwidth, over the measured kernel time.
"""

from __future__ import annotations

import json
import pathlib
import re

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
WORD = 4  # bytes per uint32 / int32
# the bitset kernels' instruction names in a device trace: the fused expand
# panel and the degree panel (the one vertex cover's expand calls)
EXPAND_KERNELS = re.compile(r"^%?(batched_degrees|batched_expand_stats)(\.\d+)?$")
DEGREES_KERNEL = re.compile(r"^%?batched_degrees(\.\d+)?$")


def is_expand_kernel(op_name: str) -> bool:
    return bool(EXPAND_KERNELS.match(op_name))


def is_degrees_kernel(op_name: str) -> bool:
    return bool(DEGREES_KERNEL.match(op_name))


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device that is not in the
    table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{', '.join(sorted(table))}"
        )
    return table[device_kind]


def degrees_bytes(tasks: int, n: int, W: int) -> int:
    """``batched_degrees``: read the (tasks, W) masks and the (W, n)
    adjacency once, write the (tasks, n) int32 degree panel."""
    return WORD * (tasks * W + W * n + tasks * n)


def hbm_roofline_share(total_bytes: int, kernel_s: float, device_kind: str) -> float:
    """Least time at peak bandwidth over the measured kernel time."""
    return total_bytes / peaks(device_kind)["hbm_bytes_per_s"] / kernel_s
