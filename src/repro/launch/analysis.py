"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model: TPU v5e —
  peak_flops  = 197e12  bf16 FLOP/s per chip
  hbm_bw      = 819e9   B/s per chip
  ici_bw      = 4.5e10  B/s per link (~50 GB/s markets as 45-50; we use 45)

Terms (per device, per step):
  compute    = HLO_FLOPs / peak_flops          (cost_analysis 'flops' is the
                                                per-device partitioned module)
  memory     = HLO_bytes / hbm_bw              (cost_analysis 'bytes accessed')
  collective = collective_bytes / ici_bw

collective_bytes convention (documented in EXPERIMENTS.md): the sum over
collective ops of the RESULT buffer size, weighted 2× for all-reduce (ring
reduce-scatter + all-gather moves ~2× payload per device) and 1× otherwise —
a standard per-device link-traffic estimate for ring algorithms.
"""

from __future__ import annotations

import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 45e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def collective_bytes(hlo_text: str) -> dict:
    """Parse an HLO module dump; returns {op_kind: bytes, 'total': bytes}."""
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, _, rhs = stripped.partition("=")
        kind = None
        for k in _COLLECTIVES:
            # match the op name at the start of the RHS expression
            if re.search(rf"(^|\)|\s){k}(-start|-done)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done(" in rhs:
            continue  # the -start op already carries the shape
        # result shape(s) appear on the RHS before the op name
        head = rhs.split(f"{kind}", 1)[0]
        nbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        weight = 2 if kind == "all-reduce" else 1
        out[kind] += weight * nbytes
        count[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def roofline(flops: float, bytes_accessed: float, coll_bytes: float) -> dict:
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    coll_s = coll_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    bound_s = max(compute_s, memory_s, coll_s)
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "bound_s": bound_s,
        # fraction of the roofline the compute term occupies: 1.0 means the
        # step is perfectly compute-bound (the best a fixed algorithm can do)
        "compute_fraction": compute_s / bound_s if bound_s else 0.0,
    }

