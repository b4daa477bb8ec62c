"""Pallas TPU kernels: batched bitset degrees + fused expand stats (the B&B
compute hot spot).

TPU-native rethink of the GPU bitset tricks (no warp ballots / popc
intrinsics assumed): the adjacency bitset matrix lives wholly in VMEM,
transposed to ``(W, n)`` so one packed word of every vertex is one sublane
row (n ≤ 2048 ⇒ ≤ 512 KiB), a grid over task blocks streams packed task
masks through the VPU, and popcount is a SWAR reduction (shift/mask adds) so
it vectorizes over the (8, 128) VREG tile regardless of Mosaic popcount
support.  Degrees come out as an ``(T, n)`` int32 panel: one AND + popcount
per (task, vertex, word) triple, accumulated over words so the VMEM working
set stays at ``BT × n`` instead of ``BT × n × W``.

Mosaic lowers only static slices of the lane (minor) axis, so the word loop
is unrolled at trace time (W ≤ 64 inside the documented n ≤ 2048): word
``w`` is the static column ``masks[:, w:w+1]`` and the static row
``adj_t[w:w+1, :]``.  The same pass gathers each vertex's own mask word
(``masks[t, v // 32]``) with a static select, which replaces a lane gather.

Grid:  (ceil(T / BT),)     BT a multiple of 8, or the whole T
  masks block  (BT, W)   VMEM
  adj_t        (W, n)    VMEM (whole matrix, every grid step)
  out block    (BT, n)   VMEM

``batched_expand_stats`` is the fused exploration plane's kernel: the same
degrees panel PLUS the per-task popcounts of the candidate mask and the
partial solution, all in one VMEM pass over the packed words — the exact
quantities a fused ``expand_tasks`` needs for bound / pivot / child-prune
(degrees feed the argmax pivot; popcounts feed the bounds), so the hot path
reads each task word once instead of once per bound.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

WORD_BITS = 32

_INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"


def default_interpret() -> bool:
    """True when the Pallas kernels should run in interpret mode.

    Native Mosaic lowering only exists on TPU; everywhere else the kernels
    run under the (slow, Python-level) interpreter, which is only good for
    validation.  ``REPRO_PALLAS_INTERPRET=0|1`` forces either mode — e.g.
    ``=1`` to debug a kernel on TPU, ``=0`` to assert a runtime really
    lowers natively.  Every kernel entry point defaulting to
    ``interpret=None`` resolves through here, so nothing silently pays the
    interpreter on TPU.
    """
    env = os.environ.get(_INTERPRET_ENV, "").strip()
    if env:  # empty/unset -> backend detection
        return env.lower() not in ("0", "false", "no", "off")
    return jax.default_backend() != "tpu"


def kernels_native() -> bool:
    """True when the Pallas kernels lower natively (worth using in hot
    paths); the complement of :func:`default_interpret`."""
    return not default_interpret()


def _swar_popcount_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Branch-free SWAR popcount on uint32 (VPU shift/mask adds)."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _degree_panel(masks, adj_t_ref, *, n: int, W: int):
    """(BT, W) masks × (W, n) adjacency -> (BT, n) degrees, -1 outside the
    task."""
    BT = masks.shape[0]
    v = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    word_of_v = v // WORD_BITS
    deg = jnp.zeros((BT, n), jnp.int32)
    own = jnp.zeros((BT, n), jnp.uint32)  # masks[t, v // 32]
    for w in range(W):  # static: Mosaic slices the lane axis statically only
        mw = masks[:, w : w + 1]  # (BT, 1)
        aw = adj_t_ref[w : w + 1, :]  # (1, n)
        deg = deg + _swar_popcount_u32(mw & aw)
        own = jnp.where(word_of_v == w, mw, own)
    inside = (own >> (v % WORD_BITS).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.where(inside != 0, deg, jnp.int32(-1))


def _row_popcount(x, W: int):
    """(BT, W) -> (BT, 1) popcount per row; stays 2-D because TPU vregs want
    a lane axis."""
    return sum(_swar_popcount_u32(x[:, w : w + 1]) for w in range(W))


def _degrees_kernel(masks_ref, adj_t_ref, out_ref, *, n: int, W: int):
    out_ref[...] = _degree_panel(masks_ref[...], adj_t_ref, n=n, W=W)


def _expand_stats_kernel(
    masks_ref, sols_ref, adj_t_ref, deg_ref, pc_ref, *, n: int, W: int
):
    """Fused panel: degrees (BT, n) + [pc_mask, pc_sol] (BT, 2) per block."""
    masks = masks_ref[...]  # (BT, W) uint32
    deg_ref[...] = _degree_panel(masks, adj_t_ref, n=n, W=W)
    col = jax.lax.broadcasted_iota(jnp.int32, pc_ref.shape, 1)
    pc_ref[...] = jnp.where(
        col == 0, _row_popcount(masks, W), _row_popcount(sols_ref[...], W)
    )


def _task_block(T: int, block_tasks: int) -> int:
    """Rows per grid step: a multiple of 8 (the sublane tile), or all of T."""
    return min(T, -(-block_tasks // 8) * 8)


@functools.partial(jax.jit, static_argnames=("block_tasks", "interpret"))
def batched_expand_stats(
    adj: jnp.ndarray,
    masks: jnp.ndarray,
    sols: jnp.ndarray,
    *,
    block_tasks: int = 8,
    interpret: Optional[bool] = None,
):
    """adj (n, W), masks/sols (T, W) uint32 -> (deg (T, n) int32,
    pc (T, 2) int32) where pc[:, 0] = popcount(mask), pc[:, 1] =
    popcount(sol) — the fused expand hot-path panel in one kernel pass.

    ``interpret=None`` resolves via :func:`default_interpret` (native on
    TPU, interpret elsewhere); an explicit bool pins the mode.
    """
    if interpret is None:
        interpret = default_interpret()
    n, W = adj.shape
    T = masks.shape[0]
    BT = _task_block(T, block_tasks)
    grid = (pl.cdiv(T, BT),)
    return pl.pallas_call(
        functools.partial(_expand_stats_kernel, n=n, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BT, W), lambda i: (i, 0)),  # task masks block
            pl.BlockSpec((BT, W), lambda i: (i, 0)),  # task sols block
            pl.BlockSpec((W, n), lambda i: (0, 0)),  # whole adjacency
        ],
        out_specs=[
            pl.BlockSpec((BT, n), lambda i: (i, 0)),
            pl.BlockSpec((BT, 2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, n), jnp.int32),
            jax.ShapeDtypeStruct((T, 2), jnp.int32),
        ],
        interpret=interpret,
    )(masks, sols, adj.T)


@functools.partial(jax.jit, static_argnames=("block_tasks", "interpret"))
def batched_degrees(
    adj: jnp.ndarray,
    masks: jnp.ndarray,
    *,
    block_tasks: int = 8,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """adj (n, W) uint32, masks (T, W) uint32 -> (T, n) int32 degrees.

    ``interpret=None`` resolves via :func:`default_interpret` (native on
    TPU, interpret elsewhere); an explicit bool pins the mode.
    """
    if interpret is None:
        interpret = default_interpret()
    n, W = adj.shape
    T = masks.shape[0]
    BT = _task_block(T, block_tasks)
    grid = (pl.cdiv(T, BT),)
    return pl.pallas_call(
        functools.partial(_degrees_kernel, n=n, W=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BT, W), lambda i: (i, 0)),  # task masks block
            pl.BlockSpec((W, n), lambda i: (0, 0)),  # whole adjacency
        ],
        out_specs=pl.BlockSpec((BT, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, n), jnp.int32),
        interpret=interpret,
    )(masks, adj.T)
