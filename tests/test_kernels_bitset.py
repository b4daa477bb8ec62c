"""Pallas bitset kernels (degrees + fused expand stats) vs the jnp oracle,
plus the backend-aware kernel-mode selection."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphs.generators import erdos_renyi
from repro.kernels.bitset_ops import (
    batched_degrees_ref,
    default_interpret,
    degrees_op,
    expand_stats_op,
    expand_stats_ref,
    kernels_native,
    max_degree_vertex,
    max_degree_vertex_ref,
)


def _random_masks(n, W, T, seed):
    rng = np.random.default_rng(seed)
    masks = rng.integers(0, 2**32, size=(T, W), dtype=np.uint32)
    rem = n % 32
    if rem:
        masks[:, -1] &= np.uint32((1 << rem) - 1)
    return masks


@pytest.mark.parametrize(
    "n,T,block",
    [(32, 4, 2), (64, 16, 8), (100, 7, 4), (128, 32, 8), (257, 9, 8), (512, 24, 16)],
)
def test_kernel_matches_ref(n, T, block):
    g = erdos_renyi(n, 0.08, n * 31 + T)
    masks = jnp.asarray(_random_masks(n, g.W, T, T))
    adj = jnp.asarray(g.adj)
    got = degrees_op(adj, masks, block_tasks=block)
    want = batched_degrees_ref(adj, masks)
    assert (got == want).all()


def test_argmax_composition():
    g = erdos_renyi(96, 0.15, 5)
    masks = jnp.asarray(_random_masks(96, g.W, 10, 3))
    adj = jnp.asarray(g.adj)
    u1, d1 = max_degree_vertex(adj, masks)
    u2, d2 = max_degree_vertex_ref(adj, masks)
    assert (d1 == d2).all()
    # argmax ties may differ only if degrees tie; verify via degree equality
    deg = batched_degrees_ref(adj, masks)
    assert (jnp.take_along_axis(deg, u1[:, None], 1)[:, 0] == d2).all()


@pytest.mark.parametrize(
    "n,T,block", [(32, 4, 2), (64, 16, 8), (100, 7, 4), (257, 9, 8)]
)
def test_fused_expand_stats_matches_ref(n, T, block):
    """The fused kernel's degrees panel AND both popcounts equal the oracle
    (which itself equals what the per-task callables compute)."""
    g = erdos_renyi(n, 0.08, n * 17 + T)
    masks = jnp.asarray(_random_masks(n, g.W, T, T))
    sols = jnp.asarray(_random_masks(n, g.W, T, T + 1)) & ~masks
    adj = jnp.asarray(g.adj)
    deg, pcm, pcs = expand_stats_op(adj, masks, sols, block_tasks=block)
    rdeg, rpcm, rpcs = expand_stats_ref(adj, masks, sols)
    assert (deg == rdeg).all()
    assert (pcm == rpcm).all() and (pcs == rpcs).all()
    # and the oracle's popcounts really are popcounts
    want = [
        sum(bin(int(w)).count("1") for w in row) for row in np.asarray(masks)
    ]
    assert np.asarray(rpcm).tolist() == want


def test_kernel_mode_auto_detection(monkeypatch):
    """interpret-mode resolution: native only on TPU, env override wins."""
    import jax

    import repro.kernels.bitset_ops.ops as ops

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert default_interpret() == (not on_tpu)
    assert kernels_native() == on_tpu
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert not ops.default_interpret() and ops.kernels_native()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.default_interpret() and not ops.kernels_native()
    # empty value == unset (leftover `VAR=` in a shell) -> backend detection;
    # alternate falsy spellings are normalized, not misread as "force on"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "")
    assert ops.default_interpret() == (not on_tpu)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "FALSE")
    assert ops.kernels_native()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "off")
    assert ops.kernels_native()


def test_degrees_op_interpret_default_follows_backend(monkeypatch):
    """degrees_op with interpret unset resolves via default_interpret (and
    still matches the oracle when forced through the kernel)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    g = erdos_renyi(48, 0.1, 9)
    masks = jnp.asarray(_random_masks(48, g.W, 5, 3))
    got = degrees_op(jnp.asarray(g.adj), masks)  # interpret resolved = True
    assert (got == batched_degrees_ref(jnp.asarray(g.adj), masks)).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_random(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 200))
    T = int(rng.integers(2, 20))
    g = erdos_renyi(n, float(rng.uniform(0.02, 0.3)), seed)
    masks = jnp.asarray(_random_masks(n, g.W, T, seed + 1))
    got = degrees_op(jnp.asarray(g.adj), masks)
    want = batched_degrees_ref(jnp.asarray(g.adj), masks)
    assert (got == want).all()
