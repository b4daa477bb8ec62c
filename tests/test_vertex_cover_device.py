"""Device-side (jnp) vertex-cover ops vs the host reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphs.bitgraph import mask_full, popcount_rows
from repro.graphs.generators import erdos_renyi
from repro.problems import sequential as seq
from repro.problems import vertex_cover as vc


@pytest.mark.parametrize("seed", range(4))
def test_degrees_match_host(seed):
    g = erdos_renyi(40, 0.2, seed)
    prob = vc.make_problem(jnp.asarray(g.adj), g.n)
    rng = np.random.default_rng(seed)
    mask = rng.integers(0, 2**32, g.W, dtype=np.uint32)
    rem = g.n % 32
    if rem:
        mask[-1] &= np.uint32((1 << rem) - 1)
    got = np.asarray(vc.degrees(prob, jnp.asarray(mask)))
    want = g.degrees(mask)
    assert (got == want).all()


@pytest.mark.parametrize("seed", range(4))
def test_reduce_instance_equivalent(seed):
    """Device and host reductions may pick different (equally valid) vertices
    but must produce covers of identical size on terminal instances and keep
    the invariant sol ∪ optimal(remaining) optimal."""
    g = erdos_renyi(30, 0.12, seed)  # sparse: reductions dominate
    prob = vc.make_problem(jnp.asarray(g.adj), g.n)
    m0 = jnp.asarray(mask_full(g.n))
    s0 = jnp.zeros(g.W, jnp.uint32)
    dm, ds = vc.reduce_instance(prob, m0, s0)
    hm, hs = seq.reduce_instance(g, mask_full(g.n), np.zeros(g.W, np.uint32))
    assert int(vc.popcount(ds)) == int(popcount_rows(hs))


def test_branch_once_terminal_detection():
    g = erdos_renyi(20, 0.3, 1)
    prob = vc.make_problem(jnp.asarray(g.adj), g.n)
    res = vc.branch_once(prob, jnp.asarray(mask_full(g.n)), jnp.zeros(g.W, jnp.uint32))
    # full graph with edges is never terminal
    assert not bool(res.is_terminal)
    # empty instance is
    res2 = vc.branch_once(prob, jnp.zeros(g.W, jnp.uint32), jnp.zeros(g.W, jnp.uint32))
    assert bool(res2.is_terminal)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 100_000))
def test_pack_unpack_roundtrip(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 70))
    W = (n + 31) // 32
    bits = rng.random(n) < 0.5
    packed = vc.pack_bits(jnp.asarray(bits), W)
    assert (np.asarray(vc.unpack_bits(packed, n)) == bits).all()


def test_verify_cover_device():
    g = erdos_renyi(24, 0.3, 2)
    best, sol, _ = seq.solve_sequential(g)
    assert bool(vc.verify_cover(jnp.asarray(g.adj), jnp.asarray(sol), g.n))
    # removing a used vertex breaks it (unless size-0 cover)
    used = np.flatnonzero(np.asarray(vc.unpack_bits(jnp.asarray(sol), g.n)))
    if len(used):
        broken = np.array(sol)
        v = int(used[0])
        broken[v // 32] &= ~np.uint32(1 << (v % 32))
        assert not bool(vc.verify_cover(jnp.asarray(g.adj), jnp.asarray(broken), g.n))
