"""Rule 3 of the vertex-cover reduction, read from packed neighbour rows.

The device reduction finds each row's first neighbour word by word and meets
that neighbour's row of the shared packed adjacency with the row.  The oracle
below is the unpacked formulation it replaced: a per-row n x n boolean
neighbour table.  The reduction must also agree with the host reference
sweep for sweep, and its compiled form must hold no n x n array.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs.bitgraph import BitGraph, mask_full
from repro.graphs.generators import erdos_renyi
from repro.problems import sequential as seq
from repro.problems import vertex_cover as vc


def _oracle_rule3(adj, mask):
    """Unpacked rule 3: (first neighbour, vw_edge) per row from the n x n
    table of ``adj & mask``."""
    n_total = adj.shape[0]
    bits = vc.unpack_bits(adj & mask[None, :], n_total)  # (n, n)
    vidx = jnp.arange(n_total, dtype=jnp.int32)
    first = jnp.where(bits, vidx[None, :], n_total).min(axis=1)
    last = jnp.where(bits, vidx[None, :], -1).max(axis=1)
    fc = jnp.clip(first, 0, n_total - 1)
    lc = jnp.clip(last, 0, n_total - 1)
    return first, bits[fc, lc]


def _random_instance(n, seed):
    """A sparse random graph with planted triangles, and a random mask (pad
    bits of the last word set too), so that rows of degree 0, 1 and 2 occur,
    with and without an edge between their two neighbours."""
    rng = np.random.default_rng([n, seed])
    upper = np.triu(rng.random((n, n)) < min(0.5, 3.0 / n), 1)
    dense = upper | upper.T
    keep = rng.random(n) < 0.6
    for a, b, c in rng.permutation(n)[: 3 * (n // 20)].reshape(-1, 3):
        # a keeps only b and c, and the three stay in the mask
        dense[a, :] = dense[:, a] = False
        dense[a, [b, c]] = dense[[b, c], a] = dense[b, c] = dense[c, b] = True
        keep[[a, b, c]] = True
    g = BitGraph.from_dense(dense)
    mask = np.array(vc.pack_bits(jnp.asarray(keep), g.W))
    rem = n % 32
    if rem:
        mask[-1] |= rng.integers(0, 2**32, dtype=np.uint32) & ~np.uint32((1 << rem) - 1)
    return g, mask


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("n", [1, 5, 31, 32, 33, 64, 70, 450])
def test_packed_rule3_matches_unpacked_table(n, seed):
    g, mask = _random_instance(n, seed)
    adj, m = jnp.asarray(g.adj), jnp.asarray(mask)
    rows = adj & m[None, :]
    first = np.asarray(jax.jit(vc._first_neighbour)(rows))
    edge = np.asarray(jax.jit(vc._ends_adjacent)(adj, rows))
    want_first, want_edge = (np.asarray(x) for x in _oracle_rule3(adj, m))
    np.testing.assert_array_equal(first, want_first)
    deg = np.asarray(vc.degrees(vc.make_problem(adj, n), m))
    two = deg == 2
    np.testing.assert_array_equal(edge[two], want_edge[two])
    if n >= 64:  # the draw reaches every case the rule distinguishes
        assert {0, 1, 2} <= set(deg.tolist())
        assert edge[two].any() and not edge[two].all()


# sparse G(n, p) draws on which rule 3 fires at least once from the full mask
STEP_CASES = [
    (40, 0.08, 0), (40, 0.08, 9), (60, 0.06, 7), (70, 0.07, 8), (100, 0.04, 9), (100, 0.04, 11),
]


@pytest.mark.parametrize("n,p,seed", STEP_CASES)
def test_reduce_steps_match_host_sweeps(n, p, seed):
    """Sweep for sweep, the device reduction fires the host reference's rule
    on its vertex and reaches its masks, bit for bit."""
    g = erdos_renyi(n, p, seed)
    data = vc.make_problem(g.adj, g.n)
    step = jax.jit(lambda m, s: vc._reduce_step(data, m, s))
    m0, s0 = mask_full(g.n), np.zeros(g.W, np.uint32)
    hm, hs, dm, ds = m0, s0, jnp.asarray(m0), jnp.asarray(s0)
    rules = []
    while True:
        hm, hs, hrule = seq.reduce_sweep(g, hm, hs)
        dm, ds, drule = step(dm, ds)
        assert int(drule) == hrule
        np.testing.assert_array_equal(np.asarray(dm), hm)
        np.testing.assert_array_equal(np.asarray(ds), hs)
        if not hrule:
            break
        rules.append(hrule)
    assert 3 in rules
    fm, fs = seq.reduce_instance(g, m0, s0)
    pm, ps = vc.reduce_instance(data, jnp.asarray(m0), jnp.asarray(s0))
    np.testing.assert_array_equal(np.asarray(pm), fm)
    np.testing.assert_array_equal(np.asarray(ps), fs)
    work = vc._reduce_counted(data, jnp.asarray(m0), jnp.asarray(s0))[2]
    assert int(work.sweeps) == len(rules) + 1
    assert np.asarray(work.fires).tolist() == [rules.count(r) for r in (1, 2, 3)]


def test_reduction_builds_no_n_by_n_array():
    """The lane-batched reduction at the benchmark's width (8 lanes, n = 450,
    W = 15) lowers to no array with two dimensions of 450 or more: no per-lane
    neighbour table."""
    L, n, W = 8, 450, 15
    data = vc.make_problem(jnp.zeros((n, W), jnp.uint32), n)
    masks = jax.ShapeDtypeStruct((L, W), jnp.uint32)
    lowered = jax.jit(
        lambda d, m, s: jax.vmap(lambda mm, ss: vc._reduce_counted(d, mm, ss))(m, s)
    ).lower(data, masks, masks)
    text = lowered.as_text(dialect="hlo")
    shapes = re.findall(r"[a-z]+[0-9]*\[([0-9,]+)\]", text)
    assert shapes  # the pattern reads this HLO's shapes
    wide = [s for s in shapes if sum(int(d) >= n for d in s.split(",")) >= 2]
    assert not wide, f"n x n arrays in the reduction: {sorted(set(wide))}"
