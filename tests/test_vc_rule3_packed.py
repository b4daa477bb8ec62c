"""Rule 3 of the vertex-cover reduction, read from packed neighbour rows.

The device reduction finds each row's first neighbour word by word and meets
that neighbour's row of the shared packed adjacency with the row.  The oracle
below is the unpacked formulation it replaced: a per-row n x n boolean
neighbour table.  The reduction probes only the degree-2 candidates, a chunk
at a time; it must agree with the host reference sweep for sweep, also where
a sweep needs more than one chunk, count the candidates it examined as a
host loop does, and its compiled form must hold no n x n array and look up
no row of every vertex.
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


def test_reduction_looks_up_no_row_of_every_vertex():
    """No gather of the lane-batched reduction (8 lanes, n = 450, W = 15)
    outputs L x n rows of W words, in any shape: rule 3 looks up the rows
    of its candidates only, not all n rows of every lane in every sweep."""
    L, n, W = 8, 450, 15
    data = vc.make_problem(jnp.zeros((n, W), jnp.uint32), n)
    masks = jax.ShapeDtypeStruct((L, W), jnp.uint32)
    text = jax.jit(
        lambda d, m, s: jax.vmap(lambda mm, ss: vc._reduce_counted(d, mm, ss))(m, s)
    ).lower(data, masks, masks).as_text(dialect="hlo")
    gathers = re.findall(r"= [a-z]+[0-9]*\[([0-9,]+)\]\S* gather\(", text)
    assert any(g.endswith(f",{W}") for g in gathers)  # the row lookups are read
    rows = [
        g for g in gathers
        if g.endswith(f",{W}") and np.prod([int(d) for d in g.split(",")[:-1]]) >= L * n
    ]
    assert not rows, f"row lookups of every vertex: {sorted(set(rows))}"


def test_sweep_scope_runs_once_a_sweep():
    """No operation under the ``sweep`` scope sits in a loop nested in the
    sweep loop (the device trace counts sweeps by how often that code ran);
    rule 3's further chunks run under a scope of their own."""
    L, n, W = 8, 70, 3
    data = vc.make_problem(jnp.zeros((n, W), jnp.uint32), n)
    masks = jax.ShapeDtypeStruct((L, W), jnp.uint32)
    text = jax.jit(
        lambda d, m, s: jax.vmap(lambda mm, ss: vc._reduce_counted(d, mm, ss))(m, s)
    ).lower(data, masks, masks).compile().as_text()
    paths = [p.split("/") for p in set(re.findall(r'op_name="([^"]*)"', text))]
    swept = [p for p in paths if "sweep" in p]
    assert swept and any("rule3_chunks" in p for p in paths)
    nested = ["/".join(p) for p in swept if "while" in p[p.index("sweep"):]]
    assert not nested, nested


# -- rule 3's candidate probe: chunks, order and the probe counter -------------

K = vc.RULE3_CHUNK


def _graph(cycle, clique_tip=False, triangle=False, n_pad=0):
    """Vertices 0..cycle-1 form a cycle (degree 2, ends not adjacent); then,
    with ``clique_tip``, a K4 on the next four vertices and one vertex
    adjacent to two of them (the only degree-2 vertex whose ends are
    adjacent); or, with ``triangle``, a triangle on the highest three; then
    ``n_pad`` isolated vertices."""
    n = cycle + 5 * clique_tip + 3 * triangle + n_pad
    dense = np.zeros((n, n), bool)
    for i in range(cycle):
        j = (i + 1) % cycle
        dense[i, j] = dense[j, i] = True
    if clique_tip:
        c = cycle
        dense[c:c + 4, c:c + 4] = True
        dense[c + 4, [c, c + 1]] = dense[[c, c + 1], c + 4] = True
    if triangle:
        t = cycle + 5 * clique_tip
        dense[t:t + 3, t:t + 3] = True
    return BitGraph.from_dense(dense)


def _host_probes(g, mask):
    """The degree-2 candidates rule 3 examines in a sweep of the host
    reference: none where rule 1 or 2 fires; else, in index order, those up
    to and including the first whose two neighbours are adjacent, or all."""
    deg = g.degrees(mask)
    inside = deg >= 0
    if (inside & (deg <= 1)).any():
        return 0
    twos = np.nonzero(inside & (deg == 2))[0]
    for i, u in enumerate(twos):
        v, w = np.nonzero(vc.unpack_bits(jnp.asarray(g.adj[u] & mask), g.n))[0]
        if g.adj[v][w // 32] >> np.uint32(w % 32) & 1:
            return i + 1
    return len(twos)


def _match_host_sweeps(g, m0, s0=None):
    """Sweep for sweep, the device step fires the host reference's rule on
    its vertex and reaches its masks; the counted reduction's sweeps, fires
    and probes equal the host loop's.  Returns the rules and probes."""
    s0 = np.zeros(g.W, np.uint32) if s0 is None else s0
    data = vc.make_problem(g.adj, g.n)
    step = jax.jit(lambda m, s: vc._reduce_step(data, m, s))
    hm, hs, dm, ds = m0, s0, jnp.asarray(m0), jnp.asarray(s0)
    rules, probes = [], []
    while True:
        probes.append(_host_probes(g, hm))
        hm, hs, hrule = seq.reduce_sweep(g, hm, hs)
        dm, ds, drule = step(dm, ds)
        assert int(drule) == hrule
        np.testing.assert_array_equal(np.asarray(dm), hm)
        np.testing.assert_array_equal(np.asarray(ds), hs)
        rules.append(hrule)
        if not hrule:
            break
    pm, ps, work = vc._reduce_counted(data, jnp.asarray(m0), jnp.asarray(s0))
    np.testing.assert_array_equal(np.asarray(pm), hm)
    np.testing.assert_array_equal(np.asarray(ps), hs)
    assert int(work.sweeps) == len(rules)
    assert np.asarray(work.fires).tolist() == [rules.count(r) for r in (1, 2, 3)]
    assert int(work.rule3_probes) == sum(probes)
    return rules, probes


@pytest.mark.parametrize("triangle", [True, False])
def test_rule3_probes_past_two_chunks(triangle):
    """A cycle of 2K + 5 vertices holds the lowest candidates, none of which
    qualifies; a triangle at the highest indices (or none) is reached only
    in the third chunk."""
    cycle = 2 * K + 5
    g = _graph(cycle, triangle=triangle)
    rules, probes = _match_host_sweeps(g, mask_full(g.n))
    if triangle:
        assert rules == [3, 0] and probes == [cycle + 1, cycle]
    else:
        assert rules == [0] and probes == [cycle]


@pytest.mark.parametrize("hit", [True, False])
@pytest.mark.parametrize("cands", [K, K + 1])
def test_rule3_probes_exactly_one_chunk_and_one_more(cands, hit):
    """K and K + 1 degree-2 candidates, the only qualifying one (if any)
    the last: the probe stops at the chunk's end or takes one more."""
    g = _graph(cands - 1 if hit else cands, clique_tip=hit)
    rules, probes = _match_host_sweeps(g, mask_full(g.n))
    assert probes[0] == cands
    assert rules[0] == (3 if hit else 0)


@pytest.mark.parametrize("n,p,seed", STEP_CASES)
def test_rule3_probes_match_a_host_loop(n, p, seed):
    rules, probes = _match_host_sweeps(erdos_renyi(n, p, seed), mask_full(n))
    assert 3 in rules and sum(probes) > rules.count(3)


def test_no_degree_two_vertex_no_probe():
    g = erdos_renyi(40, 0.5, 0)
    assert (g.degrees(mask_full(g.n)) > 2).all()
    rules, probes = _match_host_sweeps(g, mask_full(g.n))
    assert rules == [0] and probes == [0]


def test_a_lane_done_or_fired_offers_no_candidate():
    g = _graph(2 * K + 5, triangle=True)
    data = vc.make_problem(g.adj, g.n)
    m, s = jnp.asarray(mask_full(g.n)), jnp.zeros(g.W, jnp.uint32)
    assert int(vc._sweep(data, m, s, True)[3]) == 2 * K + 6
    assert int(vc._sweep(data, m, s, False)[3]) == 0
    # cut one edge of the cycle: rule 2 fires, and rule 3 examines nothing
    dense = np.array(vc.unpack_bits(jnp.asarray(g.adj), g.n))
    dense[0, 1] = dense[1, 0] = False
    cut = vc.make_problem(BitGraph.from_dense(dense).adj, g.n)
    assert [int(x) for x in vc._sweep(cut, m, s, True)[2:]] == [2, 0]


def test_lanes_in_lockstep_match_the_host_lane_by_lane():
    """Lanes of one batch that need one chunk, three chunks or none, and
    that finish at different sweeps, each reduce as the host does."""
    g = _graph(2 * K + 5, clique_tip=True, triangle=True)
    data = vc.make_problem(g.adj, g.n)
    rng = np.random.default_rng(3)
    keep = [np.ones(g.n, bool), np.arange(g.n) >= 2 * K + 5, np.arange(g.n) < 2 * K + 5]
    keep += [rng.random(g.n) < 0.8 for _ in range(5)]
    masks = np.stack([np.asarray(vc.pack_bits(jnp.asarray(k), g.W)) for k in keep])
    sols = np.zeros_like(masks)
    rm, rs, work = jax.jit(
        lambda m, s: jax.vmap(lambda mm, ss: vc._reduce_counted(data, mm, ss))(m, s)
    )(jnp.asarray(masks), jnp.asarray(sols))
    for i in range(len(keep)):
        hm, hs = seq.reduce_instance(g, masks[i], sols[i])
        np.testing.assert_array_equal(np.asarray(rm[i]), hm)
        np.testing.assert_array_equal(np.asarray(rs[i]), hs)
        _, probes = _match_host_sweeps(g, masks[i])
        assert int(work.rule3_probes[i]) == sum(probes)
