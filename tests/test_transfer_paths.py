"""Property tests for the sparse data plane and multi-task donation.

* gather and sparse transfer implementations must produce IDENTICAL
  ``WorkerState`` pytrees for arbitrary frontiers (the only permitted
  difference is the payload accounting, which is the point of the A/B);
* ``pop_k_shallowest`` conserves tasks: popped + remaining == before, and
  the popped ones are exactly the shallowest.
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.frontier import make_frontier, pop_k_shallowest, push_many
from repro.core.superstep import build_superstep_fn, make_worker_state
from repro.graphs.bitgraph import n_words
from repro.graphs.generators import erdos_renyi
from repro.problems.base import make_data
from repro.problems.registry import get_problem

VC = get_problem("vertex_cover")

N = 32
W = n_words(N)
P = 6
CAP = 24


def _random_state(seed: int):
    """A (P, ...) stacked WorkerState with a random plausible frontier:
    random subsets of vertices as masks, disjoint partial solutions, random
    depths, a random subset of slots active (some workers possibly idle)."""
    rng = np.random.default_rng(seed)
    state = jax.vmap(lambda _: make_worker_state(CAP, W, N + 1))(jnp.arange(P))
    masks = rng.integers(0, 2**32, size=(P, CAP, W), dtype=np.uint32)
    sols = rng.integers(0, 2**32, size=(P, CAP, W), dtype=np.uint32)
    rem = N % 32
    if rem:
        masks[..., -1] &= np.uint32((1 << rem) - 1)
        sols[..., -1] &= np.uint32((1 << rem) - 1)
    sols &= ~masks  # a vertex is either open or already in the cover
    depths = rng.integers(0, 20, size=(P, CAP)).astype(np.int32)
    active = rng.random((P, CAP)) < rng.random((P, 1))  # skewed per worker
    return state._replace(
        frontier=state.frontier._replace(
            masks=jnp.asarray(masks),
            sols=jnp.asarray(sols),
            depths=jnp.asarray(depths),
            active=jnp.asarray(active),
        )
    )


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4))
def test_gather_and_sparse_paths_identical(seed, donate_k):
    g = erdos_renyi(N, 0.2, seed % 17)
    data = make_data(VC, g)
    state = _random_state(seed)
    fns = {
        impl: build_superstep_fn(
            VC,
            data,
            num_workers=P,
            steps_per_round=2,
            lanes=1,
            transfer_impl=impl,
            donate_k=donate_k,
        )
        for impl in ("gather", "sparse")
    }
    sg, dg = fns["gather"](state)
    ss, ds = fns["sparse"](state)
    assert bool(dg) == bool(ds)
    for name in sg._fields:
        if name == "payload_words":
            continue  # accounting differs by design (that's the A/B)
        ga, sa = getattr(sg, name), getattr(ss, name)
        for leaf_g, leaf_s in zip(jax.tree.leaves(ga), jax.tree.leaves(sa)):
            assert (np.asarray(leaf_g) == np.asarray(leaf_s)).all(), name
    # sparse payload never exceeds gather payload
    assert int(np.asarray(ss.payload_words)[0]) <= int(
        np.asarray(sg.payload_words)[0]
    )


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.integers(0, 50), min_size=0, max_size=CAP),
    st.integers(1, 5),
    st.integers(0, 5),
)
def test_pop_k_shallowest_conserves_tasks(depth_vals, k, limit):
    f = make_frontier(CAP, W)
    if depth_vals:
        kk = len(depth_vals)
        masks = jnp.tile(
            jnp.arange(1, kk + 1, dtype=jnp.uint32)[:, None], (1, W)
        )
        f = push_many(
            f,
            masks,
            jnp.zeros((kk, W), jnp.uint32),
            jnp.asarray(depth_vals, jnp.int32),
            jnp.ones((kk,), bool),
        )
    before = int(f.pending())
    f2, masks, sols, depths, valid = pop_k_shallowest(
        f, k, limit=jnp.int32(limit)
    )
    popped = int(np.asarray(valid).sum())
    # conservation: popped + remaining == before
    assert popped + int(f2.pending()) == before
    # the cap honors both the static k and the dynamic limit
    assert popped == min(k, limit, before)
    # the popped ones are exactly the shallowest, shallowest-first
    got = [int(d) for d, v in zip(np.asarray(depths), np.asarray(valid)) if v]
    assert got == sorted(depth_vals)[:popped]
    # remaining multiset is the complement
    rest = sorted(
        int(d)
        for d, a in zip(np.asarray(f2.depths), np.asarray(f2.active))
        if a
    )
    assert rest == sorted(sorted(depth_vals)[popped:])


def test_pop_k_shallowest_no_limit_matches_k():
    f = make_frontier(8, W)
    f = push_many(
        f,
        jnp.ones((3, W), jnp.uint32),
        jnp.zeros((3, W), jnp.uint32),
        jnp.asarray([5, 1, 3], jnp.int32),
        jnp.ones((3,), bool),
    )
    f2, _, _, depths, valid = pop_k_shallowest(f, 2)
    assert [int(d) for d, v in zip(np.asarray(depths), np.asarray(valid)) if v] == [1, 3]
    assert int(f2.pending()) == 1
