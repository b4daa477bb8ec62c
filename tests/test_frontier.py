"""Frontier push/pop properties (hypothesis): never loses or duplicates."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.frontier import (
    make_frontier,
    pop_deepest,
    pop_shallowest,
    push_many,
)

W = 2


def _push(f, depth_vals):
    k = len(depth_vals)
    masks = jnp.tile(jnp.arange(1, k + 1, dtype=jnp.uint32)[:, None], (1, W))
    sols = jnp.zeros((k, W), jnp.uint32)
    depths = jnp.asarray(depth_vals, jnp.int32)
    valid = jnp.ones((k,), bool)
    return push_many(f, masks, sols, depths, valid)


def test_push_pop_deepest():
    f = make_frontier(8, W)
    f = _push(f, [3, 1, 5])
    f, masks, sols, depths, valid = pop_deepest(f, 2)
    assert valid.all()
    assert sorted(np.asarray(depths).tolist()) == [3, 5]
    assert int(f.pending()) == 1


def test_pop_shallowest():
    f = make_frontier(8, W)
    f = _push(f, [3, 1, 5])
    f, m, s, d, valid = pop_shallowest(f)
    assert bool(valid) and int(d) == 1
    assert int(f.pending()) == 2


def test_pop_empty_invalid():
    f = make_frontier(4, W)
    f, m, s, d, valid = pop_shallowest(f)
    assert not bool(valid)
    f, masks, sols, depths, valid = pop_deepest(f, 2)
    assert not bool(valid.any())


def test_overflow_flag():
    f = make_frontier(2, W)
    f = _push(f, [1, 2])
    assert not bool(f.overflow)
    f = _push(f, [3])
    assert bool(f.overflow)
    assert int(f.pending()) == 2  # dropped, not corrupted


def test_overflow_counts_every_dropped_push():
    """Saturation is never silent: ``dropped`` counts the exact number of
    lost tasks, cumulatively across pushes."""
    f = make_frontier(3, W)
    f = _push(f, [1, 2])
    assert int(f.dropped) == 0
    f = _push(f, [5, 6, 7])  # one slot free -> two dropped
    assert int(f.dropped) == 2 and bool(f.overflow)
    f = _push(f, [8])  # full -> one more dropped
    assert int(f.dropped) == 3
    assert int(f.pending()) == 3
    # the survivors are the FIRST valid pushes in order (5 took the slot)
    _, _, _, depths, valid = pop_deepest(f, 3)
    assert valid.all()
    assert sorted(np.asarray(depths).tolist()) == [1, 2, 5]


def test_push_pop_at_exact_capacity():
    """Behavior AT capacity is well-defined: a full frontier accepts zero
    pushes (counted), popping frees slots, and the freed slots take new
    pushes without disturbing survivors."""
    f = make_frontier(2, W)
    f = _push(f, [4, 9])
    assert int(f.pending()) == 2  # full
    f = _push(f, [7])
    assert int(f.dropped) == 1  # rejected at capacity
    f, _, _, d, v = pop_deepest(f, 1)
    assert bool(v.all()) and int(d[0]) == 9
    f = _push(f, [7])  # freed slot accepts again, nothing further dropped
    assert int(f.dropped) == 1 and int(f.pending()) == 2
    _, _, _, depths, valid = pop_deepest(f, 2)
    assert valid.all()
    assert sorted(np.asarray(depths).tolist()) == [4, 7]


@settings(max_examples=50, deadline=None)
@given(
    st.lists(
        st.one_of(
            st.tuples(st.just("push"), st.lists(st.integers(0, 100), min_size=1, max_size=4)),
            st.tuples(st.just("pop_deep"), st.integers(1, 3)),
            st.tuples(st.just("pop_shallow"), st.just(0)),
        ),
        min_size=1,
        max_size=40,
    )
)
def test_multiset_conservation(ops):
    """The frontier behaves as a multiset of depths: pushes add, pops remove
    the correct extremum, nothing is lost while capacity is respected."""
    cap = 32
    f = make_frontier(cap, W)
    model = []  # reference multiset of depths
    for op, arg in ops:
        if op == "push":
            take = arg[: max(0, cap - len(model))]
            f = _push(f, arg)
            model.extend(take)
        elif op == "pop_deep":
            f, _, _, depths, valid = pop_deepest(f, arg)
            got = sorted(
                int(d) for d, v in zip(np.asarray(depths), np.asarray(valid)) if v
            )
            want = sorted(model, reverse=True)[: len(got)]
            assert got == sorted(want)
            for d in got:
                model.remove(d)
        else:
            f, _, _, d, valid = pop_shallowest(f)
            if model:
                assert bool(valid) and int(d) == min(model)
                model.remove(int(d))
            else:
                assert not bool(valid)
        assert int(f.pending()) == len(model)


def test_batched_views_are_per_instance():
    """The instance-axis wrappers act on each stacked frontier independently
    (same results as looping the per-instance ops)."""
    from repro.core.frontier import (
        pending_per_worker,
        pop_deepest_b,
        pop_k_shallowest_b,
        push_many_b,
    )

    f0 = _push(make_frontier(8, W), [3, 1, 5])
    f1 = _push(make_frontier(8, W), [2, 7])
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), f0, f1)
    assert np.asarray(pending_per_worker(stacked)).tolist() == [3, 2]

    s2, masks, sols, depths, valid = pop_deepest_b(stacked, 1)
    assert np.asarray(depths)[:, 0].tolist() == [5, 7]
    assert np.asarray(pending_per_worker(s2)).tolist() == [2, 1]

    s3, _, _, depths, valid = pop_k_shallowest_b(
        stacked, 2, jnp.asarray([2, 1], jnp.int32)
    )
    assert np.asarray(depths)[0].tolist() == [1, 3]
    assert np.asarray(valid).tolist() == [[True, True], [True, False]]

    s4 = push_many_b(
        s3,
        jnp.zeros((2, 1, W), jnp.uint32),
        jnp.zeros((2, 1, W), jnp.uint32),
        jnp.full((2, 1), 9, jnp.int32),
        jnp.asarray([[True], [False]]),
    )
    assert np.asarray(pending_per_worker(s4)).tolist() == [2, 1]
