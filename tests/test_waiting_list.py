"""Algorithm 7 (equitable-startup waiting lists): exactness + properties."""

from hypothesis import given, settings, strategies as st

from repro.core.waiting_list import (
    build_waiting_lists,
    max_startup_depth,
    startup_assignment,
)


def test_paper_example_binary():
    """max_b=2, p=8: process 1 feeds 2 (d=0), 3 (d=1), 5 (d=2); process 3
    feeds 7 (q = 1·2^2 + 3); etc — the q = j·b^d + p_i formula verbatim."""
    lists = build_waiting_lists(2, 8)
    assert lists[1] == [2, 3, 5]
    assert lists[2] == [4, 6]
    assert lists[3] == [7]
    assert lists[4] == [8]
    assert lists[5] == []


def test_figure3_ternary():
    """Fig. 3 (max_b=3): p1 sends to p2, p3, p4, ..., in that order."""
    lists = build_waiting_lists(3, 9)
    assert lists[1][:2] == [2, 3]  # j=1,2 at d=0
    assert 4 in lists[1]  # j=1 at d=1: 1·3+1
    assert 7 in lists[1]  # j=2 at d=1: 2·3+1
    assert lists[1] == [2, 3, 4, 7]


@settings(max_examples=60, deadline=None)
@given(st.integers(2, 5), st.integers(1, 300))
def test_every_process_assigned_exactly_once(max_b, p):
    lists = build_waiting_lists(max_b, p)
    assigned = [q for lst in lists.values() for q in lst]
    # every process except the seed (1) appears exactly once
    assert sorted(assigned + [1]) == list(range(1, p + 1))


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 4), st.integers(1, 200))
def test_startup_assignment_is_permutation(max_b, p):
    order = startup_assignment(max_b, p)
    assert sorted(order) == list(range(1, p + 1))
    assert order[0] == 1  # the seed holder leads


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 5), st.integers(2, 300))
def test_assigner_index_below_assignee(max_b, p):
    """Tasks flow 'downhill': q = j·b^d + p_i > p_i always."""
    lists = build_waiting_lists(max_b, p)
    for pi, lst in lists.items():
        for q in lst:
            assert q > pi


def test_max_depth():
    assert max_startup_depth(2, 1) == -1
    assert max_startup_depth(2, 8) == 3
    assert max_startup_depth(3, 9) == 2
