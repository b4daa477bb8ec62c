"""Task-tree (paper §3.4, Alg. 5-6): caterpillar invariant + priority order."""

import random

from hypothesis import given, settings, strategies as st

from repro.core.task_tree import TaskTree


class T:
    """Identity-keyed payload."""

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"T({self.name})"


def test_register_and_claim():
    tree = TaskTree()
    root = T("root")
    tree.set_root(root)
    kids = [T("a"), T("b")]
    tree.register_child_instances(kids, root)
    assert tree.pending_count() == 2
    assert tree.try_claim(kids[0])
    assert tree.pending_count() == 1
    assert tree.check_caterpillar()


def test_donation_is_shallowest_leftmost():
    tree = TaskTree()
    root = T("root")
    tree.set_root(root)
    a, b = T("a"), T("b")
    tree.register_child_instances([a, b], root)
    tree.try_claim(a)  # explore a; b stays pending at depth 1
    a1, a2 = T("a1"), T("a2")
    tree.register_child_instances([a1, a2], a)  # depth 2
    got = tree.pop_highest_priority()
    assert got is b, "must donate the shallowest pending task"
    got2 = tree.pop_highest_priority()
    assert got2 is a1, "then the leftmost deeper one"


def test_rerooting_past_single_child():
    tree = TaskTree()
    root = T("root")
    tree.set_root(root)
    a = T("a")
    tree.register_child_instances([a], root)
    tree.try_claim(a)
    a1, a2 = T("a1"), T("a2")
    tree.register_child_instances([a1, a2], a)
    # root has a single (exploring) child -> Alg. 6 re-roots to a
    got = tree.pop_highest_priority()
    assert got is a1
    assert tree.root.payload is a


def test_finish_removes_and_empties():
    tree = TaskTree()
    root = T("root")
    tree.set_root(root)
    a, b = T("a"), T("b")
    tree.register_child_instances([a, b], root)
    tree.try_claim(a)
    tree.finish(a)
    assert tree.pop_highest_priority() is b
    tree.finish(root)
    assert tree.is_empty()


def test_register_after_donation_is_ignored():
    """Children of an already-donated task are not tracked (Alg. 5 guard)."""
    tree = TaskTree()
    root = T("root")
    tree.set_root(root)
    a, b = T("a"), T("b")
    tree.register_child_instances([a, b], root)
    donated = tree.pop_highest_priority()
    assert donated is a
    tree.register_child_instances([T("a1")], a)  # parent gone: no-op
    assert tree.pending_count() == 1  # only b


@settings(max_examples=60, deadline=None)
@given(st.lists(st.sampled_from(["branch2", "branch3", "donate", "up"]),
                min_size=1, max_size=120), st.integers(0, 2**31))
def test_caterpillar_invariant_random_walk(ops, seed):
    """Simulated DFS with random donations never violates the caterpillar
    topology and pending counts stay consistent."""
    rng = random.Random(seed)
    tree = TaskTree()
    root = T("root")
    tree.set_root(root)
    stack = [root]
    made = 0
    for op in ops:
        cur = stack[-1]
        if op in ("branch2", "branch3") and len(stack) < 12:
            k = 2 if op == "branch2" else 3
            kids = [T(f"n{made + i}") for i in range(k)]
            made += k
            tree.register_child_instances(kids, cur)
            child = rng.choice(kids)
            if tree.try_claim(child):
                stack.append(child)
        elif op == "donate":
            before = tree.pending_count()
            got = tree.pop_highest_priority()
            assert (got is None) == (before == 0)
            if got is not None:
                assert tree.pending_count() == before - 1
        elif op == "up" and len(stack) > 1:
            done = stack.pop()
            # finishing requires no pending children: donate them all first
            node = tree._index.get(id(done))
            if node is not None:
                while node.children:
                    c = node.children[0]
                    node.children.remove(c)
                    tree._index.pop(id(c.payload), None)
                tree.finish(done)
        assert tree.check_caterpillar()
