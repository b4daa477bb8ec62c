"""Faults planted in the solver, to show that the check catches them.

Each plant is a context manager that breaks the timed path underneath the
harness and restores it on exit.  ``control.py`` reads them at a cell's own
size on the chip; the tests read them on the CPU at a small size.

* ``state_unchanged``: a step returns its state as it got it (the explore
  phase of the superstep does nothing);
* ``half_batch``: half of the batch is left out (each explore step pops
  half of its lanes);
* ``answer_altered``: the answer is altered where it is produced (one
  vertex of the extracted solution flipped);
* ``degrees_off_by_one``: explore's batched degree panel (the Pallas
  kernel on the chip) reads one more than each degree;
* ``reduction_skipped``: explore's reduction rules never fire, so every
  node branches on its unreduced graph.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def state_unchanged():
    from repro.core import superstep

    def explore_nothing(problem, data, state, steps, lanes, explore_impl="reference"):
        return state

    with _patched(superstep, "explore_phase", explore_nothing):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.core import superstep

    pop = superstep.pop_deepest_cheap

    def pop_half(frontier, lanes):
        return pop(frontier, max(1, lanes // 2))

    with _patched(superstep, "pop_deepest_cheap", pop_half):
        yield


@contextlib.contextmanager
def answer_altered():
    from repro.core import engine

    extract = engine._extract_result

    def flipped(*args, **kwargs):
        r = extract(*args, **kwargs)
        if r.best_sol is not None:
            sol = np.array(r.best_sol, dtype=np.uint32)
            sol[0] ^= np.uint32(1)
            r.best_sol = sol
        return r

    with _patched(engine, "_extract_result", flipped):
        yield


@contextlib.contextmanager
def degrees_off_by_one():
    from repro.kernels.bitset_ops import ops

    degrees = ops.degrees_auto

    def plus_one(adj, masks):
        return degrees(adj, masks) + 1

    with _patched(ops, "degrees_auto", plus_one):
        yield


@contextlib.contextmanager
def reduction_skipped():
    from repro.problems import vertex_cover

    def unreduced(problem, mask, sol_mask):
        return mask, sol_mask

    with _patched(vertex_cover, "reduce_instance", unreduced):
        yield


PLANTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "answer_altered": answer_altered,
    "degrees_off_by_one": degrees_off_by_one,
    "reduction_skipped": reduction_skipped,
}
