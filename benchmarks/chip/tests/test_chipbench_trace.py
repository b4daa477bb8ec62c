"""Trace reduction on synthetic traces, the kernels' byte counts, the peak
table, and the plain reference's checks."""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))

from benchmarks.chip import reference, roofline, trace  # noqa: E402


def synthetic() -> trace.Trace:
    # window 0..100 ns on two devices; device 0 busy 10..30 and 50..60
    # (two overlapping ops), device 1 busy 0..40 with one collective
    ops = {
        0: [("%fusion.1", 10, 30), ("%batched_degrees.3", 50, 58), ("%fusion.2", 55, 60)],
        1: [("%fusion.1", 0, 20), ("%all-reduce.7", 20, 40), ("%outside", 150, 160)],
    }
    spans = [
        ("window", 0, 100),
        ("solve_call", 0, 70),
        ("host_sync", 30, 50),
        ("step", 60, 100),
    ]
    return trace.Trace(ops=ops, spans=spans, window=(0, 100))


def test_union_merges_and_clips():
    assert trace.union([(5, 10), (8, 12), (20, 30), (-5, 1)], 0, 25) == [
        (0, 1), (5, 12), (20, 25)
    ]


def test_busy_and_idle_share():
    t = synthetic()
    # device 0: 20 + 10 = 30 ns busy; device 1: 40 ns (the op past the
    # window does not count); mean 35 ns of a 100 ns window
    assert trace.busy_s(t) == pytest.approx(35e-9)
    assert trace.idle_share(t) == pytest.approx(0.65)
    assert t.window_s == pytest.approx(100e-9)


def test_op_time_kernel_and_collectives():
    t = synthetic()
    secs, n = trace.op_time_s(t, roofline.is_expand_kernel)
    assert n == 1 and secs == pytest.approx(8e-9 / 2)
    assert trace.collective_time_s(t) == pytest.approx(20e-9 / 2)


def test_top_ops_and_idle_gaps():
    t = synthetic()
    top = trace.top_ops(t, k=2)
    assert top[0] == ["%fusion.1", pytest.approx(40e-9 / 2)]
    assert top[1][0] == "%all-reduce.7"
    gaps = dict(trace.idle_gaps(t))
    # device 0 idles 0..10 (solve_call), 30..50 (host_sync, innermost),
    # 60..100 (midpoint 80: step)
    assert gaps == {
        "idle: solve_call": pytest.approx(10e-9),
        "idle: host_sync": pytest.approx(20e-9),
        "idle: step": pytest.approx(40e-9),
    }


def test_kernel_byte_counts():
    # 256 tasks, n = 220, W = 7: masks in, adjacency once, degrees out
    assert roofline.degrees_bytes(256, 220, 7) == 4 * (256 * 7 + 7 * 220 + 256 * 220)
    assert roofline.is_degrees_kernel("%batched_degrees.16")
    assert not roofline.is_degrees_kernel("%fusion.3")
    assert roofline.is_expand_kernel("%batched_expand_stats.2")


def test_peaks_known_and_unknown_device():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    share = roofline.hbm_roofline_share(819_000, 2e-6, "TPU v5 lite")
    assert share == pytest.approx(0.5)


def test_reference_checks():
    edges = np.array([[0, 1], [1, 2], [2, 3]])
    cover = reference.unpack(np.array([0b0110], np.uint32), 4)
    assert cover.tolist() == [False, True, True, False]
    assert reference.pack(cover, 1).tolist() == [0b0110]
    assert reference.uncovered_edges(edges, cover) == 0
    assert reference.uncovered_edges(edges, ~cover) == 1
    assert reference.min_vertex_cover(4, edges) == 2
    adj = reference.dense(4, edges)
    everything = np.ones((1, 4), bool)
    assert reference.degrees(adj, everything).tolist() == [[1, 2, 2, 1]]
    assert reference.degrees(adj, ~everything).tolist() == [[-1, -1, -1, -1]]
    # a path: rule 2 covers vertex 1 (the neighbour of the degree-1 vertex
    # 0), then vertex 3 (the neighbour of vertex 2): a terminal of size 2
    ex = reference.expand(adj, everything[0], ~everything[0])
    assert ex["terminal"] and ex["sol"].tolist() == [False, True, False, True]
    assert ex["bound"] == 2 and ex["fired"][1] == 2
    # sound tasks: (remaining graph, partial cover); unsound: an edge lost,
    # or the cover meeting the remaining graph
    masks = np.array([[1, 1, 1, 1], [0, 0, 1, 1], [0, 0, 1, 1], [1, 1, 1, 1]], bool)
    sols = np.array([[0, 0, 0, 0], [0, 1, 0, 0], [1, 0, 0, 0], [0, 1, 0, 0]], bool)
    assert reference.bad_tasks(adj, masks[:2], sols[:2]) == 0
    assert reference.bad_tasks(adj, masks, sols) == 2
