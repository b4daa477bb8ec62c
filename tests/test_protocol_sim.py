"""The asynchronous protocol simulator vs the sequential ground truth.

Validates the paper's claims: (a) correct optima under any policy/codec/
latency, (b) ZERO failed work requests (§3.1), (c) safe termination even
with in-flight tasks (§3.3).
"""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.centralized import run_centralized_sim
from repro.core.protocol_sim import run_protocol_sim
from repro.graphs.generators import erdos_renyi, p_hat_like
from repro.problems.sequential import solve_sequential, verify_cover


@pytest.mark.parametrize("policy", ["random", "priority"])
@pytest.mark.parametrize("codec", ["optimized", "basic"])
def test_matches_sequential(policy, codec):
    g = erdos_renyi(36, 0.25, 7)
    want, _, _ = solve_sequential(g)
    res = run_protocol_sim(g, num_workers=5, policy=policy, codec_name=codec)
    assert res.best_size == want
    assert verify_cover(g, res.best_sol)
    assert res.stats.failed_requests == 0


@pytest.mark.parametrize("latency", [1, 2, 5])
def test_latency_exposes_termination_race(latency):
    """Higher latency widens the §3.3 in-flight window; the sent/ack safety
    mechanism must still terminate with the right answer."""
    g = erdos_renyi(32, 0.3, 3)
    want, _, _ = solve_sequential(g)
    res = run_protocol_sim(g, num_workers=6, latency=latency)
    assert res.best_size == want
    assert res.stats.failed_requests == 0


def test_metadata_policy():
    g = erdos_renyi(30, 0.3, 11)
    want, _, _ = solve_sequential(g)
    res = run_protocol_sim(
        g, num_workers=4, policy="priority", send_metadata=True
    )
    assert res.best_size == want


def test_fpt_mode_early_stop():
    g = erdos_renyi(30, 0.25, 5)
    opt, _, _ = solve_sequential(g)
    yes = run_protocol_sim(g, num_workers=4, mode="fpt", k=opt)
    assert yes.best_size != -1 and yes.best_size <= opt
    no = run_protocol_sim(g, num_workers=4, mode="fpt", k=opt - 1)
    assert no.best_size == -1


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 8))
def test_random_graphs_property(seed, workers):
    g = erdos_renyi(26, 0.22, seed)
    want, _, _ = solve_sequential(g)
    res = run_protocol_sim(g, num_workers=workers, seed=seed)
    assert res.best_size == want
    assert res.stats.failed_requests == 0
    if res.best_sol is not None:
        assert verify_cover(g, res.best_sol)


def test_control_plane_smaller_than_centralized():
    """§4.2/§4.3: the semi-centralized scheme moves fewer total bytes; its
    center sees only integers while the centralized center sees every task."""
    g = p_hat_like(40, 0.4, 2)
    semi = run_protocol_sim(g, num_workers=5)
    cent = run_centralized_sim(g, num_workers=5)
    assert semi.best_size == cent.best_size
    assert semi.stats.total_bytes < cent.stats.total_bytes
    # every center-bound message in the semi scheme is a single integer
    assert semi.stats.center_bytes < semi.stats.total_bytes
