"""Self-healing solve plane (``repro.faults``): the recovery contracts.

The fault machinery's promise is that a deterministic fault schedule is
*observable only in the ledgers*: every injected fault is recovered, no
task is lost, and the incumbent/witness the solve lands on is bit-identical
to the fault-free run.  Grouped by tier:

1. **Plans** — seeded schedules are reproducible and JSON round-trip.
2. **Checksums** — every single-bit flip of a checked task record is
   caught (property-tested over flip positions).
3. **Checkpoint I/O retry** — bounded exponential backoff with injectable
   sleep/rng; the injector's io_hook drives the store's retry loop to a
   clean write and books the recovery.
4. **Generation retention** — a corrupted newest generation falls back to
   the retained older one with a loud warning; all-corrupt still raises.
5. **Crash anywhere** — a lane/worker crash at ANY chunk boundary leaves
   solo / fpt / solve_many / service results bit-identical (re-admission
   from tracked placement is a true replay).
6. **Cold-tier corruption** — the spill pump conserves the task multiset
   exactly under injected payload corruption (PR-9's no-drop claim holds
   under faults, not just under pressure).
7. **Quarantine + degradation** — crashed lanes are quarantined, their
   requests re-admitted, and the shed/heal accounting surfaces in stats.
8. **Timeouts** — ``request_timeout_s`` turns a hung request (queued or
   on-lane) into a typed :class:`SolveTimeout`; an awaited async solve can
   never hang.
"""

import asyncio
import json
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import PlaneCache, SolveConfig, SolverSession, SolveTimeout
from repro.api.service import AsyncSolveService
from repro.checkpoint.solve import SolveCheckpoint
from repro.checkpoint.store import (
    RetryPolicy,
    call_with_retry,
    latest_step,
    save_checkpoint,
)
from repro.core.encoding import (
    PayloadCorruptionError,
    checked_record,
    make_codec,
    strip_record,
    verify_record,
)
from repro.core.spill import FrontierSpiller
from repro.faults import FAULT_KINDS, FaultEvent, FaultInjector, FaultPlan
from repro.graphs.generators import erdos_renyi
from repro.problems.registry import get_problem

# one warm plane cache for the whole module: property examples re-solve the
# same shapes many times and must not recompile each time
_CACHE = PlaneCache()
_BASELINES: dict = {}  # (case, problem) -> the fault-free run
PROBLEMS = ["vertex_cover", "max_clique", "mis"]


def _clock():
    class FakeClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    return FakeClock()


# -- 1. plans ------------------------------------------------------------------


def test_fault_plan_random_is_seed_deterministic():
    a = FaultPlan.random(7, n_events=12, lanes=4)
    b = FaultPlan.random(7, n_events=12, lanes=4)
    assert a == b and len(a.events) == 12
    assert FaultPlan.random(8, n_events=12, lanes=4) != a
    assert sum(a.counts().values()) == 12
    for ev in a.events:
        assert ev.kind in FAULT_KINDS
        if ev.kind == "io_error":
            assert ev.op in ("write", "read")


def test_fault_plan_json_roundtrip_and_sort():
    plan = FaultPlan(
        seed=3,
        events=(
            FaultEvent("io_error", at=5, op="read"),
            FaultEvent("crash", at=1, lane=2),
            FaultEvent("stall", at=1, lane=0, duration=3),
        ),
    )
    # events normalize to (at, kind, lane) order regardless of input order
    assert [e.kind for e in plan.events] == ["crash", "stall", "io_error"]
    back = FaultPlan.from_dict(json.loads(json.dumps(plan.to_dict())))
    assert back == plan


def test_fault_event_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultEvent("meteor", at=0)
    with pytest.raises(ValueError, match="bad fault event"):
        FaultEvent("crash", at=-1)
    with pytest.raises(ValueError, match="bad fault event"):
        FaultEvent("stall", at=0, duration=0)
    with pytest.raises(ValueError, match="io op"):
        FaultEvent("io_error", at=0, op="fsync")


# -- 2. checksums --------------------------------------------------------------


def test_checked_record_roundtrip():
    rec = (np.arange(17, dtype=np.uint64) * 2654435761 % (1 << 32)).astype(
        np.uint32
    )
    ck = checked_record(rec)
    assert ck.size == rec.size + 1
    assert verify_record(ck)
    assert (strip_record(ck) == rec).all()


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 31))
def test_any_single_bit_flip_is_caught(pos, bit):
    """CRC32 detects EVERY single-bit error — including one in the checksum
    word itself — so one redelivery from the intact source always heals a
    transfer/cold corruption."""
    rec = (np.arange(9, dtype=np.uint64) * 2654435761 % (1 << 32)).astype(
        np.uint32
    )
    ck = checked_record(rec)
    bad = ck.copy()
    i = pos % bad.size
    bad[i] = np.uint32(int(bad[i]) ^ (1 << bit))
    assert not verify_record(bad)
    with pytest.raises(PayloadCorruptionError):
        strip_record(bad)


# -- 3. retry/backoff ----------------------------------------------------------


def test_call_with_retry_backs_off_exponentially():
    sleeps = []
    policy = RetryPolicy(
        max_attempts=4, base_s=0.05, sleep=sleeps.append
    )
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    with pytest.warns(RuntimeWarning, match="retrying"):
        assert call_with_retry(flaky, policy, what="unit I/O") == "ok"
    assert len(calls) == 3 and policy.retries == 2
    # exponential with multiplicative jitter in [1, 1.25]: the second delay
    # is 2x the base of the first, so their ratio stays in [2/1.25, 2*1.25]
    assert len(sleeps) == 2
    assert 0.05 <= sleeps[0] <= 0.05 * 1.25
    assert 2 / 1.25 <= sleeps[1] / sleeps[0] <= 2 * 1.25


def test_call_with_retry_exhausts_and_raises():
    policy = RetryPolicy(max_attempts=3, sleep=lambda s: None)

    def broken():
        raise OSError("permanent")

    with pytest.warns(RuntimeWarning):
        with pytest.raises(OSError, match="permanent"):
            call_with_retry(broken, policy)
    assert policy.retries == 2  # attempts beyond the first, all wasted


def test_call_with_retry_passes_corruption_through():
    """Only ``retry_on`` (I/O flakes) retries — corrupt CONTENT is not a
    flake and must fall through to the generation-fallback path at once."""
    policy = RetryPolicy(max_attempts=5, sleep=lambda s: None)
    calls = []

    def corrupt():
        calls.append(1)
        raise ValueError("checksum mismatch")

    with pytest.raises(ValueError):
        call_with_retry(corrupt, policy)
    assert len(calls) == 1 and policy.retries == 0


def test_injector_io_hook_drives_store_retry(tmp_path):
    """An injected write fault makes the first attempt raise; the store's
    backoff loop re-enters (virtual sleep, no waiting), the second attempt
    lands, and the injector books injected == recovered plus the retry."""
    inj = FaultInjector(
        FaultPlan(seed=0, events=(FaultEvent("io_error", at=0, op="write"),))
    )
    tree = {"x": np.arange(6, dtype=np.int32)}
    with pytest.warns(RuntimeWarning, match="checkpoint write"):
        save_checkpoint(
            str(tmp_path), 0, tree,
            retry=inj.retry_policy(), fault_hook=inj.io_hook,
        )
    assert latest_step(str(tmp_path)) == 0
    assert inj.injected["io_error"] == 1
    assert inj.recovered["io_error"] == 1
    assert inj.retries == 1
    assert inj.clock_s > 0  # backoff elapsed on the VIRTUAL clock only
    assert inj.report()["pending"] == 0


# -- 4. generation retention + corruption fallback -----------------------------


def _corrupt(step_dir) -> None:
    p = step_dir / "arrays.npz"
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    p.write_bytes(bytes(raw))


def test_corrupt_generation_falls_back_to_older(tmp_path):
    g = erdos_renyi(24, 0.3, 2)
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, checkpoint_every=1
    )
    sess = SolverSession("vertex_cover", config=cfg, cache=_CACHE)
    base = sess.solve(g)
    sess.solve(g, checkpoint_dir=str(tmp_path))
    steps = sorted(
        int(p.name.split("_")[1])
        for p in tmp_path.iterdir()
        if p.name.startswith("step_") and not p.name.endswith(".prev")
    )
    assert len(steps) >= 2

    # newest generation corrupt: resume warns LOUDLY and replays from the
    # older one — landing on the same answer
    _corrupt(tmp_path / f"step_{steps[-1]}")
    with pytest.warns(RuntimeWarning, match="OLDER checkpoint generation"):
        res = SolverSession.resume(str(tmp_path), cache=_CACHE)
    assert res.best_size == base.best_size
    assert (np.asarray(res.best_sol) == np.asarray(base.best_sol)).all()

    # every generation corrupt: fail loudly, not silently from scratch
    for s in steps:
        _corrupt(tmp_path / f"step_{s}")
    with pytest.raises(Exception, match="corrupt|checksum"):
        SolveCheckpoint.load_latest_good(str(tmp_path))


# -- 5. crash anywhere ---------------------------------------------------------


def _solo_case(problem):
    if ("solo", problem) not in _BASELINES:
        g = erdos_renyi(30, 0.3, 5)
        cfg = SolveConfig(num_workers=4, steps_per_round=2, chunk_rounds=1)
        sess = SolverSession(problem, config=cfg, cache=_CACHE)
        _BASELINES["solo", problem] = (g, sess, sess.solve(g))
    return _BASELINES["solo", problem]


@pytest.mark.parametrize("problem", PROBLEMS)
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 12))
def test_solo_crash_at_any_boundary_is_bit_identical(problem, boundary):
    g, sess, base = _solo_case(problem)
    inj = FaultInjector(
        FaultPlan(seed=0, events=(FaultEvent("crash", at=boundary),))
    )
    r = sess.solve(g, injector=inj)
    assert r.best_size == base.best_size
    assert (np.asarray(r.best_sol) == np.asarray(base.best_sol)).all()
    assert r.rounds == base.rounds
    assert r.stats.overflow_count == 0
    # fired -> recovered; scheduled past the end -> never fired: either way
    # nothing is left half-injected
    assert inj.injected["crash"] == inj.recovered["crash"]


@pytest.mark.parametrize("problem", PROBLEMS)
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10))
def test_fpt_crash_keeps_the_witness(problem, boundary):
    if ("fpt", problem) not in _BASELINES:
        g = erdos_renyi(26, 0.3, 4)
        k = get_problem(problem).sequential(g)[0]
        cfg = SolveConfig(
            num_workers=4, steps_per_round=2, chunk_rounds=1, mode="fpt", k=k
        )
        sess = SolverSession(problem, config=cfg, cache=_CACHE)
        _BASELINES["fpt", problem] = (g, sess, sess.solve(g))
    g, sess, base = _BASELINES["fpt", problem]
    inj = FaultInjector(
        FaultPlan(seed=0, events=(FaultEvent("crash", at=boundary),))
    )
    r = sess.solve(g, injector=inj)
    assert (r.found, r.best_size) == (base.found, base.best_size)
    assert (np.asarray(r.best_sol) == np.asarray(base.best_sol)).all()


@pytest.mark.parametrize("problem", PROBLEMS)
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 8), st.integers(0, 3))
def test_solve_many_crash_at_any_boundary_is_bit_identical(
    problem, boundary, lane
):
    if ("many", problem) not in _BASELINES:
        gs = [erdos_renyi(26, 0.3, 20 + i) for i in range(2)]
        cfg = SolveConfig(num_workers=4, steps_per_round=2, chunk_rounds=1)
        sess = SolverSession(problem, config=cfg, cache=_CACHE)
        _BASELINES["many", problem] = (gs, sess, sess.solve_many(gs))
    gs, sess, base = _BASELINES["many", problem]
    inj = FaultInjector(
        FaultPlan(
            seed=0, events=(FaultEvent("crash", at=boundary, lane=lane),)
        )
    )
    out = sess.solve_many(gs, injector=inj)
    for got, want in zip(out.results, base.results):
        assert got.best_size == want.best_size
        assert (
            np.asarray(got.best_sol) == np.asarray(want.best_sol)
        ).all()
        assert got.stats.overflow_count == 0
    assert inj.injected["crash"] == inj.recovered["crash"]


@pytest.mark.parametrize("problem", PROBLEMS)
@settings(max_examples=4, deadline=None)
@given(st.integers(0, 8), st.integers(0, 3))
def test_service_crash_at_any_boundary_is_bit_identical(
    problem, boundary, lane
):
    if ("service", problem) not in _BASELINES:
        gs = [erdos_renyi(26, 0.3, 30 + i) for i in range(3)]
        cfg = SolveConfig(
            num_workers=4, steps_per_round=2, chunk_rounds=1,
            service_lanes=2,
        )
        sess = SolverSession(problem, config=cfg, cache=_CACHE)
        svc = sess.serve()
        tix = [svc.submit(g) for g in gs]
        svc.drain()
        _BASELINES["service", problem] = (
            gs, sess, {i: svc.result(t) for i, t in enumerate(tix)}
        )
    gs, sess, want = _BASELINES["service", problem]
    inj = FaultInjector(
        FaultPlan(
            seed=0, events=(FaultEvent("crash", at=boundary, lane=lane),)
        )
    )
    svc = sess.serve(injector=inj)
    tix = [svc.submit(g) for g in gs]
    svc.drain()
    for i, t in enumerate(tix):
        got = svc.result(t)
        assert got.best_size == want[i].best_size
        assert (
            np.asarray(got.best_sol) == np.asarray(want[i].best_sol)
        ).all()
    assert inj.injected["crash"] == inj.recovered["crash"]
    s = svc.stats()
    assert s["lanes_quarantined"] == inj.injected["crash"]
    assert s["faults_injected"] == inj.faults_injected


# -- 6. cold-tier corruption conserves the task multiset -----------------------


def _pool(P=4, CAP=32, W=1, per_worker=30):
    masks = np.zeros((P, CAP, W), np.uint32)
    sols = np.zeros((P, CAP, W), np.uint32)
    depths = np.zeros((P, CAP), np.int32)
    active = np.zeros((P, CAP), bool)
    for w in range(P):
        for s in range(per_worker):
            masks[w, s] = w * CAP + s + 1
            depths[w, s] = (w * per_worker + s) % 24
            active[w, s] = True
    return masks, sols, depths, active


def _pool_keys(masks, depths, active):
    return sorted(
        (int(masks[w, s, 0]), int(depths[w, s]))
        for w, s in zip(*np.nonzero(active))
    )


def test_pump_host_conserves_multiset_under_injected_corruption():
    events = tuple(
        FaultEvent("cold_corrupt", at=0) for _ in range(3)
    ) + tuple(FaultEvent("transfer_corrupt", at=0) for _ in range(3))
    inj = FaultInjector(FaultPlan(seed=9, events=events))
    sp = FrontierSpiller(
        make_codec("optimized", 12), 4, 32, (0.25, 0.75),
        chunk_rounds=1, steps_per_round=2, lanes=1, donate_k=1,
        injector=inj,
    )
    masks, sols, depths, active = _pool()
    before = _pool_keys(masks, depths, active)
    assert sp.pump_host(masks, sols, depths, active)
    recovered = _pool_keys(masks, depths, active)
    while sp.cold_tasks:
        m2, s2 = np.zeros_like(masks), np.zeros_like(sols)
        d2, a2 = np.zeros_like(depths), np.zeros_like(active)
        assert sp.pump_host(m2, s2, d2, a2)
        recovered += _pool_keys(m2, d2, a2)
    # the multiset survives corruption exactly: no drop, no duplication
    assert sorted(recovered) == before
    assert sp.readmitted_total == sp.spilled_total
    for kind in ("cold_corrupt", "transfer_corrupt"):
        assert inj.injected[kind] >= 1
        assert inj.injected[kind] == inj.recovered[kind]
    assert sp.delivery_retries == inj.retries == inj.faults_injected


# an instance per problem whose frontier saturates capacity 16: a clique
# search runs deep on dense graphs, an MIS search on sparse ones
_SATURATING = {
    "vertex_cover": (40, 0.28, 0),
    "max_clique": (20, 0.9, 5),
    "mis": (20, 0.1, 3),
}


@pytest.mark.parametrize("problem", PROBLEMS)
def test_saturated_solve_unchanged_by_payload_corruption(problem):
    g = erdos_renyi(*_SATURATING[problem])
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=2, capacity=16,
        frontier_spill=True,
    )
    sess = SolverSession(problem, config=cfg, cache=_CACHE)
    base = sess.solve(g)
    assert base.stats.spilled_tasks > 0
    inj = FaultInjector(
        FaultPlan(
            seed=2,
            events=(
                FaultEvent("transfer_corrupt", at=1),
                FaultEvent("cold_corrupt", at=2),
            ),
        )
    )
    r = sess.solve(g, injector=inj)
    assert r.best_size == base.best_size
    assert (np.asarray(r.best_sol) == np.asarray(base.best_sol)).all()
    assert r.stats.spilled_tasks == base.stats.spilled_tasks
    assert r.stats.readmitted_tasks == base.stats.readmitted_tasks
    assert inj.faults_injected == inj.faults_recovered == 2


# -- 7. quarantine, degradation, rehabilitation --------------------------------


@pytest.mark.parametrize("problem", PROBLEMS)
def test_repeated_crashes_quarantine_shed_and_still_complete(problem):
    gs = [erdos_renyi(28, 0.3, 50 + i) for i in range(4)]
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, service_lanes=2,
    )
    sess = SolverSession(problem, config=cfg, cache=_CACHE)
    svc_ref = sess.serve()
    ref_tix = [svc_ref.submit(g) for g in gs]
    svc_ref.drain()
    want = [svc_ref.result(t) for t in ref_tix]

    inj = FaultInjector(
        FaultPlan(
            seed=0,
            events=tuple(
                FaultEvent("crash", at=2 + i, lane=i % 2) for i in range(4)
            ),
        )
    )
    svc = sess.serve(injector=inj)
    tix = [svc.submit(g) for g in gs]
    svc.drain()
    for t, w in zip(tix, want):
        got = svc.result(t)
        assert got.best_size == w.best_size
        assert (np.asarray(got.best_sol) == np.asarray(w.best_sol)).all()
    s = svc.stats()
    assert s["lanes_quarantined"] == 4
    assert s["faults_injected"] == s["faults_recovered"] == 4
    assert s["completed"] == 4
    # degradation healed by drain time: the plane is whole again
    assert s["lanes_shed"] == 0


@pytest.mark.parametrize("problem", PROBLEMS)
def test_stall_watchdog_quarantines_and_replays(problem):
    gs = [erdos_renyi(28, 0.3, 60 + i) for i in range(3)]
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, service_lanes=2,
        lane_stall_chunks=2,
    )
    sess = SolverSession(problem, config=cfg, cache=_CACHE)
    svc_ref = sess.serve()
    ref_tix = [svc_ref.submit(g) for g in gs]
    svc_ref.drain()
    want = [svc_ref.result(t) for t in ref_tix]

    inj = FaultInjector(
        FaultPlan(
            seed=0,
            events=(FaultEvent("stall", at=2, lane=1, duration=4),),
        )
    )
    svc = sess.serve(injector=inj, lane_stall_chunks=2)
    tix = [svc.submit(g) for g in gs]
    svc.drain()
    for t, w in zip(tix, want):
        got = svc.result(t)
        assert got.best_size == w.best_size
        assert (np.asarray(got.best_sol) == np.asarray(w.best_sol)).all()
    assert inj.injected["stall"] == inj.recovered["stall"] == 1
    assert svc.stats()["lanes_quarantined"] == 1


# -- 8. timeouts ---------------------------------------------------------------


def test_queued_request_times_out_with_typed_error():
    from repro.api import SolveService

    clk = _clock()
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, service_lanes=1,
        admission="fifo", request_timeout_s=5.0,
    )
    svc = SolveService("vertex_cover", cfg, clock=clk, cache=_CACHE)
    hard = svc.submit(erdos_renyi(30, 0.45, 3))
    queued = svc.submit(erdos_renyi(20, 0.3, 4))
    svc.step()  # hard takes the only lane; queued waits
    clk.t = 10.0
    completed = svc.step()  # both over budget: queued swept, hard evicted
    assert queued in completed and hard in completed
    with pytest.raises(SolveTimeout) as ei:
        svc.result(queued)
    assert ei.value.ticket == queued
    assert ei.value.result is None  # never reached a lane: no partial
    assert ei.value.waited_s >= 5.0
    assert "still queued" in str(ei.value)
    with pytest.raises(SolveTimeout) as ei:
        svc.result(hard)
    assert ei.value.result is not None  # was on a lane: anytime partial
    assert "on a lane" in str(ei.value)
    assert svc.stats()["timed_out"] == 2
    assert svc.idle()  # nothing left behind — no hung request survives


@pytest.mark.parametrize("problem", PROBLEMS)
def test_on_lane_request_times_out_with_partial_result(problem):
    from repro.api import SolveService

    clk = _clock()
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, service_lanes=1,
        request_timeout_s=5.0,
    )
    svc = SolveService(problem, cfg, clock=clk, cache=_CACHE)
    t = svc.submit(erdos_renyi(34, 0.5, 7))
    svc.step()  # on the lane, within budget
    clk.t = 10.0
    assert t in svc.step()
    with pytest.raises(SolveTimeout) as ei:
        svc.result(t)
    partial = ei.value.result
    assert partial is not None and partial.rounds >= 1  # anytime snapshot
    assert partial.stats.service.wall_deadline_hit is False
    assert partial.stats.service.deadline_hit is False
    assert "on a lane" in str(ei.value)
    assert svc.stats()["timed_out"] == 1


def test_async_awaited_solve_never_hangs():
    from repro.api import SolveService

    async def scenario():
        cfg = SolveConfig(
            num_workers=4, steps_per_round=2, chunk_rounds=1,
            service_lanes=1, request_timeout_s=1e-4,
        )
        svc = SolveService("vertex_cover", cfg, cache=_CACHE)
        async with AsyncSolveService(svc) as asvc:
            # any real chunk takes longer than 0.1ms of wall: the await
            # resolves with the typed timeout instead of hanging forever
            out = await asyncio.gather(
                asvc.solve(erdos_renyi(34, 0.5, 7)), return_exceptions=True
            )
        assert isinstance(out[0], SolveTimeout)

        cfg_ok = cfg.replace(request_timeout_s=3600.0)
        svc_ok = SolveService("vertex_cover", cfg_ok, cache=_CACHE)
        async with AsyncSolveService(svc_ok) as asvc:
            r = await asvc.solve(erdos_renyi(16, 0.3, 1))
        assert r.found

    asyncio.run(scenario())
