"""The ``Backend`` protocol and its four implementations.

A backend turns ``(problem spec, graph(s), SolveConfig)`` into the unified
:class:`~repro.api.result.SolveResult` schema:

* ``spmd`` — the TPU-adapted superstep engine, driven through the
  parametric compiled planes so a :class:`~repro.api.cache.PlaneCache`
  makes warm repeat solves reuse executables;
* ``protocol_sim`` — the faithful asynchronous MPI-protocol discrete-event
  simulator (now problem-generic via the plugin's host callables);
* ``centralized`` — the fully-centralized Abu-Khzam baseline (ditto);
* ``sequential`` — the plugin's ground-truth reference solver.

The module also hosts the legacy-shim entry points (``legacy_solve`` /
``legacy_solve_many``) that keep ``repro.core.engine.solve``/``solve_many``
working — those shims share one process-wide :data:`LEGACY_CACHE`, so even
deprecated callers stop paying per-call re-compiles.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.api.cache import PlaneCache
from repro.api.config import SolveConfig
from repro.api.result import (
    BatchSolveResult,
    LaneStats,
    SolveResult,
    from_engine_result,
    from_sequential,
    from_sim_result,
)
from repro.core import engine as _engine
from repro.core.encoding import make_codec
from repro.graphs.bitgraph import n_words
from repro.problems import base as problems_base


# -- the spmd drivers ----------------------------------------------------------
#
# Same solve loops as the legacy engine.solve/solve_many (whose helpers they
# reuse — startup scatter, result extraction, bucketing are single-sourced
# there), but the chunk executables come from a PlaneCache: ProblemData and
# FPT bounds are call-time arguments, so same-shape solves never re-trace.


def _solo_fingerprint(spec, g, cfg):
    from repro.checkpoint import solve as _ckpt

    return _ckpt.config_fingerprint(
        "solo", spec.name, cfg, [_ckpt.graph_digest(g)]
    )


def _write_solo_checkpoint(
    spec, g, cfg, fingerprint, state, rounds, spill=None,
    retry=None, fault_hook=None,
) -> None:
    """One atomic SolveCheckpoint of a solo solve at a chunk boundary."""
    from repro.checkpoint import solve as _ckpt
    from repro.core.superstep import worker_state_to_flat

    ck = _ckpt.SolveCheckpoint(
        kind="solo",
        problem=spec.name,
        config=cfg.replace(resume_from=None).to_dict(),
        fingerprint=fingerprint,
        rounds=rounds,
        arrays=worker_state_to_flat(state),
    )
    if spill is not None:
        ck.arrays.update(spill.to_flat())
    ck.pack_graphs([0], [g])
    ck.save(cfg.checkpoint_dir, rounds, retry=retry, fault_hook=fault_hook)


def solve_spmd(
    spec,
    g,
    cfg: SolveConfig,
    cache: PlaneCache,
    *,
    initial_state=None,
    mesh=None,
    injector=None,
):
    """One instance on the SPMD engine; returns a legacy ``EngineResult``
    (the session wraps it into the unified schema, the engine shim returns
    it as-is).

    Durability: with ``cfg.checkpoint_dir`` set, a
    :class:`~repro.checkpoint.solve.SolveCheckpoint` is written atomically
    every ``cfg.checkpoint_every`` chunks at the host-sync boundary (step
    number = rounds completed); with ``cfg.resume_from`` set, the solve
    restores the newest INTACT generation of that state
    (fingerprint-checked, falling back past corrupt generations with a
    warning) and continues — the loop is deterministic, so the final
    result is bit-identical to an uninterrupted run (modulo ``wall_s``).

    Robustness: ``injector`` (a :class:`repro.faults.FaultInjector`)
    exercises the recovery machinery at the host-sync boundaries only —
    a worker crash discards the device state and rebuilds it from the
    last good checkpoint (or the Algorithm-7 startup placement when the
    solve is not durable), cold-tier corruption is healed by checksum +
    redelivery inside the spiller, and checkpoint I/O errors retry under
    the injector's deterministic backoff policy.  Recovery re-executes a
    deterministic prefix, so the final result stays bit-identical.

    Tracing (:mod:`repro.tracing`): the host spans ``repro:solve`` and,
    inside it, ``solve.startup``, ``solve.chunk``, ``solve.spill_pump``,
    ``solve.checkpoint``, ``solve.fetch_state`` and ``solve.extract``, all
    with one request id; ``r.host_fetches`` / ``r.host_fetch_bytes`` count
    every device-to-host fetch.
    """
    req = tracing.new_request_id()
    with tracing.span("solve", req):
        return _solve_spmd(
            spec, g, cfg, cache, req, initial_state=initial_state, mesh=mesh,
            injector=injector,
        )


def _solve_spmd(spec, g, cfg, cache, req, *, initial_state, mesh, injector):
    if mesh is None and cfg.use_mesh:
        from repro.launch.mesh import make_solver_mesh

        mesh = make_solver_mesh(cfg.num_workers)
    k = cfg.solo_k()
    W = n_words(g.n)
    cap = cfg.capacity or (4 * g.n + 8 * cfg.lanes)
    initial_best = problems_base.initial_bound(spec, g, cfg.mode, k)
    data = problems_base.make_data(spec, g)
    pad = make_codec(cfg.codec, g.n, problem=spec).pad_words

    io_retry = injector.retry_policy() if injector is not None else None
    io_hook = injector.io_hook if injector is not None else None

    fingerprint = (
        _solo_fingerprint(spec, g, cfg)
        if (cfg.checkpoint_dir or cfg.resume_from)
        else None
    )

    fetches = tracing.Fetches()

    def build_startup():
        with tracing.span("solve.startup", req):
            s = jax.vmap(
                lambda _: _engine.make_worker_state(cap, W, initial_best)
            )(jnp.arange(cfg.num_workers))
            return _engine._scatter_startup(s, spec, g, cfg.num_workers)

    rounds = 0
    resumed_from = None
    resume_arrays = None
    if cfg.resume_from is not None:
        if initial_state is not None:
            raise ValueError("pass resume_from or initial_state, not both")
        from repro.checkpoint import solve as _ckpt
        from repro.core.superstep import worker_state_from_flat

        ck = _ckpt.SolveCheckpoint.load_latest_good(
            cfg.resume_from,
            expected_fingerprint=fingerprint,
            what=f"solve({spec.name})",
            retry=io_retry,
            fault_hook=io_hook,
        )
        if ck.kind != "solo":
            raise _ckpt.CheckpointError(
                f"{cfg.resume_from} holds a {ck.kind!r} checkpoint; "
                f"solve() resumes 'solo' checkpoints only"
            )
        state = worker_state_from_flat(ck.arrays)
        rounds = ck.rounds
        resumed_from = cfg.resume_from
        resume_arrays = ck.arrays
        cap = int(state.frontier.masks.shape[-2])
    elif initial_state is None:
        state = build_startup()
    else:
        state = initial_state
        cap = int(state.frontier.masks.shape[-2])

    spill = None
    if cfg.frontier_spill:
        if mesh is not None or cfg.use_mesh:
            raise ValueError(
                "frontier_spill has no mesh path yet (vmap virtual workers "
                "only) — drop use_mesh or disable frontier_spill"
            )
        from repro.core.spill import FrontierSpiller, make_spiller

        spill = make_spiller(cfg, spec, g, cap, cfg.num_workers, injector)
        if resume_arrays is not None and FrontierSpiller.present_in(
            resume_arrays
        ):
            spill.load_flat(resume_arrays)

    use_fpt = cfg.mode == "fpt"
    plane = cache.solo_plane(spec, cfg, pad, use_fpt, mesh)
    cache.note(
        "solo", spec, cfg, pad, use_fpt, (g.n, W, cap, cfg.num_workers), mesh,
    )
    if use_fpt:
        bound = jnp.int32(spec.fpt_target(k))
        step = lambda s: plane(data, s, bound)  # noqa: E731
    else:
        step = lambda s: plane(data, s)  # noqa: E731

    t0 = time.perf_counter()
    chunks = 0
    checkpoints_written = 0
    while rounds < cfg.max_rounds:
        with tracing.span("solve.chunk", req):
            state, done, ran, hot = step(state)
            done, ran, hot = fetches.get((done, ran, hot))
        rounds += int(ran)
        chunks += 1
        done = bool(done)
        if spill is not None and spill.wants_pump(hot, done):
            with tracing.span("solve.spill_pump", req):
                # an FPT bound hit finishes the solve regardless of cold
                # backlog (quiescent-done without the bound must refill and
                # continue)
                fpt_hit = (
                    done
                    and use_fpt
                    and int(fetches.get(state.best_val.min()))
                    <= int(spec.fpt_target(k))
                )
                if not fpt_hit:
                    fetches.add(tracing.nbytes(_pool(state.frontier)))
                    frontier, hot = spill.pump_frontier(state.frontier)
                    state = state._replace(frontier=frontier)
                    done = done and int(hot.sum()) == 0
        if injector is not None:
            injector.step_boundary()
            if injector.take_crash():
                # the worker plane died at this boundary: its device state
                # is gone.  Rebuild from the last good checkpoint when the
                # solve is durable, else replay from the deterministic
                # Algorithm-7 startup placement — both re-execute a prefix
                # of the SAME trajectory, so the final answer is unchanged.
                from repro.checkpoint import store as _store

                if (
                    cfg.checkpoint_dir is not None
                    and _store.latest_step(cfg.checkpoint_dir) is not None
                ):
                    from repro.checkpoint import solve as _ckpt
                    from repro.core.superstep import worker_state_from_flat

                    ck = _ckpt.SolveCheckpoint.load_latest_good(
                        cfg.checkpoint_dir,
                        expected_fingerprint=fingerprint,
                        what=f"solve({spec.name}) crash recovery",
                        retry=io_retry,
                        fault_hook=io_hook,
                    )
                    state = worker_state_from_flat(ck.arrays)
                    rounds = ck.rounds
                    if spill is not None:
                        from repro.core.spill import (
                            FrontierSpiller,
                            make_spiller,
                        )

                        spill = make_spiller(
                            cfg, spec, g, cap, cfg.num_workers, injector
                        )
                        if FrontierSpiller.present_in(ck.arrays):
                            spill.load_flat(ck.arrays)
                elif initial_state is not None:
                    state = initial_state
                    rounds = 0
                else:
                    state = build_startup()
                    rounds = 0
                    if spill is not None:
                        from repro.core.spill import make_spiller

                        spill = make_spiller(
                            cfg, spec, g, cap, cfg.num_workers, injector
                        )
                injector.note_recovered("crash")
                done = False
        if done:
            break
        if (
            cfg.checkpoint_dir is not None
            and chunks % cfg.checkpoint_every == 0
        ):
            with tracing.span("solve.checkpoint", req):
                fetches.add(tracing.nbytes(state))
                _write_solo_checkpoint(
                    spec, g, cfg, fingerprint, state, rounds, spill,
                    retry=io_retry, fault_hook=io_hook,
                )
            checkpoints_written += 1
    wall = time.perf_counter() - t0

    with tracing.span("solve.fetch_state", req):
        fetches.add(tracing.nbytes(state))
        host = _engine._fetch_batch_state(
            jax.tree.map(lambda x: x[None], state)
        )
    with tracing.span("solve.extract", req):
        r = _engine._extract_result(
            host,
            0,
            spec,
            g,
            rounds,
            wall,
            mode=cfg.mode,
            k=k,
            num_workers=cfg.num_workers,
            packed_status=cfg.packed_status,
        )
    r.host_fetches, r.host_fetch_bytes = fetches.count, fetches.bytes
    r.checkpoints_written = checkpoints_written
    r.resumed_from = resumed_from
    if spill is not None:
        r.spilled_tasks = spill.spilled_total
        r.readmitted_tasks = spill.readmitted_total
        r.cold_bytes_peak = spill.cold_bytes_peak
    return r


def _pool(frontier):
    """The frontier arrays a spill pump fetches."""
    return (frontier.masks, frontier.sols, frontier.depths, frontier.active)


def solve_many_spmd(spec, graphs, cfg: SolveConfig, cache: PlaneCache,
                    injector=None):
    """B instances on one batched plane; returns a legacy ``BatchResult``.

    Identical bucketing/padding/compaction behavior to the legacy
    ``engine.solve_many``; the one structural difference is that compaction
    RESLICES and keeps calling the same parametric plane function instead of
    rebuilding an executable, so a compacted width that was seen before
    (this call or any earlier one) is already warm.

    The loop runs on the :class:`~repro.core.superstep.LaneState` lifecycle
    (``tag`` = original instance index, per-lane ``rounds`` accumulated on
    device) — the same per-lane machinery the continuous service drives —
    and reports plane occupancy in ``BatchResult.lane_stats``.

    Durability mirrors :func:`solve_spmd`: every ``cfg.checkpoint_every``
    chunks the in-flight bucket's full LaneState/ProblemData plus every
    already-finalized result is checkpointed (step number = cumulative
    chunk count, monotonic across buckets); ``cfg.resume_from`` restores
    mid-bucket and skips the buckets whose results are already final.
    Results are finalized EAGERLY (at compaction / bucket end) so the
    checkpoint never needs a lane that was compacted away; per-instance
    ``wall_s`` (the amortized bucket share) is patched at bucket end and
    is the one field outside the bit-identity contract.

    Tracing: the spans of :func:`solve_spmd` under ``solve_many.*``; a
    result's host fetches are those made while its bucket ran.
    """
    req = tracing.new_request_id()
    with tracing.span("solve_many", req):
        return _solve_many_spmd(spec, graphs, cfg, cache, injector, req)


def _solve_many_spmd(spec, graphs, cfg, cache, injector, req):
    from repro.core.superstep import (
        LaneState,
        lane_resume,
        lane_state_from_flat,
        lane_state_to_flat,
        lane_swap_in,
        slice_lanes,
        step_lanes,
    )

    if cfg.frontier_spill:
        from repro.core.spill import FrontierSpiller, make_spiller

    if cfg.use_mesh:
        raise ValueError(
            "solve_many has no mesh path yet (vmap virtual workers only); "
            "use solve() per instance or a config with use_mesh=False"
        )
    graphs = list(graphs)
    B = len(graphs)
    use_fpt = cfg.mode == "fpt"
    if use_fpt:
        ks = list(cfg.k) if isinstance(cfg.k, tuple) else [cfg.k] * B
        if len(ks) != B or any(kk is None for kk in ks):
            raise ValueError("fpt mode needs one k (or one per instance)")
    else:
        ks = [None] * B
    results: dict = {}
    bucket_record = []
    compactions = 0
    wall_total = 0.0
    lane_stats = {"chunk_calls": 0, "lane_chunks": 0, "live_lane_chunks": 0}
    chunks_total = 0
    checkpoints_written = 0

    fingerprint = None
    if cfg.checkpoint_dir is not None or cfg.resume_from is not None:
        from repro.checkpoint import solve as _ckpt

        fingerprint = _ckpt.config_fingerprint(
            "many", spec.name, cfg, [_ckpt.graph_digest(g) for g in graphs]
        )

    fetches = tracing.Fetches()
    bucket_mark = (0, 0)  # fetches before the bucket in flight

    def extract(host, lane, oi, rounds_i, wall):
        with tracing.span("solve_many.extract", req):
            r = _engine._extract_result(
                host,
                lane,
                spec,
                graphs[oi],
                rounds_i,
                wall,
                mode=cfg.mode,
                k=ks[oi],
                num_workers=cfg.num_workers,
                packed_status=cfg.packed_status,
            )
        r.host_fetches = fetches.count - bucket_mark[0]
        r.host_fetch_bytes = fetches.bytes - bucket_mark[1]
        return r

    def fetch_state(lanes):
        with tracing.span("solve_many.fetch_state", req):
            fetches.add(tracing.nbytes(lanes.worker))
            host = _engine._fetch_batch_state(lanes.worker)
            return host, np.asarray(fetches.get(lanes.rounds))

    io_retry = injector.retry_policy() if injector is not None else None
    io_hook = injector.io_hook if injector is not None else None

    resume_ck = None
    resume_bucket = -1
    if cfg.resume_from is not None:
        from repro.checkpoint import solve as _ckpt

        resume_ck = _ckpt.SolveCheckpoint.load_latest_good(
            cfg.resume_from,
            expected_fingerprint=fingerprint,
            what=f"solve_many({spec.name})",
            retry=io_retry,
            fault_hook=io_hook,
        )
        if resume_ck.kind != "many":
            raise _ckpt.CheckpointError(
                f"{cfg.resume_from} holds a {resume_ck.kind!r} checkpoint; "
                f"solve_many() resumes 'many' checkpoints only"
            )
        meta = resume_ck.meta
        results = {
            int(i): _ckpt.engine_result_from_dict(d)
            for i, d in meta["results"].items()
        }
        compactions = int(meta["compactions"])
        chunks_total = int(meta["chunks_total"])
        lane_stats.update(
            {k: int(v) for k, v in meta["lane_stats"].items() if k in lane_stats}
        )
        resume_bucket = int(meta["bucket_idx"])

    def patch_spill(r, sp):
        if sp is not None:
            r.spilled_tasks = sp.spilled_total
            r.readmitted_tasks = sp.readmitted_total
            r.cold_bytes_peak = sp.cold_bytes_peak

    def write_checkpoint(bi, lanes, datas, fpt_bounds, total_ran, spillers):
        from repro.checkpoint import solve as _ckpt

        fetches.add(tracing.nbytes((lanes.worker, lanes.done, lanes.rounds)))
        fetches.add(tracing.nbytes(datas))

        ck = _ckpt.SolveCheckpoint(
            kind="many",
            problem=spec.name,
            config=cfg.replace(resume_from=None).to_dict(),
            fingerprint=fingerprint,
            rounds=total_ran,
            arrays=lane_state_to_flat(lanes),
            meta={
                "bucket_idx": bi,
                "total_ran": total_ran,
                "chunks_total": chunks_total,
                "compactions": compactions,
                "lane_stats": {
                    k: int(v) for k, v in lane_stats.items()
                },
                "results": {
                    str(i): _ckpt.engine_result_to_dict(r)
                    for i, r in results.items()
                },
            },
        )
        ck.arrays.update(_ckpt.data_to_flat(datas, "datas"))
        if fpt_bounds is not None:
            ck.arrays["fpt_bounds"] = np.asarray(fetches.get(fpt_bounds))
        for lane, sp in enumerate(spillers):
            if sp is not None:
                ck.arrays.update(sp.to_flat(f"spill{lane}"))
        ck.pack_graphs(range(B), graphs)
        ck.save(cfg.checkpoint_dir, chunks_total,
                retry=io_retry, fault_hook=io_hook)

    buckets = _engine._bucket_instances(graphs, by_n=(cfg.codec == "basic"))
    for bi, ((W, _), idxs) in enumerate(sorted(buckets.items())):
        bucket_graphs = [graphs[i] for i in idxs]
        n_max = max(g.n for g in bucket_graphs)
        bucket_record.append((W, n_max, list(idxs)))
        if resume_ck is not None and bi < resume_bucket:
            continue  # fully finalized before the checkpoint — restored above
        t0 = time.perf_counter()
        bucket_mark = (fetches.count, fetches.bytes)
        cap = cfg.capacity or (4 * n_max + 8 * cfg.lanes)
        pad = make_codec(cfg.codec, n_max, problem=spec).pad_words

        if resume_ck is not None and bi == resume_bucket:
            from repro.checkpoint import solve as _ckpt

            lanes = lane_state_from_flat(resume_ck.arrays)
            datas = _ckpt.data_from_flat(resume_ck.arrays, "datas")
            fpt_bounds = (
                jnp.asarray(resume_ck.arrays["fpt_bounds"]) if use_fpt else None
            )
            total_ran = int(resume_ck.meta["total_ran"])
            live_h = ~np.asarray(fetches.get(lanes.done))
            spillers = [None] * lanes.num_lanes
            if cfg.frontier_spill:
                for lane in range(lanes.num_lanes):
                    sp = make_spiller(
                        cfg, spec, graphs[int(lanes.tag[lane])], cap,
                        cfg.num_workers, injector,
                    )
                    if FrontierSpiller.present_in(
                        resume_ck.arrays, f"spill{lane}"
                    ):
                        sp.load_flat(resume_ck.arrays, f"spill{lane}")
                    spillers[lane] = sp
            resume_ck = None  # at most one in-flight bucket per checkpoint
        else:
            initial_bests = [
                problems_base.initial_bound(spec, g, cfg.mode, ks[i])
                for i, g in zip(idxs, bucket_graphs)
            ]
            with tracing.span("solve_many.startup", req):
                datas = problems_base.make_batch_data(
                    spec, bucket_graphs, n_max, W
                )
                lanes = LaneState(
                    worker=_engine._make_batch_state(
                        spec, bucket_graphs, cfg.num_workers, cap, W,
                        initial_bests,
                    ),
                    done=jnp.zeros((len(idxs),), bool),
                    tag=np.asarray(idxs, np.int32),
                    rounds=jnp.zeros((len(idxs),), jnp.int32),
                )
            fpt_bounds = (
                jnp.asarray(
                    np.array([spec.fpt_target(ks[i]) for i in idxs], np.int32)
                )
                if use_fpt
                else None
            )
            total_ran = 0
            live_h = np.ones(len(idxs), bool)  # live entering the next chunk
            spillers = [None] * len(idxs)
            if cfg.frontier_spill:
                spillers = [
                    make_spiller(cfg, spec, graphs[i], cap, cfg.num_workers,
                                 injector)
                    for i in idxs
                ]

        plane = cache.batch_plane(spec, cfg, pad, use_fpt)

        def note(n_lanes):
            cache.note(
                "batch", spec, cfg, pad, use_fpt,
                (n_max, W, cap, cfg.num_workers, n_lanes),
            )

        note(lanes.num_lanes)
        while total_ran < cfg.max_rounds:
            lane_stats["chunk_calls"] += 1
            lane_stats["lane_chunks"] += lanes.num_lanes
            lane_stats["live_lane_chunks"] += int(live_h.sum())
            with tracing.span("solve_many.chunk", req):
                lanes, ran, hot = step_lanes(plane, datas, lanes, fpt_bounds)
                done_h, ran_h, hot_h = fetches.get((lanes.done, ran, hot))
            total_ran += int(ran_h)
            chunks_total += 1
            done_h = np.array(done_h)
            if cfg.frontier_spill:
                hot_h = np.array(hot_h)
                best_h = bounds_h = None
                for lane, sp in enumerate(spillers):
                    if sp is None or not sp.wants_pump(
                        hot_h[lane], bool(done_h[lane])
                    ):
                        continue
                    with tracing.span("solve_many.spill_pump", req):
                        if bool(done_h[lane]) and use_fpt:
                            if best_h is None:
                                best_h = np.asarray(
                                    fetches.get(lanes.worker.best_val)
                                )[:, 0]
                                bounds_h = np.asarray(fetches.get(fpt_bounds))
                            if int(best_h[lane]) <= int(bounds_h[lane]):
                                continue  # FPT bound hit — finished for real
                        fetches.add(
                            tracing.nbytes(_pool(lanes.worker.frontier))
                            // lanes.num_lanes
                        )
                        lanes, hot_lane = sp.pump_lane(lanes, lane)
                    hot_h[lane] = hot_lane
                    if bool(done_h[lane]) and int(hot_lane.sum()) > 0:
                        lanes = lane_resume(lanes, lane)
                        done_h[lane] = False
            if injector is not None:
                injector.step_boundary()
                live_lanes = [
                    lane for lane in range(lanes.num_lanes)
                    if not bool(done_h[lane])
                ]
                for lane in injector.take_crashes(live_lanes):
                    # the lane's occupant died with its device state; the
                    # center still knows WHICH instance was placed there
                    # (the tag), so re-admission rebuilds it from the
                    # Algorithm-7 startup placement — a deterministic
                    # replay whose final result is bit-identical.
                    oi = int(lanes.tag[lane])
                    worker = _engine.make_instance_state(
                        spec, graphs[oi], cfg.num_workers, cap, W,
                        problems_base.initial_bound(
                            spec, graphs[oi], cfg.mode, ks[oi]
                        ),
                    )
                    lanes = lane_swap_in(lanes, lane, worker, oi)
                    done_h[lane] = False
                    if cfg.frontier_spill:
                        spillers[lane] = make_spiller(
                            cfg, spec, graphs[oi], cap, cfg.num_workers,
                            injector,
                        )
                    injector.note_recovered("crash")
            live_h = ~done_h
            if done_h.all():
                break
            n_live = int(live_h.sum())
            n_lanes = lanes.num_lanes
            target = _engine._pow2_at_least(n_live)
            if (
                cfg.compact_threshold > 0
                and n_live <= cfg.compact_threshold * n_lanes
                and target < n_lanes
            ):
                # collect finished lanes now, keep live ones (plus frozen
                # finished fillers up to the pow2 target), reslice every
                # tensor — the SAME plane function serves the new width.
                host, rounds_h = fetch_state(lanes)
                live = np.flatnonzero(~done_h)
                fillers = np.flatnonzero(done_h)[: target - n_live]
                for lane in np.flatnonzero(done_h):
                    oi = int(lanes.tag[lane])
                    if oi not in results and lane not in fillers:
                        results[oi] = extract(
                            host, lane, oi, int(rounds_h[lane]), 0.0
                        )
                        patch_spill(results[oi], spillers[lane])
                sel = np.concatenate([live, fillers]).astype(np.int64)
                lanes = slice_lanes(lanes, sel)
                datas = problems_base.slice_instances(datas, sel)
                spillers = [spillers[i] for i in sel]
                if fpt_bounds is not None:
                    fpt_bounds = fpt_bounds[sel]
                live_h = live_h[sel]
                compactions += 1
                note(lanes.num_lanes)
            if (
                cfg.checkpoint_dir is not None
                and chunks_total % cfg.checkpoint_every == 0
            ):
                with tracing.span("solve_many.checkpoint", req):
                    write_checkpoint(
                        bi, lanes, datas, fpt_bounds, total_ran, spillers
                    )
                checkpoints_written += 1

        host, rounds_h = fetch_state(lanes)
        for lane in range(lanes.num_lanes):
            oi = int(lanes.tag[lane])
            if oi not in results:
                results[oi] = extract(host, lane, oi, int(rounds_h[lane]), 0.0)
                patch_spill(results[oi], spillers[lane])
        bucket_wall = time.perf_counter() - t0
        wall_total += bucket_wall
        per_wall = bucket_wall / max(len(idxs), 1)
        for oi in idxs:
            results[oi].wall_s = per_wall

    lane_stats["occupancy"] = (
        lane_stats["live_lane_chunks"] / lane_stats["lane_chunks"]
        if lane_stats["lane_chunks"]
        else 0.0
    )
    for r in results.values():
        r.checkpoints_written = checkpoints_written
        r.resumed_from = cfg.resume_from
    return _engine.BatchResult(
        results=[results[i] for i in range(B)],
        wall_s=wall_total,
        buckets=bucket_record,
        compactions=compactions,
        lane_stats=lane_stats,
    )


# -- the Backend protocol ------------------------------------------------------


class Backend:
    """One engine behind the session façade.

    ``solve``/``solve_many`` take the RESOLVED problem spec, the validated
    config and the session's plane cache, and return the unified schema.
    The default ``solve_many`` loops ``solve`` per instance (honoring
    per-instance ``k`` tuples); backends with a real batch plane override.
    """

    name: str = "?"

    def solve(self, spec, g, cfg: SolveConfig, cache: PlaneCache) -> SolveResult:
        raise NotImplementedError

    def solve_many(
        self, spec, graphs, cfg: SolveConfig, cache: PlaneCache
    ) -> BatchSolveResult:
        graphs = list(graphs)
        ks = (
            list(cfg.k)
            if isinstance(cfg.k, tuple)
            else [cfg.k] * len(graphs)
        )
        if len(ks) != len(graphs):
            raise ValueError("per-instance k needs one entry per graph")
        out = [
            self.solve(spec, g, cfg.replace(k=kk), cache)
            for g, kk in zip(graphs, ks)
        ]
        return BatchSolveResult(
            problem=spec.name,
            backend=self.name,
            results=out,
            wall_s=sum(r.wall_s for r in out),
        )


class SpmdBackend(Backend):
    name = "spmd"

    def solve(self, spec, g, cfg, cache, *, initial_state=None, mesh=None,
              injector=None):
        r = solve_spmd(spec, g, cfg, cache, initial_state=initial_state,
                       mesh=mesh, injector=injector)
        return from_engine_result(r, problem=spec.name, backend=self.name)

    def solve_many(self, spec, graphs, cfg, cache, *, injector=None):
        br = solve_many_spmd(spec, graphs, cfg, cache, injector=injector)
        return BatchSolveResult(
            problem=spec.name,
            backend=self.name,
            results=[
                from_engine_result(r, problem=spec.name, backend=self.name)
                for r in br.results
            ],
            wall_s=br.wall_s,
            buckets=br.buckets,
            compactions=br.compactions,
            lane_stats=LaneStats(**br.lane_stats),
        )


class ProtocolSimBackend(Backend):
    name = "protocol_sim"

    def solve(self, spec, g, cfg, cache):
        from repro.core.protocol_sim import run_protocol_sim

        t0 = time.perf_counter()
        r = run_protocol_sim(
            g,
            num_workers=cfg.num_workers,
            latency=cfg.latency,
            policy=cfg.policy,
            codec_name=cfg.codec,
            mode=cfg.mode,
            k=cfg.solo_k(),
            send_metadata=cfg.send_metadata,
            max_ticks=cfg.max_ticks,
            seed=cfg.seed,
            problem=spec,
        )
        wall = time.perf_counter() - t0
        return from_sim_result(r, problem=spec.name, backend=self.name, wall_s=wall)


class CentralizedBackend(Backend):
    name = "centralized"

    def solve(self, spec, g, cfg, cache):
        from repro.core.centralized import run_centralized_sim

        t0 = time.perf_counter()
        r = run_centralized_sim(
            g,
            num_workers=cfg.num_workers,
            latency=cfg.latency,
            codec_name=cfg.codec,
            queue_cap_per_p=cfg.queue_cap_per_p,
            use_priority_queue=cfg.use_priority_queue,
            max_ticks=cfg.max_ticks,
            mode=cfg.mode,
            k=cfg.solo_k(),
            problem=spec,
        )
        wall = time.perf_counter() - t0
        return from_sim_result(r, problem=spec.name, backend=self.name, wall_s=wall)


class SequentialBackend(Backend):
    name = "sequential"

    def solve(self, spec, g, cfg, cache):
        if spec.sequential is None:
            raise ValueError(f"problem {spec.name!r} has no sequential reference")
        t0 = time.perf_counter()
        best, sol, stats = spec.sequential(g, mode=cfg.mode, k=cfg.solo_k())
        wall = time.perf_counter() - t0
        return from_sequential(best, sol, stats, problem=spec.name, wall_s=wall)


# -- backend registry ----------------------------------------------------------

BACKENDS = {
    b.name: b
    for b in (
        SpmdBackend(),
        ProtocolSimBackend(),
        CentralizedBackend(),
        SequentialBackend(),
    )
}

BACKEND_ALIASES = {
    "protocol": "protocol_sim",
    "central": "centralized",
    "centralised": "centralized",
    "seq": "sequential",
}


def known_backends() -> list:
    return sorted(BACKENDS)


def get_backend(name) -> Backend:
    """Resolve a backend by name (or pass an instance through); unknown
    names raise a ``ValueError`` listing what IS available."""
    if isinstance(name, Backend):
        return name
    key = BACKEND_ALIASES.get(name, name)
    if key not in BACKENDS:
        raise ValueError(
            f"unknown backend {name!r}; known backends: "
            f"{', '.join(known_backends())} "
            f"(aliases: {', '.join(sorted(BACKEND_ALIASES))})"
        )
    return BACKENDS[key]


# -- legacy engine shim plumbing -----------------------------------------------

#: one process-wide cache for the deprecated ``engine.solve``/``solve_many``
#: shims — legacy callers pool their executables too.
LEGACY_CACHE = PlaneCache()


def config_from_legacy(policy_priority: bool = True, **kw) -> SolveConfig:
    """Map the legacy kwargs surface onto :class:`SolveConfig`."""
    return SolveConfig(
        policy=("priority" if policy_priority else "random"), **kw
    )
