"""Architecture guard: the core solve plane must stay problem-generic.

The PR-3 refactor extracted the :class:`BranchingProblem` plugin protocol so
no module under ``src/repro/core/`` depends on a concrete problem's device
ops.  This test pins that invariant: the refactor cannot silently regress by
someone re-importing ``repro.problems.vertex_cover`` (or any other concrete
plugin's device module) from core.  Core may import the protocol
(``repro.problems.base``) and the name registry
(``repro.problems.registry``); the host sims (protocol_sim / centralized)
may keep using the sequential REFERENCE module, which predates and is
independent of the device plane.
"""

import ast
import pathlib

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"
CORE = SRC / "core"
PROBLEMS = SRC / "problems"

# concrete problem plugins core must never import
FORBIDDEN = {
    "repro.problems.vertex_cover",
    "repro.problems.max_clique",
    "repro.problems.mis",
}


def _imports_of(path: pathlib.Path):
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module


def test_core_never_imports_a_concrete_problem():
    assert CORE.is_dir(), CORE
    offenders = {}
    for path in sorted(CORE.glob("*.py")):
        bad = [
            mod
            for mod in _imports_of(path)
            if mod in FORBIDDEN
            or any(mod.startswith(f + ".") for f in FORBIDDEN)
        ]
        if bad:
            offenders[path.name] = bad
    assert not offenders, (
        f"core modules import concrete problem plugins: {offenders} — "
        f"route through repro.problems.registry / repro.problems.base instead"
    )


def _module_level_imports_of(path: pathlib.Path):
    """Every import executed AT IMPORT TIME: the module body plus any
    statement block reachable from it (if/try/with/for/while, class bodies)
    — only function bodies are excluded, because only those defer execution.
    Relative imports are resolved against the file's package so ``from
    ..kernels import x`` is caught like its absolute spelling."""
    tree = ast.parse(path.read_text())
    # package of this module, e.g. src/repro/problems/base.py -> repro.problems
    parts = path.with_suffix("").parts
    pkg = list(parts[parts.index("repro"):-1] or ["repro"])

    def walk(nodes):
        for node in nodes:
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue  # deferred execution: lazy imports live here
            if isinstance(node, ast.Import):
                for alias in node.names:
                    yield alias.name
            elif isinstance(node, ast.ImportFrom):
                if node.level:  # relative: resolve against the package
                    base = pkg[: len(pkg) - (node.level - 1)]
                    yield ".".join(base + ([node.module] if node.module else []))
                elif node.module:
                    yield node.module
            else:
                for child in ast.iter_child_nodes(node):
                    yield from walk([child])

    yield from walk(tree.body)


def test_reference_explore_path_never_imports_kernels_at_module_level():
    """The reference explore path must stay Pallas-free: importing
    ``repro.core`` / ``repro.problems`` (what every CPU-only solve touches)
    may not pull in ``repro.kernels`` — the fused impls reach the bitset
    kernels through function-level lazy imports only, so they load only if
    a fused plane actually runs."""
    offenders = {}
    for directory in (CORE, PROBLEMS):
        for path in sorted(directory.glob("*.py")):
            bad = [
                mod
                for mod in _module_level_imports_of(path)
                if mod == "repro.kernels" or mod.startswith("repro.kernels.")
            ]
            if bad:
                offenders[path.name] = bad
    assert not offenders, (
        f"module-level repro.kernels imports in the solve plane: {offenders}"
        f" — keep kernel imports lazy (inside the fused expand functions)"
    )


def test_core_resolves_problems_through_the_registry():
    """The engine's defaults come from the registry, not a hardcoded plugin:
    the default-problem constant lives in problems/, and core references it
    by import."""
    from repro.core import engine
    from repro.problems.registry import DEFAULT_PROBLEM, get_problem

    assert engine.DEFAULT_PROBLEM == DEFAULT_PROBLEM
    # and the registry resolves it to a real spec
    assert get_problem(DEFAULT_PROBLEM).name == DEFAULT_PROBLEM


# -- the public API surface ----------------------------------------------------

# The PR-4 redesign made `repro.api` THE public surface.  This snapshot pins
# it: adding or removing a name is a deliberate, reviewed change (update the
# list here AND the README quickstart), never an accidental side effect of a
# refactor.
PUBLIC_API = [
    "AsyncSolveService",
    "BACKENDS",
    "Backend",
    "BatchSolveResult",
    "CacheStats",
    "CheckpointError",
    "LaneStats",
    "PlaneCache",
    "ServiceStats",
    "SolveCheckpoint",
    "SolveConfig",
    "SolveResult",
    "SolveService",
    "SolveStats",
    "SolveTimeout",
    "SolverSession",
    "get_backend",
    "known_backends",
    "solve_stream_session",
]


def test_public_api_snapshot():
    import repro.api as api

    assert sorted(api.__all__) == PUBLIC_API, (
        "repro.api.__all__ drifted from the pinned public-API snapshot — "
        "if intentional, update tests/test_arch_guard.py and the README"
    )
    # every advertised name must actually resolve
    for name in api.__all__:
        assert hasattr(api, name), f"repro.api.__all__ lists missing {name!r}"


def test_backend_registry_covers_the_advertised_backends():
    from repro.api import known_backends

    assert known_backends() == [
        "centralized", "protocol_sim", "sequential", "spmd"
    ]


# Field snapshot of the one public config: adding/removing/renaming a knob is
# a deliberate, reviewed change (update here AND the README perf-knobs
# section), never a refactor side effect.  Defaults are pinned for the knobs
# whose silent flip would change what every solve runs (hot-path selection).
SOLVE_CONFIG_FIELDS = [
    "admission",
    "batch_size",
    "capacity",
    "checkpoint_dir",
    "checkpoint_every",
    "chunk_rounds",
    "codec",
    "compact_threshold",
    "donate_k",
    "explore_impl",
    "frontier_spill",
    "k",
    "lane_stall_chunks",
    "lanes",
    "latency",
    "max_rounds",
    "max_ticks",
    "mode",
    "num_workers",
    "packed_status",
    "policy",
    "queue_cap_per_p",
    "request_timeout_s",
    "resume_from",
    "seed",
    "send_metadata",
    "service_lanes",
    "skip_empty_transfer",
    "spill_codec",
    "spill_watermarks",
    "steps_per_round",
    "tenant_max_lanes",
    "transfer_impl",
    "use_mesh",
    "use_priority_queue",
]


def test_solve_config_field_snapshot():
    import dataclasses

    from repro.api import SolveConfig

    assert sorted(
        f.name for f in dataclasses.fields(SolveConfig)
    ) == SOLVE_CONFIG_FIELDS, (
        "SolveConfig fields drifted from the pinned snapshot — if "
        "intentional, update tests/test_arch_guard.py and the README"
    )
    cfg = SolveConfig()
    # the fused exploration plane is the default hot path; the reference
    # path stays reachable for A/B
    assert cfg.explore_impl == "fused"
    assert cfg.transfer_impl == "sparse"


# Field snapshots of the typed stats schema (PR-7): every backend writes into
# ONE SolveStats shape, so renaming/dropping a counter is a schema change every
# consumer sees — pin it like the config.
SOLVE_STATS_FIELDS = [
    "center_bytes",
    "checkpoints_written",
    "cold_bytes_peak",
    "control_bytes_per_round",
    "failed_requests",
    "host_fetch_bytes",
    "host_fetches",
    "max_depth",
    "msg_bytes",
    "msg_count",
    "overflow",
    "overflow_count",
    "pruned",
    "readmitted_tasks",
    "reduce_fires_rule1",
    "reduce_fires_rule2",
    "reduce_fires_rule3",
    "reduce_lane_sweeps",
    "reduce_rule3_probes",
    "reduce_worker_sweeps",
    "resumed_from",
    "service",
    "solutions",
    "spilled_tasks",
    "tasks_sent_remote",
    "termination_cancelled",
    "ticks",
    "total_bytes",
    "transfer_bytes_per_round",
    "transfer_bytes_total",
    "transfer_rounds",
]
SERVICE_STATS_FIELDS = [
    "deadline_hit",
    "faults_injected",
    "faults_recovered",
    "lane",
    "lanes_quarantined",
    "plane",
    "residency_s",
    "retries",
    "wait_s",
    "wall_deadline_hit",
]
LANE_STATS_FIELDS = ["chunk_calls", "lane_chunks", "live_lane_chunks", "occupancy"]


def test_stats_schema_field_snapshots():
    import dataclasses

    from repro.api import LaneStats, ServiceStats, SolveStats

    for cls, want in (
        (SolveStats, SOLVE_STATS_FIELDS),
        (ServiceStats, SERVICE_STATS_FIELDS),
        (LaneStats, LANE_STATS_FIELDS),
    ):
        assert sorted(f.name for f in dataclasses.fields(cls)) == want, (
            f"{cls.__name__} fields drifted from the pinned snapshot — if "
            f"intentional, update tests/test_arch_guard.py and the README"
        )


def test_stats_dict_access_shim_warns_and_delegates():
    """Legacy ``r.stats["overflow"]`` keeps working through the deprecation
    shim — but warns, and ``to_dict()`` stays the warning-free export."""
    import warnings

    import pytest

    from repro.api import SolveStats

    s = SolveStats(overflow_count=3)
    with pytest.warns(DeprecationWarning, match="dict-style access"):
        assert s["overflow_count"] == 3
    with pytest.warns(DeprecationWarning):
        assert "overflow" in s and s.get("missing", 7) == 7
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # attribute + to_dict never warn
        assert s.overflow_count == 3
        assert s.to_dict()["overflow_count"] == 3
