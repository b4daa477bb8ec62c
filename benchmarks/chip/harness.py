"""The data-driven harness: one cell, one run, one result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to it is found by name:

* ``workloads/<cell>.json``: its configuration's name, its traffic
  generator and parameters, its window driver, its check limits;
* ``configs/<config>.json``: the deployment (problem, pinned ``SolveConfig``
  fields, source, what was reduced and assumed);
* ``traffic/<generator>.py``: makes the inputs from the seed;
* ``drivers/<driver>.py``: set-up, the measured window and the comparison
  with the plain reference;
* ``layer_metrics/<metric>.py``: one reader per per-layer metric.

A run: refuse without the chips the cell asks for; set up (compile
included) and time it; run the window; read peak device memory; compare
what the window produced with the reference; print the check lines on
standard error and the result as the last line of standard output.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"  # the traced run's profile, overwritten per cell


class Refused(SystemExit):
    """The run cannot be made here: no result is printed, exit code 2."""

    def __init__(self, msg: str):
        print(f"[bench] refused: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_plugin(kind: str, name: str):
    """Import ``<kind>/<name>.py`` by path (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module {path.relative_to(ROOT)}")
    key = f"chipbench_{kind}_{name.replace('.', '_')}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key]


def benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise Refused("no BENCHMARK.json at the root of the checkout")
    return json.loads(path.read_text())


def cell_entry(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise Refused(f"BENCHMARK.json has no workload {workload!r}")


def metrics_for(entries: list, workload: str, e2e_names=None) -> list:
    """The metric entries that a cell reports: those that list it under
    ``workloads``, or, without that key, every cell (per-layer metrics: every
    cell that reports the end-to-end metric they move)."""
    out = []
    for m in entries:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m["moves"] in e2e_names:
            out.append(m)
    return out


class Spans:
    """The harness's own host spans: kept in memory (perf_counter seconds)
    and, while a trace is recorded, written into it as ``bench:<name>``
    annotations on the profiler's clock."""

    def __init__(self):
        self.records: list = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]


class Compiles:
    """Counts XLA compilations (backend compiles reported through JAX's
    monitoring events) and the solver's plane traces."""

    def __init__(self):
        import jax

        self.count = 0

        def listener(event, duration_secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)

    def total(self) -> int:
        from repro.core import superstep

        return self.count + superstep.PLANE_TRACES


@dataclasses.dataclass
class Check:
    """One number compared: the run is correct when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's files, the run's arguments, the
    spans, and whether (and where) this run traces."""

    workload: str
    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    spans: Spans
    traffic: object

    @contextlib.contextmanager
    def profiled(self):
        """Record the profiler trace around a block (a no-op untraced).
        The block is the ``window`` span either way."""
        if not self.trace:
            with self.spans("window"):
                yield
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.trace_dir))
        self.spans.tracing = True
        try:
            with self.spans("window"):
                yield
        finally:
            self.spans.tracing = False
            jax.profiler.stop_trace()

    @property
    def trace_dir(self) -> pathlib.Path:
        return TRACE_DIR / self.workload


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_chip: bool) -> tuple:
    """(devices used, the result's ``device`` dict); refuses without a TPU
    or with fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_chip:
        if platform != "tpu":
            raise Refused(f"JAX found no TPU (platform {platform})")
        if len(devices) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}")
    used = devices[:chips]
    return used, {
        "platform": platform,
        "kind": used[0].device_kind,
        "count": len(used),
    }


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_chip: bool = True,
    overrides: dict | None = None,
) -> tuple:
    """One run of one cell; returns (result dict, checks, the driver's
    window record).

    ``require_chip=False`` and ``overrides`` (``{"config": {...}, "cell":
    {...}}``, each key's dict merged into the file's) are for the harness's
    own tests on the CPU at a small size."""
    if require_chip and os.environ.get("REPRO_PALLAS_INTERPRET"):
        raise Refused("REPRO_PALLAS_INTERPRET is set; the benchmark runs native kernels")
    if not (SRC / "repro").is_dir():
        raise Refused(f"no solver package under {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    bench = benchmark()
    entry = cell_entry(bench, workload)
    cell = load_json("workloads", workload)
    config = load_json("configs", cell["config"])
    for part, over in (overrides or {}).items():
        target = config if part == "config" else cell
        for key, value in over.items():
            target[key] = {**target[key], **value} if isinstance(value, dict) else value
    used, device = device_info(entry["chips"], require_chip)
    if device["platform"] != "cpu":
        enable_compile_cache()

    spans = Spans()
    ctx = Ctx(
        workload=workload, cell=cell, config=config, seed=seed % 2**64,
        seconds=seconds, trace=trace, spans=spans,
        traffic=load_plugin("traffic", cell["traffic"]),
    )
    driver = load_plugin("drivers", cell["driver"])
    compiles = Compiles()

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    before = compiles.total()
    win = driver.window(ctx, state)
    win.compiles_in_window = compiles.total() - before
    device["memory_peak_bytes"] = memory_peak_bytes(used)
    if trace:
        from benchmarks.chip import trace as tr

        win.trace = tr.load(str(ctx.trace_dir), [d.id for d in used])
        device["busy_s"] = tr.busy_s(win.trace)
        device["window_s"] = win.trace.window_s
    del state
    checks = [Check("compiles_in_window", win.compiles_in_window, 0)]
    checks += driver.check(ctx, win)
    for key, value in win.info.items():
        print(f"[info] {key} = {value}", file=sys.stderr)
    correct = all(c.ok for c in checks)

    e2e = metrics_for(bench["end_to_end"], workload)
    if not trace:
        values = {"setup_s": setup_s, **win.metrics}
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in e2e if m["name"] in values
        }
    else:
        metrics = {}
        for m in metrics_for(bench["per_layer"], workload, {m["name"] for m in e2e}):
            value = load_plugin("layer_metrics", m["name"]).read(ctx, win, device)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": correct,
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        from benchmarks.chip import trace as tr

        result["breakdown"] = {
            "device_ops": tr.top_ops(win.trace),
            "idle_gaps": tr.idle_gaps(win.trace),
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result, checks, win
