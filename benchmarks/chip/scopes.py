"""The traced plane's device time by the program's named scopes.

The program names its phases with ``jax.named_scope``: ``explore/pop``,
``explore/expand`` (with ``degrees``, ``reduce`` and its ``sweep``, and
``pivot`` inside), ``explore/push``, ``center``, ``transfer`` and
``termination``.  A scope reaches the compiled program only as the
``op_name`` of each instruction's metadata: the TPU trace's operation
events carry no such stat (their stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``) and the trace's
``/host:metadata`` plane is empty.  So the scope of each traced operation
is read from the compiled plane's HLO text, by instruction name; a fusion
takes the ``op_name`` of its fused computation's root.

Instruction names are unique within one program only, so the operations
read here are those that ran inside the plane's own executions: the
``XLA Modules`` events of the module whose name the HLO text gives, and of
those the program that took the most device time.

The program's host spans (``repro:<name>``, see ``repro.tracing``) are read
from the host planes as well, so that idle device time can be labelled by
them beside the harness's ``bench:`` spans.

Everything past :func:`load` and :func:`hlo_scopes` is arithmetic on plain
data, tested on synthetic traces.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
import traceback

from benchmarks.chip import trace

REPRO_PREFIX = "repro:"
MODULES_LINE = "XLA Modules"
# the program's top-level scopes of one superstep
TOP_SCOPES = ("explore", "center", "transfer", "termination")
BREAKDOWN = (
    "explore/pop", "explore/expand/degrees", "explore/expand/reduce",
    "explore/expand/pivot", "explore/expand", "explore/push", "explore",
    "center", "transfer", "termination",
)

_INSTR = re.compile(r"^\s*(ROOT\s+)?(%[\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=(%[\w.\-]+)")
# the computations that a while, a conditional or a call runs
_CALLED = re.compile(
    r"\b(?:condition|body|to_apply|true_computation|false_computation)"
    r"=(%[\w.\-]+)"
)
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?(%[\w.\-]+)\s.*\{\s*$")
_WRAPPED = re.compile(r"^[\w.\-]*\((.*)\)$")


def scope_path(op_name: str) -> tuple:
    """The components of an ``op_name`` with transformation wrappers taken
    off: ``jit(f)/vmap(reduce)/while/body/sweep/and`` ->
    ``('f', 'reduce', 'while', 'body', 'sweep', 'and')``.  Of names that XLA
    merged with ``;``, the first is kept."""
    out = []
    for part in op_name.split(";", 1)[0].split("/"):
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        if part:
            out.append(part)
    return tuple(out)


def has_scope(path: tuple, scope: str) -> bool:
    """Whether ``scope`` (``a`` or ``a/b``: consecutive components) lies on
    ``path``."""
    want = tuple(scope.split("/"))
    k = len(want)
    return any(path[i:i + k] == want for i in range(len(path) - k + 1))


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope path, from a compiled module's HLO text.

    A fusion takes its fused computation's root's ``op_name``.  An
    instruction that the compiler added without one (a layout copy, say)
    takes the path of the instruction that runs its computation: the
    ``while``, ``conditional`` or ``call`` whose body it is."""
    own, roots, fused, home, caller = {}, {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(2), m.group(3)
        op = _OP_NAME.search(rest)
        own[name] = op.group(1) if op else ""
        home[name] = computation
        if m.group(1) and computation is not None:
            roots[computation] = name
        c = _CALLS.search(rest)
        if c:
            fused[name] = c.group(1)
        else:
            called = _CALLED.findall(rest)
            for branches in _BRANCHES.findall(rest):
                called += [c.strip() for c in branches.split(",")]
            for c in called:
                caller.setdefault(c, name)
    for name, root in ((n, roots.get(c)) for n, c in fused.items()):
        if root is not None and own.get(root):
            own[name] = own[root]

    def path(name, depth=0):
        if own.get(name) or depth > 64:
            return scope_path(own.get(name, ""))
        up = caller.get(home.get(name))
        return path(up, depth + 1) if up is not None else ()

    return {name: path(name) for name in own}


def module_name(hlo_text: str) -> str:
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


@dataclasses.dataclass
class Raw:
    """What :func:`load` reads: per device, ``modules`` (name, start, end)
    and ``ops`` (instruction name, start, end) in nanoseconds; ``spans``:
    the program's host spans (name with its ``repro:`` prefix, start, end,
    request id)."""

    modules: dict
    ops: dict
    spans: list


def load(directory: str, devices=None) -> Raw:
    """Read the newest ``.xplane.pb`` under ``directory`` (as
    :func:`trace.load` does) for its module executions and the program's
    host spans."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise ValueError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(files[-1])
    modules, ops, spans = {}, {}, []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name not in (MODULES_LINE, trace.OPS_LINE):
                    continue
                is_ops = line.name == trace.OPS_LINE
                out = (ops if is_ops else modules).setdefault(dev, [])
                for e in line.events:
                    name = e.name.split(" = ", 1)[0].strip()
                    if is_ops and trace.CONTAINER.match(name):
                        continue  # spans the operations it runs
                    out.append((name, int(e.start_ns), int(e.start_ns + e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(REPRO_PREFIX):
                        request = dict(e.stats).get("request")
                        spans.append((
                            e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns), request,
                        ))
    return Raw(modules=modules, ops=ops, spans=spans)


def plane_ops(raw: Raw, module: str) -> dict:
    """Per device, the operations that ran inside the executions of the
    program named ``module`` (``<module>(<program id>)`` in the trace) that
    took the most device time."""
    out = {}
    for dev, mods in raw.modules.items():
        total: dict = {}
        for name, s, e in mods:
            if name.split("(", 1)[0] == module:
                total[name] = total.get(name, 0) + e - s
        if not total:
            continue
        program = max(total, key=total.get)
        runs = trace.union([(s, e) for n, s, e in mods if n == program], 0, 2**63)
        keep, i = [], 0
        for name, s, e in sorted(raw.ops.get(dev, []), key=lambda ev: ev[1]):
            while i < len(runs) and runs[i][1] <= s:
                i += 1
            if i < len(runs) and runs[i][0] <= s:
                keep.append((name, s, e))
        out[dev] = keep
    return out


@dataclasses.dataclass
class Plane:
    """The plane's traced operations with their scope paths."""

    ops: dict  # device -> [(instruction name, start, end)]
    paths: dict  # instruction name -> scope path
    window: tuple

    def _events(self, scope: str):
        lo, hi = self.window
        for evs in self.ops.values():
            for name, s, e in evs:
                if e > lo and s < hi and has_scope(self.paths.get(name, ()), scope):
                    yield name, max(s, lo), min(e, hi)

    def time_s(self, scope: str) -> float:
        """Device seconds of the operations under ``scope``, per chip."""
        ns = sum(e - s for _, s, e in self._events(scope))
        return ns / max(len(self.ops), 1) / 1e9

    def event_counts(self, scope: str) -> dict:
        """Instruction name -> its events under ``scope``, over all chips."""
        counts: dict = {}
        for name, _, _ in self._events(scope):
            counts[name] = counts.get(name, 0) + 1
        return counts

    def executions(self, scope: str) -> float:
        """How often the code under ``scope`` ran, per chip: its
        operations' events over its distinct instructions (each runs once
        per execution)."""
        counts = self.event_counts(scope)
        if not counts:
            return 0.0
        return sum(counts.values()) / len(counts) / max(len(self.ops), 1)

    def scoped(self) -> bool:
        """Whether the program names its phases at all."""
        return any(
            has_scope(p, s) for p in self.paths.values() for s in TOP_SCOPES
        )


def labelled_idle_gaps(tr: trace.Trace, spans: list, k: int = 10) -> list:
    """:func:`trace.idle_gaps` with the program's host spans beside the
    harness's: each gap is named by the innermost span of either."""
    both = list(tr.spans) + [(name, s, e) for name, s, e, _ in spans]
    return trace.idle_gaps(dataclasses.replace(tr, spans=both), k)


def repro_idle_share(gaps: list) -> float:
    """The share of idle seconds that a ``repro:`` span labels."""
    total = sum(s for _, s in gaps)
    ours = sum(s for label, s in gaps if label.startswith("idle: " + REPRO_PREFIX))
    return ours / total if total else 0.0


def breakdown(plane: Plane, busy_s: float) -> dict:
    """Device seconds per scope, and the share of busy time that the
    program's scopes name."""
    out = {scope: plane.time_s(scope) for scope in BREAKDOWN}
    named = sum(out[s] for s in TOP_SCOPES)
    out["named_share_of_busy"] = named / busy_s if busy_s else 0.0
    return out


# -- reading a traced run of the solo plane --------------------------------------


def _plane_hlo(win) -> str:
    """The compiled solo plane of the window's session, as HLO text (the
    executable comes back from the compile caches: nothing new runs)."""
    import jax

    from repro.core.encoding import make_codec
    from repro.problems import base

    session, g = win.session, win.graph
    spec, cfg = session.problem, session.config
    pad = make_codec(cfg.codec, g.n, problem=spec).pad_words
    use_fpt = cfg.mode == "fpt"
    fn = session.cache.solo_plane(spec, cfg, pad, use_fpt)
    state = jax.tree.map(lambda x: x[0], win.last_state)
    args = (base.make_data(spec, g), state)
    if use_fpt:
        args += (jax.numpy.int32(spec.fpt_target(cfg.solo_k())),)
    return fn.lower(*args).compile().as_text()


def of(ctx, win):
    """The traced plane of ``win`` (read once, kept on ``win``), or None
    where the program names no scopes or the plane cannot be read.  The
    first read prints the breakdown by scope and the idle gaps labelled by
    the program's spans, as one ``[scopes]`` JSON line on standard error."""
    if hasattr(win, "scoped_plane"):
        return win.scoped_plane
    win.scoped_plane = None
    try:
        hlo = _plane_hlo(win)
        raw = load(str(ctx.trace_dir), list(win.trace.ops))
        plane = Plane(
            ops=plane_ops(raw, module_name(hlo)),
            paths=hlo_scopes(hlo),
            window=win.trace.window,
        )
    except Exception:  # noqa: BLE001 - a reader returns nothing, never raises
        print("[scopes] not read:\n" + traceback.format_exc(), file=sys.stderr)
        return None
    if not plane.scoped() or not any(plane.ops.values()):
        print("[scopes] the plane names no scopes", file=sys.stderr)
        return None
    gaps = labelled_idle_gaps(win.trace, raw.spans, k=len(raw.spans) + 2)
    sweeps = plane.event_counts("sweep").values()
    report = {
        "scopes_s": breakdown(plane, trace.busy_s(win.trace)),
        "sweep_events_per_instruction": [min(sweeps, default=0), max(sweeps, default=0)],
        "idle_gaps": gaps[:10],
        "repro_idle_share": repro_idle_share(gaps),
        "requests": sorted({r for *_, r in raw.spans if r is not None}),
    }
    print("[scopes] " + json.dumps(report), file=sys.stderr)
    win.scoped_plane = plane
    return plane
