import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the REAL step function (train_step with AdamW, or
prefill / serve step with the model's cache), jits it with production
in/out shardings, and runs ``.lower(...).compile()`` against abstract
ShapeDtypeStruct inputs — no weights are ever allocated.  The compiled
artifact yields ``memory_analysis()`` (proves per-device fit),
``cost_analysis()`` (FLOPs / bytes for §Roofline) and the HLO text from
which collective traffic is parsed.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  python -m repro.launch.dryrun --all --mesh single --out dryrun_single.json
  python -m repro.launch.dryrun --all --mesh multi  --out dryrun_multi.json
  python -m repro.launch.dryrun --arch rwkv6-3b --shape train_4k \
      --set batch=data,model --set embed=          # §Perf sharding overrides
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ShapeConfig
from repro.configs.registry import ALIASES
from repro.launch.analysis import collective_bytes, model_flops, roofline
from repro.launch.mesh import batch_axes_for, make_production_mesh
from repro.models.registry import Model, get_model
from repro.models.sharding import logical_to_spec, rules_for_mesh
from repro.optim.adamw import OptState, adamw_init, adamw_update

SKIP = {
    # long_500k needs sub-quadratic attention (DESIGN.md §4): only the SSM
    # and hybrid archs run it; pure full-attention archs skip by assignment.
    ("whisper-large-v3", "long_500k"): "full attention (O(S) KV decode at 512k infeasible)",
    ("qwen1.5-0.5b", "long_500k"): "full attention",
    ("phi3-medium-14b", "long_500k"): "full attention",
    ("minitron-4b", "long_500k"): "full attention",
    ("starcoder2-3b", "long_500k"): "full attention",
    ("pixtral-12b", "long_500k"): "full attention",
    ("llama4-scout-17b-a16e", "long_500k"): "full attention",
    ("qwen3-moe-235b-a22b", "long_500k"): "full attention",
}


def _eval_shape_with_specs(fn):
    """eval_shape an (arrays, static_spec_tree) initializer: returns
    (ShapeDtypeStruct tree, spec tree) without allocating anything."""
    captured = {}

    def wrapper():
        arrays, specs = fn()
        captured["specs"] = specs
        return arrays

    shapes = jax.eval_shape(wrapper)
    return shapes, captured["specs"]


def _sharding_for_leaf(shape_struct, logical, mesh, rules):
    """NamedSharding for one leaf; mesh axes that do not divide the dim are
    dropped (e.g. whisper's vocab 51866 on a 16-way model axis)."""
    spec = logical_to_spec(tuple(logical), rules)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    parts = list(spec) + [None] * (len(shape_struct.shape) - len(spec))
    out = []
    for dim, names in zip(shape_struct.shape, parts):
        if names is None:
            out.append(None)
            continue
        tup = (names,) if isinstance(names, str) else tuple(names)
        total = 1
        for n in tup:
            total *= sizes[n]
        out.append(names if total and dim % total == 0 else None)
    return NamedSharding(mesh, P(*out))


def _is_spec_leaf(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def shardings_for(tree_shapes, spec_tree, mesh, rules):
    return jax.tree.map(
        lambda s, logical: _sharding_for_leaf(s, logical, mesh, rules),
        tree_shapes,
        spec_tree,
    )


def build_cell(model: Model, shape: ShapeConfig, mesh, rules):
    """Returns (step_fn, abstract_args, in_shardings, out_shardings, donate)."""
    cfg = model.cfg
    key = jax.random.key(0)
    params_shapes, specs = _eval_shape_with_specs(lambda: model.init(key))
    params_sh = shardings_for(params_shapes, specs, mesh, rules)
    repl = NamedSharding(mesh, P())

    batch_axes = batch_axes_for(shape.global_batch, mesh)
    bspec = NamedSharding(mesh, P(batch_axes))
    batch_shapes = model.batch_spec(shape)
    batch_sh = {k: bspec for k in batch_shapes}

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        opt_sh = OptState(step=repl, m=params_sh, v=params_sh)

        def train_step(params, opt, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss_fn(p, batch, rules=rules)
            )(params)
            params, opt, stats = adamw_update(params, grads, opt)
            return params, opt, loss, stats["grad_norm"]

        return (
            train_step,
            (params_shapes, opt_shapes, batch_shapes),
            (params_sh, opt_sh, batch_sh),
            (params_sh, opt_sh, repl, repl),
            (0, 1),
        )

    if shape.kind == "prefill":

        def prefill_step(params, batch):
            return model.forward(params, batch, rules=rules)

        return (
            prefill_step,
            (params_shapes, batch_shapes),
            (params_sh, batch_sh),
            None,
            (),
        )

    # decode / serve step: one new token against a seq_len-deep cache
    cache_shapes, cache_specs = _eval_shape_with_specs(
        lambda: model.init_decode_cache(shape.global_batch, shape.seq_len)
    )
    if cache_specs is None:
        cache_sh = jax.tree.map(lambda s: bspec, cache_shapes)
    else:
        cache_sh = shardings_for(cache_shapes, cache_specs, mesh, rules)
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)

    def serve_step(params, cache, tokens):
        return model.decode_fn(params, cache, tokens, rules=rules)

    return (
        serve_step,
        (params_shapes, cache_shapes, tokens),
        (params_sh, cache_sh, bspec),
        (None, cache_sh),
        (1,),
    )


def _compile_cell(cfg, shape, mesh, rules):
    model = get_model(cfg)
    fn, args, in_sh, out_sh, donate = build_cell(model, shape, mesh, rules)
    with mesh:
        jitted = jax.jit(
            fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate
        )
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def _cost_of(compiled):
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(coll["total"]),
        coll,
    )


def _reduced_depths(cfg):
    """(cfg_2units, cfg_4units, units): XLA's cost analysis counts a while
    body ONCE, so the cost pass compiles with scans UNROLLED at 2 and 4 depth
    units and fits the per-unit slope — exact for homogeneous stacks (hybrid
    tails are a documented fractional-unit approximation)."""
    import dataclasses

    unit = max(len(cfg.pattern), 1)
    if cfg.family == "encdec":
        c1 = dataclasses.replace(cfg, n_layers=2, n_enc_layers=2)
        c2 = dataclasses.replace(cfg, n_layers=4, n_enc_layers=4)
        units = cfg.n_layers  # whisper: enc and dec counts are equal
    else:
        c1 = dataclasses.replace(cfg, n_layers=2 * unit)
        c2 = dataclasses.replace(cfg, n_layers=4 * unit)
        units = cfg.n_layers / unit
    return c1, c2, units


def run_cell(arch: str, shape_name: str, mesh, *, rule_overrides=None) -> dict:
    from repro.configs.registry import get_config

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if (cfg.name, shape_name) in SKIP:
        return {
            "arch": cfg.name,
            "shape": shape_name,
            "status": "SKIP",
            "reason": SKIP[(cfg.name, shape_name)],
        }
    overrides = dict(rule_overrides or {})
    overrides.setdefault("batch", batch_axes_for(shape.global_batch, mesh))
    if shape.kind == "decode":
        # decode caches shard their SEQUENCE dim over the model axis (split-K
        # flash-decoding): kv-head counts rarely divide a 16-way axis, and the
        # softmax partitions cleanly (local q·K + small psum for max/sum/p·V).
        overrides.setdefault("seq_kv", ("model",))
        overrides.setdefault("kv", None)
    rules = rules_for_mesh(mesh, overrides)

    # 1. the REQUIRED pass: full config lower+compile (memory proof)
    t0 = time.time()
    compiled = _compile_cell(cfg, shape, mesh, rules)
    t_compile = time.time() - t0
    result = {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "status": "OK",
        "compile_s": round(t_compile, 1),
    }
    try:
        mem = compiled.memory_analysis()
        result["memory"] = {
            "argument_size_b": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size_b": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size_b": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size_b": int(
                getattr(mem, "generated_code_size_in_bytes", 0)
            ),
        }
    except Exception as e:  # pragma: no cover - backend dependent
        result["memory"] = {"error": str(e)}

    # 2. cost terms: compile UNROLLED at 2 and 4 depth units, fit the slope
    # (XLA counts while bodies once; unrolling makes every layer visible)
    from repro.models import layers as _L

    c1_cfg, c2_cfg, units = _reduced_depths(cfg)
    _L.SCAN_UNROLL[0] = True
    try:
        f1, b1, k1, coll1 = _cost_of(_compile_cell(c1_cfg, shape, mesh, rules))
        f2, b2, k2, coll2 = _cost_of(_compile_cell(c2_cfg, shape, mesh, rules))
    finally:
        _L.SCAN_UNROLL[0] = False

    def fit(v1, v2):  # linear through (2 units, v1), (4 units, v2)
        slope = (v2 - v1) / 2.0
        return v1 + (units - 2) * slope

    flops = fit(f1, f2)
    bytes_accessed = fit(b1, b2)
    coll_total = fit(k1, k2)
    result["cost"] = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "extrapolation": {
            "units": units,
            "at_2units": {"flops": f1, "bytes": b1, "coll": k1},
            "at_4units": {"flops": f2, "bytes": b2, "coll": k2},
        },
    }
    per_kind = {
        k: fit(coll1[k], coll2[k])
        for k in coll1
        if k not in ("total", "counts")
    }
    result["collectives"] = {**per_kind, "total": coll_total}
    result["collective_counts"] = coll2["counts"]

    n_dev = mesh.devices.size
    rl = roofline(flops, bytes_accessed, coll_total)
    mf = model_flops(cfg, shape)
    rl["model_flops_global"] = mf
    rl["model_flops_per_dev"] = mf / n_dev
    rl["hlo_flops_per_dev"] = flops
    rl["useful_flop_ratio"] = (mf / n_dev) / flops if flops else 0.0
    result["roofline"] = rl
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (assignment spelling)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--out", default=None, help="write/merge JSON results here")
    ap.add_argument(
        "--set",
        action="append",
        default=[],
        help="logical=axis1,axis2 sharding-rule override (repeatable)",
    )
    ap.add_argument(
        "--moe-impl",
        default=None,
        choices=["gspmd", "shard_map"],
        help="MoE dispatch implementation (§Perf cell A)",
    )
    ap.add_argument(
        "--remat",
        default=None,
        choices=["nothing", "dots"],
        help="remat policy (§Perf knob)",
    )
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    overrides = {}
    for item in args.set:
        k, _, v = item.partition("=")
        overrides[k] = tuple(x for x in v.split(",") if x) or None
    if args.moe_impl:
        overrides["_moe_impl"] = args.moe_impl
    if args.remat:
        from repro.models import layers as _L

        _L.REMAT_POLICY[0] = args.remat

    cells = []
    if args.all:
        for arch in ALIASES:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"]) for r in results if r.get("status") != "ERROR"}

    for arch, shape in cells:
        from repro.configs.registry import get_config

        name = get_config(arch).name
        if (name, shape) in done:
            print(f"[skip-done] {name} × {shape}")
            continue
        print(f"[dryrun] {name} × {shape} on {args.mesh} ...", flush=True)
        try:
            r = run_cell(arch, shape, mesh, rule_overrides=overrides or None)
        except Exception:
            r = {
                "arch": name,
                "shape": shape,
                "status": "ERROR",
                "traceback": traceback.format_exc(limit=10),
            }
        results = [
            x for x in results if not (x["arch"] == name and x["shape"] == shape)
        ] + [r]
        if r["status"] == "OK":
            m = r.get("memory", {})
            print(
                f"  OK compile={r['compile_s']}s "
                f"args={m.get('argument_size_b', 0)/2**30:.2f}GiB "
                f"temp={m.get('temp_size_b', 0)/2**30:.2f}GiB "
                f"flops/dev={r['cost'].get('flops', 0):.3g} "
                f"coll={r['collectives'].get('total', 0)/2**20:.1f}MiB "
                f"dominant={r['roofline']['dominant']}",
                flush=True,
            )
        else:
            print(f"  {r['status']}: {r.get('reason', '')}"
                  f"{r.get('traceback', '')[-600:]}", flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_err = sum(r["status"] == "ERROR" for r in results)
    print(f"dryrun complete: {n_ok} OK, {n_skip} SKIP, {n_err} ERROR")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
