"""Mixture-of-Experts block: top-k routing with sort-based grouped dispatch.

The memory-sane TPU formulation (no (T, E, C) one-hot dispatch tensor):

  1. router logits -> top_k (probs, expert ids) per token;
  2. flatten the T·k assignments and argsort by expert id;
  3. position-within-expert via a searchsorted segment offset; assignments
     beyond the per-expert capacity C = ceil(k·T/E · capacity_factor) drop
     (their tokens fall back to the residual stream only — standard
     "dropped tokens" semantics);
  4. gather tokens into the (E, C, d) expert batch, run the per-expert SwiGLU
     as batched einsums over E (MXU-friendly, sharded over the 'experts'
     logical axis = EP on the model mesh axis);
  5. scatter-add the outputs back weighted by the router probability.

The load-balancing auxiliary loss (Switch-style) is returned to the caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.sharding import constrain


def moe_init(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    scale = d**-0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * scale).astype(
            jnp.float32
        ),
        "w1": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * scale).astype(dt),
        "w3": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * scale).astype(dt),
        "w2": (
            jax.random.normal(ks[3], (E, f, d), jnp.float32) * f**-0.5
        ).astype(dt),
    }
    # EP: the expert bank shards over the model axis; the per-expert f dim is
    # NOT tensor-parallel (it would duplicate the mesh axis) — fine-grained
    # experts (qwen3: f=1536) are too narrow to split anyway.
    s = {
        "router": ("embed", "experts"),
        "w1": ("experts", "embed", None),
        "w3": ("experts", "embed", None),
        "w2": ("experts", None, "embed"),
    }
    return p, s


def num_groups(rules) -> int:
    """Data-parallel group count = product of the mesh-axis sizes the 'batch'
    rule maps to (1 when running unsharded)."""
    if not rules or not rules.get("batch"):
        return 1
    sizes = rules.get("_sizes") or {}
    g = 1
    for a in rules["batch"]:
        g *= sizes.get(a, 1)
    return g


def moe_apply(cfg: ModelConfig, p, x, rules=None):
    """Dispatch on rules['_moe_impl']: 'gspmd' (baseline, below) or
    'shard_map' (§Perf cell A: explicit per-shard dispatch + psum combine)."""
    if (
        rules
        and rules.get("_moe_impl") == "shard_map"
        and rules.get("_mesh") is not None
        and rules.get("experts")
    ):
        return _moe_shard_map(cfg, p, x, rules)
    return _moe_gspmd(cfg, p, x, rules)


def _moe_gspmd(cfg: ModelConfig, p, x, rules=None):
    """x (B, S, d) -> (out (B, S, d), aux_loss ()).

    Tokens are reshaped to (G, T/G, d) with G = data-shard count so that
    routing, sort and capacity are GROUP-LOCAL (no cross-shard gathers) and
    the only cross-shard movement is the (G, E, C, d) buffer resharding from
    G→data to E→model — which GSPMD lowers to the canonical MoE all-to-all.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    G = num_groups(rules)
    while T % G:  # batch not divisible (decode with odd batch): halve groups
        G //= 2
    Tg = T // G
    xt = constrain(x.reshape(G, Tg, d), ("batch", None, None), rules)

    logits = xt.astype(jnp.float32) @ p["router"]  # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)  # (G, Tg, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e (fraction routed to e) * (mean prob of e)
    counts = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    aux = E * jnp.sum((counts / (T * K)) * probs.mean((0, 1)))

    A = Tg * K  # assignments per group
    flat_e = top_e.reshape(G, A)
    flat_t = jnp.tile(
        jnp.repeat(jnp.arange(Tg, dtype=jnp.int32), K)[None], (G, 1)
    )
    flat_p = top_p.reshape(G, A)

    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=1)
    st = jnp.take_along_axis(flat_t, order, axis=1)
    sp = jnp.take_along_axis(flat_p, order, axis=1)

    C = int(max(1, (K * Tg / E) * cfg.capacity_factor))
    seg_start = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E, dtype=row.dtype))
    )(se)  # (G, E)
    pos = jnp.arange(A, dtype=jnp.int32)[None] - jnp.take_along_axis(
        seg_start, se, axis=1
    ).astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, se.astype(jnp.int32) * C + pos, E * C)  # OOB -> drop

    gi = jnp.arange(G, dtype=jnp.int32)[:, None]
    buf = jnp.zeros((G, E * C, d), x.dtype).at[gi, slot].set(
        jnp.take_along_axis(xt, st[..., None], axis=1), mode="drop"
    )
    # 2D-sharded expert batch: groups stay on their data shard, the expert
    # dim shards over model — dispatch is LOCAL (xt is replicated over the
    # model axis); only the combine below moves data between shards.
    buf = constrain(
        buf.reshape(G, E, C, d), ("batch", "experts", None, None), rules
    )
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w1"])) * jnp.einsum(
        "gecd,edf->gecf", buf, p["w3"]
    )
    y = jnp.einsum("gecf,efd->gecd", h, p["w2"])
    y = constrain(y, ("batch", "experts", None, None), rules).reshape(G, E * C, d)
    # combine: expert outputs return to their token's shard (baseline lowers
    # this as an all-gather over the model axis; see EXPERIMENTS.md §Perf)
    y = constrain(y, ("batch", None, None), rules)

    contrib = jnp.where(
        keep[..., None],
        y[gi, jnp.clip(slot, 0, E * C - 1)] * sp[..., None].astype(x.dtype),
        0,
    )
    out = jnp.zeros((G, Tg, d), x.dtype).at[gi, st].add(contrib)
    return out.reshape(B, S, d), aux


def _moe_shard_map(cfg: ModelConfig, p, x, rules):
    """§Perf cell A: explicit shard_map MoE.

    GSPMD cannot partition a data-dependent scatter whose written dim is
    sharded — the baseline replicates the (G, E·C, d) buffer per device
    (O(E/k · T · d) bytes moved per layer).  Under shard_map every index op is
    shard-LOCAL: each (data, model) device routes ITS tokens, keeps only the
    assignments that hit ITS experts, and the single cross-shard movement is
    one psum of the (Tg, d) combined output over the model axis — the same
    O(T·d) cost as a dense TP layer.
    """
    import numpy as _np
    from jax.sharding import PartitionSpec as P

    mesh = rules["_mesh"]
    sizes = rules["_sizes"]
    data_axes = tuple(rules.get("batch") or ())
    model_axis = rules["experts"][0]
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    M = sizes[model_axis]
    G = num_groups(rules)
    while T % G:
        G //= 2
    if E % M or G == 0:
        return _moe_gspmd(cfg, p, x, rules)
    Tg = T // G
    C = int(max(1, -(-K * Tg * cfg.capacity_factor // E)))
    E_loc = E // M
    dt = x.dtype

    def body(xt, router, w1, w3, w2):
        xt = xt.reshape(Tg, d)  # this data-shard's group
        router_full = jax.lax.all_gather(
            router, model_axis, axis=1, tiled=True
        )  # (d, E): tiny
        logits = xt.astype(jnp.float32) @ router_full  # (Tg, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        counts_loc = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
        counts = jax.lax.psum(counts_loc, data_axes) if data_axes else counts_loc
        pmean = probs.mean(0)
        if data_axes:
            pmean = jax.lax.pmean(pmean, data_axes)
        aux = E * jnp.sum((counts / (T * K)) * pmean)
        # identical on every model shard by construction; the pmean marks it
        # replicated for the VMA checker (O(1) payload)
        aux = jax.lax.pmean(aux, model_axis)

        A = Tg * K
        flat_e = top_e.reshape(A)
        flat_t = jnp.repeat(jnp.arange(Tg, dtype=jnp.int32), K)
        flat_p = top_p.reshape(A)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sp = flat_e[order], flat_t[order], flat_p[order]
        seg_start = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
        pos = jnp.arange(A, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
        keep = pos < C

        e0 = (jax.lax.axis_index(model_axis) * E_loc).astype(jnp.int32)
        rel = se.astype(jnp.int32) - e0
        mine = keep & (rel >= 0) & (rel < E_loc)
        slot = jnp.where(mine, rel * C + pos, E_loc * C)  # OOB -> dropped

        buf = jnp.zeros((E_loc * C, d), dt).at[slot].set(xt[st], mode="drop")
        buf3 = buf.reshape(E_loc, C, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf3, w1)) * jnp.einsum(
            "ecd,edf->ecf", buf3, w3
        )
        y = jnp.einsum("ecf,efd->ecd", h, w2).reshape(E_loc * C, d)
        contrib = jnp.where(
            mine[:, None], y[jnp.clip(slot, 0, E_loc * C - 1)] * sp[:, None].astype(dt), 0
        )
        out = jnp.zeros((Tg, d), dt).at[st].add(contrib)
        out = jax.lax.psum(out, model_axis)  # the ONLY big collective
        return out.reshape(1, Tg, d), aux

    xr = x.reshape(G, Tg, d)
    dspec = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dspec, None, None),
            P(None, model_axis),
            P(model_axis, None, None),
            P(model_axis, None, None),
            P(model_axis, None, None),
        ),
        out_specs=(P(dspec, None, None), P()),
    )(xr, p["router"], p["w1"], p["w3"], p["w2"])
    return out.reshape(B, S, d), aux
