"""Grouped MoE dispatch: routing correctness and group invariance."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.configs.base import ModelConfig
from repro.models.moe import moe_apply, moe_init


def _cfg(E=8, K=2, cf=8.0):
    return ModelConfig(
        name=f"moe-test-{E}-{K}-{cf}", family="moe", n_layers=1, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=16, vocab=64, n_experts=E, top_k=K,
        capacity_factor=cf, dtype="float32",
    )


def test_group_invariance_with_ample_capacity():
    """With capacity >> demand nothing drops, so the G-grouped dispatch must
    equal the ungrouped (G=1) computation exactly.  (G is taken from the
    rules' _sizes; the mesh axes themselves are size-1 on CPU, so the
    constrain calls are trivial but still traced.)"""
    cfg = _cfg(cf=16.0)
    p, _ = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 8, 32))
    out1, aux1 = moe_apply(cfg, p, x, rules=None)  # G=1
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rules = {"batch": ("data",), "_sizes": {"data": 4}}
    with mesh:
        out4, aux4 = moe_apply(cfg, p, x, rules=rules)
    assert float(jnp.abs(out1 - out4).max()) < 1e-5
    assert abs(float(aux1) - float(aux4)) < 1e-5


def test_manual_two_expert_routing():
    """Force deterministic routing and check outputs against a hand einsum."""
    cfg = _cfg(E=2, K=1, cf=8.0)
    p, _ = moe_init(jax.random.key(0), cfg)
    # router sends feature<0 tokens to expert 0, else expert 1
    router = np.zeros((32, 2), np.float32)
    router[0, 0] = -100.0
    router[0, 1] = 100.0
    p["router"] = jnp.asarray(router)
    x = jax.random.normal(jax.random.key(1), (1, 6, 32))
    out, _ = moe_apply(cfg, p, x)
    eid = (np.asarray(x[0, :, 0]) > 0).astype(int)
    want = []
    for t in range(6):
        e = eid[t]
        h = jax.nn.silu(x[0, t] @ p["w1"][e]) * (x[0, t] @ p["w3"][e])
        want.append(h @ p["w2"][e])
    want = jnp.stack(want)
    assert float(jnp.abs(out[0] - want).max()) < 1e-4


def test_capacity_drops_dont_nan():
    cfg = _cfg(E=4, K=2, cf=0.1)  # absurdly tight capacity: most tokens drop
    p, _ = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    out, aux = moe_apply(cfg, p, x)
    assert not bool(jnp.isnan(out).any())
    assert jnp.isfinite(aux)


def test_grad_flows():
    cfg = _cfg()
    p, _ = moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, 32))

    def loss(p):
        out, aux = moe_apply(cfg, p, x)
        return jnp.sum(out**2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert all(jnp.isfinite(v).all() for v in jax.tree.leaves(g))
    assert float(jnp.abs(g["w1"]).max()) > 0
