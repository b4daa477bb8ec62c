"""Logical-axis sharding rules + the MoE group math (single-device mesh)."""

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.models.moe import num_groups
from repro.models.sharding import (
    DEFAULT_RULES,
    constrain,
    gather_params,
    logical_to_spec,
    rules_for_mesh,
    spec_tree_of,
)


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def test_logical_to_spec():
    rules = {"embed": ("data",), "heads": ("model",), "batch": ("pod", "data"),
             None: None}
    assert logical_to_spec(("embed", "heads"), rules) == P("data", "model")
    assert logical_to_spec(("batch", None), rules) == P(("pod", "data"), None)
    assert logical_to_spec((None, "missing"), rules) == P(None, None)


def test_rules_drop_missing_axes():
    rules = rules_for_mesh(_mesh11())
    assert rules["batch"] == ("data",)  # 'pod' dropped on the single-pod mesh
    assert rules["_sizes"] == {"data": 1, "model": 1}


def test_num_groups():
    assert num_groups(None) == 1
    rules = {"batch": ("data",), "_sizes": {"data": 16, "model": 16}}
    assert num_groups(rules) == 16
    rules2 = {"batch": ("pod", "data"), "_sizes": {"pod": 2, "data": 16}}
    assert num_groups(rules2) == 32
    assert num_groups({"batch": None, "_sizes": {}}) == 1


def test_constrain_noop_without_rules():
    x = jnp.zeros((4, 4))
    assert constrain(x, ("batch", None), None) is x


def test_gather_params_drops_fsdp_axes():
    """Under a real (1,1) mesh the regather is a semantic no-op but must
    trace/compile cleanly through jit."""
    mesh = _mesh11()
    rules = rules_for_mesh(mesh)
    tree = {"w": jnp.ones((8, 8))}
    spec = {"w": ("embed", "heads")}
    with mesh:
        out = jax.jit(lambda t: gather_params(t, spec, rules))(tree)
    assert (out["w"] == 1).all()


def test_spec_tree_of_no_allocation():
    calls = []

    def init():
        calls.append(1)
        return {"w": jnp.zeros((1024, 1024))}, {"w": ("embed", "heads")}

    specs = spec_tree_of(init)
    assert specs == {"w": ("embed", "heads")}


def test_default_rules_cover_all_logical_names():
    for name in ["batch", "embed", "heads", "kv", "mlp", "experts", "vocab",
                 "seq", "seq_kv", "layers", "rnn", "conv", "lora", "stack"]:
        assert name in DEFAULT_RULES
