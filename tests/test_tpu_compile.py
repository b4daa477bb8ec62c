"""Compile the main path's kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler is installed and compiles for a
topology that is only described, so what Mosaic would refuse on the chip
(unaligned slices, lane gathers, unsupported primitives) fails here, at no
chip time.  Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a module that loaded it
while being collected would hold it for the whole test worker.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import superstep
from repro.graphs.generators import erdos_renyi
from repro.kernels.bitset_ops.kernel import batched_degrees, batched_expand_stats
from repro.problems.base import make_data
from repro.problems.registry import get_problem

T = 64  # tasks per kernel call: eight sublane tiles of task rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [256, 2048])
@pytest.mark.parametrize("kernel", ["degrees", "expand_stats"])
def test_bitset_kernel_compiles_natively(one_chip, kernel, n):
    W = -(-n // 32)
    adj = _shape((n, W), jnp.uint32, one_chip)
    masks = _shape((T, W), jnp.uint32, one_chip)
    if kernel == "degrees":
        fn = jax.jit(lambda a, m: batched_degrees(a, m, interpret=False))
        args = (adj, masks)
    else:
        fn = jax.jit(
            lambda a, m, s: batched_expand_stats(a, m, s, interpret=False)
        )
        args = (adj, masks, masks)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_solo_plane_compiles_with_the_kernel(one_chip, monkeypatch):
    """The solo plane a lanes=8 solve runs, compiled for one described chip:
    the fused explore path must lower through the Pallas kernel, not the
    jnp reference it takes on CPU."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")  # as on a TPU runtime
    n, workers, lanes = 256, 8, 8
    spec = get_problem("vertex_cover")
    g = erdos_renyi(n, 0.05, 0)
    plane = superstep.build_plane_fn(
        spec, steps_per_round=32, lanes=lanes, explore_impl="fused"
    )
    cap = 4 * n + 8 * lanes
    state = jax.eval_shape(
        lambda: jax.vmap(
            lambda _: superstep.make_worker_state(cap, g.W, n + 1)
        )(jnp.arange(workers))
    )
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: _shape(np.shape(x), x.dtype, one_chip), tree
    )
    text = (
        plane.lower(described(make_data(spec, g)), described(state))
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text


def test_mesh_plane_compiles_for_four_chips(topo, monkeypatch):
    """The solo plane sharded over a described four-chip host, two workers
    of 8 lanes on each chip: it compiles with the kernel, and the center's
    collectives run across the chips."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")  # as on a TPU runtime
    n, workers, lanes = 256, 8, 8
    mesh = Mesh(np.array(topo.devices), ("chips",), axis_types=(AxisType.Auto,))
    spec = get_problem("vertex_cover")
    g = erdos_renyi(n, 0.05, 0)
    plane = superstep.build_plane_fn(
        spec, steps_per_round=32, lanes=lanes, explore_impl="fused", mesh=mesh
    )
    cap = 4 * n + 8 * lanes
    state = jax.eval_shape(
        lambda: jax.vmap(
            lambda _: superstep.make_worker_state(cap, g.W, n + 1)
        )(jnp.arange(workers))
    )
    described = lambda tree, parts: jax.tree.map(  # noqa: E731
        lambda x: _shape(np.shape(x), x.dtype, NamedSharding(mesh, parts)), tree
    )
    text = (
        plane.lower(
            described(make_data(spec, g), PartitionSpec()),
            described(state, PartitionSpec("chips")),
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text
    assert "all-reduce(" in text and "replica_groups={{0,1,2,3}}" in text
