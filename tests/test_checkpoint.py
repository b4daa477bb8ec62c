"""Checkpoint/restart fault tolerance: atomicity, resume-exactness, async."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
    wait_for_pending,
)


def test_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.int32(7)}}
    save_checkpoint(str(tmp_path), 5, tree, extra={"x": 1})
    got, step, extra = restore_checkpoint(str(tmp_path), tree)
    assert step == 5 and extra == {"x": 1}
    assert (np.asarray(got["a"]) == np.asarray(tree["a"])).all()
    assert int(got["b"]["c"]) == 7


def test_latest_step_and_atomicity(tmp_path):
    tree = {"a": jnp.zeros(3)}
    save_checkpoint(str(tmp_path), 1, tree)
    save_checkpoint(str(tmp_path), 9, tree)
    # a stale .tmp dir must be ignored
    os.makedirs(tmp_path / "step_50.tmp")
    assert latest_step(str(tmp_path)) == 9


def test_async_write(tmp_path):
    tree = {"a": jnp.ones((64, 64))}
    save_checkpoint(str(tmp_path), 3, tree, blocking=False)
    wait_for_pending()
    got, step, _ = restore_checkpoint(str(tmp_path), tree)
    assert step == 3 and float(got["a"].sum()) == 64 * 64


def test_kill_mid_write_leaves_previous_step_intact(tmp_path, monkeypatch):
    """A writer dying inside the npz write (the long I/O phase) must leave
    the directory exactly as before: latest_step unchanged, no tmp litter,
    and the previous step still restorable."""
    tree = {"a": jnp.arange(8.0)}
    save_checkpoint(str(tmp_path), 1, tree, extra={"x": "old"})

    real_savez = np.savez

    def dying_savez(path, **payload):
        with open(path, "wb") as f:
            f.write(b"PK\x03\x04 partial garbage")  # half-written archive
        raise RuntimeError("simulated kill mid-write")

    monkeypatch.setattr(np, "savez", dying_savez)
    with pytest.raises(RuntimeError, match="simulated kill"):
        save_checkpoint(str(tmp_path), 2, {"a": jnp.zeros(8)}, extra={"x": "new"})
    monkeypatch.setattr(np, "savez", real_savez)

    assert latest_step(str(tmp_path)) == 1
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    got, step, extra = restore_checkpoint(str(tmp_path), tree)
    assert step == 1 and extra == {"x": "old"}
    assert (np.asarray(got["a"]) == np.arange(8.0)).all()


def _dummy_solve_checkpoint():
    from repro.checkpoint.solve import SolveCheckpoint

    return SolveCheckpoint(
        kind="solo",
        problem="vertex_cover",
        config={},
        fingerprint="f" * 64,
        rounds=3,
        arrays={"worker.rounds": np.arange(4, dtype=np.int32)},
    )


def test_truncated_solve_checkpoint_raises_checkpoint_error(tmp_path):
    from repro.checkpoint.solve import CheckpointError, SolveCheckpoint

    step_dir = _dummy_solve_checkpoint().save(str(tmp_path), 3)
    npz = os.path.join(step_dir, "arrays.npz")
    with open(npz, "r+b") as f:  # truncate mid-archive
        f.truncate(os.path.getsize(npz) // 2)
    with pytest.raises(CheckpointError, match="corrupt or truncated"):
        SolveCheckpoint.load(str(tmp_path))


def test_corrupt_manifest_raises_checkpoint_error(tmp_path):
    from repro.checkpoint.solve import CheckpointError, SolveCheckpoint

    step_dir = _dummy_solve_checkpoint().save(str(tmp_path), 1)
    with open(os.path.join(step_dir, "manifest.msgpack"), "wb") as f:
        f.write(b"\xc1\xc1 not msgpack")
    with pytest.raises(CheckpointError, match="corrupt or truncated"):
        SolveCheckpoint.load(step_dir)  # step_<N> path form


def test_missing_manifest_raises_checkpoint_error(tmp_path):
    from repro.checkpoint.solve import CheckpointError, SolveCheckpoint

    step_dir = _dummy_solve_checkpoint().save(str(tmp_path), 1)
    os.remove(os.path.join(step_dir, "manifest.msgpack"))
    with pytest.raises(CheckpointError, match="incomplete checkpoint"):
        SolveCheckpoint.load(str(tmp_path))


def test_raw_store_checkpoint_is_not_a_solve_checkpoint(tmp_path):
    from repro.checkpoint.solve import CheckpointError, SolveCheckpoint

    save_checkpoint(str(tmp_path), 4, {"a": jnp.zeros(2)}, extra={"x": 1})
    with pytest.raises(CheckpointError, match="not a solve checkpoint"):
        SolveCheckpoint.load(str(tmp_path))


def test_fingerprint_mismatch_refuses_resume(tmp_path):
    """Resuming under ANY changed trajectory knob (here num_workers) or a
    different instance graph must refuse with CheckpointError, not silently
    run a different solve."""
    from repro.api import CheckpointError, SolveConfig, SolverSession
    from repro.graphs.generators import erdos_renyi

    g = erdos_renyi(24, 0.3, seed=5)
    cfg = SolveConfig(
        num_workers=4, steps_per_round=2, chunk_rounds=1, checkpoint_every=1
    )
    d = str(tmp_path / "ck")
    SolverSession(config=cfg).solve(g, checkpoint_dir=d)
    assert latest_step(d) is not None
    with pytest.raises(CheckpointError, match="fingerprint mismatch"):
        SolverSession.resume(d, num_workers=8)
    # changing a POST-trajectory knob is allowed
    r = SolverSession.resume(d, max_rounds=10_000)
    assert r.found

