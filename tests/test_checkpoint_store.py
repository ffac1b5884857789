"""CheckpointStore: pytree checkpoints paged into the LSM store, restored
bit-identical after reopen, gc and compaction, and after a crash in the
middle of a save."""

import dataclasses
import json
import shutil

import jax
import numpy as np
import pytest

from repro.checkpoint.store import (CHUNK_BYTES, CheckpointStore,
                                    _hash_path, _key, checkpoint_db_config)
from repro.lsm import faults
from repro.lsm.faults import SimulatedCrash


def one_device_mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def params(step, layers=2):
    """A parameter tree whose values follow from ``step``: one tensor of
    several chunks, small per-layer tensors and a scalar."""
    rng = np.random.default_rng(step)
    return {"emb": rng.standard_normal((2, 1500)).astype(np.float32),
            "layers": [{"w": rng.standard_normal((4, 4)).astype(np.float32),
                        "n": rng.integers(0, 1 << 20, (4,), np.int32)}
                       for _ in range(layers)],
            "step": np.int32(step)}


def assert_tree_equal(got, want):
    lg, lw = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(lg) == len(lw)
    for g, w in zip(lg, lw):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


# ---------------------------------------------------------------------------
# round trip
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": np.arange(10000, dtype=np.float32).reshape(100, 100),
            "b": {"c": np.ones((7,), np.int32),
                  "d": np.float32(3.5)}}
    store = CheckpointStore(str(tmp_path / "ck"))
    store.save(3, tree)
    got = store.restore(3, like=tree)
    for (p1, l1), (p2, l2) in zip(
            jax.tree_util.tree_flatten_with_path(tree)[0],
            jax.tree_util.tree_flatten_with_path(got)[0]):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    store.close()


def test_checkpoint_steps_and_gc(tmp_path):
    store = CheckpointStore(str(tmp_path / "ck"))
    tree = {"w": np.random.default_rng(0).standard_normal((64, 64))
            .astype(np.float32)}
    for s in (5, 10, 15):
        store.save(s, tree)
    assert store.steps() == [5, 10, 15]
    store.gc(keep_steps=[15])
    assert store.steps() == [15]
    with pytest.raises(KeyError):
        store.restore(5, like=tree)
    got = store.restore(15, like=tree)
    np.testing.assert_array_equal(got["w"], tree["w"])
    store.close()


def test_checkpoint_restore_onto_new_sharding(tmp_path):
    """Mesh-agnostic restore: save from host arrays, restore as sharded
    device arrays (the elastic-restart path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    store = CheckpointStore(str(tmp_path / "ck"))
    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    store.save(1, tree)
    mesh = one_device_mesh()
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    got = store.restore(1, like=tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(got["w"]), tree["w"])
    assert got["w"].sharding == sh["w"]
    store.close()


def test_resave_of_a_step_returns_the_newest(tmp_path):
    store = CheckpointStore(str(tmp_path / "ck"))
    store.save(5, params(1))
    newer = params(2)
    newer["emb"] = newer["emb"][:, :100]     # fewer chunks than before
    store.save(5, newer)
    assert store.steps() == [5]
    assert_tree_equal(store.restore(5, like=newer), newer)
    store.close()


# ---------------------------------------------------------------------------
# reopen, gc and compaction on either engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["cpu", "device"])
def test_reopen_restores_every_kept_step(tmp_path, engine):
    """Five saves flush five L0 tables, which compact (L0 trigger 4);
    every step restores bit-identical after a reopen."""
    path, cfg = str(tmp_path / "ck"), checkpoint_db_config(engine)
    store = CheckpointStore(path, cfg)
    steps = [1, 2, 3, 4, 5]
    for s in steps:
        store.save(s, params(s))
    store.db.maybe_compact()
    assert store.db.stats.compactions >= 1
    assert store.steps() == steps
    store.close()
    store = CheckpointStore(path, cfg)
    assert store.steps() == steps
    for s in steps:
        assert_tree_equal(store.restore(s, like=params(s)), params(s))
    store.close()


@pytest.mark.parametrize("engine", ["cpu", "device"])
def test_gc_survives_compaction_and_reopen(tmp_path, engine):
    """gc's tombstones flush as a fourth L0 table, and the compaction
    they trigger drops the deleted steps' records for good."""
    path, cfg = str(tmp_path / "ck"), checkpoint_db_config(engine)
    store = CheckpointStore(path, cfg)
    for s in (1, 2, 3):
        store.save(s, params(s))
    store.gc(keep_steps=[3])
    store.db.flush()
    store.db.maybe_compact()
    assert store.db.stats.compact_entries_dropped > 0
    store.close()
    store = CheckpointStore(path, cfg)
    assert store.steps() == [3]
    for s in (1, 2):
        with pytest.raises(KeyError):
            store.restore(s, like=params(s))
    assert_tree_equal(store.restore(3, like=params(3)), params(3))
    store.close()


# ---------------------------------------------------------------------------
# crash in the middle of a save
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["tensors", "manifest"])
def test_crash_mid_save_keeps_the_previous_step(tmp_path, where):
    """``save`` writes the step's tensor chunks, then its manifest chunks,
    then the ``//manifest-len`` record that makes the step visible.  A
    crash before that last record leaves only the earlier step listed,
    and it restores bit-identical."""
    path = str(tmp_path / "ck")
    cfg = dataclasses.replace(checkpoint_db_config("cpu"), sync_writes=True)
    layers = 32             # a manifest of more than one chunk
    store = CheckpointStore(path, cfg)
    manifest = store.save(1, params(1, layers))
    store.close()
    tensor_chunks = sum(t["chunks"] for t in manifest["tensors"])
    manifest_chunks = -(-len(json.dumps(manifest).encode()) // CHUNK_BYTES)
    assert tensor_chunks >= 2 and manifest_chunks >= 2
    # WAL appends that succeed before the crash: one tensor chunk, or
    # every tensor chunk and the first manifest chunk
    after = 1 if where == "tensors" else tensor_chunks + 1
    crashing = CheckpointStore(path, dataclasses.replace(
        cfg, failpoints={"wal.append": f"crash:a{after}:x1"}))
    try:
        with pytest.raises(SimulatedCrash):
            crashing.save(2, params(2, layers))
    finally:
        faults.FAILPOINTS.clear()
    crash = str(tmp_path / "crash")
    shutil.copytree(path, crash)
    store = CheckpointStore(crash, cfg)
    # the records written before the crash are durable: the crash fell
    # where the case says
    manifest_key = _key(_hash_path("//manifest"), 2, 0)
    assert (store.db.get(manifest_key) is not None) == (where == "manifest")
    assert store.db.get(_key(_hash_path("emb"), 2, 0)) is not None
    assert store.steps() == [1]
    assert_tree_equal(store.restore(1, like=params(1, layers)),
                      params(1, layers))
    with pytest.raises(KeyError):
        store.restore(2, like=params(2, layers))
    store.close()
