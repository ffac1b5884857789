"""SessionStore backends: roundtrip, batched resume bit-identity,
atomic page-out/drop under crash failpoints.

The load-path contract tested across every backend cell:
``store.load_many(names)`` is bit-identical to ``[store.load(n) for n
in names]``.  The write-path contract on the LSM backend: ``save`` and
``drop`` are single ``write_batch`` calls, so a crash mid page-out or
mid-drop leaves the session fully old / fully new / cleanly absent --
never a head pointing at missing chunks, never orphan chunks.
"""

import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import SSTGeometry
from repro.core.scheduler import SchedulerConfig
from repro.lsm import faults
from repro.lsm.db import DBConfig, LsmDB
from repro.lsm.faults import SimulatedCrash
from repro.lsm.sharded import ShardedDB, uniform_boundaries
from repro.serving.session_store import (LsmSessionStore, MemorySessionStore,
                                         SessionStore, decode_state,
                                         encode_state)

GEOM = SSTGeometry(key_bytes=16, value_bytes=256, block_bytes=4096,
                   sst_bytes=32 * 1024)


def cfg(**kw):
    return DBConfig(
        geom=GEOM, engine="cpu",
        memtable_bytes=kw.pop("memtable_bytes", 4096),
        scheduler=SchedulerConfig(l0_trigger=3, base_bytes=400_000), **kw)


def template():
    return {"kv": jnp.zeros((1, 1), jnp.float32),
            "pos": jnp.zeros((1,), jnp.int32)}


def make_state(rng, i, big=False):
    shape = (8, 97) if big else (3, 17)
    return {"kv": jnp.asarray(rng.standard_normal(shape), jnp.float32),
            "pos": jnp.asarray([i], jnp.int32)}


def assert_state_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype and xa.shape == ya.shape
        assert xa.tobytes() == ya.tobytes()


def _backends(tmp_path, template=template):
    """(name, store, closer) for every backend cell."""
    out = [("memory", MemorySessionStore(template), lambda: None)]
    for mode in ("sync", "async"):
        db = LsmDB(str(tmp_path / f"lsm-{mode}"),
                   cfg(async_compaction=(mode == "async")))
        out.append((f"lsm-{mode}", LsmSessionStore(db, template), db.close))
        sdb = ShardedDB.open(str(tmp_path / f"sharded-{mode}"),
                             cfg(async_compaction=(mode == "async")),
                             boundaries=uniform_boundaries(4))
        out.append((f"sharded-{mode}", LsmSessionStore(sdb, template),
                    sdb.close))
    return out


# ---------------------------------------------------------------------------
# roundtrip + batched-resume bit-identity on every backend
# ---------------------------------------------------------------------------


def test_load_many_bit_identical_to_scalar_on_every_backend(tmp_path):
    rng = np.random.default_rng(7)
    states = {f"s{i:02d}": make_state(rng, i, big=(i % 3 == 0))
              for i in range(10)}
    names = sorted(states)
    for name, store, close in _backends(tmp_path):
        assert isinstance(store, SessionStore), name
        for s, st in states.items():
            store.save(s, st)
        batched = store.load_many(names)
        scalar = [store.load(s) for s in names]
        for b, sc, want in zip(batched, scalar, (states[n] for n in names)):
            assert_state_equal(b, sc)
            assert_state_equal(b, want)
        close()


def _bf16_layer_cache(rng, i):
    """A decoder's cache: a (k, v) pair of bf16 [batch, heads, time, dim]
    arrays per layer, and the int32 position of each sequence."""
    kv = lambda: jnp.asarray(rng.standard_normal((2, 2, 24, 16)),
                             jnp.bfloat16)
    return {"cache": tuple((kv(), kv()) for _ in range(3)),
            "pos": jnp.asarray([i, i + 7], jnp.int32)}


def _f16_cache(rng, i):
    return {"kv": jnp.asarray(rng.standard_normal((4, 33, 8)), jnp.float16)}


def _int8_scaled_cache(rng, i):
    """Quantized cache: int8 values with one f32 scale per row."""
    return {"q": jnp.asarray(rng.integers(-128, 128, (6, 40)), jnp.int8),
            "scale": jnp.asarray(rng.random((6, 1)) + 0.01, jnp.float32)}


def _scalar_leaf_cache(rng, i):
    return {"kv": jnp.asarray(rng.standard_normal((5, 9)), jnp.float32),
            "pos": jnp.asarray(i, jnp.int32)}


@pytest.mark.parametrize("make", [_bf16_layer_cache, _f16_cache,
                                  _int8_scaled_cache, _scalar_leaf_cache])
def test_kv_cache_shaped_state_roundtrips_on_every_backend(tmp_path, make):
    """Cache pytrees of the dtypes and nestings a decoder pages: every
    leaf comes back with its dtype, shape and bytes, from ``load`` and
    ``load_many`` alike."""
    rng = np.random.default_rng(17)
    states = {f"s{i}": make(rng, i) for i in range(5)}
    names = sorted(states)
    like = jax.tree.map(jnp.zeros_like, states["s0"])
    for name, store, close in _backends(tmp_path, like):
        for s, st in states.items():
            store.save(s, st)
        for s, got in zip(names, store.load_many(names)):
            assert_state_equal(got, states[s])
            assert_state_equal(store.load(s), states[s])
        close()


def test_backends_decode_identically(tmp_path):
    # the memory backend stores the ENCODED payload, so a state read
    # back from any backend is byte-for-byte the same
    rng = np.random.default_rng(3)
    st = make_state(rng, 5, big=True)
    mem = MemorySessionStore(template)
    db = LsmDB(str(tmp_path / "db"), cfg())
    lsm = LsmSessionStore(db, template)
    mem.save("x", st)
    lsm.save("x", st)
    assert_state_equal(mem.load("x"), lsm.load("x"))
    db.close()


def test_overwrite_returns_newest_and_reclaims_tail(tmp_path):
    rng = np.random.default_rng(1)
    db = LsmDB(str(tmp_path / "db"), cfg())
    store = LsmSessionStore(db, template)
    store.save("s", make_state(rng, 0, big=True))    # many chunks
    small = make_state(rng, 1)
    store.save("s", small)                           # fewer chunks
    assert_state_equal(store.load("s"), small)
    # the shrinking overwrite deleted the stale tail in the same batch
    pref = LsmSessionStore._key("s", 0)[:8]
    n_chunks = int.from_bytes(db.get(LsmSessionStore._key("s", 0))[:4],
                              "big")
    rows = db.scan(pref, pref + b"\xff" * 8)
    assert len(rows) == n_chunks + 1
    db.close()


def test_missing_session_semantics(tmp_path):
    db = LsmDB(str(tmp_path / "db"), cfg())
    store = LsmSessionStore(db, template)
    rng = np.random.default_rng(0)
    store.save("have", make_state(rng, 0))
    with pytest.raises(KeyError, match="nope"):
        store.load("nope")
    with pytest.raises(KeyError, match="nope"):
        store.load_many(["have", "nope"])
    out = store.load_many(["nope", "have"], missing_ok=True)
    assert out[0] is None
    assert_state_equal(out[1], store.load("have"))
    assert store.exists("have") and not store.exists("nope")
    db.close()


def test_drop_removes_head_and_all_chunks(tmp_path):
    rng = np.random.default_rng(2)
    db = LsmDB(str(tmp_path / "db"), cfg())
    store = LsmSessionStore(db, template)
    store.save("s", make_state(rng, 0, big=True))
    pref = LsmSessionStore._key("s", 0)[:8]
    assert db.scan(pref, pref + b"\xff" * 8)
    assert store.drop("s") is True
    assert db.scan(pref, pref + b"\xff" * 8) == []   # no orphan chunks
    assert store.drop("s") is False
    with pytest.raises(KeyError):
        store.load("s")
    db.close()


def test_encode_decode_roundtrip_pure():
    rng = np.random.default_rng(9)
    st = make_state(rng, 4)
    meta, raw = encode_state(st)
    assert_state_equal(decode_state(meta, raw, template()), st)
    # wrong template shape -> loud error, not garbage
    with pytest.raises(IOError, match="leaves"):
        decode_state(meta, raw, {"only": jnp.zeros((1,))})


# ---------------------------------------------------------------------------
# crash failpoints: page-out and drop are all-or-nothing
# ---------------------------------------------------------------------------


def _reopen(tmp_path, path, sharded=False):
    faults.FAILPOINTS.clear()
    crash = str(tmp_path / "crash")
    shutil.copytree(path, crash)
    shutil.rmtree(path)
    if sharded:
        return ShardedDB.open(crash, cfg(), repair=True)
    return LsmDB.open(crash, cfg(), repair=True)


def test_crash_mid_page_out_after_wal_resumes_new_state(tmp_path):
    rng = np.random.default_rng(11)
    old, new = make_state(rng, 0), make_state(rng, 1, big=True)
    path = str(tmp_path / "db")
    db = LsmDB(path, cfg(sync_writes=True,
                         failpoints={"db.write_batch": "crash:a1:x1"}))
    store = LsmSessionStore(db, template)
    store.save("s", old)            # batch #1: acked baseline
    with pytest.raises(SimulatedCrash):
        store.save("s", new)        # batch #2 dies after the WAL append
    db2 = _reopen(tmp_path, path)
    store2 = LsmSessionStore(db2, template)
    # the WAL record was durable: the NEW state is fully resumable
    assert_state_equal(store2.load("s"), new)
    db2.close()


def test_torn_page_out_keeps_old_state_fully(tmp_path):
    rng = np.random.default_rng(12)
    old, new = make_state(rng, 0), make_state(rng, 1, big=True)
    path = str(tmp_path / "db")
    db = LsmDB(path, cfg(sync_writes=True,
                         failpoints={"wal.append": "torn:a1:x1"}))
    store = LsmSessionStore(db, template)
    store.save("s", old)            # WAL append #1: acked baseline
    with pytest.raises(SimulatedCrash):
        store.save("s", new)        # append #2 tears mid-record
    db2 = _reopen(tmp_path, path)
    store2 = LsmSessionStore(db2, template)
    # the torn batch was discarded wholesale: OLD state fully intact
    assert_state_equal(store2.load("s"), old)
    db2.close()


@pytest.mark.parametrize("spec,survives", [
    ({"db.write_batch": "crash:a1:x1"}, False),   # WAL durable: drop lands
    ({"wal.append": "torn:a1:x1"}, True),         # torn: drop discarded
])
def test_crash_mid_drop_fully_present_or_fully_absent(tmp_path, spec,
                                                      survives):
    rng = np.random.default_rng(13)
    st = make_state(rng, 0, big=True)
    path = str(tmp_path / "db")
    db = LsmDB(path, cfg(sync_writes=True, failpoints=spec))
    store = LsmSessionStore(db, template)
    store.save("s", st)             # fires the a1-skipped first hit
    with pytest.raises(SimulatedCrash):
        store.drop("s")
    db2 = _reopen(tmp_path, path)
    store2 = LsmSessionStore(db2, template)
    pref = LsmSessionStore._key("s", 0)[:8]
    rows = db2.scan(pref, pref + b"\xff" * 8)
    if survives:
        assert_state_equal(store2.load("s"), st)  # fully resumable
        n = int.from_bytes(rows[0][1][:4], "big")
        assert len(rows) == n + 1
    else:
        with pytest.raises(KeyError):
            store2.load("s")
        assert rows == []           # cleanly absent, no orphan chunks
    db2.close()


def test_sharded_session_routes_to_one_shard_and_drops_atomically(tmp_path):
    rng = np.random.default_rng(14)
    st = make_state(rng, 0, big=True)
    path = str(tmp_path / "db")
    sdb = ShardedDB.open(path, cfg(sync_writes=True,
                                   failpoints={"db.write_batch": "crash:x1"}),
                         boundaries=uniform_boundaries(4))
    store = LsmSessionStore(sdb, template)
    # all keys of one session share the 8-byte hash prefix -> one shard
    keys = [LsmSessionStore._key("s", i) for i in range(4)]
    assert len({sdb.shard_of(k) for k in keys}) == 1
    with pytest.raises(SimulatedCrash):
        store.save("s", st)
    db2 = _reopen(tmp_path, path, sharded=True)
    store2 = LsmSessionStore(db2, template)
    # the single-shard batch was durable: fully resumable
    assert_state_equal(store2.load("s"), st)
    assert store2.drop("s")
    pref = LsmSessionStore._key("s", 0)[:8]
    assert db2.scan(pref, pref + b"\xff" * 8) == []
    db2.close()
