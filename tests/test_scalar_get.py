"""The scalar get's filter check: one key hashed once per get, each
table's filter row checked in Python integers, bit for bit the answer
of the numpy filter query and of the rows either engine builds."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats
from repro.core.formats import SSTGeometry
from repro.core.scheduler import SchedulerConfig
from repro.kernels import ops
from repro.lsm import cpu_engine as ce
from repro.lsm import sstable
from repro.lsm.db import DBConfig, LsmDB
from repro.obs import Tracer

GEOM = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                   sst_bytes=2048)


def _random_keys(rng, n):
    """Distinct user keys of 1 to 16 bytes, none ending with NUL."""
    keys = set()
    while len(keys) < n:
        k = bytes(rng.integers(0, 256, int(rng.integers(1, 17)),
                               dtype=np.uint8))
        if not k.endswith(b"\x00"):
            keys.add(k)
    return sorted(keys)


@pytest.mark.parametrize("granularity", ["block", "sst"])
def test_scalar_filter_check_matches_np_bloom_query(granularity):
    geom = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                       sst_bytes=2048, bloom_granularity=granularity)
    rng = np.random.default_rng(4)
    groups, per = geom.filter_groups(geom.sst_kvs)
    n_words = geom.bloom_words(per)
    members = _random_keys(rng, groups * per)
    lanes = np.stack([formats.pack_key_bytes(k, geom.key_bytes)
                      for k in members]).reshape(groups, per, -1)
    valid = rng.random((groups, per)) < 0.8
    rows = ce.np_bloom_build(lanes, valid, n_words, geom.bloom_probes)
    # the device kernel writes the same rows
    dev = ops.bloom_build(jnp.asarray(lanes),
                          jnp.asarray(valid.astype(np.uint32)),
                          n_words=n_words, n_probes=geom.bloom_probes)
    np.testing.assert_array_equal(np.asarray(dev), rows)
    probes = members + _random_keys(rng, 600)
    checked = {True: 0, False: 0}
    for key in probes:
        h = ce.bloom_hashes_of(key, geom.key_bytes)
        probe_lanes = formats.pack_key_bytes(key, geom.key_bytes)
        for g in range(groups):
            want = bool(ce.np_bloom_query(rows[g][None],
                                          probe_lanes[None, None, :],
                                          geom.bloom_probes)[0, 0])
            got = ce.bloom_row_may_contain(rows[g], *h, geom.bloom_probes)
            assert got == want, (key, g)
            checked[want] += 1
    # members are never pruned from their own row
    for i, key in enumerate(members):
        g, j = divmod(i, per)
        if valid[g, j]:
            assert ce.bloom_row_may_contain(
                rows[g], *ce.bloom_hashes_of(key, geom.key_bytes),
                geom.bloom_probes)
    assert checked[True] and checked[False]


def test_filterless_table_is_never_pruned(tmp_path):
    eng = ce.CpuCompactionEngine(GEOM)
    keys = [b"key%04d" % i for i in range(0, 200, 2)]
    img = eng.build_image(
        np.stack([formats.pack_key_bytes(k, GEOM.key_bytes) for k in keys]),
        np.array([(i + 1) << 1 | 1 for i in range(len(keys))], np.uint32),
        np.stack([formats.pack_value_bytes(k, GEOM.value_bytes)
                  for k in keys]))
    img = img._replace(bloom=np.zeros((0, 1), np.uint32))
    path = str(tmp_path / "000001.sst")
    meta = sstable.write_sst(path, img, 1)
    rdr = sstable.TableReader(meta, GEOM)
    tr = Tracer()
    for i in range(200):
        key = b"key%04d" % i
        found, value, pruned = rdr.probe(key, tracer=tr)
        assert not pruned
        assert (found, value) == ((True, key) if i % 2 == 0
                                  else (False, None))
    assert not _spans(tr, "get.filter")


def _spans(tr, name):
    return [e for e in tr.to_chrome()["traceEvents"] if e["name"] == name]


def _store(path, engine, tracer=None):
    return LsmDB(path, DBConfig(
        geom=GEOM, engine=engine, memtable_bytes=600, tracer=tracer,
        scheduler=SchedulerConfig(l0_trigger=3, base_bytes=40_000)))


def test_get_identical_across_engines(tmp_path):
    """Stores written by the device engine and by the CPU engine answer
    every get alike, tombstones included, and as the dict does; the
    filter checks of the traced store record ``get.filter`` spans."""
    rng = np.random.default_rng(21)
    tr = Tracer()
    dbs = [_store(str(tmp_path / "dev"), "device", tracer=tr),
           _store(str(tmp_path / "cpu"), "cpu")]
    model = {}
    for i in range(700):
        k = b"key%03d" % rng.integers(0, 150)
        if rng.random() < 0.2:
            for db in dbs:
                db.delete(k)
            model[k] = None
        else:
            v = b"v%06d" % i
            for db in dbs:
                db.put(k, v)
            model[k] = v
    for db in dbs:
        db.flush()
    assert all(db.stats.compactions > 0 for db in dbs)
    probes = [b"key%03d" % i for i in range(160)] + [b"kez", b"a", b"zzz"]
    assert any(v is None for v in model.values())
    for k in probes:
        want = model.get(k)
        assert [db.get(k) for db in dbs] == [want, want], k
    filters = _spans(tr, "get.filter")
    assert filters and all(isinstance(e["args"]["pruned"], bool)
                           for e in filters)
    assert any(e["args"]["pruned"] for e in filters)
    for db in dbs:
        db.close()


def test_get_hashes_a_key_once_and_only_for_a_filter_check(tmp_path,
                                                           monkeypatch):
    """A get hashes its key at most once, however many tables it visits,
    and not at all where no filter is checked (a key outside every
    table, or a cached block); a key longer than the geometry's is
    absent and never hashed."""
    db = _store(str(tmp_path / "db"), "cpu")
    for i in range(300):
        db.put(b"key%03d" % (i % 120), b"v%06d" % i)
    db.flush()
    calls = []
    hashes_of = ce.bloom_hashes_of
    monkeypatch.setattr(ce, "bloom_hashes_of",
                        lambda *a: calls.append(a) or hashes_of(*a))
    for k in [b"key%03d" % i for i in range(120)] + [b"kez%03d" % i
                                                     for i in range(40)]:
        n = len(calls)
        db.get(k)
        assert len(calls) - n <= 1, k
    assert calls
    n = len(calls)
    assert db.get(b"zzz") is None and db.get(b"a") is None
    assert db.get(b"key000" + b"x" * GEOM.key_bytes) is None
    assert len(calls) == n
    db.close()
