"""The store's two read caches: ``BlockCache`` (decoded blocks, LRU) and
``TableCache`` (open table readers, LRU), alone and inside ``LsmDB``."""

import pytest

from repro.core.formats import SSTGeometry
from repro.core.scheduler import SchedulerConfig
from repro.lsm.db import DBConfig, LsmDB
from repro.lsm.sstable import BlockCache
from repro.obs import Tracer

GEOM = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                   sst_bytes=2048)


def store(path, **kw):
    return LsmDB(path, DBConfig(
        geom=GEOM, engine="cpu", memtable_bytes=600,
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=40_000), **kw))


# ---------------------------------------------------------------------------
# BlockCache alone
# ---------------------------------------------------------------------------


def test_block_cache_evicts_least_recently_used_at_capacity():
    cache = BlockCache(3)
    blocks = {b: object() for b in range(4)}
    for b in range(3):
        cache.put(7, b, blocks[b])
    assert cache.get(7, 0) is blocks[0]       # 0 is now the newest
    cache.put(7, 3, blocks[3])                # evicts 1, the oldest
    assert len(cache) == 3
    assert cache.get(7, 1) is None
    assert [cache.get(7, b) for b in (0, 2, 3)] == [blocks[0], blocks[2],
                                                    blocks[3]]
    cache.put(7, 1, blocks[1])                # evicts 0: 2, 3 were read since
    assert cache.get(7, 0) is None
    assert len(cache) == 3


def test_block_cache_of_no_capacity_caches_nothing():
    for capacity in (0, -1):
        cache = BlockCache(capacity)
        cache.put(1, 0, object())
        assert len(cache) == 0 and cache.get(1, 0) is None


def test_block_cache_counts_each_lookup():
    seen = []
    cache = BlockCache(2, on_hit=lambda: seen.append("hit"),
                       on_miss=lambda: seen.append("miss"))
    cache.get(1, 0)
    cache.put(1, 0, object())
    for _ in range(3):
        cache.get(1, 0)
    cache.get(1, 1)
    assert seen == ["miss", "hit", "hit", "hit", "miss"]


# ---------------------------------------------------------------------------
# both caches inside the store
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tables,capacity", [(4, 8), (8, 8), (9, 8)])
def test_table_cache_reloads_only_past_its_capacity(tmp_path, tables,
                                                    capacity):
    """``tables`` L0 tables of disjoint key ranges, one get per table per
    pass, in the same order: the first pass opens every table; the
    second opens none while the cache holds them all, and every one
    when it holds fewer (LRU over a cyclic pass)."""
    tr = Tracer()
    db = store(str(tmp_path / "db"), table_cache=capacity,
               auto_compact=False, tracer=tr)
    for t in range(tables):
        for i in range(8):
            db.put(b"t%02d-key%02d" % (t, i), b"v%02d-%02d" % (t, i))
        db.flush()
    assert db.level_sizes()[0] == tables

    def table_loads():
        tr.clear()
        for t in range(tables):
            assert db.get(b"t%02d-key03" % t) == b"v%02d-03" % t
        return sum(e["name"] == "get.table_load"
                   for e in tr.to_chrome()["traceEvents"])

    assert table_loads() == tables
    assert table_loads() == (0 if tables <= capacity else tables)
    db.close()


def test_compaction_drops_its_inputs_from_both_caches(tmp_path):
    db = store(str(tmp_path / "db"))
    for r in range(3):
        for i in range(0, 40, 3):
            db.put(b"key%03d" % i, b"v%d-%03d" % (r, i))
        db.flush()
    inputs = {f.file_no for f in db.versions.current.levels[0]}
    assert len(inputs) == 3
    for i in range(0, 40, 3):
        assert db.get(b"key%03d" % i) == b"v2-%03d" % i
    assert db.get(b"key001") is None          # probes every L0 table
    assert inputs <= set(db.cache._c)
    assert inputs & {f for f, _ in db.block_cache._c}
    db.put(b"key001", b"new")
    db.flush()                                # a fourth L0 table
    db.maybe_compact()
    assert db.stats.compactions >= 1
    live = {f.file_no for level in db.versions.current.levels
            for f in level}
    assert not inputs & live
    assert not inputs & set(db.cache._c)
    assert not inputs & {f for f, _ in db.block_cache._c}
    for i in range(3, 40, 3):
        assert db.get(b"key%03d" % i) == b"v2-%03d" % i
    db.close()


def test_repeated_get_hits_the_block_cache(tmp_path):
    db = store(str(tmp_path / "db"))
    for i in range(40):
        db.put(b"key%03d" % i, b"v%03d" % i)
    db.flush()
    assert db.get(b"key017") == b"v017"
    hits, misses = db.stats.block_cache_hits, db.stats.block_cache_misses
    assert misses >= 1
    assert db.get(b"key017") == b"v017"
    assert db.stats.block_cache_hits == hits + 1
    assert db.stats.block_cache_misses == misses
    db.close()
