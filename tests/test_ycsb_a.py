"""YCSB workload A on the device engine, against a plain dict.

YCSB-A (Cooper et al., SoCC 2010) is 50% reads and 50% updates of
existing records, zipfian 0.99 over the keys, here with 16 B keys and
128 B values.  Zipfian updates put several versions of one key into the
runs of one compaction job, so the merge's tie order (key, then sequence
number descending) and the survivor mask decide which version a reader
gets back.  The store runs as it is deployed: ``engine="device"``,
``sort_mode="merge"``, asynchronous flush and compaction; every get of
the stream is checked against the dict, then every key is read back
before and after reopen.  In synchronous mode the device and CPU engines
write the same SST files byte for byte.
"""

import filecmp
import os

import numpy as np

from repro.core.formats import SSTGeometry
from repro.core.scheduler import SchedulerConfig
from repro.lsm.db import DBConfig, LsmDB

VALUE = 128
#: 16 B keys, 128 B values in a 144 B slot (4 B length prefix + slack);
#: 4 KB blocks hold 16 entries (rounded to the restart interval), 16
#: blocks to an SST
GEOM = SSTGeometry(key_bytes=16, value_bytes=VALUE + 16, block_bytes=4096,
                   sst_bytes=16 * 4096, restart_interval=16,
                   bloom_bits_per_key=10)
#: a memtable holds 125 distinct records and each flush writes one L0 file
#: of 8 blocks; the records fit one L1 file of 16 blocks.  So the first
#: compaction job (four L0 files) lays out in 4 slots of 8 blocks and the
#: later ones (four to seven L0 files and the L1 file) in 8 slots of 16
#: blocks.  The synchronous test runs first and compiles both programs
MEMTABLE_RECORDS = 125
RECORDS = 250
OPS = 6000
THETA = 0.99
SEED = 2**31 + 15

_MULT, _MASK = 0x9E3779B97F4A7C15, (1 << 48) - 1


def key_of(i: int) -> bytes:
    """YCSB's hashed record key: ``user`` + 12 hex digits."""
    return b"user%012x" % ((i * _MULT) & _MASK)


def value_of(tag: int) -> bytes:
    return ((b"%016d" % tag) * (VALUE // 16 + 1))[:VALUE]


def ycsb_a_ops(seed: int = SEED, n: int = OPS, records: int = RECORDS):
    """``[(is_update, record id)]``: half gets, half updates, record ids
    zipfian (theta 0.99) by rank."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, records + 1) ** THETA
    ids = rng.choice(records, size=n, p=p / p.sum())
    kinds = rng.permutation(np.arange(n) % 2)
    return [(bool(k), int(i)) for k, i in zip(kinds, ids)]


def config(engine: str, *, async_mode: bool) -> DBConfig:
    return DBConfig(
        geom=GEOM, engine=engine, sort_mode="merge",
        memtable_bytes=MEMTABLE_RECORDS * (16 + VALUE),
        scheduler=SchedulerConfig(l0_trigger=4, base_bytes=1 << 20),
        block_cache_blocks=16, async_compaction=async_mode,
        flush_workers=1, max_pending_memtables=4)


def run_ycsb_a(db: LsmDB, ref: dict) -> int:
    """Preload, then the op stream; returns gets that disagree with
    ``ref``."""
    for i in range(RECORDS):
        db.put(key_of(i), value_of(i))
        ref[key_of(i)] = value_of(i)
    bad = 0
    for j, (update, i) in enumerate(ycsb_a_ops()):
        k = key_of(i)
        if update:
            v = value_of(10**9 + j)
            db.put(k, v)
            ref[k] = v
        else:
            bad += db.get(k) != ref[k]
    return bad


def read_back(db: LsmDB, ref: dict) -> int:
    absent = [key_of(RECORDS + 10**6 + j) for j in range(50)]
    keys = list(ref) + absent
    bad = sum(db.get(k) != ref.get(k) for k in keys)
    got = db.multi_get(keys)
    return bad + sum(g != ref.get(k) for g, k in zip(got, keys))


def test_ycsb_a_sync_engines_write_the_same_files(tmp_path):
    """Each engine replays the stream in synchronous mode; the device
    engine's SST files equal the CPU engine's byte for byte."""
    def build(engine: str) -> tuple[str, list[str], int]:
        path = str(tmp_path / engine)
        db = LsmDB(path, config(engine, async_mode=False))
        ref = {}
        try:
            assert run_ycsb_a(db, ref) == 0
            db.flush()
            db.maybe_compact()
            assert read_back(db, ref) == 0
            dropped = db.stats.compact_entries_dropped
        finally:
            db.close()
        return path, sorted(f for f in os.listdir(path)
                            if f.endswith(".sst")), dropped

    cpu_path, cpu_files, cpu_dropped = build("cpu")
    dev_path, dev_files, dev_dropped = build("device")
    assert cpu_files and cpu_dropped > 0
    assert dev_files == cpu_files and dev_dropped == cpu_dropped
    for name in cpu_files:
        assert filecmp.cmp(os.path.join(cpu_path, name),
                           os.path.join(dev_path, name), shallow=False), name


def test_ycsb_a_on_the_device_engine_matches_a_dict(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, config("device", async_mode=True))
    l0_drops = []
    install = db.apply_compaction

    def apply_compaction(job, out, es):
        install(job, out, es)
        if job.level == 0:
            l0_drops.append(int(es.n_dropped))
    db.apply_compaction = apply_compaction
    ref = {}
    try:
        assert run_ycsb_a(db, ref) == 0
        db.wait_idle()
        assert read_back(db, ref) == 0
        # shadowed versions went through the device merge and were dropped
        assert any(d > 0 for d in l0_drops), l0_drops
        assert db.stats.compact_entries_dropped > 0
        assert db.stats.compact_bytes_out < db.stats.compact_bytes_in
        assert db.stats.engine_fallbacks == 0
        assert db.engine.fallbacks == 0 and db.engine.launch_retries == 0
    finally:
        db.close()
    db = LsmDB(path, config("device", async_mode=True))
    try:
        assert read_back(db, ref) == 0
    finally:
        db.close()
