"""ShardedDB: routing, cross-shard scans, batched device compactions,
per-shard crash isolation."""

import os
import shutil

import numpy as np
import pytest

from repro.core.formats import SSTGeometry
from repro.core.offload import run_slots
from repro.core.scheduler import SchedulerConfig
from repro.lsm import faults
from repro.lsm.db import DBConfig, LsmDB
from repro.lsm.sharded import (ShardedDB, boundaries_from_sample,
                               uniform_boundaries)

GEOM = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                   sst_bytes=2048)


def scfg(engine="device", **kw):
    return DBConfig(
        geom=GEOM, engine=engine,
        memtable_bytes=kw.pop("memtable_bytes", 600),
        scheduler=SchedulerConfig(l0_trigger=3, base_bytes=40_000),
        **kw)


def rand_key(rng):
    # first byte spreads across the uniform boundary table
    return bytes([int(rng.integers(1, 255))]) + b"k%04d" % rng.integers(0, 300)


# ---------------------------------------------------------------------------
# boundary tables + routing
# ---------------------------------------------------------------------------


def test_uniform_boundaries_routing(tmp_path):
    db = ShardedDB(str(tmp_path / "sh"), scfg(), shards=4)
    assert db.n_shards == 4
    assert db.boundaries == [b"\x40", b"\x80", b"\xc0"]
    assert db.shard_of(b"\x01") == 0
    assert db.shard_of(b"\x40") == 1   # boundary belongs to the right shard
    assert db.shard_of(b"\xff") == 3
    db.put(b"\x01aa", b"v0")
    db.put(b"\x90bb", b"v2")
    assert db.shards[0].stats.puts == 1
    assert db.shards[2].stats.puts == 1
    assert db.get(b"\x01aa") == b"v0"
    assert db.get(b"\x90bb") == b"v2"
    db.close()


def test_boundaries_from_sample_balances_skewed_keys():
    # YCSB-style keys live in a thin byte-space slice: uniform splits
    # would route everything to one shard, sample splits balance
    keys = [b"user%012d" % i for i in range(1000)]
    cuts = boundaries_from_sample(keys, 4)
    assert len(cuts) == 3 and cuts == sorted(cuts)
    import bisect
    counts = [0] * 4
    for k in keys:
        counts[bisect.bisect_right(cuts, k)] += 1
    assert max(counts) - min(counts) <= 2
    with pytest.raises(ValueError):
        boundaries_from_sample([b"same"] * 10, 4)
    with pytest.raises(ValueError):
        uniform_boundaries(1000)


def test_boundary_table_persisted_and_conflict_checked(tmp_path):
    path = str(tmp_path / "sh")
    keys = [b"user%012d" % i for i in range(200)]
    db = ShardedDB(path, scfg(), shards=4, sample_keys=keys)
    cuts = db.boundaries
    for i in range(50):
        db.put(keys[i], b"v%d" % i)
    db.close()
    db2 = ShardedDB(path, scfg(), shards=4)   # reopen: table from disk
    assert db2.boundaries == cuts
    assert db2.get(keys[7]) == b"v7"
    db2.close()
    with pytest.raises(ValueError):
        ShardedDB(path, scfg(), boundaries=[b"zzz"])


# ---------------------------------------------------------------------------
# randomized cross-shard scan vs single-DB oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [1, 4])
def test_sharded_matches_single_db_oracle(tmp_path, shards):
    db = ShardedDB(str(tmp_path / "sh"), scfg(), shards=shards)
    oracle = LsmDB(str(tmp_path / "oracle"), scfg())
    rng = np.random.default_rng(7)
    keys = []
    for i in range(900):
        k = rand_key(rng)
        keys.append(k)
        if rng.random() < 0.12:
            db.delete(k)
            oracle.delete(k)
        else:
            v = b"v%06d" % i
            db.put(k, v)
            oracle.put(k, v)
    db.flush()
    oracle.flush()
    db.maybe_compact()
    oracle.maybe_compact()
    for k in keys[:200]:
        assert db.get(k) == oracle.get(k), k
    # randomized range scans, including cross-boundary and full-range
    for _ in range(25):
        a, b = sorted(int(x) for x in rng.integers(0, 256, 2))
        start, end = bytes([a]), bytes([min(b + 1, 255)]) + b"\xff"
        assert db.scan(start, end) == oracle.scan(start, end), (start, end)
    assert db.scan(b"\x00", b"\xff\xff") == oracle.scan(b"\x00", b"\xff\xff")
    assert db.stats.puts == oracle.stats.puts
    db.close()
    oracle.close()


# ---------------------------------------------------------------------------
# batched compactions
# ---------------------------------------------------------------------------


def _sst_writer(eng, tmp_path, rng):
    """``make_sst(prefix, n, dead=0)``: write an SST of ``n`` sorted keys
    under ``prefix`` (every ``dead``-th a tombstone) and return its
    path."""
    from repro.core import formats
    from repro.lsm import sstable
    no = [0]

    def make_sst(prefix, n, dead=0):
        keys = sorted(prefix + b"key%04d" % int(x)
                      for x in rng.choice(2000, n, replace=False))
        karr = np.stack([formats.pack_key_bytes(k, GEOM.key_bytes)
                         for k in keys])
        meta = np.array([(i + 1) << 1 | bool(not dead or i % dead)
                         for i in range(n)], np.uint32)
        vals = np.stack([formats.pack_value_bytes(b"v%d" % i,
                                                  GEOM.value_bytes)
                         for i in range(n)])
        img = eng.build_image(karr, meta, vals)
        no[0] += 1
        p = str(tmp_path / ("%06d.sst" % no[0]))
        sstable.write_sst(p, img, no[0])
        return p
    return make_sst


def test_batch_signature_is_the_run_slot_class():
    """(slots, slot blocks): a power-of-two run count and a power-of-two
    block count that holds the largest run -- the jobs of a store at the
    paper's geometry (4 MiB SSTs of 1,024 blocks).  The bottom level is
    a traced flag, not part of the signature."""
    l0 = [250, 253, 253, 253, 1024, 788]
    assert run_slots(l0) == (8, 1024)
    assert run_slots([253] * 4 + [1024, 770]) == (8, 1024)
    assert run_slots([1024] * 14) == run_slots([1024] * 15) == (16, 1024)
    assert run_slots([1024] * 17) == (32, 1024)
    assert run_slots([256] * 4) == (4, 256)
    assert run_slots([256, 257]) == (2, 512)
    assert run_slots([7]) == (1, 8)


def test_compact_many_bit_identical_and_batched(tmp_path):
    """compact_many must (a) coalesce >=2 same-bucket jobs into one
    stacked launch and (b) emit output bit-identical to sequential
    per-job compact_paths."""
    from repro.lsm.cpu_engine import DeviceCompactionEngine

    eng = DeviceCompactionEngine(GEOM)
    rng = np.random.default_rng(3)
    make_sst = _sst_writer(eng, tmp_path, rng)

    # 3 jobs: two share a shape bucket, one is bigger (own bucket)
    jobs = [([make_sst(b"a", 25), make_sst(b"a", 30)], False),
            ([make_sst(b"b", 28), make_sst(b"b", 24)], False),
            ([make_sst(b"c", 120), make_sst(b"c", 110)], True)]
    sigs = [run_slots([max(1, -(-n // GEOM.block_kvs)) for n in (25, 30)]),
            run_slots([max(1, -(-n // GEOM.block_kvs)) for n in (28, 24)])]
    assert sigs[0] == sigs[1]   # the two small jobs really share a bucket

    seq = [eng.compact_paths(p, bottom_level=b) for p, b in jobs]
    launches0 = eng.batch_launches
    batched = eng.compact_many(jobs)
    assert eng.batch_launches == launches0 + 1   # ONE stacked launch
    assert eng.batch_jobs >= 2 and eng.max_batch_jobs >= 2
    for (o1, s1), (o2, s2) in zip(seq, batched):
        for a, b, name in zip(o1, o2, o1._fields):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        assert (s1.n_input, s1.n_live, s1.n_dropped, s1.crc_ok,
                s1.bytes_in, s1.bytes_out) == \
               (s2.n_input, s2.n_live, s2.n_dropped, s2.crc_ok,
                s2.bytes_in, s2.bytes_out)
    # the odd-shaped job fell back to the single path, un-batched
    assert batched[2][1].batched is False
    assert batched[0][1].batched and batched[1][1].batched


def test_compact_many_isolates_per_job_crc_verdicts(tmp_path):
    """A corrupt input must fail ITS job only -- batch mates still verify."""
    from repro.lsm import sstable
    from repro.lsm.cpu_engine import DeviceCompactionEngine

    eng = DeviceCompactionEngine(GEOM)
    rng = np.random.default_rng(5)
    make_sst = _sst_writer(eng, tmp_path, rng)

    jobs = [([make_sst(b"a", 25), make_sst(b"a", 30)], False),
            ([make_sst(b"b", 26), make_sst(b"b", 29)], False)]
    # flip a payload bit in job 1's first input, keeping the file CRC valid
    bad = jobs[1][0][0]
    img = sstable.read_sst(bad)
    vals = np.asarray(img.vals).copy()
    vals[0, 0, 0] ^= 1
    sstable.write_sst(bad, img._replace(vals=vals),
                      int(os.path.basename(bad).split(".")[0]))
    results = eng.compact_many(jobs)
    assert results[0][1].crc_ok is True
    assert results[1][1].crc_ok is False
    assert eng.max_batch_jobs >= 2   # they still rode one launch


def test_compact_many_stacks_jobs_of_one_slot_class(tmp_path):
    """Jobs with other run counts and lengths but one run-slot class
    (at most 4 runs of at most 4 blocks) ride one stacked launch, laid
    out in one image, bit-identical to the single-job path."""
    from repro.lsm.cpu_engine import DeviceCompactionEngine

    eng = DeviceCompactionEngine(GEOM)
    make_sst = _sst_writer(eng, tmp_path, np.random.default_rng(9))
    jobs = [([make_sst(b"a", 60), make_sst(b"a", 30), make_sst(b"a", 17)],
             False),
            ([make_sst(b"b", 64), make_sst(b"b", 5), make_sst(b"b", 40),
              make_sst(b"b", 64)], False)]
    seq = [eng.compact_paths(p, bottom_level=b) for p, b in jobs]
    launches0 = eng.batch_launches
    batched = eng.compact_many(jobs)
    assert eng.batch_launches == launches0 + 1
    assert ("batch", 2, 4, 4) in eng.jit_signature_counts
    for (o1, s1), (o2, s2) in zip(seq, batched):
        assert s2.batched and (s1.n_live, s1.bytes_out) == \
            (s2.n_live, s2.bytes_out)
        for a, b, name in zip(o1, o2, o1._fields):
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_compact_many_stacks_bottom_and_non_bottom_jobs(tmp_path):
    """The bottom level is a per-job flag of the stacked launch: a bottom
    job and a non-bottom job of one run-slot class ride one launch, each
    bit-identical to its single-job run."""
    from repro.lsm.cpu_engine import DeviceCompactionEngine

    eng = DeviceCompactionEngine(GEOM)
    make_sst = _sst_writer(eng, tmp_path, np.random.default_rng(10))
    jobs = [([make_sst(b"a", 60, dead=3), make_sst(b"a", 30)], False),
            ([make_sst(b"a", 50, dead=3), make_sst(b"b", 64)], True)]
    seq = [eng.compact_paths(p, bottom_level=b) for p, b in jobs]
    # the bottom job drops its tombstones, the other keeps them
    out0 = seq[0][0]
    valid0 = np.arange(GEOM.block_kvs) < np.asarray(out0.nvalid)[:, None]
    assert np.any((np.asarray(out0.meta)[valid0] & 1) == 0)
    assert seq[1][1].n_dropped >= 50 // 3 and seq[1][1].n_input == 114
    launches0 = eng.batch_launches
    batched = eng.compact_many(jobs)
    assert eng.batch_launches == launches0 + 1
    assert ("batch", 2, 2, 4) in eng.jit_signature_counts
    for (o1, s1), (o2, s2) in zip(seq, batched):
        assert s2.batched and (s1.n_live, s1.n_dropped, s1.bytes_out) == \
            (s2.n_live, s2.n_dropped, s2.bytes_out)
        for a, b, name in zip(o1, o2, o1._fields):
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_compact_many_launches_a_singleton_in_its_staged_class(tmp_path):
    """Every group of a call is laid out before the first launch: a
    (4, 4) job behind an (8, 4) job launches the (4, 4) image it was
    laid out in and is counted under that class, with no class reuse,
    bit-identical to its single-job run."""
    from repro.lsm.cpu_engine import DeviceCompactionEngine
    from repro.obs import Tracer

    tracer = Tracer()
    eng = DeviceCompactionEngine(GEOM, tracer=tracer)
    make_sst = _sst_writer(eng, tmp_path, np.random.default_rng(12))
    jobs = [([make_sst(b"a", 64) for _ in range(5)], False),
            ([make_sst(b"b", 64), make_sst(b"b", 30), make_sst(b"b", 50)],
             True)]
    out = eng.compact_many(jobs)
    assert eng.jit_signature_counts == {("one", 8, 4): 1, ("one", 4, 4): 1}
    assert (eng.jit_bucket_misses, eng.class_reuses) == (2, 0)
    spans = [e["args"] for e in tracer.to_chrome()["traceEvents"]
             if e["ph"] == "X" and e["name"] == "compact.execute"]
    assert [(a["slots"], a["slot_blocks"], a["sig_hit"]) for a in spans] \
        == [(8, 4, False), (4, 4, False)]
    assert not any("reused_class" in a for a in spans)
    for (paths, bottom), (o2, s2) in zip(jobs, out):
        o1, s1 = DeviceCompactionEngine(GEOM).compact_paths(
            paths, bottom_level=bottom)
        assert (s1.n_live, s1.n_dropped, s1.bytes_out) == \
            (s2.n_live, s2.n_dropped, s2.bytes_out)
        for a, b, name in zip(o1, o2, o1._fields):
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_sharded_batches_cross_shard_jobs(tmp_path):
    """Shards publishing similar jobs into the global queue must coalesce
    into stacked launches, observable via engine + DB stats."""
    db = ShardedDB(str(tmp_path / "sh"), scfg(), shards=4)
    rng = np.random.default_rng(11)
    for i in range(1600):
        db.put(rand_key(rng), b"v%06d" % i)
    db.flush()
    db.maybe_compact()
    s = db.stats
    assert s.compactions >= 2
    assert db.engine.batch_launches >= 1
    assert db.engine.max_batch_jobs >= 2
    assert s.batched_compactions >= 2
    # contents survived the batched path
    db.close()


# ---------------------------------------------------------------------------
# crash recovery: one shard's crash state never touches siblings
# ---------------------------------------------------------------------------


def test_shard_crash_isolated_from_siblings(tmp_path):
    path = str(tmp_path / "sh")
    db = ShardedDB(path, scfg(), shards=4)
    rng = np.random.default_rng(13)
    model = {}
    for i in range(700):
        k = rand_key(rng)
        v = b"v%06d" % i
        db.put(k, v)
        model[k] = v
    db.flush()
    db.maybe_compact()
    # kill -9 image: snapshot the live directory, then "crash" by copying
    # over a fresh path (every install is write-ahead)
    snap = str(tmp_path / "snap")
    shutil.copytree(path, snap)
    db.close()

    # wreck one shard's files in the snapshot beyond recovery
    victim = os.path.join(snap, "shard-0001")
    for f in os.listdir(victim):
        if f.endswith(".sst"):
            with open(os.path.join(victim, f), "wb") as fh:
                fh.write(b"garbage")
    shutil.rmtree(os.path.join(snap, "shard-0001"), ignore_errors=True)

    db2 = ShardedDB(snap, scfg(), shards=4)
    lost = hit = 0
    for k, v in model.items():
        if db2.shard_of(k) == 1:
            lost += 1        # the wrecked shard starts empty
            assert db2.get(k) is None
        else:
            hit += 1
            assert db2.get(k) == v, k   # siblings fully intact
    assert lost > 0 and hit > 0
    db2.close()


def test_sharded_reopen_recovers_wal(tmp_path):
    """Unflushed writes in every shard's WAL replay on reopen.
    ``sync_wal=True`` so appends are durable at the kill -9 snapshot."""
    path = str(tmp_path / "sh")
    db = ShardedDB(path, scfg(memtable_bytes=100_000, sync_wal=True),
                   shards=4)
    rng = np.random.default_rng(17)
    model = {}
    for i in range(80):
        k = rand_key(rng)
        model[k] = b"v%04d" % i
        db.put(k, model[k])
    # simulate a crash: snapshot without close (WALs still hold the data)
    snap = str(tmp_path / "snap")
    shutil.copytree(path, snap)
    db.close()
    db2 = ShardedDB(snap, scfg(), shards=4)
    for k, v in model.items():
        assert db2.get(k) == v, k
    db2.close()


# ---------------------------------------------------------------------------
# async shards share the same queue
# ---------------------------------------------------------------------------


def test_sharded_async_mode(tmp_path):
    db = ShardedDB(str(tmp_path / "sh"),
                   scfg(async_compaction=True, flush_workers=2), shards=4)
    rng = np.random.default_rng(19)
    model = {}
    for i in range(1200):
        k = rand_key(rng)
        v = b"v%06d" % i
        db.put(k, v)
        model[k] = v
    db.wait_idle()
    for k, v in list(model.items())[:300]:
        assert db.get(k) == v, k
    assert db.stats.flushes >= 4
    assert db.stats.compactions >= 1
    db.close()


# ---------------------------------------------------------------------------
# fault injection: torn boundary table, one-shard bg_error isolation
# ---------------------------------------------------------------------------


@pytest.fixture
def _clean_failpoints():
    faults.FAILPOINTS.clear()
    yield
    faults.FAILPOINTS.clear()


def test_torn_boundary_table_write_recovered_by_repair(
        tmp_path, _clean_failpoints):
    """A kill mid-``SHARDS.json`` creation leaves only a torn temp file;
    ``ShardedDB.open(repair=True)`` must clean it up and a fresh boundary
    table must install without ever reading the torn bytes."""
    path = str(tmp_path / "sh")
    with pytest.raises(faults.SimulatedCrash):
        ShardedDB(path, scfg("cpu", failpoints={"shards.write": "torn:x1"}),
                  shards=4)
    faults.FAILPOINTS.clear()
    assert os.path.exists(os.path.join(path, "SHARDS.json.tmp"))
    assert not os.path.exists(os.path.join(path, "SHARDS.json"))

    db = ShardedDB.open(path, scfg("cpu"), repair=True, shards=4)
    assert not os.path.exists(os.path.join(path, "SHARDS.json.tmp"))
    assert os.path.exists(os.path.join(path, "SHARDS.json"))
    assert db.n_shards == 4
    db.put(b"\x01aa", b"v0")
    db.put(b"\xf0bb", b"v1")
    assert db.get(b"\x01aa") == b"v0"
    assert db.get(b"\xf0bb") == b"v1"
    db.close()

    # the repaired table is durable: a plain reopen agrees on routing
    db2 = ShardedDB(path, scfg("cpu"), shards=4)
    assert db2.get(b"\x01aa") == b"v0"
    db2.close()


def test_one_shard_bg_error_isolated_and_resumable(
        tmp_path, _clean_failpoints):
    """A hard background-flush failure halts ONE shard; siblings keep
    serving reads and writes, and ``ShardedDB.resume()`` brings the
    failed shard back without losing its acknowledged (WAL-held) rows."""
    path = str(tmp_path / "sh")
    # fire counts outlive clear(): count this test's fires only
    fired0 = faults.FAILPOINTS.fired("flush.build")
    # async mode: flushes run on the background executor, so a failure
    # lands as a classified bg_error (the sync path surfaces foreground
    # errors directly to the caller and never halts)
    db = ShardedDB(path,
                   scfg("cpu", sync_writes=True, async_compaction=True,
                        failpoints={"flush.build": "hard:x1"}),
                   boundaries=[b"\x80"])
    try:
        # route every write to shard 0 until its flush trips the failpoint;
        # the classified error may surface at a rotation, flush() or
        # wait_idle() depending on scheduling
        with pytest.raises((faults.BackgroundError, IOError)):
            for i in range(400):
                db.put(b"a%04d" % i, b"v%04d" % i)
            db.shards[0].flush()
            db.shards[0].wait_idle()
        assert faults.FAILPOINTS.fired("flush.build") - fired0 == 1
        assert db.shards[0]._bg_error is not None
        assert db.shards[0]._bg_error.severity == "hard"

        # shard 0 is halted...
        with pytest.raises(IOError, match="resume"):
            db.put(b"a9999", b"halted")
        # ...but the sibling shard is business as usual
        db.put(b"\xf0sib", b"alive")
        assert db.get(b"\xf0sib") == b"alive"
        db.shards[1].flush()
        db.shards[1].wait_idle()
        assert db.shards[1]._bg_error is None

        # resume restarts the failed shard's pipeline; the one-shot
        # failpoint is exhausted so the re-run flush succeeds
        assert db.resume() is True
        assert db.shards[0].stats.bg_resumes == 1
        db.put(b"a9999", b"post")
        assert db.get(b"a9999") == b"post"
        assert db.get(b"a0000") == b"v0000"   # acked rows survived the halt
        db.flush()
        db.wait_idle()
        assert db.resume() is False           # healthy resume is a no-op
    finally:
        db.close()
