"""Failpoint wall: injection grammar, crash recovery, self-healing
error paths (retry/backoff, resume, CPU fallback) and offline repair.

Process death is simulated via ``SimulatedCrash`` (a BaseException, so
nothing can accidentally "handle" it) plus a directory snapshot, exactly
like tests/test_recovery.py; the crash-consistency matrix itself lives
in ``repro.testing.crashmatrix`` and is smoke-run here on a bounded
subset of cells.
"""

import os
import shutil

import pytest

from repro.core.formats import SSTGeometry
from repro.core.scheduler import SchedulerConfig
from repro.lsm import faults, repair, sstable, wal
from repro.lsm.db import DBConfig, LsmDB
from repro.lsm.faults import (BackgroundError, FaultInjected,
                              SimulatedCrash, classify, parse_failpoints,
                              with_retries)
from repro.testing import crashmatrix

GEOM = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                   sst_bytes=2048)


def fcfg(engine="cpu", **kw):
    return DBConfig(
        geom=GEOM, engine=engine,
        memtable_bytes=kw.pop("memtable_bytes", 600),
        scheduler=SchedulerConfig(l0_trigger=3, base_bytes=40_000),
        bg_retry_base_s=1e-4, **kw)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    faults.FAILPOINTS.clear()
    yield
    faults.FAILPOINTS.clear()


# ---------------------------------------------------------------------------
# spec grammar + registry semantics
# ---------------------------------------------------------------------------


def test_parse_failpoint_grammar():
    specs = parse_failpoints(
        "wal.append=torn, flush.build=raise:x2,engine.launch=hard:p0.25:a3")
    assert specs["wal.append"].action == "torn"
    assert specs["flush.build"].count == 2
    assert specs["engine.launch"].rate == 0.25
    assert specs["engine.launch"].after == 3
    # dict-of-strings and dict-of-tuples forms
    specs = parse_failpoints({"sst.write": ("crash", None, 1, 2)})
    assert (specs["sst.write"].after, specs["sst.write"].count) == (1, 2)


def test_parse_rejects_unknown_names_and_actions():
    with pytest.raises(ValueError, match="unknown failpoint"):
        parse_failpoints("wal.apend=raise")
    with pytest.raises(ValueError, match="unknown failpoint action"):
        parse_failpoints("wal.append=explode")
    with pytest.raises(ValueError, match="rate out of"):
        parse_failpoints("wal.append=raise:p1.5")


def test_fire_count_and_after_gates():
    reg = faults.FailpointRegistry({"flush.build": "raise:a2:x1"})
    assert reg.fire("flush.build") is None      # hit 1: still arming
    assert reg.fire("flush.build") is None      # hit 2: still arming
    with pytest.raises(FaultInjected):
        reg.fire("flush.build")                 # hit 3: fires
    assert reg.fire("flush.build") is None      # count exhausted
    assert reg.fired("flush.build") == 1


def test_active_scoping_restores_prior_spec():
    reg = faults.FailpointRegistry({"wal.append": "raise"})
    with reg.active({"wal.append": "off"}):
        assert reg.fire("wal.append") is None
    with pytest.raises(FaultInjected):
        reg.fire("wal.append")


def test_classify_severity():
    assert classify(FaultInjected("x", "transient")) == "transient"
    assert classify(FaultInjected("x", "hard")) == "hard"
    assert classify(OSError("disk hiccup")) == "transient"
    assert classify(IOError("SST block checksum mismatch")) == "hard"
    assert classify(TypeError("logic bug")) == "hard"


def test_with_retries_transient_only():
    calls = {"n": 0, "retries": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert with_retries(flaky, retries=3, base_s=1e-5,
                        on_retry=lambda: calls.__setitem__(
                            "retries", calls["retries"] + 1)) == "ok"
    assert calls["retries"] == 2

    def hard():
        raise IOError("corrupt block")

    with pytest.raises(IOError, match="corrupt"):
        with_retries(hard, retries=5, base_s=1e-5)


# ---------------------------------------------------------------------------
# torn writes + repair
# ---------------------------------------------------------------------------


def test_torn_wal_record_discarded_acked_survive(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg(sync_writes=True,
                          failpoints="wal.append=torn:a20"))
    acked = {}
    with pytest.raises(SimulatedCrash):
        for i in range(100):
            k, v = b"key%03d" % i, b"val%03d" % i
            db.put(k, v)
            acked[k] = v
    faults.FAILPOINTS.clear()
    assert len(acked) == 20
    crash = shutil.copytree(path, str(tmp_path / "crash"))
    shutil.rmtree(path)

    rep = repair.repair(crash)
    assert rep.wal_truncated, "torn tail not truncated"
    db2 = LsmDB(crash, fcfg())
    for k, v in acked.items():
        assert db2.get(k) == v, k
    db2.close()


def test_torn_manifest_repaired_and_reopenable(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg(sync_writes=True,
                          failpoints="manifest.append=torn:a1"))
    acked = {}
    with pytest.raises(SimulatedCrash):
        for i in range(300):
            k, v = b"key%03d" % i, b"val%03d" % i
            db.put(k, v)
            acked[k] = v
    faults.FAILPOINTS.clear()
    crash = shutil.copytree(path, str(tmp_path / "crash"))
    shutil.rmtree(path)

    rep = repair.repair(crash)
    assert rep.manifest_rebuilt
    db2 = LsmDB.open(crash, fcfg())
    for k, v in acked.items():
        assert db2.get(k) == v, k
    db2.close()


def test_repair_quarantines_corrupt_sst(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg(auto_compact=False))
    for i in range(60):
        db.put(b"key%03d" % i, b"val%03d" % i)
    db.flush()
    db.close()
    ssts = [f for f in os.listdir(path) if f.endswith(".sst")]
    assert ssts
    victim = os.path.join(path, sorted(ssts)[0])
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) // 2)
        f.write(b"\xde\xad\xbe\xef")

    rep = repair.repair(path)
    assert victim in rep.quarantined
    assert rep.manifest_rebuilt
    assert os.path.exists(os.path.join(path, "lost",
                                       os.path.basename(victim)))
    # openable afterwards; the quarantined file's rows are gone, the
    # store itself is healthy
    db2 = LsmDB(path, fcfg())
    db2.put(b"post", b"repair")
    assert db2.get(b"post") == b"repair"
    db2.close()


def test_repair_adopts_ssts_when_manifest_missing(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg(auto_compact=False))
    acked = {}
    for i in range(120):
        k, v = b"key%03d" % i, b"val%03d" % i
        db.put(k, v)
        acked[k] = v
        if i % 40 == 39:
            db.flush()
    db.flush()
    db.close()
    os.remove(os.path.join(path, "MANIFEST"))

    rep = repair.repair(path)
    assert rep.adopted and rep.manifest_rebuilt
    db2 = LsmDB(path, fcfg())
    for k, v in acked.items():
        assert db2.get(k) == v, k
    # file-number counter must advance past adopted files
    assert db2.versions.next_file_no > max(
        fm.file_no for _, fm in db2.versions.current.all_files())
    db2.close()


def test_repair_dry_run_touches_nothing(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg(auto_compact=False))
    for i in range(60):
        db.put(b"key%03d" % i, b"val%03d" % i)
    db.flush()
    db.close()
    victim = os.path.join(path, sorted(
        f for f in os.listdir(path) if f.endswith(".sst"))[0])
    with open(victim, "r+b") as f:
        f.write(b"\x00" * 16)
    before = {f: os.path.getsize(os.path.join(path, f))
              for f in os.listdir(path)}
    rep = repair.repair(path, dry_run=True)
    assert rep.quarantined and rep.dry_run
    after = {f: os.path.getsize(os.path.join(path, f))
             for f in os.listdir(path)}
    assert before == after


def test_repair_cli_main(tmp_path, capsys):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg())
    db.put(b"k", b"v")
    db.flush()
    db.close()
    assert repair.main([path]) == 0
    out = capsys.readouterr().out
    assert "clean" in out


def test_orphan_gc_on_open(tmp_path):
    path = str(tmp_path / "db")
    db = LsmDB(path, fcfg())
    db.put(b"k", b"v")
    db.flush()
    db.close()
    # a stale temp file and an unreferenced SST from a dead flush
    with open(os.path.join(path, "999999.sst.tmp"), "wb") as f:
        f.write(b"junk")
    shutil.copyfile(
        os.path.join(path, sorted(f for f in os.listdir(path)
                                  if f.endswith(".sst"))[0]),
        os.path.join(path, "999998.sst"))
    db2 = LsmDB(path, fcfg())
    assert db2.stats.orphans_removed >= 2
    assert not os.path.exists(os.path.join(path, "999999.sst.tmp"))
    assert not os.path.exists(os.path.join(path, "999998.sst"))
    assert db2.get(b"k") == b"v"
    db2.close()


# ---------------------------------------------------------------------------
# self-healing background errors
# ---------------------------------------------------------------------------


def test_transient_flush_failure_auto_retries(tmp_path):
    db = LsmDB(str(tmp_path / "db"),
               fcfg(async_compaction=True,
                    failpoints="flush.build=raise:x2"))
    for i in range(120):
        db.put(b"key%03d" % i, b"val%03d" % i)
    db.flush()
    db.wait_idle()          # must NOT raise: retries absorb the fault
    assert db.stats.bg_retries >= 2
    assert db.get(b"key042") == b"val042"
    db.close()


def test_hard_flush_failure_halts_then_resume_recovers(tmp_path):
    db = LsmDB(str(tmp_path / "db"),
               fcfg(async_compaction=True,
                    failpoints="flush.build=hard"))
    # the classified error can surface at a rotation, flush() or
    # wait_idle(), whichever drains the executor first
    with pytest.raises(BackgroundError) as ei:
        for i in range(120):
            db.put(b"key%03d" % i, b"val%03d" % i)
        db.flush()
        db.wait_idle()
    assert ei.value.severity == "hard"
    assert "resume()" in str(ei.value)
    # writes are halted until resume()
    with pytest.raises(IOError, match="resume"):
        for i in range(5000):
            db.put(b"x%05d" % i, b"y")
    faults.FAILPOINTS.clear()
    assert db.resume() is True
    db.wait_idle()
    assert db.stats.bg_resumes == 1
    assert db.get(b"key042") == b"val042"
    db.put(b"post", b"resume")
    db.flush()
    db.wait_idle()
    assert db.get(b"post") == b"resume"
    db.close()


def test_resume_without_error_is_noop(tmp_path):
    db = LsmDB(str(tmp_path / "db"), fcfg())
    assert db.resume() is False
    db.close()


def test_bg_error_gauge_tracks_state(tmp_path):
    db = LsmDB(str(tmp_path / "db"),
               fcfg(async_compaction=True,
                    failpoints="flush.build=hard"))
    with pytest.raises(BackgroundError):
        for i in range(120):
            db.put(b"key%03d" % i, b"val%03d" % i)
        db.flush()
        db.wait_idle()
    assert db.metrics.gauge("lsm.bg_error").value == 2    # hard
    faults.FAILPOINTS.clear()
    db.resume()
    assert db.metrics.gauge("lsm.bg_error").value == 0
    db.close()


# ---------------------------------------------------------------------------
# engine fallback: device launch failures degrade to CPU, bit-identically
# ---------------------------------------------------------------------------


def _fill(db, n=240):
    for i in range(n):
        db.put(b"key%03d" % ((i * 53) % n), b"val%05d" % i)
        if i % 60 == 59:
            db.flush()
            db.maybe_compact()
    db.flush()
    db.maybe_compact()
    db.wait_idle()


def test_device_launch_failure_falls_back_to_cpu_bit_identical(tmp_path):
    ok = LsmDB(str(tmp_path / "ok"), fcfg("device"))
    _fill(ok)
    faults.FAILPOINTS.clear()
    fb = LsmDB(str(tmp_path / "fb"),
               fcfg("device", failpoints="engine.launch=raise"))
    _fill(fb)
    faults.FAILPOINTS.clear()
    assert fb.engine.fallbacks >= 1
    assert fb.engine.launch_retries >= 1
    assert fb.stats.engine_fallbacks >= 1
    for i in range(240):
        k = b"key%03d" % i
        assert ok.get(k) == fb.get(k), k
    ok.close()
    fb.close()


def test_crc_failure_verdict_falls_back_to_cpu(tmp_path):
    # a single CRC fault is absorbed by the retry (second device attempt
    # succeeds); a persistent one must degrade to the CPU engine
    db = LsmDB(str(tmp_path / "db"),
               fcfg("device", failpoints="engine.crc=raise"))
    _fill(db)
    faults.FAILPOINTS.clear()
    assert db.engine.fallbacks >= 1
    assert db.engine.launch_retries >= 1
    assert db.get(b"key001") is not None
    db.close()


def test_launch_fault_fallback_records_cause(tmp_path):
    from repro.obs import Tracer
    tracer = Tracer()
    db = LsmDB(str(tmp_path / "db"),
               fcfg("device", failpoints="engine.launch=raise"),
               tracer=tracer)
    _fill(db)
    faults.FAILPOINTS.clear()
    assert db.engine.fallbacks >= 1
    assert db.engine.fault_causes
    assert all(c.startswith("FaultInjected: ") and "engine.launch" in c
               for c in db.engine.fault_causes)
    spans = [e for e in tracer.to_chrome()["traceEvents"]
             if e["name"] == "compact.fallback"]
    assert spans and all("engine.launch" in e["args"]["cause"]
                         for e in spans)
    db.close()


def test_kernel_lowering_error_propagates(tmp_path, monkeypatch):
    """A kernel that cannot lower is not a launch fault: the device
    engine raises instead of finishing the job on the CPU."""
    import numpy as np

    from repro.core import formats
    from repro.kernels import common
    from repro.lsm.cpu_engine import (CpuCompactionEngine,
                                      DeviceCompactionEngine)
    # a geometry of its own: no compiled pipeline of another test is reused
    geom = SSTGeometry(key_bytes=16, value_bytes=44, block_bytes=512,
                       sst_bytes=2048)
    keys = np.stack([formats.pack_key_bytes(b"key%03d" % i, 16)
                     for i in range(40)])
    meta = np.array([(i + 1) << 1 | 1 for i in range(40)], np.uint32)
    vals = np.stack([formats.pack_value_bytes(b"v%d" % i, 44)
                     for i in range(40)])
    cpu = CpuCompactionEngine(geom)
    images = [cpu.build_image(keys[:20], meta[:20], vals[:20]),
              cpu.build_image(keys[20:], meta[20:], vals[20:])]
    # compiled Pallas kernels cannot lower for the CPU backend
    monkeypatch.setattr(common, "default_interpret", lambda: False)
    eng = DeviceCompactionEngine(geom, backend="pallas")
    with pytest.raises(ValueError, match="interpret mode"):
        eng.compact(images)
    assert eng.fallbacks == 0 and eng.launch_retries == 0
    assert not eng.fault_causes


def test_single_launch_fault_absorbed_by_retry(tmp_path):
    db = LsmDB(str(tmp_path / "db"),
               fcfg("device", failpoints="engine.launch=raise:x1"))
    _fill(db)
    faults.FAILPOINTS.clear()
    assert db.engine.launch_retries >= 1
    assert db.engine.fallbacks == 0     # retry succeeded, no degrade
    assert db.get(b"key001") is not None
    db.close()


# ---------------------------------------------------------------------------
# crash matrix smoke (the full grid runs in the fault-matrix CI job)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_crashmatrix_cell_smoke(mode):
    res = crashmatrix.run_cell("wal.append", mode, n=200)
    assert res.crashed
    assert res.ok, res.errors


def test_crashmatrix_sabotage_detects_data_loss():
    res = crashmatrix.run_cell("compact.install", "sync", n=300,
                               sabotage=True)
    assert res.crashed
    assert not res.ok, "sabotaged image passed -- the wall is dead"
