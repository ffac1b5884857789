"""The store under test: its configuration, the preload, the run's copy.

The preload is YCSB's load phase of the configuration (record ``i`` is
``key_of(i) -> value_of(i)``, inserted in id order through ``LsmDB.put``).
It is built with inline (synchronous) flushes and compactions, so the
same configuration always gives the same files, and then flushed and
compacted to rest.  The first run of a
configuration in a checkout builds it and keeps it under
``bench/.cache/<name>-<hash of the configuration file>``; every run works
on a fresh copy in ``bench/.work/`` whose table files are hard links (SST
files are written once and never changed in place; a compaction unlinks
its inputs and writes new files), so no run sees another run's writes.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import time

from gen import preload_records
from spec import BENCH_DIR



def cache_dir(root: str | None = None) -> str:
    return os.path.join(root or BENCH_DIR, ".cache")


def work_dir(root: str | None = None) -> str:
    return os.path.join(root or BENCH_DIR, ".work")


def db_config(cfg: dict, *, async_mode: bool, tracer=None):
    """The store's ``DBConfig`` as the configuration file states it."""
    from repro.core.formats import SSTGeometry
    from repro.core.scheduler import SchedulerConfig
    from repro.lsm.db import DBConfig
    geom = SSTGeometry(
        key_bytes=cfg["key_bytes"], value_bytes=cfg["value_slot_bytes"],
        block_bytes=cfg["block_bytes"], sst_bytes=cfg["sst_bytes"],
        restart_interval=cfg["restart_interval"],
        bloom_bits_per_key=cfg["bloom_bits_per_key"],
        bloom_granularity=cfg["bloom_granularity"])
    sched = SchedulerConfig(l0_trigger=cfg["l0_trigger"],
                            base_bytes=cfg["l1_base_bytes"],
                            ratio=cfg["level_ratio"])
    return DBConfig(
        geom=geom, engine=cfg["engine"], sort_mode=cfg["sort_mode"],
        memtable_bytes=cfg["memtable_bytes"], scheduler=sched,
        table_cache=cfg["table_cache"],
        block_cache_blocks=cfg["block_cache_blocks"],
        sync_wal=cfg["sync_wal"], sync_writes=cfg["sync_writes"],
        async_compaction=async_mode, flush_workers=cfg["flush_workers"],
        max_pending_memtables=cfg["max_pending_memtables"], tracer=tracer)


def store_paths(name: str, cfg_bytes: bytes,
                root: str | None = None) -> tuple[str, str]:
    """(kept preload, working path).  The store records its files by
    path, so every run works at the path the preload was built at."""
    tag = f"{name}-{hashlib.sha256(cfg_bytes).hexdigest()[:16]}"
    return os.path.join(cache_dir(root), tag), os.path.join(work_dir(root),
                                                            tag)


def build_preload(work: str, kept: str, cfg: dict, log=print):
    """Build the preload at ``work`` and keep it at ``kept`` (renamed only
    once it is whole, so a half-built store is never found there)."""
    from repro.lsm.db import LsmDB
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.dirname(kept), exist_ok=True)
    # the store's CPU engine writes SST files bit-identical to the device
    # engine's (the store's own tests assert it), and compiles nothing:
    # the device engine would compile a merge program for every distinct
    # set of input run lengths the build meets, none of which the window
    # uses
    db = LsmDB(work, db_config(dict(cfg, engine="cpu"), async_mode=False))
    t0 = time.perf_counter()
    step = max(1, cfg["records"] // 10)
    try:
        for i, (key, value) in enumerate(
                preload_records(cfg["records"], cfg["value_size"])):
            db.put(key, value)
            if i % step == step - 1:
                log(f"preload: {i + 1} records in "
                    f"{time.perf_counter() - t0:.1f} s")
        db.flush()
        db.maybe_compact()
        log(f"preload: {cfg['records']} records, files per level "
            f"{db.level_sizes()}, {db.stats.compactions} compactions")
    finally:
        db.close()
    os.rename(work, kept)


def fresh_copy(name: str, cfg_path: str, cfg: dict, *,
               root: str | None = None, log=print) -> str:
    """A private copy of the preload for one run, at the path it was
    built at: SST files hard-linked, everything else (MANIFEST, WAL)
    copied.  Builds the preload first if this checkout has none."""
    with open(cfg_path, "rb") as f:
        kept, work = store_paths(name, f.read(), root)
    if not os.path.isdir(kept):
        build_preload(work, kept, cfg, log=log)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    for fname in os.listdir(kept):
        s, d = os.path.join(kept, fname), os.path.join(work, fname)
        if fname.endswith(".sst"):
            try:
                os.link(s, d)
                continue
            except OSError:
                pass
        shutil.copy2(s, d)
    return work
