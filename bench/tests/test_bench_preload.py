"""The preload is built with the store's CPU engine because the device
engine would compile a merge program per distinct set of input run
lengths; the two engines write the same SST files byte for byte."""

import filecmp
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import gen  # noqa: E402
import store  # noqa: E402

TINY = {"records": 800, "sst_bytes": 65536, "memtable_bytes": 16384,
        "l1_base_bytes": 131072, "block_cache_blocks": 64}


def build(path, cfg, engine):
    from repro.lsm.db import LsmDB
    db = LsmDB(path, store.db_config(dict(cfg, engine=engine),
                                     async_mode=False))
    for key, value in gen.preload_records(cfg["records"], cfg["value_size"]):
        db.put(key, value)
    db.flush()
    db.maybe_compact()
    compactions = db.stats.compactions
    db.close()
    return compactions, sorted(f for f in os.listdir(path)
                               if f.endswith(".sst"))


def test_cpu_and_device_engines_build_the_same_preload(tmp_path):
    with open(os.path.join(BENCH, "configs", "luda-128b.json")) as f:
        cfg = dict(json.load(f), **TINY)
    n_cpu, cpu = build(str(tmp_path / "cpu"), cfg, "cpu")
    n_dev, dev = build(str(tmp_path / "device"), cfg, "device")
    assert n_cpu == n_dev >= 1
    assert cpu == dev and cpu
    for name in cpu:
        assert filecmp.cmp(tmp_path / "cpu" / name, tmp_path / "device" / name,
                           shallow=False), name
