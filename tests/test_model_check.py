"""The store against a dict model, at the benchmark's own shapes.

Each case opens ``LsmDB`` with the ``DBConfig`` that ``bench/store.py``
builds from ``bench/configs/<config>.json`` (merge sort, background
flush and compaction, 16-entry blocks of 1,040 B or 144 B value slots),
with only the sizes scaled down so that every case flushes and compacts
at least twice.  Every ``get``, ``multi_get`` and ``scan`` answer is
checked against the dict, during the traffic and at its end, and again
after a reopen.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

import gen  # noqa: E402
import store  # noqa: E402
from ycsb import key_of, value_of  # noqa: E402

from repro.lsm.db import LsmDB  # noqa: E402

#: the sizes each configuration is cut to: 64-entry SSTs, about 40
#: entries per memtable, an L1 of a few SSTs, and caches smaller than
#: the store so that tables and blocks are evicted and reloaded
SIZES = {
    "luda-1kb": {"sst_bytes": 16384, "memtable_bytes": 40 * 1040,
                 "l1_base_bytes": 4 * 70_000, "table_cache": 8,
                 "block_cache_blocks": 16},
    "luda-128b": {"sst_bytes": 16384, "memtable_bytes": 40 * 144,
                  "l1_base_bytes": 4 * 12_000, "table_cache": 8,
                  "block_cache_blocks": 16},
}
OPS = 480
PRELOAD = 240
#: the last key any record can have, for scans over every record
KEY_END = b"user" + b"\xff" * 12


def load_config(name: str, engine: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    return dict(cfg, engine=engine, **SIZES[name])


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


class Model:
    """The dict the store is checked against."""

    def __init__(self):
        self.d: dict[bytes, bytes | None] = {}

    def get(self, key):
        return self.d.get(key)

    def scan(self, start, end):
        return [(k, v) for k, v in sorted(self.d.items())
                if start <= k < end and v is not None]


def check_get(db, model, key):
    assert db.get(key) == model.get(key), key


def check_scan(db, model, start, end):
    assert db.scan(start, end) == model.scan(start, end), (start, end)


def check_all(db, model, rng):
    """Every key ever written and as many never written, by ``get`` and
    by ``multi_get``; scans over random ranges and over every record."""
    keys = list(model.d)
    keys += [key_of((1 << 40) + i) for i in range(len(keys))]
    for k in keys:
        check_get(db, model, k)
    for i in range(0, len(keys), 32):
        batch = keys[i:i + 32]
        assert db.multi_get(batch) == [model.get(k) for k in batch]
    for _ in range(8):
        a, b = sorted(key_of(int(i)) for i in rng.integers(0, 1 << 40, 2))
        check_scan(db, model, a, b)
    check_scan(db, model, b"user", KEY_END)


def run_load(db, model, cfg, rng):
    stream = gen.OpStream(load_traffic("load"), records=0,
                          value_size=cfg["value_size"], seed=1, stream=0)
    _, keys, values = stream.next_chunk(OPS)
    for k, v in zip(keys, values):
        db.put(k, v)
        model.d[k] = v


def run_ycsb_a(db, model, cfg, rng):
    for k, v in gen.preload_records(PRELOAD, cfg["value_size"]):
        db.put(k, v)
        model.d[k] = v
    stream = gen.OpStream(load_traffic("ycsb-a"), records=PRELOAD,
                          value_size=cfg["value_size"], seed=2, stream=0)
    kinds, keys, values = stream.next_chunk(OPS)
    assert kinds.count(gen.GET) == kinds.count(gen.PUT) == OPS // 2
    for kind, k, v in zip(kinds, keys, values):
        if kind == gen.GET:
            check_get(db, model, k)
        else:
            db.put(k, v)
            model.d[k] = v


def run_churn(db, model, cfg, rng):
    """Puts, deletes, write batches of both, and scans, over 160 keys."""
    n_keys, width = 160, cfg["value_size"]
    for i in range(OPS):
        k = key_of(int(rng.integers(0, n_keys)))
        op = rng.random()
        if op < 0.45:
            v = value_of(i, width)
            db.put(k, v)
            model.d[k] = v
        elif op < 0.65:
            db.delete(k)
            model.d[k] = None
        elif op < 0.85:
            batch = []
            for j in range(int(rng.integers(2, 9))):
                bk = key_of(int(rng.integers(0, n_keys)))
                if rng.random() < 0.7:
                    batch.append(("put", bk, value_of(OPS * (j + 1) + i,
                                                      width)))
                else:
                    batch.append(("delete", bk))
            db.write_batch(batch)
            for op_ in batch:
                model.d[op_[1]] = op_[2] if op_[0] == "put" else None
        else:
            a, b = sorted(key_of(int(j)) for j in rng.integers(0, n_keys, 2))
            check_scan(db, model, a, b)
            check_get(db, model, k)


MIXES = {"load": run_load, "ycsb-a": run_ycsb_a, "churn": run_churn}


@pytest.mark.parametrize("reopen", [False, True])
@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("engine", ["cpu", "device"])
@pytest.mark.parametrize("config", sorted(SIZES))
def test_store_matches_dict_model(tmp_path, config, engine, mix, reopen):
    cfg = load_config(config, engine)
    db_cfg = store.db_config(cfg, async_mode=True)
    assert db_cfg.sort_mode == "merge" and db_cfg.async_compaction
    assert db_cfg.geom.block_kvs == 16
    path = str(tmp_path / "db")
    rng = np.random.default_rng(7)
    model = Model()
    db = LsmDB(path, db_cfg)
    try:
        MIXES[mix](db, model, cfg, rng)
        db.wait_idle()
        assert db.stats.flushes >= 2 and db.stats.compactions >= 2, \
            db.stats
        check_all(db, model, rng)
    finally:
        db.close()
    if reopen:
        db = LsmDB(path, db_cfg)
        try:
            check_all(db, model, rng)
        finally:
            db.close()
