"""The cell ``ycsb-a.128b`` (YCSB-A at 128 B values: ``luda-128b`` under
the ``ycsb-a`` mix): what ``BENCHMARK.json`` makes of it, its read-path
readers on a hand-made ``RunData``, and a tiny run of it on the CPU."""

import dataclasses
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402

CELL = "ycsb-a.128b"
READERS = ("get_us", "get_table_load_share", "get_block_load_share")
#: the cell's configuration cut to a size that runs in seconds on the CPU
TINY = {"records": 3000, "sst_bytes": 65536, "memtable_bytes": 16384,
        "l1_base_bytes": 131072, "block_cache_blocks": 64, "engine": "cpu"}


def read_all(spans, window_s=10.0):
    run = harness.RunData(CELL, window_s, list(spans), {}, None,
                          peaks.peaks("TPU v5 lite"))
    return {n: spec.load_reader(n, BENCH)(run) for n in READERS}


def test_cell_reports_throughput_setup_and_the_read_path():
    c = spec.find_cell(CELL, ROOT)
    assert (c.config_name, c.chips) == ("luda-128b", 1)
    assert c.traffic == spec.load_traffic("ycsb-a", BENCH)
    assert [m.name for m in c.end_to_end] == ["ops_per_s", "setup_s"]
    assert {m.name for m in c.per_layer} == set(READERS)
    for m in c.per_layer:
        assert (m.moves, m.source, m.better, m.workloads) == (
            "ops_per_s", "program_span", "lower", (CELL,))


def test_get_time_and_load_shares_in_the_window():
    spans = [
        # before the window, and past its end: counted by nothing
        ("db.get", -1.0, 0.5), ("get.block_load", -0.9, 0.3),
        ("get.table_load", -0.95, 0.01),
        ("db.get", 9.99, 0.1), ("get.block_load", 9.995, 0.05),
        # three gets in the window, 400 us in all; one opened a table and
        # missed the block cache
        ("db.get", 1.0, 100e-6), ("get.table_load", 1.00001, 20e-6),
        ("get.block_load", 1.00004, 40e-6),
        ("db.get", 2.0, 250e-6),
        ("db.get", 3.0, 50e-6),
        ("db.put", 4.0, 1e-3),
    ]
    assert read_all(spans) == pytest.approx({"get_us": 400 / 3,
                                             "get_table_load_share": 5.0,
                                             "get_block_load_share": 10.0})


def test_gets_without_a_cache_miss_read_zero():
    spans = [("db.get", 1.0, 1e-4)]
    assert read_all(spans) == {"get_us": pytest.approx(100.0),
                               "get_table_load_share": 0.0,
                               "get_block_load_share": 0.0}


def test_a_window_without_gets_reads_nothing():
    # a write-only window, and a store that records no get spans
    spans = [("db.put", 1.0, 1e-5), ("compact.job", 2.0, 1.0),
             ("get.block_load", -1.0, 1e-4), ("get.table_load", 3.0, 1e-4)]
    assert read_all(spans) == dict.fromkeys(READERS)
    assert read_all([]) == dict.fromkeys(READERS)


def test_tiny_run_of_the_cell_is_correct(tmp_path, monkeypatch):
    from repro.kernels import common
    # the record of interpreted kernels is process-wide: count this run's
    monkeypatch.setattr(common, "_INTERPRETED", set())
    with open(os.path.join(BENCH, "configs", "luda-128b.json")) as f:
        cfg = dict(json.load(f), **TINY)
    path = str(tmp_path / "luda-128b.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    c = dataclasses.replace(spec.find_cell(CELL, ROOT), config=cfg,
                            config_path=path)
    r = harness.run_cell(c, seed=2**31 + 1515, seconds=0.5, trace=False,
                         t_start=time.perf_counter(), require_tpu=False,
                         work_root=str(tmp_path))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ops_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
