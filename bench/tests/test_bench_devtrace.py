"""The reduction from a profiler trace to numbers, on hand-made traces
and on a small trace recorded on a TPU v5e (``data/trace_sample.json``)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402

SAMPLE = os.path.join(BENCH, "tests", "data", "trace_sample.json")


def trace(ops, modules=(), window=(0, 100)):
    return {"devices": [{"plane": "/device:TPU:0", "ops": list(ops),
                         "modules": list(modules)}],
            "host_marks": [["bench.window", window[0],
                            window[1] - window[0]]],
            "planes": []}


def test_busy_is_the_union_of_overlapping_ops():
    busy, gaps = devtrace.busy_and_gaps(
        [["a", 10, 20], ["b", 15, 10], ["c", 50, 10]], 0, 100)
    assert busy == pytest.approx(30e-9)
    assert gaps == [(0, 10), (30, 50), (60, 100)]


def test_ops_are_clipped_to_the_window():
    busy, gaps = devtrace.busy_and_gaps([["a", -10, 30], ["b", 90, 50]],
                                        0, 100)
    assert busy == pytest.approx(30e-9)
    assert gaps == [(20, 90)]


def test_no_ops_is_all_idle():
    assert devtrace.busy_and_gaps([], 0, 100) == (0.0, [(0, 100)])


def test_module_seconds_match_by_name():
    dev = {"modules": [["jit_compact(3)", 0, 40], ["jit_compact_batch(1)",
                                                     40, 20],
                       ["jit_build_image(2)", 60, 30]]}
    assert devtrace.module_seconds(dev, r"jit_compact(\(|$)", 0, 100) == \
        pytest.approx(40e-9)
    assert devtrace.module_seconds(dev, r"jit_build_image", 0, 70) == \
        pytest.approx(10e-9)


def test_top_ops_name_their_program():
    dev = {"ops": [["%fusion.1 = u32[8]{0} fusion(%p)", 0, 10],
                   ["%fusion.7 = u32[8]{0} fusion(%q)", 20, 10],
                   ["copy.2", 40, 5], ["copy.2", 200, 5]],
           "modules": [["jit_compact(3)", 0, 35], ["jit_x(1)", 40, 10]]}
    assert devtrace.top_ops(dev, 0, 100) == [
        ["jit_compact/fusion", 20e-9], ["jit_x/copy", 5e-9]]


def test_idle_gaps_go_to_the_innermost_open_span():
    gaps = [(0, 10), (30, 50), (60, 100)]
    spans = [("compact.job", 25, 95), ("compact.read_inputs", 28, 55)]
    got = dict(map(tuple, devtrace.attribute_gaps(gaps, spans)))
    assert got == {"client": 10e-9, "compact.read_inputs": 20e-9,
                   "compact.job": 40e-9}


def test_reduce_gives_busy_window_and_breakdown():
    tr = trace([["f", 10, 40]], [["jit_compact(1)", 10, 40]], (0, 100))
    red = devtrace.reduce(tr, [("db.put", 60, 90)])
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["breakdown"]["device_ops"] == [["jit_compact/f", 40e-9]]
    assert dict(map(tuple, red["breakdown"]["idle_gaps"])) == {
        "client": 10e-9, "db.put": 50e-9}


def test_a_trace_without_a_device_plane_is_an_error():
    tr = trace([])
    tr["devices"] = []
    with pytest.raises(ValueError):
        devtrace.reduce(tr)


def reader_run(name, tr, counters, spans=(), window_s=1e-7):
    red = devtrace.reduce(tr, [])
    red["trace"] = tr
    run = harness.RunData("load.1kb", window_s, list(spans), counters, red,
                          peaks.peaks("TPU v5 lite"))
    return spec.load_reader(name, BENCH)(run)


def test_roofline_share_counts_bytes_once_over_program_time():
    # 819 bytes in and out at 819 GB/s take 1 ns; the program took 10 ns
    tr = trace([["f", 0, 10]], [["jit_compact(1)", 0, 10]])
    got = reader_run("compact_roofline", tr,
                     {"compact_bytes_in": 409, "compact_bytes_out": 410})
    assert got == pytest.approx(10.0)


def test_readers_find_nothing_and_say_so():
    tr = trace([["f", 0, 10]], [["jit_build_image(1)", 0, 10]])
    assert reader_run("compact_roofline", tr, {"compact_bytes_in": 5}) \
        is None
    assert reader_run("flush_build_ms", tr, {}) is None
    assert reader_run("block_cache_hit_rate.get", tr, {}) is None
    assert reader_run("device_idle_share", tr, {}) == pytest.approx(90.0)


def test_span_readers():
    tr = trace([])
    spans = [("write_stall", 0.0, 0.25), ("flush.build", 0.1, 0.002),
             ("flush.build", 0.5, 0.004), ("compact.job", 0.2, 0.5),
             ("db.multi_get", 0.0, 0.5), ("read.bloom_probe", 0.0, 0.1),
             ("read.block_gather", 0.1, 0.15), ("write_stall", 0.9, 0.5)]
    c = {"compact_bytes_in": 10**6, "block_cache_hits": 3,
         "block_cache_misses": 1}
    got = {n: reader_run(n, tr, c, spans, window_s=1.0) for n in
           ("write_stall_share", "flush_build_ms", "compact_mb_per_s",
            "block_cache_hit_rate.get", "read_launch_share.multi_get")}
    assert got == pytest.approx({
        "write_stall_share": 25.0, "flush_build_ms": 3.0,
        "compact_mb_per_s": 2.0, "block_cache_hit_rate.get": 75.0,
        "read_launch_share.multi_get": 50.0})


def test_recorded_trace_sample():
    """100 ms of a traced ``load.1kb`` window, recorded on a TPU v5e: the
    reduction gives the numbers it gave when the sample was cut."""
    with open(SAMPLE) as f:
        sample = json.load(f)
    red = devtrace.reduce(sample["trace"], [tuple(s) for s in
                                            sample["spans"]])
    want = sample["expect"]
    assert red["busy_s"] == pytest.approx(want["busy_s"])
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["breakdown"]["device_ops"][0][0] == \
        want["top_op"]
    total_idle = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert total_idle <= red["window_s"] - red["busy_s"] + 1e-9
