"""The compaction-engine and compile readers on a hand-made ``RunData``:
what they read, and that a run without their spans reads nothing."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402

READERS = ("compact_dispatch_share", "compact_device_wait_share",
           "jit_compiles")


def read_all(spans, window_s=10.0):
    run = harness.RunData("load.1kb", window_s, list(spans), {}, None,
                          peaks.peaks("TPU v5 lite"))
    return {n: spec.load_reader(n, BENCH)(run) for n in READERS}


def test_shares_of_compaction_time_and_compile_count():
    spans = [
        # before the window: counted by nothing
        ("jit.compile", -2.0, 0.5), ("compact.job", -1.0, 0.5),
        ("compact.dispatch", -1.0, 0.4),
        # two jobs in the window, 4 s in all
        ("compact.job", 1.0, 3.0), ("compact.dispatch", 1.1, 2.4),
        ("jit.trace", 1.1, 0.2), ("jit.lower", 1.3, 0.3),
        ("jit.compile", 1.6, 1.5), ("compact.device_wait", 3.5, 0.1),
        ("compact.d2h", 3.6, 0.2),
        ("compact.job", 6.0, 1.0), ("compact.dispatch", 6.1, 0.6),
        ("compact.device_wait", 6.7, 0.1), ("compact.d2h", 6.8, 0.1),
        ("jit.compile", 8.0, 0.1),
        # past the window's end
        ("jit.compile", 9.95, 0.1),
    ]
    assert read_all(spans) == pytest.approx({
        "compact_dispatch_share": 75.0,
        "compact_device_wait_share": 5.0,
        "jit_compiles": 2})


def test_instrumented_run_without_compiles_reads_zero():
    spans = [("compact.job", 1.0, 1.0), ("compact.dispatch", 1.0, 0.5),
             ("compact.device_wait", 1.5, 0.5)]
    assert read_all(spans) == {"compact_dispatch_share": 50.0,
                               "compact_device_wait_share": 50.0,
                               "jit_compiles": 0}


def test_a_store_without_the_spans_reads_nothing():
    # the spans a store records without the launch's children
    spans = [("compact.job", 1.0, 3.0), ("compact.execute", 1.5, 2.0),
             ("compact.read_inputs", 1.0, 0.5), ("db.put", 0.1, 0.001)]
    assert read_all(spans) == dict.fromkeys(READERS)
    assert read_all([]) == dict.fromkeys(READERS)
