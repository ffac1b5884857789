"""The reader of ``get_filter_share`` (summed ``get.filter`` over summed
``db.get`` in the window) on hand-made ``RunData``."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import peaks  # noqa: E402
import spec  # noqa: E402


def read(spans, window_s=10.0):
    run = harness.RunData("ycsb-a.128b", window_s, list(spans), {}, None,
                          peaks.peaks("TPU v5 lite"))
    return spec.load_reader("get_filter_share", BENCH)(run)


def test_filter_share_of_get_time_in_the_window():
    spans = [
        # before the window and past its end: counted by nothing
        ("db.get", -1.0, 0.5), ("get.filter", -0.9, 0.3),
        ("db.get", 9.99, 0.1), ("get.filter", 9.995, 0.05),
        # two gets in the window, 400 us; three filter checks, 8 us
        ("db.get", 1.0, 100e-6), ("get.filter", 1.00001, 2e-6),
        ("get.filter", 1.00004, 2e-6),
        ("db.get", 2.0, 300e-6), ("get.filter", 2.0001, 4e-6),
        ("db.put", 4.0, 1e-3),
    ]
    assert read(spans) == pytest.approx(2.0)


def test_no_reading_without_gets_or_filter_spans():
    # a write-only window; a store that records no get.filter span
    assert read([("db.put", 1.0, 1e-5), ("get.filter", 2.0, 1e-6)]) is None
    assert read([("db.get", 1.0, 1e-4)]) is None
    assert read([]) is None
