"""Share of the HBM roofline reached by the compaction programs, in %.

The least time a compaction can take on the device is reading its input
once and writing its output once at the chip's HBM bandwidth: (bytes in
+ bytes out) / peak bytes/s, whatever implements it.  The bytes are the
store's ``compact_bytes_in`` and ``compact_bytes_out`` counters over the
window (which starts and ends with the store idle, so every job counted
ran inside it); the time is the device time of the ``jit_compact``
programs in the profiler trace of the same window.  HBM-bound: the
pipeline does a few integer operations per byte."""

import devtrace

PROGRAM = r"jit_compact(\(|$)"


def read(run):
    dev = run.device
    if not dev:
        return None
    secs = sum(devtrace.module_seconds(d, PROGRAM, dev["lo"], dev["hi"])
               for d in dev["trace"]["devices"])
    nbytes = (run.counters.get("compact_bytes_in", 0) +
              run.counters.get("compact_bytes_out", 0))
    if secs <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / run.peaks.hbm_bw / secs
