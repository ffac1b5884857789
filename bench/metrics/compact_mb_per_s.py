"""Compaction's data processing speed (the paper's measure): input bytes
compacted in the window (the store's ``compact_bytes_in`` counter) over
the summed seconds of its ``compact.job`` spans, in MB/s (10^6 bytes)."""


def read(run):
    secs = sum(run.span_seconds("compact.job"))
    nbytes = run.counters.get("compact_bytes_in", 0)
    if secs <= 0 or nbytes <= 0:
        return None
    return nbytes / 1e6 / secs
