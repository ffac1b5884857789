"""Share of the window that writers spent stalled on a full immutable-
memtable queue (summed ``write_stall`` spans / window), in %."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * sum(run.span_seconds("write_stall")) / run.window_s
