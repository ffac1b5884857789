"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals / window), in %.  From the
profiler trace; averaged over the chips used."""


def read(run):
    dev = run.device
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
