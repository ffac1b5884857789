"""Share of compaction time the host waits for the device: the summed
``compact.device_wait`` spans (``block_until_ready`` on a launch's
outputs) over the summed ``compact.job`` spans in the window, in %.  None
where the store records no ``compact.device_wait`` span."""


def read(run):
    wait = run.span_seconds("compact.device_wait")
    jobs = sum(run.span_seconds("compact.job"))
    if not wait or jobs <= 0:
        return None
    return 100.0 * sum(wait) / jobs
