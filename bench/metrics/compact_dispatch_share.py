"""Share of compaction time spent dispatching device launches: the summed
``compact.dispatch`` spans (the call into the jitted pipeline: trace,
lower, compile or persistent-cache load, enqueue) over the summed
``compact.job`` spans in the window, in %.  None where the store records
no ``compact.dispatch`` span (a build without the span)."""


def read(run):
    dispatch = run.span_seconds("compact.dispatch")
    jobs = sum(run.span_seconds("compact.job"))
    if not dispatch or jobs <= 0:
        return None
    return 100.0 * sum(dispatch) / jobs
