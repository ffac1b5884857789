"""Share of scalar ``get`` time spent checking tables' filter rows: the
summed ``get.filter`` spans over the summed ``db.get`` spans in the
window, in %.  None where the window holds no ``db.get`` span, or a
store that records no ``get.filter`` span."""


def read(run):
    total = sum(run.span_seconds("db.get"))
    checks = run.span_seconds("get.filter")
    if total <= 0 or not checks:
        return None
    return 100.0 * sum(checks) / total
