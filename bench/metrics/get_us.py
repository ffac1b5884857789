"""Mean duration of the store's ``db.get`` spans in the window (one
scalar ``get`` call: memtables, then L0 and the levels), in us.  None
where the window holds no ``db.get`` span."""


def read(run):
    d = run.span_seconds("db.get")
    return 1e6 * sum(d) / len(d) if d else None
