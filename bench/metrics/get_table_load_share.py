"""Share of scalar ``get`` time spent opening tables the table cache did
not hold (the whole file read, its CRC and the blocks' first keys): the
summed ``get.table_load`` spans over the summed ``db.get`` spans in the
window, in %.  None where the window holds no ``db.get`` span."""


def read(run):
    total = sum(run.span_seconds("db.get"))
    if total <= 0:
        return None
    return 100.0 * sum(run.span_seconds("get.table_load")) / total
