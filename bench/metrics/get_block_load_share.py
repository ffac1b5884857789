"""Share of scalar ``get`` time spent loading blocks that missed the
block cache: the summed ``get.block_load`` spans over the summed
``db.get`` spans in the window, in %.  None where the window holds no
``db.get`` span."""


def read(run):
    total = sum(run.span_seconds("db.get"))
    if total <= 0:
        return None
    return 100.0 * sum(run.span_seconds("get.block_load")) / total
