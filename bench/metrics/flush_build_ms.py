"""Mean duration of the store's ``flush.build`` spans in the window (sort
the memtable, pack its entries, build the SST image on the device), in
ms."""


def read(run):
    d = run.span_seconds("flush.build")
    return 1000.0 * sum(d) / len(d) if d else None
