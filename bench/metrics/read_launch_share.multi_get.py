"""Share of ``multi_get`` time spent in the batched filter probe and the
block gather, the two stages that launch the read kernels (summed
``read.bloom_probe`` + ``read.block_gather`` spans over summed
``db.multi_get`` spans), in %."""


def read(run):
    total = sum(run.span_seconds("db.multi_get"))
    if total <= 0:
        return None
    stages = (sum(run.span_seconds("read.bloom_probe")) +
              sum(run.span_seconds("read.block_gather")))
    return 100.0 * stages / total
