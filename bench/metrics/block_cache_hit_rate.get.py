"""Block-cache hits over lookups in the window (the store's
``block_cache_hits`` and ``block_cache_misses`` counters), in %."""


def read(run):
    hits = run.counters.get("block_cache_hits", 0)
    total = hits + run.counters.get("block_cache_misses", 0)
    return 100.0 * hits / total if total else None
