"""Block-cache hits / (hits + misses) over the window, in percent, from the
cache's own counters (`GenomicArchive.cache_info()`)."""


def read(r):
    hits, misses = r.cache_delta("hits"), r.cache_delta("misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
