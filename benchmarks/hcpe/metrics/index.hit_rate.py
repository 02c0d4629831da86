"""index.hit_rate: index-cache hits over lookups in the window, in
percent (``IndexCache.stats`` deltas)."""


def read(rec):
    """Hit percent of lookups."""
    c = rec["cache"]
    looked = c["hits"] + c["misses"]
    return 100.0 * c["hits"] / looked if looked else None
