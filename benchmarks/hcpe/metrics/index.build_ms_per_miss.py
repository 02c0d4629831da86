"""index.build_ms_per_miss: milliseconds of index construction from the
distances (``index_seconds``, the ``pathenum.index.build`` span) per
index-cache miss of the window's micro-batches."""


def read(rec):
    """Index-build milliseconds per miss."""
    misses = sum(b["misses"] for b in rec["batches"])
    if not misses:
        return None
    return sum(b["index_s"] for b in rec["batches"]) * 1e3 / misses
