"""index.bfs_ms_per_miss: milliseconds of the stacked distance BFS
(``distance_seconds``, the ``pathenum.index.bfs`` span: the BFS, the
listing of the edges each index keeps, their launches and the copy
back) per index-cache miss of the window's micro-batches."""


def read(rec):
    """BFS milliseconds per miss."""
    misses = sum(b["misses"] for b in rec["batches"])
    if not misses:
        return None
    return sum(b["distance_s"] for b in rec["batches"]) * 1e3 / misses
