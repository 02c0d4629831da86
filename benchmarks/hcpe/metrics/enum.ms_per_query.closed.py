"""Milliseconds of enumeration (``enumerate_seconds``: shared, fused and
solo drivers, their device dispatches and host syncs) per distinct
query of the window's micro-batches."""


def read(rec):
    """Enumeration milliseconds per distinct query."""
    distinct = sum(b["distinct"] for b in rec["batches"])
    if not distinct:
        return None
    return sum(b["enumerate_s"] for b in rec["batches"]) * 1e3 / distinct
